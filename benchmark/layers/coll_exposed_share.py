"""``coll_exposed_share``: the part of the traced window in which a
collective was in flight and no other operation ran on the chip, as a
share of the window (device trace).  Layer: trainer, product."""

from benchmark import reduce


def read(trace, counters, cell):
    return trace.mean(lambda ops: reduce.exposed_collective_ns(ops)
                      / reduce.total([reduce.window(ops)]))
