"""``coll_s_per_step``: device time per step in which a collective was
in flight (device trace, ``reduce.collective_intervals``), averaged over
the traced chips.  0 in a world of one.  Layer: trainer, product."""

from benchmark import reduce


def read(trace, counters, cell):
    return trace.mean(lambda ops: reduce.total(
        reduce.collective_intervals(ops))) * 1e-9 / trace.steps
