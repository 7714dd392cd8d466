"""``optimizer_share``: device time under the scope ``hvd_optimizer`` —
the inner optimizer's update and, in the flagship step, its
``apply_updates`` — as a share of the busy time of the traced leaves
(``benchmark/scopes.py``).  Layer: the compiled train step."""

from benchmark import scopes


def read(trace, counters, cell):
    return scopes.part_share(trace, cell, "optimizer")
