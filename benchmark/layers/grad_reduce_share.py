"""``grad_reduce_share``: device time under the scope ``hvd_grad_reduce``
— the gradient collective and what the product packs, casts and
divides around it — as a share of the busy time of the traced leaves
(``benchmark/scopes.py``).  About 0 in a world of one.  Layer: trainer,
product."""

from benchmark import scopes


def read(trace, counters, cell):
    return scopes.part_share(trace, cell, "grad_reduce")
