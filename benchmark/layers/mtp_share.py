"""``mtp_share``: device time under the scope ``hvd_mtp`` — the
multi-token-prediction module: its embedding lookup, the joining
projection, its one expert block and its loss head, all passes — as a
share of the busy time of the traced leaves (``benchmark/scopes.py``):
what the second loss costs a step.  Its expert layer's grouped-product
kernels, a fifth of the step's, carry no scope (``benchmark/experts.py``)
and are left out: ``moe_share`` has them.  Layer: the compiled train
step."""

from benchmark import scopes


def read(trace, counters, cell):
    return scopes.scope_share(trace, cell, "hvd_mtp")
