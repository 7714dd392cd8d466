"""``conv_share``: device time under the scope ``hvd_conv`` — a gated
short-convolution sub-layer: the in-projection to ``B | C | x``, the
gates and the causal convolution and the out-projection, forward,
recomputed and backward — as a share of the
busy time of the traced leaves (``benchmark/scopes.py``).  Cuts across
``fwd_share`` and ``bwd_share``.  Nothing where the step holds no
operation under that scope.  Layer: short-convolution layer."""

from benchmark import scopes


def read(trace, counters, cell):
    return scopes.scope_share(trace, cell, "hvd_conv")
