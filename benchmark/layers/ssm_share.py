"""``ssm_share``: device time under the scope ``hvd_ssm`` — a Mamba-2
mixer: the in-projection, the causal convolution, the time steps, the
chunked scan (``hvd_ssm_scan`` inside it), the gate, the grouped norm
and the out-projection, forward, recomputed and backward — as a share
of the busy time of the traced leaves (``benchmark/scopes.py``).  Cuts
across ``fwd_share`` and ``bwd_share``.  Nothing where the step holds no
operation under that scope.  Layer: state-space layer."""

from benchmark import scopes


def read(trace, counters, cell):
    return scopes.scope_share(trace, cell, "hvd_ssm")
