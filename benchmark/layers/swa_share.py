"""``swa_share``: device time under the scope ``hvd_swa`` — a
sliding-window attention sub-layer: the five projections (the gate's
among them), the two per-head norms, the rotary positions, the
``ring_attention`` call under a window (``hvd_attn`` inside it, its
kernels named ``hvd_flash_*_win``), the gate and the output projection,
forward, recomputed and backward — as a share of the busy time of the
traced leaves (``benchmark/scopes.py``).  The full-attention sub-layers
run under ``hvd_gattn`` and are not counted.  Cuts across ``fwd_share``
and ``bwd_share``.  Nothing where the step holds no operation under that
scope.  Layer: attention kernels."""

from benchmark import scopes


def read(trace, counters, cell):
    return scopes.scope_share(trace, cell, "hvd_swa")
