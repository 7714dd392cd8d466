"""``attn_share``: device time under the scope ``hvd_attn`` — the
``ring_attention`` call of every block, XLA or Pallas, forward and
backward — as a share of the busy time of the traced leaves
(``benchmark/scopes.py``).  Cuts across ``fwd_share`` and
``bwd_share``.  Layer: attention kernels."""

from benchmark import scopes


def read(trace, counters, cell):
    return scopes.scope_share(trace, cell, "hvd_attn")
