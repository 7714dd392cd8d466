"""``mla_share``: device time under the scope ``hvd_mla`` — a block's
latent attention: the five projections, the latent norms, rotary, and
inside it the ``ring_attention`` call, forward, recomputed and backward
— as a share of the busy time of the traced leaves
(``benchmark/scopes.py``).  The MTP module's block counts too.  Cuts
across ``fwd_share`` and ``bwd_share``.  Layer: attention kernels."""

from benchmark import scopes


def read(trace, counters, cell):
    return scopes.scope_share(trace, cell, "hvd_mla")
