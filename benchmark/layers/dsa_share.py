"""``dsa_share``: device time under the scope ``hvd_dsa`` — an indexed
attention sub-layer: the indexer's three projections, its scores and the
selection of each query's keys (``hvd_dsa_index`` inside it), the four
projections, the two per-head norms, the rotary positions, the
``ring_attention`` call under the selection (``hvd_attn`` inside it, its
kernels named ``hvd_flash_*_sel``) and the output projection, forward,
recomputed and backward — as a share of the busy time of the traced
leaves (``benchmark/scopes.py``).  Cuts across ``fwd_share`` and
``bwd_share``.  Nothing where the step holds no operation under that
scope.  Layer: attention kernels."""

from benchmark import scopes


def read(trace, counters, cell):
    return scopes.scope_share(trace, cell, "hvd_dsa")
