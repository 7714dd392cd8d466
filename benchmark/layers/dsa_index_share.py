"""``dsa_index_share``: device time under the scope ``hvd_dsa_index`` —
the indexer alone: its three projections, the scores of every query
against every earlier key, the search for each row's threshold and the
packing of the kept keys' bits; forward only (it takes no gradient, and
a recomputed layer keeps its selection) — as a share of the busy time of
the traced leaves (``benchmark/scopes.py``).  The part of ``dsa_share``
that picks the keys.  Nothing where the step holds no operation under
that scope.  Layer: attention kernels."""

from benchmark import scopes


def read(trace, counters, cell):
    return scopes.scope_share(trace, cell, "hvd_dsa_index")
