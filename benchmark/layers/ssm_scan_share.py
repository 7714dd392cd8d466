"""``ssm_scan_share``: device time under the scope ``hvd_ssm_scan`` —
the state-space recurrence alone, as the program computes it (a chunked
scan: the products inside a chunk, the chunks' end states, the loop that
passes them on), every pass — as a share of the busy time of the traced
leaves (``benchmark/scopes.py``).  The part of ``ssm_share`` that is
not projections.  Nothing where the step holds no operation under that
scope.  Layer: state-space layer."""

from benchmark import scopes


def read(trace, counters, cell):
    return scopes.scope_share(trace, cell, "hvd_ssm_scan")
