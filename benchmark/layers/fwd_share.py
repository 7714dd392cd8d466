"""``fwd_share``: device time in the forward pass — operations whose
``op_name`` shows ``jvp(`` and no later part of the step — as a share of
the busy time of the traced leaves (device trace joined to the
program's scopes, ``benchmark/scopes.py``).  Layer: the compiled train
step."""

from benchmark import scopes


def read(trace, counters, cell):
    return scopes.part_share(trace, cell, "fwd")
