"""``bwd_share``: device time in the backward pass — operations whose
``op_name`` shows ``transpose(``, which JAX writes around what it
differentiates, and no later part of the step — as a share of the busy
time of the traced leaves (``benchmark/scopes.py``).  Layer: the
compiled train step."""

from benchmark import scopes


def read(trace, counters, cell):
    return scopes.part_share(trace, cell, "bwd")
