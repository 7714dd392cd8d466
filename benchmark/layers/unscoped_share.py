"""``unscoped_share``: device time of the operations no name of the
program reaches — compiler copies, async waits, what a family's own
code adds after the gradients — as a share of the busy time of the
traced leaves (``benchmark/scopes.py``).  With ``fwd_share``,
``bwd_share``, ``optimizer_share`` and ``grad_reduce_share`` it adds up
to 1.  Layer: device."""

from benchmark import scopes


def read(trace, counters, cell):
    return scopes.part_share(trace, cell, "unscoped")
