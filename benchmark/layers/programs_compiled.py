"""``programs_compiled``: how many programs the run compiled or loaded:
the count of ``hvd_compile`` records (flight ring; the worst rank's).
The weights' initialisers, the reference check, the train step and
every small program set-up dispatches are among them.  Layer: launcher
and bootstrap."""

from benchmark import rings


def read(trace, counters, cell):
    return rings.worst(cell, rings.programs_compiled)
