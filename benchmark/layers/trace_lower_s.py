"""``trace_lower_s``: what tracing and lowering cost over every program
the run compiled, the cache cannot save it: ``trace_s + lower_s`` added
up over the ``hvd_compile`` records (flight ring; the worst rank's).
Every Python frame under ``loss_fn`` is paid for here.  Layer: launcher
and bootstrap."""

from benchmark import rings


def read(trace, counters, cell):
    return rings.worst(cell, rings.trace_lower_s)
