"""``backend_compile_s``: the seconds of XLA's own compiles: ``backend_s``
added up over the ``hvd_compile`` records the persistent cache did not
serve (``cache`` other than ``hit``; flight ring; the worst rank's).  0
on a warm cache.  Layer: launcher and bootstrap."""

from benchmark import rings


def read(trace, counters, cell):
    return rings.worst(cell, rings.backend_compile_s)
