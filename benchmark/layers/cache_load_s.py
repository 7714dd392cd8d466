"""``cache_load_s``: what reading and loading executables from the
persistent cache cost: ``backend_s`` added up over the ``hvd_compile``
records with ``cache`` ``hit`` (flight ring; the worst rank's).  Layer:
launcher and bootstrap."""

from benchmark import rings


def read(trace, counters, cell):
    return rings.worst(cell, rings.cache_load_s)
