"""``hvd_init_s``: the span ``hvd_init`` whole, around ``hvd.init()``:
backend, world, meshes and planes (flight-recorder span; the worst
rank's).  What ``init_backend_s`` and ``init_world_s`` split in the
inline cells, here from every rank of a launched world too.  Layer:
launcher and bootstrap."""

from benchmark import rings


def read(trace, counters, cell):
    return rings.worst(cell, rings.hvd_init_s)
