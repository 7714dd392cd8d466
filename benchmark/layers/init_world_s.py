"""``init_world_s``: what forming the world costs inside ``hvd.init()``:
the spans ``hvd_init.distributed`` (the coordinator), ``.topology``
(two exchanges over its key-value store) and ``.runtime`` (background
thread and round-0 handshake), added up (flight-recorder spans, this
process's ring).  About 0 in a world of one.  Layer: launcher and
bootstrap."""

from benchmark import scopes


def read(trace, counters, cell):
    return scopes.init_seconds("hvd_init.distributed", "hvd_init.topology",
                               "hvd_init.runtime")
