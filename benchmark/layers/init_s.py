"""``init_s``: from the start of the run's first process until
``hvd.init()`` had returned in this one (host clock).  Layer: launcher
and bootstrap.  Holds interpreter start, imports, the launcher's spawn of
the ranks and the forming of the world."""


def read(trace, counters, cell):
    return counters["init_s"]
