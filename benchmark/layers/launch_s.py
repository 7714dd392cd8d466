"""``launch_s``: from the start of ``hvdrun``'s own process (its
interpreter and imports count) until the last rank's process had started
(``hvd_process.started_wall`` of the launcher's ring and of the ranks',
docs/flight-recorder.md).  The ``hvd_launch.*`` spans of the launcher's
ring say what filled it.  A launched cell's only.  Layer: launcher and
bootstrap."""

from benchmark import rings


def read(trace, counters, cell):
    return rings.launch_s(rings.of_cell(cell))
