"""``import_s``: from the start of a rank's process until it entered
``hvd.init()``: the interpreter and the imports of ``jax`` and
``horovod_tpu`` (``hvd_process.started_wall`` to the ``B`` of the span
``hvd_init``, wall clock; the worst rank's).  Layer: launcher and
bootstrap."""

from benchmark import rings


def read(trace, counters, cell):
    return rings.worst(cell, rings.import_s)
