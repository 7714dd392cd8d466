"""``init_backend_s``: the span ``hvd_init.backend`` of ``hvd.init()``:
its first call into JAX's backend (``jax.process_count()``), which
opens the TPU runtime (flight-recorder span, this process's ring).
Layer: launcher and bootstrap."""

from benchmark import scopes


def read(trace, counters, cell):
    return scopes.init_seconds("hvd_init.backend")
