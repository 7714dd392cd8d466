"""``cache_misses``: compile requests of the whole run that JAX's
persistent cache did not serve (``jax.monitoring``: backend-compile
events minus cache hits).  0 from the second run in a checkout on.
Layer: launcher and bootstrap."""


def read(trace, counters, cell):
    return counters["cache_misses"]
