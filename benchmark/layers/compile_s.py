"""``compile_s``: wall time of the train step's ``lower().compile()``
(host clock): a compilation in a fresh checkout, a load from the
persistent cache after it.  Layer: launcher and bootstrap."""


def read(trace, counters, cell):
    return counters["compile_s"]
