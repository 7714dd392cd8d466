"""``step_ms_p50``: the median over the window's chunks of a chunk's
wall time divided by its steps, in milliseconds (host clock around
blocked chunks; the number of chunks is on an earlier line).  Layer: the
compiled train step of either trainer."""

import statistics


def read(trace, counters, cell):
    return (statistics.median(counters["chunk_walls"])
            / counters["chunk_steps"] * 1e3)
