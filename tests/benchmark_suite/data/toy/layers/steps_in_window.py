"""A per-layer metric the tests drop in as a new file: the harness
finds it by the name the manifest gives it."""


def read(trace, counters, cell):
    return len(counters["chunk_walls"]) * counters["chunk_steps"]
