"""``peak_hbm_gb``: the most this process's chip held, in GB: the
runtime's reserved peak (a loaded program's scratch) plus the live
buffers after the window, or set-up's buffer peak if that is larger
(``memory_stats()``; ``harness._memory_peak_bytes``).  Layer: device."""


def read(trace, counters, cell):
    return counters["memory_peak_bytes"] / 1e9
