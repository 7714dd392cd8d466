"""A kernel's share of its roofline from a cost and a time."""


def percent(cost: dict, peaks: dict, seconds: float) -> float:
    """The least time the chip could take for ``cost`` (``{"flops",
    "bytes"}``) — the larger of FLOPs / peak FLOP/s and bytes / peak
    B/s, from a ``peaks.json`` entry — over ``seconds``, in percent."""
    least_s = max(cost["flops"] / peaks["bf16_flops_per_s"],
                  cost["bytes"] / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / seconds
