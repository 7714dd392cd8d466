"""``flash_attn_roofline``: the least time the chip could take for what
a step requires of the flash-attention kernels — the larger of required
FLOPs / peak FLOP/s and required bytes / peak B/s, from the family's
``kernel_costs`` and ``peaks.json`` — over the time the kernels took, in
percent (device trace).  At the cells' shapes the FLOPs bound it.
Nothing where no kernel ran.  Layer: attention kernels."""

from benchmark import reduce


def read(trace, counters, cell):
    cost = counters["kernel_costs"].get("flash_attn")
    kernel_s = trace.mean(reduce.kernel_ns) * 1e-9 / trace.steps
    if cost is None or kernel_s == 0:
        return None
    peaks = counters["peaks"]
    least_s = max(cost["flops"] / peaks["bf16_flops_per_s"],
                  cost["bytes"] / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / kernel_s
