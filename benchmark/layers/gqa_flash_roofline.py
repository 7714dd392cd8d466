"""``gqa_flash_roofline``: the least time the chip could take for what
a step requires of the three flash-attention kernels under grouped-query
attention — seven causal products an attention layer over the query
heads at a head size of 128, ``k`` and ``v`` moved once a key/value
head, from the family's ``kernel_costs()["gqa_flash"]`` and
``peaks.json``: the larger of FLOPs / peak FLOP/s and bytes / peak B/s,
FLOP-bound here — over the time the calls named ``hvd_flash_fwd``,
``hvd_flash_bwd_dq`` and ``hvd_flash_bwd_dkv`` took, in percent (device
trace).  The kernels read ``k`` and ``v`` broadcast over their query
heads; a kernel that reads a shared key/value head is judged on this
share.  Nothing where the family states no such cost or no such kernel
ran.  Layer: attention kernels."""

from benchmark import roofline, scopes


def read(trace, counters, cell):
    cost = counters["kernel_costs"].get("gqa_flash")
    names = scopes.names_of(cell)
    if cost is None or names is None:
        return None
    kernel_s = trace.mean(lambda ops: sum(
        scopes.kernel_ns(ops, names, kernel)
        for kernel in scopes.FLASH_KERNELS)) * 1e-9 / trace.steps
    if kernel_s == 0:
        return None
    return roofline.percent(cost, counters["peaks"], kernel_s)
