"""``mla_flash_roofline``: the least time the chip could take for what
a step requires of the three flash-attention kernels at the latent
attention's head sizes — seven causal products a block, QK^T-type at
192 and PV-type at 128, from the family's ``kernel_costs()["mla_flash"]``
and ``peaks.json``: the larger of FLOPs / peak FLOP/s and bytes / peak
B/s, FLOP-bound here — over the time the calls named ``hvd_flash_fwd``,
``hvd_flash_bwd_dq`` and ``hvd_flash_bwd_dkv`` took, in percent (device
trace).  The forward kernel runs twice a block (the block is recomputed
in the backward pass); the second run is time and not required work.
Nothing where no such kernel ran.  Layer: attention kernels."""

from benchmark import roofline, scopes


def read(trace, counters, cell):
    cost = counters["kernel_costs"].get("mla_flash")
    names = scopes.names_of(cell)
    if cost is None or names is None:
        return None
    kernel_s = trace.mean(lambda ops: sum(
        scopes.kernel_ns(ops, names, kernel)
        for kernel in scopes.FLASH_KERNELS)) * 1e-9 / trace.steps
    if kernel_s == 0:
        return None
    return roofline.percent(cost, counters["peaks"], kernel_s)
