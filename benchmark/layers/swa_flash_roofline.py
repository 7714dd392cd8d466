"""``swa_flash_roofline``: the least time the chip could take for what a
step requires of attention under a sliding window — seven products of
``2 x head_dim`` FLOPs a (query, key) pair over the pairs the window
leaves (``0 <= i - j < window``), every query head of every sliding
layer, ``q``, ``k``, ``v``, ``o`` and their gradients moved once, from
the family's ``kernel_costs()["swa_flash"]`` and ``peaks.json``: the
larger of FLOPs / peak FLOP/s and bytes / peak B/s, FLOP-bound at the
cell's sizes — over the time the calls named ``hvd_flash_fwd_win``,
``hvd_flash_bwd_dq_win`` and ``hvd_flash_bwd_dkv_win`` took, in percent
(device trace).  The required work is the window's, not the live tiles',
so the share is comparable across whatever tiles or kernel compute it:
at 1024 x 1024 tiles and a window of 2,048 the live tiles cover half as
many slots again as the window leaves pairs.  Nothing where the family
states no such cost or no such kernel ran.  Layer: attention kernels."""

from benchmark import roofline, scopes

KERNELS = tuple(kernel + "_win" for kernel in scopes.FLASH_KERNELS)


def read(trace, counters, cell):
    cost = counters["kernel_costs"].get("swa_flash")
    names = scopes.names_of(cell)
    if cost is None or names is None:
        return None
    kernel_s = trace.mean(lambda ops: sum(
        scopes.kernel_ns(ops, names, kernel)
        for kernel in KERNELS)) * 1e-9 / trace.steps
    if kernel_s == 0:
        return None
    return roofline.percent(cost, counters["peaks"], kernel_s)
