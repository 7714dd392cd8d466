"""``flash_bwd_dkv_roofline``: the least time the chip could take for
what a step requires of ``flash_bwd_dkv`` — P^T dO, dS^T Q and half of
the QK^T and dO V^T that both backward kernels recompute, three of a
layer's seven required causal products (``scopes.flash_costs``;
FLOP-bound at the cells' shapes) — over the time the calls named
``hvd_flash_bwd_dkv`` took, in percent (device trace).  Nothing where
no such kernel ran.  Layer: attention kernels."""

from benchmark import scopes


def read(trace, counters, cell):
    return scopes.kernel_roofline(trace, counters, cell, "hvd_flash_bwd_dkv")
