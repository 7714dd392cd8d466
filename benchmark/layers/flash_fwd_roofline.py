"""``flash_fwd_roofline``: the least time the chip could take for what a
step requires of the forward flash-attention kernel — QK^T and PV, two
of a layer's seven required causal products (``scopes.flash_costs``;
FLOP-bound at the cells' shapes) — over the time the calls named
``hvd_flash_fwd`` took, in percent (device trace).  Nothing where no
such kernel ran.  Layer: attention kernels."""

from benchmark import scopes


def read(trace, counters, cell):
    return scopes.kernel_roofline(trace, counters, cell, "hvd_flash_fwd")
