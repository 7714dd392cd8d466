"""``conv_roofline``: the least time the chip could take for what a step
requires of the gated short-convolution sub-layers — the in-projection
to ``B | C | x`` forward, again in the replay and twice backward, the
out-projection forward and twice backward, and the gates and taps, from
the family's ``kernel_costs()["conv"]`` and ``peaks.json``: the larger
of FLOPs / peak FLOP/s and bytes / peak B/s, the FLOPs here — over the
device time under the scope ``hvd_conv``, every pass, in percent
(device trace).  The scope holds the whole sub-layer, so the share does
not depend on which of its operations the compiler fuses with which.
Nothing where the family states no such cost or no operation ran under
that scope.  Layer: short-convolution layer."""

from benchmark import roofline, scopes


def read(trace, counters, cell):
    cost = counters["kernel_costs"].get("conv")
    names = scopes.names_of(cell)
    if cost is None or names is None:
        return None
    conv_s = trace.mean(lambda ops: scopes.scope_ns(
        ops, names, "hvd_conv")) * 1e-9 / trace.steps
    if conv_s == 0:
        return None
    return roofline.percent(cost, counters["peaks"], conv_s)
