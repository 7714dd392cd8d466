"""``ssm_scan_roofline``: the least time the chip could take for what a
step requires of the state-space recurrence — a multiply-accumulate
into and one out of every element of the state a time step, forward and
twice that backward, and ``x``, the time steps, ``B``, ``C``, ``y`` and
their gradients moved once a pass, from the family's
``kernel_costs()["ssm_scan"]`` and ``peaks.json``: the larger of FLOPs /
peak FLOP/s and bytes / peak B/s, the bytes here — over the device time
under the scope ``hvd_ssm_scan``, every pass with the recomputed one, in
percent (device trace).  The required work is the recurrence's, not the
chunked form's, so the share is comparable across whatever computes the
scan.  Nothing where the family states no such cost or no operation ran
under that scope.  Layer: state-space layer."""

from benchmark import roofline, scopes


def read(trace, counters, cell):
    cost = counters["kernel_costs"].get("ssm_scan")
    names = scopes.names_of(cell)
    if cost is None or names is None:
        return None
    scan_s = trace.mean(lambda ops: scopes.scope_ns(
        ops, names, "hvd_ssm_scan")) * 1e-9 / trace.steps
    if scan_s == 0:
        return None
    return roofline.percent(cost, counters["peaks"], scan_s)
