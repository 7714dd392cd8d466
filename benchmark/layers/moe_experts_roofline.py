"""``moe_experts_roofline``: the least time the chip could take for the
held experts' grouped products of a step — gate, up and down over the
pairs the routing sends the experts held here, forward and backward:
the family's ``expert_cost`` of the pairs a token sends them by the
``hvd_moe_route`` records (the reference check's sequence at step 0;
``benchmark/experts.py``) times the step's tokens, and ``peaks.json``:
the larger of FLOPs / peak FLOP/s and bytes / peak B/s — over the device
time of whatever runs them: the sort of the pairs, the gather and the
scatter-add under the scope ``hvd_moe_experts`` and the compiler's
grouped-product kernels, told by their name, in every pass, in percent
(device trace).  Nothing where the program wrote no routing record or
no operation ran under that scope.  Layer: expert layer."""

from benchmark import experts, manifest, roofline, scopes


def read(trace, counters, cell):
    names = scopes.names_of(cell)
    records = experts.routing()
    if ("moe_experts" not in counters["kernel_costs"] or names is None
            or not records):
        return None
    experts_s = trace.mean(lambda ops: experts.scope_ns(
        ops, names, "hvd_moe_experts")) * 1e-9 / trace.steps
    if experts_s == 0:
        return None
    job = cell.job
    pairs = (job["batch_per_chip"] * job["seq"]
             * experts.pairs_per_token(records))
    cost = manifest.load_family(cell).expert_cost(cell.config, pairs)
    return roofline.percent(cost, counters["peaks"], experts_s)
