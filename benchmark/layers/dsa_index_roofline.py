"""``dsa_index_roofline``: the least time the chip could take for the
indexer's scores — ``2 x heads x head size`` FLOPs a causal (query, key)
pair, once a step (the indexer takes no gradient and a recomputed layer
keeps its selection), its queries, keys and weights read and the
selection's packed bits written, from the family's
``kernel_costs()["dsa_index"]`` and ``peaks.json``: the larger of FLOPs /
peak FLOP/s and bytes / peak B/s, the FLOPs here — over the device time
under the scope ``hvd_dsa_index``: the indexer's projections, the scores,
the search for each row's threshold, the packing; in percent (device
trace).  A program that made the selection a second time in the backward
pass would read half; nothing can raise it past 100.  Nothing where the
family states no such cost or no operation ran under that scope.  Layer:
attention kernels."""

from benchmark import roofline, scopes


def read(trace, counters, cell):
    cost = counters["kernel_costs"].get("dsa_index")
    names = scopes.names_of(cell)
    if cost is None or names is None:
        return None
    index_s = trace.mean(lambda ops: scopes.scope_ns(
        ops, names, "hvd_dsa_index")) * 1e-9 / trace.steps
    if index_s == 0:
        return None
    return roofline.percent(cost, counters["peaks"], index_s)
