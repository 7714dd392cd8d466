"""``dsa_attend_roofline``: the least time the chip could take for what a
step requires of attention under the indexer's selection — seven products
of ``2 x head_dim`` FLOPs a (query, key) pair over the pairs the
selection leaves (every earlier key of the first ``topk`` queries,
``topk`` a query after them), every query head of every layer, ``q``,
``k``, ``v``, ``o`` and their gradients moved once, from the family's
``kernel_costs()["dsa_attend"]`` and ``peaks.json``: the larger of FLOPs
/ peak FLOP/s and bytes / peak B/s, FLOP-bound at the cell's sizes — over
the device time under the scope ``hvd_attn`` (in a cell whose every
attention call runs under a selection: the three ``hvd_flash_*_sel``
kernels and what stands between them), in percent (device trace).  The
required work is the selection's, not the live tiles', so the share is
comparable across whatever computes it: a masked causal call computes
4.27 times the pairs at 16,384 tokens and 2,048 kept, and reads at most
23 %.  Nothing where the family states no such cost or no operation ran
under that scope.  Layer: attention kernels."""

from benchmark import roofline, scopes


def read(trace, counters, cell):
    cost = counters["kernel_costs"].get("dsa_attend")
    names = scopes.names_of(cell)
    if cost is None or names is None:
        return None
    attend_s = trace.mean(lambda ops: scopes.scope_ns(
        ops, names, "hvd_attn")) * 1e-9 / trace.steps
    if attend_s == 0:
        return None
    return roofline.percent(cost, counters["peaks"], attend_s)
