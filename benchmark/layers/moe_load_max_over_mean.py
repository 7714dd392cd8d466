"""``moe_load_max_over_mean``: how unevenly the router loads the
experts held here: the pairs sent to the busiest held expert over the
mean of the held experts, the largest over the expert layers.  From the
``hvd_moe_route`` records the program writes to this process's flight
ring (``transformer.record_routing``, once, outside the window; one
record a layer, ``dropped`` 0 in each; ``benchmark/experts.py``), read
as ``scopes.init_spans`` reads its spans.  1 is even; the grouped
products take as long as the sum, so this is what a straggling chip of
the deployment would wait for.  Nothing where the program wrote no such
record.  Layer: expert layer."""

from benchmark import experts


def read(trace, counters, cell):
    loads = [record["pairs"] for record in experts.routing()]
    if not loads:
        return None
    return max(max(pairs) * len(pairs) / sum(pairs) for pairs in loads)
