"""``dsa_kept_share``: the (query, key) pairs the indexer's selections
keep over the pairs a causal call would compute, all layers added up:
``kept_pairs / causal_pairs`` of the ``hvd_dsa_select`` records the
program writes to this process's flight ring
(``transformer.record_selection``, once, outside the window: the
reference check's sequence at step 0; the last check's records where the
ring holds several), read as ``benchmark/experts.py`` reads the routing
records.  0.2344 at 16,384 tokens and 2,048 kept: what
attention is required to compute, where a masked causal call computes 1.
Nothing where the program wrote no such record.  Layer: attention
kernels."""

import sys


def read(trace, counters, cell):
    flight = sys.modules.get("horovod_tpu.runtime.flight")
    if flight is None:
        return None
    records = [event for event in flight.recorder().snapshot()
               if event["kind"] == "hvd_dsa_select"]
    if not records:
        return None
    # the last check's: from its first layer on (a run makes one check;
    # a test process may hold older ones)
    records = records[max(i for i, record in enumerate(records)
                          if record["layer"] == 0):]
    return (sum(record["kept_pairs"] for record in records)
            / sum(record["causal_pairs"] for record in records))
