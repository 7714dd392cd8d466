"""What the benchmark reads of the expert layer beside its scopes.

**The grouped products.**  On a TPU ``lax.ragged_dot`` runs as the
compiler's own kernels: custom calls named ``ragged-dot-none.<n>`` (the
products) and ``ragged-dot-metadata.<n>`` (the layout of their tiles),
to which the compiler gives an ``op_name`` of its own
(``ragged-dot-none``).  They show no ``hvd_*`` scope and no pass, so
``scopes.scope_ns`` finds everything the program runs under
``hvd_moe_experts`` — the sort of the pairs, the gather, the
scatter-add — but not the products, and ``scopes.part_ns`` counts them
as ``unscoped``.  Here they are told by their instruction's name, as
``scopes.kernel_ns`` tells a Mosaic call by its own, and counted with
the expert layer: only ``parallel/moe.py`` calls ``lax.ragged_dot``.
On the CPU the products are plain ``dot`` instructions under the scope
and nothing bears that name.

**The routing records.**  ``transformer.record_routing`` writes one
``hvd_moe_route`` record an expert layer to this process's flight ring,
once, outside the window (the reference check's sequence at step 0):
the pairs sent to each held expert and the tokens routed.
"""

from __future__ import annotations

import sys

from benchmark import manifest, reduce, scopes

GROUPED = "ragged-dot"


def _grouped(op) -> bool:
    return op.name.startswith(GROUPED)


def scope_ns(ops: list, names: dict, scope: str) -> int:
    """Time in which a leaf under ``scope`` or a grouped-product kernel
    ran, either pass."""
    return reduce.total(reduce.merge(
        [[op.start, op.end] for op in reduce.leaves(ops)
         if _grouped(op) or scope in scopes.scopes_of(names.get(op.name, ""))]))


def scope_share(trace: reduce.Trace, cell: manifest.Cell,
                scope: str) -> float | None:
    """``scopes.scope_share`` with the grouped-product kernels counted
    under ``scope``.  ``None`` where the step holds no operation under
    it."""
    names = scopes.names_of(cell)
    if names is None or not any(scope in scopes.scopes_of(n)
                                for n in names.values()):
        return None
    return trace.mean(lambda ops: scope_ns(ops, names, scope)
                      / reduce.total(reduce.busy(reduce.leaves(ops))))


def routing() -> list:
    """The ``hvd_moe_route`` records of this process's flight ring that
    sent a pair; none where the program writes no such record."""
    flight = sys.modules.get("horovod_tpu.runtime.flight")
    if flight is None:
        return []
    return [event for event in flight.recorder().snapshot()
            if event["kind"] == "hvd_moe_route" and sum(event["pairs"])]


def pairs_per_token(records: list) -> float:
    """(token, expert) pairs a token sends the held experts, added up
    over the expert layers the records cover."""
    return sum(sum(record["pairs"]) / record["tokens"]
               for record in records)
