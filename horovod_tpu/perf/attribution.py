"""Attribution: device events -> framework scopes -> per-step truth.

Turns a parsed :class:`~horovod_tpu.perf.xplane.XSpace` into the
numbers every wire-efficiency claim in this repo actually needs
(docs/perf.md):

* step windows from ``hvd.trace_step``'s ``StepTraceAnnotation``
  events (``step_num`` stat);
* per-step **device** comm seconds split into *hidden under math* vs
  *exposed* — the true overlap efficiency of the PR 5/7 bucket
  schedules, measured as interval intersection;
* per-collective device seconds by kind (all-reduce, all-gather,
  reduce-scatter, collective-permute, all-to-all);
* per-scope seconds for the framework's named buckets
  (``hvd_overlap_rs/math/ag<k>``, ``hvd_zero2_rs<k>``,
  ``hvd_zero3_ag<k>``, ...);
* MFU when a flops-per-step hint is available (XLA ``cost_analysis``
  flops, supplied through the capture hook) against the chip's
  peak (spec-sheet table below; an unknown device raises).

Works on TPU device planes and on the CPU backend's host-plane XLA
executor events alike (both carry an ``hlo_op`` stat), so the whole
pipeline is testable without a chip.
"""

from __future__ import annotations

import re

from horovod_tpu.perf import xplane as _xp

# bf16 peak FLOP/s per chip, keyed by a tag found in jax's
# ``device_kind`` (lower-cased, spaces removed; "TPU v5 lite" is what a
# v5e announces).  Source: Google Cloud TPU documentation, the "System
# architecture" page of each generation.
_PEAK_FLOPS = [
    ("v6", 918e12), ("v5p", 459e12), ("v5lite", 197e12), ("v5e", 197e12),
    ("v5", 459e12), ("v4", 275e12), ("v3", 123e12), ("v2", 46e12),
]

_PS = 1e-12

# Collective kinds by canonical name; matched against the event name,
# the resolved op_name scope path, and the hlo_op stat.
_COMM_KINDS = (
    ("all-reduce", ("all-reduce", "allreduce", "all_reduce", "psum")),
    ("reduce-scatter", ("reduce-scatter", "reducescatter",
                        "reduce_scatter", "psum-scatter", "psum_scatter")),
    ("all-gather", ("all-gather", "allgather", "all_gather")),
    ("collective-permute", ("collective-permute", "collective_permute",
                            "ppermute")),
    ("all-to-all", ("all-to-all", "alltoall", "all_to_all")),
)

_KINDS = frozenset(kind for kind, _ in _COMM_KINDS)

# Framework scopes whose WORK is communication even when the individual
# ops inside are slices/dynamic-updates around the wire op.
_COMM_SCOPE = re.compile(
    r"^hvd_(overlap_(rs|ag)|zero2_(rs|ag)|zero3_(rs|ag))\d*$")
_HVD_SCOPE = re.compile(r"^hvd_\w+$")


def peak_flops_per_chip(device_kind: str) -> float:
    """Spec-sheet bf16 peak for a ``jax`` ``device_kind`` string.  A
    device the table does not know raises: a utilization against a
    guessed or absent peak is not a measurement.  (A CPU run has no
    utilization at all — callers do not ask for one.)"""
    kind = (device_kind or "").lower().replace(" ", "")
    for tag, peak in _PEAK_FLOPS:
        if tag in kind:
            return peak
    raise ValueError(
        f"no peak FLOP/s known for device_kind {device_kind!r}; add it "
        "to perf/attribution._PEAK_FLOPS with its source")


# ---------------------------------------------------------------------------
# Interval arithmetic (ps integers; events can nest and overlap freely)
# ---------------------------------------------------------------------------


def _merge(intervals: list) -> list:
    if not intervals:
        return []
    intervals = sorted(intervals)
    out = [list(intervals[0])]
    for s, e in intervals[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _total(merged: list) -> int:
    return sum(e - s for s, e in merged)


def _intersect(a: list, b: list) -> list:
    out = []
    i = j = 0
    while i < len(a) and j < len(b):
        s = max(a[i][0], b[j][0])
        e = min(a[i][1], b[j][1])
        if s < e:
            out.append([s, e])
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


# ---------------------------------------------------------------------------
# Event extraction
# ---------------------------------------------------------------------------


def _scope_of(op_name: str) -> str | None:
    """Last ``hvd_*`` component of a scoped op_name path, e.g.
    ``jit(f)/jit(main)/hvd_overlap_rs0/dot_general`` -> that bucket.
    Nested scopes resolve to the innermost hvd component: a bucket
    inside ``hvd_grad_reduce`` stays that bucket."""
    for part in reversed(op_name.split("/")):
        if _HVD_SCOPE.match(part):
            return part
    return None


def _comm_kind(*names) -> str | None:
    for text in names:
        if not text:
            continue
        low = text.lower()
        for kind, pats in _COMM_KINDS:
            for pat in pats:
                if pat in low:
                    return kind
    return None


_OPCODE = re.compile(r"([a-z][a-z0-9_\-]*)\(")


def _name_and_opcode(text: str) -> tuple:
    """``(instruction name, opcode)`` of an event's name.  A TPU device
    plane names an event by the instruction's whole text
    (``%fusion.7 = f32[8]{0} fusion(...), kind=kLoop``): the name is
    its head, the opcode the first word before an opening bracket.  A
    bare name (``fusion.7``, the CPU backend and the ``hlo_op`` stat)
    has no opcode."""
    head, found, rest = text.partition(" = ")
    match = _OPCODE.search(rest) if found else None
    return head.lstrip("%"), match.group(1) if match else None


def _op_events(space: _xp.XSpace, scopes: dict):
    """Yield ``(event, scope, comm_kind)`` for every execution-looking
    event: the ``XLA Ops`` line of a device plane, plus any event
    carrying an ``hlo_op`` stat (the CPU backend's executor threads live
    on the host plane).  An asynchronous collective is one event, in
    flight from the start of its ``-start`` to the end of the next
    ``-done`` of its kind.
    """
    for plane in space.planes:
        on_device = plane.name.startswith("/device:")
        for line in plane.lines:
            # A device plane's other lines (Steps, XLA Modules, Async
            # XLA Ops, TC Overlay, ...) restate the op timeline —
            # counting them doubles everything.
            if on_device and line.name != "XLA Ops":
                continue
            started: dict = {}      # kind -> the -start events in flight
            for ev in sorted(line.events, key=lambda e: e.start_ps):
                if ev.duration_ps <= 0:
                    continue
                hlo_op = ev.stats.get("hlo_op")
                if not on_device and not hlo_op:
                    continue
                name, opcode = _name_and_opcode(ev.name)
                key = hlo_op if isinstance(hlo_op, str) else name
                if (opcode or key.split(".")[0]) in ("call", "while",
                                                     "conditional"):
                    # whole-computation wrapper thunks: their span COVERS
                    # the inner ops (comm included) — counting them as
                    # compute would report every collective as "hidden"
                    continue
                op_name = scopes.get(key) or scopes.get(name) or ""
                scope = _scope_of(op_name)
                if opcode is None:
                    tf_op = ev.stats.get("tf_op")
                    kind = _comm_kind(
                        ev.name, key, op_name,
                        tf_op if isinstance(tf_op, str) else None)
                else:
                    # by opcode: a fusion that reads %all-reduce.1 is
                    # no communication
                    stem = opcode.removesuffix("-start") \
                        .removesuffix("-done")
                    kind = stem if stem in _KINDS else None
                    if kind and opcode.endswith("-start"):
                        started.setdefault(kind, []).append((ev, scope))
                        continue
                    if kind and opcode.endswith("-done"):
                        if not started.get(kind):
                            continue    # its -start is outside the capture
                        first, scope = started[kind].pop(0)
                        ev = _xp.XEvent(
                            first.name, first.start_ps,
                            ev.start_ps + ev.duration_ps - first.start_ps,
                            first.stats)
                yield ev, scope, kind


def _step_events(space: _xp.XSpace, step_name: str) -> list:
    """``(step_num, start_ps, end_ps)`` from StepTraceAnnotation spans.

    The annotation shows up as a host TraceMe named ``step_name`` with
    a ``step_num`` stat; TPU device planes restate it on a ``Steps``
    line.  Device ``Steps`` spans win when present — they bound actual
    device execution, while on an async backend the host span only
    brackets the dispatch and can end before the chip starts.  Host
    spans are the fallback (CPU captures have no device ``Steps`` line
    and execute synchronously inside the host span anyway).
    """
    host, device = [], []
    for plane in space.planes:
        on_device = plane.name.startswith("/device:")
        for line in plane.lines:
            for ev in line.events:
                if ev.duration_ps <= 0:
                    continue
                num = ev.stats.get("step_num")
                is_step = (ev.name == step_name
                           or (on_device and line.name == "Steps"))
                if not is_step or num is None:
                    continue
                try:
                    num = int(num)
                except (TypeError, ValueError):
                    continue
                (device if on_device else host).append(
                    (num, ev.start_ps, ev.start_ps + ev.duration_ps))
    # Every device plane restates the step on its own ``Steps`` line:
    # a process with D local devices would otherwise yield D
    # near-identical windows per step_num, and every summed total
    # (compute/comm/wall, steps count) would inflate ~D-fold.  Merge
    # windows sharing a step_num into one [min start, max end] span.
    merged: dict = {}
    for num, s, e in (device or host):
        if num in merged:
            s0, e0 = merged[num]
            merged[num] = (min(s0, s), max(e0, e))
        else:
            merged[num] = (s, e)
    return sorted((n, s, e) for n, (s, e) in merged.items())


# ---------------------------------------------------------------------------
# The attribution itself
# ---------------------------------------------------------------------------


def attribute(space: _xp.XSpace, flops_per_step: float | None = None,
              peak_flops: float | None = None,
              wire_bytes: float | None = None,
              step_name: str = "hvd_step") -> dict:
    """Per-step device-truth attribution for one capture.

    Returns a plain dict (JSON-ready)::

        {"steps": [{"step", "wall_s", "compute_s", "comm_s",
                    "comm_hidden_s", "comm_exposed_s", "overlap_eff",
                    "comm_by_kind": {...}, "scopes": {...}, "mfu"}],
         "totals": {... same keys summed/averaged ...},
         "op_events": N, "planes": [...], "truncated": bool,
         "scopes_resolved": N}

    With no step annotations in the capture the whole trace collapses
    to one synthetic step (``step = -1``) so totals still land.
    Never raises.
    """
    try:
        return _attribute(space, flops_per_step, peak_flops, wire_bytes,
                          step_name)
    except Exception as exc:  # background-analyzer contract
        return {"steps": [], "totals": {}, "op_events": 0,
                "planes": [p.name for p in getattr(space, "planes", [])],
                "truncated": True, "scopes_resolved": 0,
                "error": repr(exc)[:200]}


def _attribute(space, flops_per_step, peak_flops, wire_bytes, step_name):
    import bisect

    scopes = _xp.scope_map(space)
    events = sorted(_op_events(space, scopes),
                    key=lambda t: t[0].start_ps)
    steps = _step_events(space, step_name)
    if not steps:
        if events:
            lo = min(e.start_ps for e, _, _ in events)
            hi = max(e.start_ps + e.duration_ps for e, _, _ in events)
            steps = [(-1, lo, hi)]
        else:
            steps = []
    # A whole-run bridge capture can hold hundreds of annotated steps
    # over the same 100k+ op events; bound the per-step scan to events
    # that can overlap the window (sorted starts + the longest event
    # as the look-back slack) instead of rescanning everything.
    starts = [e.start_ps for e, _, _ in events]
    max_dur = max((e.duration_ps for e, _, _ in events), default=0)

    per_step = []
    for num, lo, hi in steps:
        comm_iv, compute_iv = [], []
        comm_by_kind: dict = {}
        scope_s: dict = {}
        first = bisect.bisect_left(starts, lo - max_dur)
        last = bisect.bisect_left(starts, hi)
        for ev, scope, kind in events[first:last]:
            s, e = ev.start_ps, ev.start_ps + ev.duration_ps
            if e <= lo or s >= hi:
                continue
            s, e = max(s, lo), min(e, hi)
            is_comm = kind is not None or (
                scope is not None and _COMM_SCOPE.match(scope))
            if is_comm:
                comm_iv.append([s, e])
                k = kind or "scoped-comm"
                kiv = comm_by_kind.setdefault(k, [])
                kiv.append([s, e])
            else:
                compute_iv.append([s, e])
            if scope:
                siv = scope_s.setdefault(scope, [])
                siv.append([s, e])
        comm_m = _merge(comm_iv)
        compute_m = _merge(compute_iv)
        comm_s = _total(comm_m) * _PS
        hidden_s = _total(_intersect(comm_m, compute_m)) * _PS
        wall_s = (hi - lo) * _PS
        entry = {
            "step": num,
            "wall_s": round(wall_s, 6),
            "compute_s": round(_total(compute_m) * _PS, 6),
            "comm_s": round(comm_s, 6),
            "comm_hidden_s": round(hidden_s, 6),
            "comm_exposed_s": round(comm_s - hidden_s, 6),
            "overlap_eff": (round(hidden_s / comm_s, 4) if comm_s > 0
                            else None),
            "comm_by_kind": {k: round(_total(_merge(v)) * _PS, 6)
                             for k, v in sorted(comm_by_kind.items())},
            "scopes": {k: round(_total(_merge(v)) * _PS, 6)
                       for k, v in sorted(scope_s.items())},
        }
        if flops_per_step and peak_flops and wall_s > 0:
            entry["mfu"] = round(flops_per_step / (peak_flops * wall_s), 4)
        per_step.append(entry)

    totals: dict = {}
    if per_step:
        n = len(per_step)
        for key in ("wall_s", "compute_s", "comm_s", "comm_hidden_s",
                    "comm_exposed_s"):
            totals[key] = round(sum(s[key] for s in per_step), 6)
            totals[f"{key}_per_step"] = round(totals[key] / n, 6)
        tc = totals["comm_s"]
        totals["overlap_eff"] = (round(totals["comm_hidden_s"] / tc, 4)
                                 if tc > 0 else None)
        mfus = [s["mfu"] for s in per_step if s.get("mfu") is not None]
        if mfus:
            totals["mfu"] = round(sum(mfus) / len(mfus), 4)
        if wire_bytes is not None:
            totals["wire_bytes"] = wire_bytes
            if tc > 0:
                totals["wire_gb_s"] = round(wire_bytes / tc / 1e9, 3)
        totals["steps"] = n
    return {
        "steps": per_step,
        "totals": totals,
        "op_events": len(events),
        "planes": [p.name for p in space.planes],
        "truncated": bool(space.truncated),
        "scopes_resolved": len(scopes),
    }
