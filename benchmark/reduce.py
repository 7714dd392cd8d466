"""From a profiler trace to the numbers the per-layer metrics read.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes, read with
``jax.profiler.ProfileData`` (nothing but JAX).  What a v5e trace holds
(looked at by hand, PR 22; PERF.md section 3 has the list): one plane
``/device:TPU:<n>`` per chip whose line ``XLA Ops`` has one event per
executed HLO instruction, named by the instruction's whole text
(``%fusion.7 = f32[16,1024,50257]{...} fusion(...), kind=...``) and
carrying no category; ``Steps``, ``XLA Modules`` and ``Async XLA Ops``
lines that restate the same time; and a ``/host:CPU`` plane with one
line per thread, where the ``TraceAnnotation`` spans of the benchmark's
loop land on the line ``python3``.  A Pallas kernel is a ``custom-call``
whose text holds ``custom_call_target="tpu_custom_call"``; the
program gives its kernels no name, so they cannot be told apart here.

Interval arithmetic is a copy of ``horovod_tpu/perf/attribution.py``'s
(merge, intersect), in nanoseconds.  Nothing here swallows an error: a
trace that cannot be reduced fails the run.
"""

from __future__ import annotations

import functools
import gzip
import re
from dataclasses import dataclass

OPS_LINE = "XLA Ops"
MOSAIC_TARGET = 'custom_call_target="tpu_custom_call"'
# instructions whose event covers the events of their body
WRAPPERS = ("while", "call", "conditional")
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")


_OPCODE = re.compile(r"([a-z][a-z0-9_\-]*)\(")
_LAYOUT = re.compile(r"\{[^{}]*\}")


@dataclass(frozen=True)
class Op:
    """One executed instruction on one chip; times in nanoseconds.
    ``text`` is the event's name: on a TPU the instruction's whole text
    (``%fusion.12 = f32[8]{0} fusion(...), kind=...``), on the CPU its
    bare name (``fusion.12``)."""
    text: str
    start: int
    end: int

    @functools.cached_property
    def _parts(self) -> tuple:
        """(name, opcode, signature), parsed once."""
        head, found, rest = self.text.partition(" = ")
        name = head.lstrip("%")
        match = _OPCODE.search(rest) if found else None
        if match is None:
            return name, name.split(".")[0], name
        shape = _LAYOUT.sub("", rest[:match.start()]).strip()
        return name, match.group(1), f"{match.group(1)} {shape}"

    @property
    def name(self) -> str:
        """``fusion.12``."""
        return self._parts[0]

    @property
    def opcode(self) -> str:
        """``fusion``, ``custom-call``, ``all-reduce-start`` ...: the word
        before the operands; of a bare name, the name without its
        number."""
        return self._parts[1]

    @property
    def signature(self) -> str:
        """Opcode and result shape without layouts: what the repeats of
        one operation (one per layer, one per step) have in common."""
        return self._parts[2]

    @property
    def is_kernel(self) -> bool:
        return MOSAIC_TARGET in self.text


def load(path: str):
    """A trace file as ``ProfileData``: ``.xplane.pb`` as the profiler
    wrote it, or the text form of the same message, gzipped (the recorded
    trace the tests hold the reduction to)."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path, "rt", encoding="utf-8") as f:
            return ProfileData.from_text_proto(f.read())
    return ProfileData.from_file(path)


def device_ops(profile) -> dict:
    """``{plane name: [Op, ...]}`` for every chip that ran something,
    each list sorted by start.  A backend without device planes (the CPU
    the tests run on) has its executor's events, which carry an
    ``hlo_op`` stat, on host threads: they stand in as one device."""
    chips: dict = {}
    for plane in profile.planes:
        on_device = plane.name.startswith("/device:")
        for line in plane.lines:
            if on_device and line.name != OPS_LINE:
                continue
            for event in line.events:
                if event.duration_ns <= 0:
                    continue
                if not on_device and not any(
                        key == "hlo_op" for key, _ in event.stats):
                    continue
                start = int(event.start_ns)
                chips.setdefault(plane.name, []).append(Op(
                    event.name, start, start + int(event.duration_ns)))
    if any(name.startswith("/device:") for name in chips):
        chips = {k: v for k, v in chips.items() if k.startswith("/device:")}
    return {name: sorted(ops, key=lambda op: op.start)
            for name, ops in chips.items()}


def host_spans(profile, names: tuple) -> list:
    """``[(name, start, end), ...]`` of the host's annotations called
    ``names``, sorted by start."""
    spans = []
    for plane in profile.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for event in line.events:
                if event.name in names and event.duration_ns > 0:
                    start = int(event.start_ns)
                    spans.append((event.name, start,
                                  start + int(event.duration_ns)))
    return sorted(spans, key=lambda s: s[1])


# ---------------------------------------------------------------------------
# Interval arithmetic
# ---------------------------------------------------------------------------


def merge(intervals: list) -> list:
    out: list = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return out


def total(merged: list) -> int:
    return sum(end - start for start, end in merged)


def intersect(a: list, b: list) -> list:
    """Of two merged lists."""
    out = []
    i = j = 0
    while i < len(a) and j < len(b):
        start = max(a[i][0], b[j][0])
        end = min(a[i][1], b[j][1])
        if start < end:
            out.append([start, end])
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


# ---------------------------------------------------------------------------
# One chip's operations
# ---------------------------------------------------------------------------


def leaves(ops: list) -> list:
    """Without the instructions whose event only covers their body."""
    return [op for op in ops if op.opcode not in WRAPPERS]


def window(ops: list) -> tuple:
    """From the first operation's start to the last one's end."""
    return min(op.start for op in ops), max(op.end for op in ops)


def busy(ops: list) -> list:
    """The merged intervals in which an operation ran."""
    return merge([[op.start, op.end] for op in ops])


def idle_gaps(ops: list) -> list:
    """``[(start, end), ...]`` inside the window in which none ran,
    longest first."""
    merged = busy(ops)
    gaps = [(a[1], b[0]) for a, b in zip(merged, merged[1:])]
    return sorted(gaps, key=lambda g: g[0] - g[1])


def collective_kind(op: Op) -> str | None:
    for kind in COLLECTIVES:
        if op.opcode in (kind, kind + "-start", kind + "-done"):
            return kind
    return None


def collective_intervals(ops: list) -> list:
    """Merged intervals in which a collective was in flight.  A
    synchronous one is its own event; an asynchronous one runs from the
    start of its ``-start`` to the end of the next ``-done`` of its
    kind (first in, first out)."""
    spans, pending = [], {}
    for op in ops:
        kind = collective_kind(op)
        if kind is None:
            continue
        if op.opcode.endswith("-start"):
            pending.setdefault(kind, []).append(op.start)
        elif op.opcode.endswith("-done"):
            if not pending.get(kind):
                raise ValueError(f"{op.name} at {op.start} ns closes no "
                                 f"{kind}-start in the traced window")
            spans.append([pending[kind].pop(0), op.end])
        else:
            spans.append([op.start, op.end])
    return merge(spans)


def exposed_collective_ns(ops: list) -> int:
    """Time a collective was in flight and no other operation ran."""
    compute = merge([[op.start, op.end] for op in leaves(ops)
                     if collective_kind(op) is None])
    flight = collective_intervals(ops)
    return total(flight) - total(intersect(flight, compute))


def kernel_ns(ops: list) -> int:
    """Time in Mosaic (Pallas) kernels."""
    return sum(op.end - op.start for op in ops if op.is_kernel)


def seconds_by_signature(ops: list) -> list:
    """``[[label, seconds], ...]`` over the leaves, most time first.
    Operations with one signature add up (the same fusion in every
    layer and step); the label is the signature, one instruction that
    has it, and how many events do."""
    sums: dict = {}
    for op in leaves(ops):
        entry = sums.setdefault(op.signature, [0, 0, op.name])
        entry[0] += op.end - op.start
        entry[1] += 1
    return [[f"{signature[:120]} [{name}, x{count}]", ns * 1e-9]
            for signature, (ns, count, name) in
            sorted(sums.items(), key=lambda kv: -kv[1][0])]


def label_gaps(gaps: list, spans: list) -> list:
    """``[[label, seconds], ...]``: each idle gap under the host
    annotation that covers most of it (the innermost wins a tie, being
    later in ``spans``), or ``"no annotation"``."""
    out = []
    for start, end in gaps:
        best, covered = "no annotation", 0
        for name, s, e in spans:
            overlap = min(end, e) - max(start, s)
            if overlap >= covered and overlap > 0:
                best, covered = name, overlap
        out.append([best, (end - start) * 1e-9])
    return out


@dataclass(frozen=True)
class Trace:
    """What a per-layer metric's reader is handed."""
    chips: dict       # plane name -> [Op, ...] sorted by start
    spans: list       # the loop's host annotations, (name, start, end)
    steps: int        # train steps inside the traced window

    def mean(self, fn) -> float:
        """``fn(ops)`` averaged over the chips."""
        return sum(fn(ops) for ops in self.chips.values()) / len(self.chips)


def read_trace(path: str, steps: int, span_names: tuple) -> Trace:
    profile = load(path)
    chips = device_ops(profile)
    if not chips:
        raise ValueError(f"{path}: no operation ran on a device in the "
                         f"traced window; planes "
                         f"{[p.name for p in profile.planes]}")
    return Trace(chips, host_spans(profile, span_names), steps)
