"""The names the program gives its own parts, joined to a traced run.

**Scopes.**  The compiled train step names its parts with
``jax.named_scope`` and a kernel's ``name=`` (``hvd_attn``,
``hvd_loss_head``, ``hvd_grad_reduce``, ``hvd_optimizer``,
``hvd_flash_fwd`` / ``_bwd_dq`` / ``_bwd_dkv``; docs/perf.md has the
list), and JAX marks the passes itself: every instruction's
``op_name`` reads ``jit(step)/jvp(hvd_attn)/dot_general`` in the
forward pass, ``jit(step)/transpose(jvp(hvd_attn))/...`` in the
backward pass and ``jit(step)/hvd_optimizer/mul`` after the gradients.
A v5e trace's ``XLA Ops`` events carry no ``op_name``, but each is
named by its instruction, and the trace's ``/host:metadata`` plane
holds the ``HloProto`` of the very module that ran (which
``jax.profiler.ProfileData`` shows as a plane without lines; the wire
format is read here, as ``horovod_tpu/perf/xplane.py`` reads it).  So
instruction name -> ``op_name`` is a join inside one trace file, and
the harness, which hands a reader no compiled text, need not change.

**An instruction's op_name** is its own, except where it calls other
computations, as a fusion does: then the instruction and every
instruction of the called computations vote with their class — the
part (below) and the ``hvd_*`` scopes their ``op_name`` shows.  Where a
matrix product or a convolution is among them it decides alone: the
rest is its prologue and epilogue (ResNet's weight-gradient
convolutions end in the optimizer's ``-lr * g``, and are the backward
pass's).  A fusion without one is a pass over memory.  XLA fuses
producers into their consumers, and the step's values flow forward ->
backward -> gradient reduction -> optimizer, so such a fusion is of the
latest of these parts that any voter shows: it writes that part's
results.  (By count, the Adam update of GPT-2's stacked layer weights,
which also pads and adds up the twelve layers' gradients, would be the
backward pass's, 35 votes to 25, and a backward fusion that recomputes
forward values the forward pass's.)  Among the voters of that part the
scopes most of them show win, the root's on a tie, and the fusion takes
the ``op_name`` of the first such voter.  An ``op_name`` that shows no
scope and no pass does not vote: ResNet's SGD fusion holds ``-lr * g``
from ``hvd_optimizer`` and ``p + u`` from the family's own
``apply_updates`` under no name, and is the optimizer's.

**Parts.**  Each traced leaf operation belongs to one part of the step,
the first that fits: ``grad_reduce`` (scope ``hvd_grad_reduce``),
``optimizer`` (scope ``hvd_optimizer``), ``bwd`` (``transpose(`` in the
``op_name``), ``fwd`` (``jvp(``), else ``unscoped`` (compiler copies,
async waits, what a family adds after the gradients).  The five
partition the leaves' busy time: where operations overlap, as on the
CPU's thread pool, an instant belongs to the first part that runs in
it.  A scope's share (``hvd_attn``, ``hvd_loss_head``) cuts across the
passes and is no part of that sum.  A program that names none of its
parts (the parent of PR 23) gives no part and no share.

**Spans.**  ``hvd.init()`` runs its phases under flight-recorder spans
(``hvd_init`` around ``hvd_init.distributed`` / ``.backend`` /
``.topology`` / ``.meshes`` / ``.planes`` / ``.runtime``);
``init_spans`` reads them from this process's ring.

``python -m benchmark.scopes <trace.xplane.pb> [<names.json.gz>]``
prints a trace's parts, scopes and largest unscoped operations, and
writes the instruction -> ``op_name`` map a recorded trace is kept
beside (tests/benchmark_suite/data).
"""

from __future__ import annotations

import collections
import functools
import glob
import gzip
import json
import os
import re
import sys

from benchmark import harness, manifest, reduce

PARTS = ("grad_reduce", "optimizer", "bwd", "fwd", "unscoped")
# the parts in the order the step runs them: a fusion is of the latest
STAGES = ("fwd", "bwd", "grad_reduce", "optimizer")
# opcodes of a matrix product or convolution: it decides its fusion
PRODUCTS = ("convolution", "dot")
FLASH_KERNELS = ("hvd_flash_fwd", "hvd_flash_bwd_dq", "hvd_flash_bwd_dkv")
METADATA_PLANE = "/host:metadata"

_SCOPE = re.compile(r"hvd_\w+")


# ---------------------------------------------------------------------------
# Classes of an op_name
# ---------------------------------------------------------------------------


def scopes_of(op_name: str) -> tuple:
    """Every ``hvd_*`` component, outermost first."""
    return tuple(_SCOPE.findall(op_name))


def part_of(op_name: str) -> str:
    scopes = scopes_of(op_name)
    if "hvd_grad_reduce" in scopes:
        return "grad_reduce"
    if "hvd_optimizer" in scopes:
        return "optimizer"
    if "transpose(" in op_name:
        return "bwd"
    if "jvp(" in op_name:
        return "fwd"
    return "unscoped"


def _class_of(op_name: str) -> tuple:
    return part_of(op_name), scopes_of(op_name)


_NO_CLASS = ("unscoped", ())


# ---------------------------------------------------------------------------
# The protobuf wire format, as far as a trace's HloProto needs it
# ---------------------------------------------------------------------------


def _varint(data: bytes, at: int) -> tuple:
    value = shift = 0
    while True:
        byte = data[at]
        at += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, at
        shift += 7


def _fields(data: bytes, start: int, end: int):
    """``(field number, wire type, value)`` of one message; the value of
    a length-delimited field is its ``(start, end)`` in ``data``, of a
    fixed-width one ``None``."""
    at = start
    while at < end:
        tag, at = _varint(data, at)
        number, wire = tag >> 3, tag & 7
        if wire == 0:
            value, at = _varint(data, at)
        elif wire == 2:
            size, at = _varint(data, at)
            value, at = (at, at + size), at + size
        elif wire in (1, 5):
            value, at = None, at + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"wire type {wire} at byte {at}: not a "
                             f"protobuf message")
        yield number, wire, value
    if at != end:
        raise ValueError(f"a field runs past the end of its message "
                         f"({at} > {end})")


def _children(data: bytes, span: tuple, number: int) -> list:
    """The length-delimited fields ``number`` of the message at
    ``span``."""
    return [value for n, wire, value in _fields(data, *span)
            if n == number and wire == 2]


def _text(data: bytes, span: tuple) -> str:
    return data[span[0]:span[1]].decode("utf-8")


def hlo_modules(data: bytes) -> list:
    """The ``HloModuleProto`` of every module a trace file holds, as
    ``(start, end)`` spans of ``data`` (a serialized ``XSpace``):
    ``planes[name == "/host:metadata"].event_metadata[*].stats[*]
    .bytes_value`` is an ``HloProto`` whose field 1 is the module."""
    modules = []
    for plane in _children(data, (0, len(data)), 1):           # XSpace.planes
        names = _children(data, plane, 2)
        if not names or _text(data, names[0]) != METADATA_PLANE:
            continue
        for entry in _children(data, plane, 4):       # event_metadata map
            for event in _children(data, entry, 2):   # its XEventMetadata
                for stat in _children(data, event, 5):
                    for proto in _children(data, stat, 6):
                        modules += _children(data, proto, 1)
    return modules


def _instruction(data: bytes, span: tuple) -> tuple:
    """``(id, name, opcode, op_name, called computation ids)`` of one
    ``HloInstructionProto``."""
    ident, name, opcode, op_name, called = None, "", "", "", []
    for number, wire, value in _fields(data, *span):
        if number == 1 and wire == 2:
            name = _text(data, value)
        elif number == 2 and wire == 2:
            opcode = _text(data, value)
        elif number == 35 and wire == 0:
            ident = value
        elif number == 7 and wire == 2:               # OpMetadata
            for found in _children(data, value, 2):
                op_name = _text(data, found)
        elif number == 38 and wire == 0:
            called.append(value)
        elif number == 38 and wire == 2:              # packed
            at = value[0]
            while at < value[1]:
                ident_called, at = _varint(data, at)
                called.append(ident_called)
    return ident, name, opcode, op_name, called


def module_op_names(data: bytes, module: tuple) -> dict:
    """``{instruction name: op_name}`` of one module, an instruction
    that calls computations voting with their members (module
    docstring)."""
    computations = {}       # id -> ([instruction, ...], root id)
    for span in _children(data, module, 3):
        ident = root = None
        for number, wire, value in _fields(data, *span):
            if number == 5 and wire == 0:
                ident = value
            elif number == 6 and wire == 0:
                root = value
        computations[ident] = ([_instruction(data, s)
                                for s in _children(data, span, 2)], root)
    names = {}
    for instructions, _ in computations.values():
        for _, name, opcode, op_name, called in instructions:
            voters = [(op_name, False, opcode in PRODUCTS)]
            for ident in called:
                members, root = computations.get(ident, ((), None))
                voters += [(m[3], m[0] == root, m[2] in PRODUCTS)
                           for m in members]
            names[name] = elect(voters) if called else op_name
    return names


def elect(voters: list) -> str:
    """The ``op_name`` that stands for ``[(op_name, is the root, is a
    product), ...]``, the instruction itself first (module docstring).
    Without a voter that shows a class, the instruction's own."""
    shown = [(voter, _class_of(voter[0])) for voter in voters]
    shown = [(voter, c) for voter, c in shown if c != _NO_CLASS]
    shown = [(voter, c) for voter, c in shown if voter[2]] or shown
    if not shown:
        return voters[0][0]
    latest = max((c[0] for _, c in shown), key=STAGES.index)
    votes = collections.Counter(c for _, c in shown if c[0] == latest)
    most = max(votes.values())
    tied = [c for c, n in votes.items() if n == most]
    of_root = [c for voter, c in shown if voter[1] and c in tied]
    winner = (of_root or tied)[0]
    return next(voter[0] for voter, c in shown if c == winner)


def load(path: str) -> dict | None:
    """``{instruction name: op_name}`` of a traced run, from the trace
    file itself (``.xplane.pb``) or from the map a recorded trace is
    kept beside (``.json.gz``).  Where several modules ran in the traced
    window the largest names an instruction first.  ``None`` where no
    ``op_name`` shows an ``hvd_*`` scope: the program does not name its
    parts, and nothing is read from it."""
    return _load(path, os.path.getmtime(path), os.path.getsize(path))


@functools.lru_cache(maxsize=4)
def _load(path: str, _mtime: float, _size: int) -> dict | None:
    """``load``, once for each state of the file: every reader asks."""
    if path.endswith(".json.gz"):
        with gzip.open(path, "rt", encoding="utf-8") as f:
            names = json.load(f)
    else:
        with open(path, "rb") as f:
            data = f.read()
        names = {}
        for module in sorted(hlo_modules(data), key=lambda m: m[0] - m[1]):
            for name, op_name in module_op_names(data, module).items():
                names.setdefault(name, op_name)
    if not any(scopes_of(op_name) for op_name in names.values()):
        return None
    return names


def names_of(cell: manifest.Cell) -> dict | None:
    """``load`` of the trace the harness left under the cell's output
    directory; ``None`` without one."""
    found = glob.glob(os.path.join(cell.out_dir, "trace", "**",
                                   "*.xplane.pb"), recursive=True)
    return load(found[0]) if len(found) == 1 else None


# ---------------------------------------------------------------------------
# One chip's operations under their names
# ---------------------------------------------------------------------------


def part_ns(ops: list, names: dict) -> dict:
    """``{part: nanoseconds}`` over the leaves; the five add up to the
    leaves' busy time."""
    spans = {part: [] for part in PARTS}
    for op in reduce.leaves(ops):
        spans[part_of(names.get(op.name, ""))].append([op.start, op.end])
    claimed, out = [], {}
    for part in PARTS:
        mine = reduce.merge(spans[part])
        out[part] = reduce.total(mine) - reduce.total(
            reduce.intersect(mine, claimed))
        claimed = reduce.merge(claimed + mine)
    return out


def scope_ns(ops: list, names: dict, scope: str) -> int:
    """Time in which a leaf under ``scope`` ran, either pass."""
    return reduce.total(reduce.merge(
        [[op.start, op.end] for op in reduce.leaves(ops)
         if scope in scopes_of(names.get(op.name, ""))]))


def kernel_ns(ops: list, names: dict, kernel: str) -> int:
    """Time in the Mosaic calls named ``kernel`` (not in a copy the
    compiler puts before one under the same ``op_name``)."""
    return sum(op.end - op.start for op in ops
               if op.is_kernel and kernel in scopes_of(names.get(op.name, "")))


def _busy_ns(ops: list) -> int:
    return reduce.total(reduce.busy(reduce.leaves(ops)))


def part_share(trace: reduce.Trace, cell: manifest.Cell,
               part: str) -> float | None:
    """A part's share of the leaves' busy time, averaged over the chips:
    what ``fwd_share`` ... ``unscoped_share`` read."""
    names = names_of(cell)
    if names is None:
        return None
    return trace.mean(lambda ops: part_ns(ops, names)[part] / _busy_ns(ops))


def scope_share(trace: reduce.Trace, cell: manifest.Cell,
                scope: str) -> float | None:
    """What ``attn_share`` and ``loss_head_share`` read.  ``None`` where
    the step holds no operation under ``scope``."""
    names = names_of(cell)
    if names is None or not any(scope in scopes_of(n)
                                for n in names.values()):
        return None
    return trace.mean(lambda ops: scope_ns(ops, names, scope)
                      / _busy_ns(ops))


# ---------------------------------------------------------------------------
# The three flash-attention kernels
# ---------------------------------------------------------------------------


def flash_costs(cell: manifest.Cell) -> dict:
    """What one train step requires of each flash-attention kernel, over
    all layers, per chip: ``{kernel: {"flops", "bytes"}}``; the three
    add up to the family's ``kernel_costs()["flash_attn"]``.

    FLOPs: the seven causal products a layer shared out 2 / 2 / 3.  The
    forward kernel computes QK^T and PV.  ``flash_bwd_dq`` computes
    dS K, ``flash_bwd_dkv`` P^T dO and dS^T Q, and each of the two
    recomputes QK^T and dO V^T, which are required once: half of both
    is charged to each.  Bytes: bf16 tensors of rows x seq x head_dim,
    each read or written once: forward q, k, v in and o out (4);
    backward q, k, v, o, dO in, halved between the two kernels that
    both read them (2.5 each), dq out (1) and dk, dv out (2); the f32
    row statistics written forward (1) and read backward (half
    each)."""
    family = manifest.load_family(cell)
    s, job = family._sizes(cell.config), cell.job
    seq, mesh = job["seq"], job["mesh"]
    rows = job["batch_per_chip"] * s["n_heads"] // mesh["tp"]
    layers = s["n_layers"] // mesh["pp"]
    product = float(rows * s["head_dim"] * seq * (seq + 1))
    tensor, stats = 2 * rows * seq * s["head_dim"], 4 * rows * seq
    shared = {"hvd_flash_fwd": (2, 4, 1), "hvd_flash_bwd_dq": (2, 3.5, 0.5),
              "hvd_flash_bwd_dkv": (3, 4.5, 0.5)}
    return {kernel: {"flops": layers * products * product,
                     "bytes": layers * (tensors * tensor + rows_ * stats)}
            for kernel, (products, tensors, rows_) in shared.items()}


def kernel_roofline(trace: reduce.Trace, counters: dict,
                    cell: manifest.Cell, kernel: str) -> float | None:
    """The least time the chip could take for what a step requires of
    ``kernel`` (the larger of FLOPs / peak FLOP/s and bytes / peak B/s)
    over the time its calls took, in percent.  ``None`` where no
    operation under that name ran."""
    names = names_of(cell)
    if names is None:
        return None
    kernel_s = trace.mean(lambda ops: kernel_ns(ops, names, kernel)) \
        * 1e-9 / trace.steps
    if kernel_s == 0:
        return None
    cost, peaks = flash_costs(cell)[kernel], counters["peaks"]
    least_s = max(cost["flops"] / peaks["bf16_flops_per_s"],
                  cost["bytes"] / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / kernel_s


# ---------------------------------------------------------------------------
# The spans of hvd.init()
# ---------------------------------------------------------------------------


def init_spans() -> dict | None:
    """``{span name: seconds}`` of this process's last whole
    ``hvd_init`` span and the ``hvd_init.*`` spans inside it (two of one
    name add up), from the flight recorder's ring.  ``None`` where the
    program records no such span, or in a process that ran no
    ``hvd.init()`` (the parent of a launched world)."""
    flight = sys.modules.get("horovod_tpu.runtime.flight")
    if flight is None:
        return None
    begun, whole = {}, []       # id -> B event; (B, E) of each closed span
    for event in flight.recorder().snapshot():
        if not event["kind"].startswith("hvd_init"):
            continue
        if event["ph"] == "B":
            begun[event["id"]] = event
        elif event["ph"] == "E" and event["id"] in begun:
            whole.append((begun.pop(event["id"]), event))
    outer = [pair for pair in whole if pair[0]["kind"] == "hvd_init"]
    if not outer:
        return None
    first, last = outer[-1][0]["mono"], outer[-1][1]["mono"]
    seconds: dict = collections.defaultdict(float)
    for begin, end in whole:
        if first <= begin["mono"] and end["mono"] <= last:
            seconds[begin["kind"]] += end["mono"] - begin["mono"]
    return dict(seconds)


def init_seconds(*kinds: str) -> float | None:
    """The spans ``kinds`` of ``init_spans`` added up; one a world of one
    does not open counts 0."""
    spans = init_spans()
    if spans is None:
        return None
    return sum(spans.get(kind, 0.0) for kind in kinds)


# ---------------------------------------------------------------------------
# By hand
# ---------------------------------------------------------------------------


def describe(trace: reduce.Trace, names: dict) -> dict:
    """Of the first chip: the parts and scopes as shares of busy time,
    each flash kernel's seconds a step, and the ten ``unscoped``
    operations with most time."""
    ops = next(iter(trace.chips.values()))
    busy = _busy_ns(ops)
    scopes = sorted({s for n in names.values() for s in scopes_of(n)})
    unscoped = [op for op in reduce.leaves(ops)
                if part_of(names.get(op.name, "")) == "unscoped"]
    return {
        "busy_s_per_step": busy * 1e-9 / trace.steps,
        "parts": {part: ns / busy
                  for part, ns in part_ns(ops, names).items()},
        "scopes": {s: scope_ns(ops, names, s) / busy for s in scopes},
        "kernel_s_per_step": {k: kernel_ns(ops, names, k) * 1e-9 / trace.steps
                              for k in FLASH_KERNELS},
        "unscoped_ops": [[label, seconds / trace.steps] for label, seconds
                         in reduce.seconds_by_signature(unscoped)[:10]],
    }


def main(argv: list) -> int:
    source, *target = argv
    names = load(source)
    if names is None:
        print(f"{source}: no op_name shows an hvd_* scope", file=sys.stderr)
        return 1
    trace = reduce.read_trace(source, harness.TRACED_STEPS, harness.SPANS)
    print(json.dumps(describe(trace, names), indent=1))
    if target:
        traced = {op.name for ops in trace.chips.values() for op in ops}
        with gzip.open(target[0], "wt", encoding="utf-8") as f:
            json.dump({name: names[name] for name in sorted(traced)
                       if name in names}, f, indent=0)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
