"""Device-truth perf observatory (docs/perf.md).

Covers the stdlib xplane wire-format reader (synthetic fixtures for
varint edges, nested scopes, and truncation — the parser must degrade
to partial results, never raise out of the background analyzer), a
real ``jax.profiler`` capture on CPU (the ``test_eager_single.py``
``test_jax_profiler_capture`` pattern, but read BACK), the sampled
continuous-capture hook with its rotation and gauges, and the profiler
bridge's elastic re-init lifecycle.
"""

from __future__ import annotations

import json
import os
import struct
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from horovod_tpu.perf import attribution as A  # noqa: E402
from horovod_tpu.perf import report as R  # noqa: E402
from horovod_tpu.perf import xplane as X  # noqa: E402


# ---------------------------------------------------------------------------
# Protobuf wire-format encoder (test-side golden writer)
# ---------------------------------------------------------------------------


def _uv(v: int) -> bytes:
    out = bytearray()
    while True:
        b = v & 0x7F
        v >>= 7
        if v:
            out.append(b | 0x80)
        else:
            out.append(b)
            return bytes(out)


def V(f: int, v: int) -> bytes:
    """Varint field; negatives use the proto int64 10-byte form."""
    if v < 0:
        v += 1 << 64
    return _uv(f << 3) + _uv(v)


def LD(f: int, payload: bytes) -> bytes:
    return _uv((f << 3) | 2) + _uv(len(payload)) + payload


def F64(f: int, x: float) -> bytes:
    return _uv((f << 3) | 1) + struct.pack("<d", x)


def S(f: int, s: str) -> bytes:
    return LD(f, s.encode())


def _stat_meta(mid: int, name: str) -> bytes:
    return LD(5, V(1, mid) + LD(2, V(1, mid) + S(2, name)))


def _event_meta(mid: int, name: str, blob: bytes = b"") -> bytes:
    body = V(1, mid) + S(2, name)
    if blob:
        body += LD(3, blob)
    return LD(4, V(1, mid) + LD(2, body))


def _event(mid: int, off_ps: int, dur_ps: int, stats: bytes = b"") -> bytes:
    return LD(4, V(1, mid) + V(2, off_ps) + V(3, dur_ps) + stats)


def _line(name: str, ts_ns: int, events: bytes) -> bytes:
    return LD(3, V(1, 1) + S(2, name) + V(3, ts_ns) + events)


def _plane(name: str, body: bytes) -> bytes:
    return LD(1, S(2, name) + body)


US = 1_000_000  # ps per us


def _device_fixture() -> bytes:
    """Synthetic TPU-shaped capture: one device plane with one comm op
    (all-gather, 0-100us) and one compute op (fusion, 50-150us under a
    nested hvd scope), plus a host plane with an hvd_step annotation
    spanning 0-200us (step_num=7)."""
    # instruction protos for the scope map: {1: name, 7: {2: op_name}}
    instr = LD(2, S(1, "fusion.1") + S(2, "fusion")
               + LD(7, S(2, "jit(f)/jit(main)/hvd_overlap_math1/"
                            "nested/mul")))
    instr2 = LD(2, S(1, "all-gather.3") + S(2, "all-gather")
                + LD(7, S(2, "jit(f)/jit(main)/hvd_overlap_ag1/"
                             "all_gather")))
    module = LD(1, LD(3, S(1, "main") + instr + instr2))
    meta_plane = _plane("/host:metadata",
                        _event_meta(1, "jit_f(1)", module))
    dev = _plane(
        "/device:TPU:0",
        _event_meta(10, "all-gather.3") + _event_meta(11, "fusion.1")
        + _line("XLA Ops", 1000,
                _event(10, 0, 100 * US) + _event(11, 50 * US, 100 * US)))
    host = _plane(
        "/host:CPU",
        _event_meta(20, "hvd_step") + _stat_meta(3, "step_num")
        + _line("python", 1000,
                _event(20, 0, 200 * US, LD(4, V(1, 3) + V(4, 7)))))
    return meta_plane + dev + host


def test_parse_synthetic_device_fixture():
    space = X.parse_xspace(_device_fixture())
    assert not space.truncated
    names = [p.name for p in space.planes]
    assert names == ["/host:metadata", "/device:TPU:0", "/host:CPU"]
    dev = space.plane("/device:TPU:0")
    assert dev.event_names[10] == "all-gather.3"
    (line,) = dev.lines
    assert line.name == "XLA Ops" and len(line.events) == 2
    # absolute times: line ts 1000ns -> 1e6 ps base
    assert line.events[0].start_ps == 1000 * 1000


def test_scope_map_nested_scopes():
    space = X.parse_xspace(_device_fixture())
    scopes = X.scope_map(space)
    assert scopes["fusion.1"].endswith("hvd_overlap_math1/nested/mul")
    # nested path still resolves to the outermost hvd_* component
    assert A._scope_of(scopes["fusion.1"]) == "hvd_overlap_math1"
    assert A._scope_of(scopes["all-gather.3"]) == "hvd_overlap_ag1"
    assert A._scope_of("jit(f)/no_scope/mul") is None


def test_attribute_overlap_hidden_exposed():
    """comm 0-100us, compute 50-150us, step 0-200us: 50us hidden,
    50us exposed, overlap efficiency 0.5 — the interval-intersection
    semantics the PR 5/7 schedules are judged by."""
    res = A.attribute(X.parse_xspace(_device_fixture()))
    (step,) = res["steps"]
    assert step["step"] == 7
    assert step["wall_s"] == pytest.approx(200e-6)
    assert step["comm_s"] == pytest.approx(100e-6)
    assert step["comm_hidden_s"] == pytest.approx(50e-6)
    assert step["comm_exposed_s"] == pytest.approx(50e-6)
    assert step["overlap_eff"] == pytest.approx(0.5)
    assert step["compute_s"] == pytest.approx(100e-6)
    assert step["comm_by_kind"] == {"all-gather": pytest.approx(100e-6)}
    assert step["scopes"]["hvd_overlap_ag1"] == pytest.approx(100e-6)
    assert res["scopes_resolved"] >= 2


def test_attribute_mfu():
    res = A.attribute(X.parse_xspace(_device_fixture()),
                      flops_per_step=1e9, peak_flops=1e13)
    # 1e9 flops over 200us at 1e13 peak -> 0.5 MFU
    assert res["steps"][0]["mfu"] == pytest.approx(0.5)
    assert res["totals"]["mfu"] == pytest.approx(0.5)


def test_a_v5e_event_is_read_by_its_head_not_by_substring():
    """A TPU device plane names an event by the instruction's whole
    text: the name and the opcode come from its head, so the scope map
    hits and a fusion that merely reads ``%all-reduce.1`` is compute;
    the lines that restate ``XLA Ops`` are not counted again; an
    asynchronous collective is in flight from ``-start`` to ``-done``."""
    fusion = ("%fusion.7 = f32[8]{0:T(8)} fusion(f32[8]{0} %all-reduce.1), "
              "kind=kLoop, calls=%fused_computation.7")
    start = ("%all-gather-start.2 = (f32[8]{0}, f32[32]{0}) "
             "all-gather-start(f32[8]{0} %fusion.7), dimensions={0}")
    done = ("%all-gather-done.2 = f32[32]{0} all-gather-done((f32[8]{0}, "
            "f32[32]{0}) %all-gather-start.2)")
    loop = "%while.3 = (s32[], f32[8]{0}) while((s32[], f32[8]{0}) %t)"
    assert A._name_and_opcode(fusion) == ("fusion.7", "fusion")
    assert A._name_and_opcode(start) == ("all-gather-start.2",
                                         "all-gather-start")
    assert A._name_and_opcode("fusion.7") == ("fusion.7", None)
    dev = _plane(
        "/device:TPU:0",
        _event_meta(1, fusion) + _event_meta(2, start)
        + _event_meta(3, done) + _event_meta(4, loop)
        + _line("XLA Ops", 0,
                _event(4, 0, 100 * US) + _event(2, 0, 1 * US)
                + _event(1, 10 * US, 20 * US) + _event(3, 39 * US, 1 * US))
        + _line("Async XLA Ops", 0, _event(2, 0, 40 * US))
        + _line("TC Overlay", 0, _event(1, 10 * US, 20 * US)))
    space = X.parse_xspace(dev)
    got = list(A._op_events(space, {"fusion.7": "jit(f)/hvd_optimizer/mul"}))
    assert [(e.start_ps, e.duration_ps, scope, kind)
            for e, scope, kind in got] == [
        (10 * US, 20 * US, "hvd_optimizer", None),
        (0, 40 * US, None, "all-gather")]
    (step,) = A.attribute(space)["steps"]
    assert step["compute_s"] == pytest.approx(20e-6)
    assert step["comm_s"] == pytest.approx(40e-6)
    assert step["comm_hidden_s"] == pytest.approx(20e-6)


def test_attribute_agrees_with_the_benchmarks_reduction_on_a_v5e_trace():
    """The program keeps this reducer and the benchmark its own
    (``benchmark/reduce.py``): on the recorded v5e trace of
    ``gpt2-124m.s8192`` both give the same compute, collective and
    exposed-collective seconds."""
    from benchmark import reduce

    path = os.path.join(REPO, "tests", "benchmark_suite", "data",
                        "trace_gpt2-124m.s8192_2steps.textproto.gz")
    space = X.XSpace()
    for plane in reduce.load(path).planes:
        xplane = X.XPlane(name=plane.name)
        for line in plane.lines:
            xplane.lines.append(X.XLine(name=line.name, events=[
                X.XEvent(e.name, int(e.start_ns) * 1000,
                         int(e.duration_ns) * 1000) for e in line.events]))
        space.planes.append(xplane)
    totals = A.attribute(space)["totals"]
    (ops,) = reduce.device_ops(reduce.load(path)).values()
    compute = reduce.total(reduce.merge(
        [[op.start, op.end] for op in reduce.leaves(ops)
         if reduce.collective_kind(op) is None]))
    assert compute > 4e9 and totals["steps"] == 1
    assert totals["compute_s"] == pytest.approx(compute * 1e-9, abs=1e-6)
    assert totals["comm_s"] == pytest.approx(
        reduce.total(reduce.collective_intervals(ops)) * 1e-9, abs=1e-6)
    assert totals["comm_exposed_s"] == pytest.approx(
        reduce.exposed_collective_ns(ops) * 1e-9, abs=1e-6)
    assert totals["comm_s"] > 0.1 > totals["comm_exposed_s"] > 0


def test_attribute_no_steps_synthesizes_window():
    dev = _plane(
        "/device:TPU:0",
        _event_meta(10, "all-reduce.1")
        + _line("XLA Ops", 0, _event(10, 0, 10 * US)))
    res = A.attribute(X.parse_xspace(dev))
    (step,) = res["steps"]
    assert step["step"] == -1
    assert step["comm_by_kind"] == {"all-reduce": pytest.approx(10e-6)}


def test_step_windows_dedupe_across_device_planes():
    """Every device plane restates the step on its own ``Steps`` line:
    a D-device process must yield ONE per-step entry (window = union of
    the planes' windows), not D near-duplicates inflating the totals."""
    def dev_plane(idx, step_end_us):
        stat = LD(4, V(1, 3) + V(4, 3))  # step_num = 3
        return _plane(
            f"/device:TPU:{idx}",
            _event_meta(10, "fusion.9") + _stat_meta(3, "step_num")
            + _line("XLA Ops", 1000, _event(10, 0, 100 * US))
            + _line("Steps", 1000, _event(10, 0, step_end_us * US, stat)))

    res = A.attribute(X.parse_xspace(dev_plane(0, 150) + dev_plane(1, 160)))
    (step,) = res["steps"]
    assert step["step"] == 3
    assert step["wall_s"] == pytest.approx(160e-6)
    assert res["totals"]["steps"] == 1


def test_varint_edge_cases():
    """Multi-byte varints, 2-byte tags (field > 15), negative int64,
    and 64-bit extremes all round-trip through the stat decoder."""
    cases = [0, 1, 127, 128, 300, 2 ** 32, 2 ** 63 - 1, -1, -(2 ** 62)]
    stats = b"".join(LD(4, V(1, 100 + i) + V(4, v))
                     for i, v in enumerate(cases))
    metas = b"".join(_stat_meta(100 + i, f"s{i}")
                     for i in range(len(cases)))
    plane = _plane("/device:TPU:0",
                   _event_meta(1, "op") + metas
                   + _line("XLA Ops", 0, _event(1, 1, 1, stats)))
    space = X.parse_xspace(plane)
    (ev,) = space.planes[0].lines[0].events
    for i, v in enumerate(cases):
        assert ev.stats[f"s{i}"] == v, (i, v, ev.stats)
    # high field number on the event itself parses and is ignored
    plane2 = _plane("/device:TPU:0",
                    _event_meta(1, "op")
                    + _line("XLA Ops", 0,
                            LD(4, V(1, 1) + V(2, 5) + V(3, 5)
                               + V(1000, 42))))
    space2 = X.parse_xspace(plane2)
    assert space2.planes[0].lines[0].events[0].duration_ps == 5


def test_stat_value_types():
    stats = (LD(4, V(1, 1) + F64(2, 2.5))        # double
             + LD(4, V(1, 2) + S(5, "text"))     # str
             + LD(4, V(1, 3) + V(7, 4)))         # ref -> stat name
    plane = _plane("/device:TPU:0",
                   _event_meta(9, "op") + _stat_meta(1, "d")
                   + _stat_meta(2, "s") + _stat_meta(3, "r")
                   + _stat_meta(4, "referenced-name")
                   + _line("XLA Ops", 0, _event(9, 0, 1, stats)))
    (ev,) = X.parse_xspace(plane).planes[0].lines[0].events
    assert ev.stats["d"] == pytest.approx(2.5)
    assert ev.stats["s"] == "text"
    assert ev.stats["r"] == "referenced-name"


def test_truncated_input_never_raises_and_keeps_partial():
    data = _device_fixture()
    full = A.attribute(X.parse_xspace(data))
    assert full["op_events"] == 2
    for cut in range(len(data)):
        space = X.parse_xspace(data[:cut])
        res = A.attribute(space)  # must never raise either
        assert isinstance(res, dict)
    # a cut mid-plane keeps the earlier planes
    half = X.parse_xspace(data[:len(data) // 2])
    assert half.truncated or len(half.planes) < 3


def test_truncated_mid_line_keeps_earlier_events():
    """A file cut inside an op line (where crashes usually truncate —
    op lines dominate the bytes) keeps the events parsed before the
    cut instead of dropping the whole line/plane."""
    ev1 = _event(10, 0, 5 * US)
    ev2 = _event(10, 10 * US, 5 * US)
    plane = _plane("/device:TPU:0",
                   _event_meta(10, "all-reduce.1")
                   + _line("XLA Ops", 0, ev1 + ev2))
    space = X.parse_xspace(plane[:len(plane) - 3])  # cut inside ev2
    assert space.truncated
    (line,) = space.planes[0].lines
    assert line.events and line.events[0].duration_ps == 5 * US


def test_garbage_input():
    for blob in (b"", b"\xff" * 64, b"\x00" * 64, os.urandom(256)):
        space = X.parse_xspace(blob)
        assert isinstance(space, X.XSpace)
    assert X.parse_xspace(b"\xff" * 64).truncated


def test_read_xspace_missing_file(tmp_path):
    space = X.read_xspace(str(tmp_path / "nope.xplane.pb"))
    assert space.truncated and space.errors


def test_comm_kind_patterns():
    assert A._comm_kind("all-reduce.5") == "all-reduce"
    assert A._comm_kind("fusion.2", "jit(f)/ppermute") \
        == "collective-permute"
    assert A._comm_kind("reduce-scatter.1") == "reduce-scatter"
    assert A._comm_kind("all-to-all.9") == "all-to-all"
    assert A._comm_kind("fusion.3", None) is None
    # reduce-window must NOT read as a collective
    assert A._comm_kind("reduce-window.1") is None


def test_peak_flops_table():
    """One table, keyed by what jax announces ("TPU v5 lite" is a v5e);
    a device it does not know raises instead of dropping MFU, and no
    environment variable can supply a peak."""
    assert A.peak_flops_per_chip("TPU v4") == 275e12
    assert A.peak_flops_per_chip("TPU v5 lite") == 197e12
    for unknown in ("cpu", "TPU v9 mega", ""):
        with pytest.raises(ValueError, match="no peak FLOP/s known"):
            A.peak_flops_per_chip(unknown)


# ---------------------------------------------------------------------------
# Real jax.profiler capture on CPU (test_eager_single.py:172 pattern)
# ---------------------------------------------------------------------------


def _real_capture(tmp_path, steps=2):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x):
        with jax.named_scope("hvd_overlap_rs0"):
            y = x @ x
        with jax.named_scope("hvd_overlap_math0"):
            z = jnp.sin(y)
        return z

    x = jnp.ones((128, 128))
    f(x).block_until_ready()  # compile outside the capture
    jax.profiler.start_trace(str(tmp_path))
    try:
        for s in range(steps):
            with jax.profiler.StepTraceAnnotation("hvd_step",
                                                  step_num=s):
                f(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    caps = [os.path.join(dp, fn)
            for dp, _dn, fns in os.walk(tmp_path)
            for fn in fns if fn.endswith(".xplane.pb")]
    assert caps, "no xplane capture written"
    return caps[0]


def test_real_cpu_capture_roundtrip(tmp_path):
    """A real capture parses with hvd named scopes resolved and the
    StepTraceAnnotation windows attributed per step — the read-back
    proof for the write half test_eager_single.py:172 checks."""
    path = _real_capture(tmp_path)
    space = X.read_xspace(path, want_stats=X.ANALYSIS_STATS)
    assert not space.truncated
    res = A.attribute(space)
    assert [s["step"] for s in res["steps"]] == [0, 1]
    assert res["scopes_resolved"] >= 2
    all_scopes = set()
    for s in res["steps"]:
        all_scopes |= set(s["scopes"])
        assert s["wall_s"] > 0
    assert "hvd_overlap_rs0" in all_scopes
    assert "hvd_overlap_math0" in all_scopes
    # the rs scope classifies as comm by framework semantics
    tot = res["totals"]
    assert tot["comm_s"] > 0 and tot["compute_s"] > 0


def test_report_on_raw_capture_dir(tmp_path):
    _real_capture(tmp_path / "rank0", steps=1)
    rep = R.analyze_dir(str(tmp_path))
    assert len(rep["captures"]) == 1
    assert rep["captures"][0]["rank"] == 0
    text = R.format_report(rep)
    assert "rank 0" in text and "compute" in text


# ---------------------------------------------------------------------------
# Sampled continuous capture
# ---------------------------------------------------------------------------


def test_sampled_capture_rotation_and_gauges(tmp_path, monkeypatch):
    from horovod_tpu.perf import capture as C
    from horovod_tpu.runtime import metrics as M

    import jax
    import jax.numpy as jnp

    monkeypatch.setenv("HOROVOD_PROFILE_EVERY_N_STEPS", "2")
    monkeypatch.setenv("HOROVOD_PROFILE_DIR", str(tmp_path))
    monkeypatch.setenv("HOROVOD_PROFILE_KEEP", "1")
    C.reset()
    C.set_step_flops(2 * 128 ** 3)
    f = jax.jit(lambda x: x @ x)
    x = jnp.ones((128, 128))
    try:
        for step in range(6):
            with M.trace_step(step=step):
                f(x).block_until_ready()
            # join the analyzer between spans: backpressure would
            # (correctly) skip the next due span while it runs, and
            # this test pins WHICH steps get captured
            C.drain(60)
    finally:
        C.reset()
    # every_n=2 skips span 0 -> captures at steps 2 and 4; keep=1
    # rotates step2 away
    kept = sorted(os.listdir(tmp_path / "rank0"))
    assert kept == ["step00000004"], kept
    last = json.load(open(tmp_path / "rank0" / "step00000004"
                          / "analysis.json"))
    assert last["captured_step"] == 4
    assert last["totals"]["steps"] >= 1
    snap = M.metrics()["metrics"]
    for g in ("hvd_device_compute_seconds",
              "hvd_device_comm_exposed_seconds",
              "hvd_profile_captures_total"):
        assert g in snap, sorted(k for k in snap if "device" in k)
    # a utilization is a device metric: the CPU run publishes none
    assert last["totals"].get("mfu") is None
    assert snap["hvd_profile_captures_total"]["series"][0]["value"] >= 2
    # report reuses analysis.json (no re-parse) and renders
    rep = R.analyze_dir(str(tmp_path))
    assert rep["captures"][0]["captured_step"] == 4


def test_sampled_capture_backpressure(tmp_path, monkeypatch):
    """Steps outpacing the analyzer must SKIP sampling (counted) — not
    pile up a thread per sample and rotate away capture dirs whose
    queued analysis never ran."""
    import threading

    from horovod_tpu.perf import capture as C
    from horovod_tpu.runtime import metrics as M

    monkeypatch.setenv("HOROVOD_PROFILE_EVERY_N_STEPS", "1")
    monkeypatch.setenv("HOROVOD_PROFILE_DIR", str(tmp_path))
    C.reset()
    gate = threading.Event()
    slow = threading.Thread(target=gate.wait, daemon=True)
    slow.start()
    try:
        with C._lock:
            C._state["count"] = 1  # span 0 (jit compile) already seen
            C._state["threads"] = [slow]  # analyzer still in flight
        skips0 = M.counter("hvd_profile_skips_total").total()
        assert C.maybe_start(1) is None
        assert (M.counter("hvd_profile_skips_total").total()
                == skips0 + 1)
        gate.set()
        slow.join(10)
        tok = C.maybe_start(2)  # backlog cleared: sampling resumes
        assert tok is not None
        C.stop_and_analyze(tok)
        C.drain(60)
        assert os.path.isdir(tmp_path / "rank0" / "step00000002")
    finally:
        gate.set()
        C.reset()


def test_sampled_capture_yields_to_bridge(tmp_path, monkeypatch):
    """The whole-run JaxProfilerBridge owns the profiler; the sampler
    must decline instead of fighting it for start_trace."""
    from horovod_tpu.common import basics
    from horovod_tpu.perf import capture as C

    class FakeBridge:
        _active = True

    monkeypatch.setenv("HOROVOD_PROFILE_EVERY_N_STEPS", "1")
    monkeypatch.setenv("HOROVOD_PROFILE_DIR", str(tmp_path))
    C.reset()
    monkeypatch.setattr(basics.state(), "profiler", FakeBridge())
    try:
        for _ in range(3):
            assert C.maybe_start(None) is None
        assert not (tmp_path / "rank0").exists()
    finally:
        C.reset()


def test_capture_off_by_default(tmp_path, monkeypatch):
    from horovod_tpu.perf import capture as C

    monkeypatch.delenv("HOROVOD_PROFILE_EVERY_N_STEPS", raising=False)
    C.reset()
    assert C.maybe_start(0) is None
    assert C._state["count"] == 0  # the counter only runs when sampling


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def test_perf_cli_report(tmp_path):
    from horovod_tpu.perf.__main__ import main

    # report on an empty dir: informative nonzero, no exception
    assert main(["report", str(tmp_path / "empty")]) == 1


# ---------------------------------------------------------------------------
# Dependency discipline
# ---------------------------------------------------------------------------


def test_perf_import_is_tf_free():
    """Acceptance: no TF/tensorboard import anywhere in
    horovod_tpu.perf — the stdlib wire reader is the whole point.  The
    raw parser additionally loads with NOTHING beyond the stdlib (jax
    included — file-loaded without the parent package, whose own
    __init__ legitimately pulls jax in)."""
    script = (
        "import importlib.util, os, sys\n"
        f"spec = importlib.util.spec_from_file_location('xp', "
        f"{os.path.join(REPO, 'horovod_tpu', 'perf', 'xplane.py')!r})\n"
        "mod = importlib.util.module_from_spec(spec)\n"
        "sys.modules['xp'] = mod\n"
        "spec.loader.exec_module(mod)\n"
        "bad = [m for m in sys.modules if m.split('.')[0] in\n"
        "       ('jax', 'jaxlib', 'numpy', 'tensorflow',\n"
        "        'tensorboard')]\n"
        "assert not bad, ('xplane.py must be stdlib-only', bad)\n"
        "import horovod_tpu.perf\n"
        "bad = [m for m in sys.modules if m.split('.')[0] in\n"
        "       ('tensorflow', 'tensorboard',\n"
        "        'tensorboard_plugin_profile', 'prometheus_client')]\n"
        "assert not bad, bad\n"
        "print('CLEAN')\n")
    out = subprocess.run([sys.executable, "-c", script],
                         capture_output=True, text=True, timeout=120,
                         cwd=REPO)
    assert out.returncode == 0, out.stderr
    assert "CLEAN" in out.stdout


# ---------------------------------------------------------------------------
# Profiler bridge elastic lifecycle (satellite)
# ---------------------------------------------------------------------------


def test_bridge_generation_dirs(tmp_path):
    """Generation 1 keeps the historical rank<k> layout; re-formed
    generations write gen<g>/rank<k> so the old capture survives."""
    from horovod_tpu.runtime.timeline import JaxProfilerBridge

    b1 = JaxProfilerBridge(str(tmp_path), 0, generation=1)
    b1.close()
    b2 = JaxProfilerBridge(str(tmp_path), 0, generation=2)
    b2.close()
    assert (tmp_path / "rank0").is_dir()
    assert (tmp_path / "gen2" / "rank0").is_dir()
    for d in (tmp_path / "rank0", tmp_path / "gen2" / "rank0"):
        files = [p for p in d.rglob("*") if p.is_file()]
        assert any("xplane" in p.name for p in files), (d, files)


@pytest.mark.slow  # ~16 s profiler+elastic teardown (ci.sh full suite)
def test_teardown_closes_profiler_bridge(tmp_path):
    """Regression (satellite 2): teardown_distributed must close the
    bridge so (a) the old generation's capture lands and (b) the
    re-init's new bridge can start.  Before the fix the stale bridge
    held the profiler and the re-formed generation recorded nothing.
    Subprocess: teardown clears real backend caches."""
    script = f"""
import os
os.environ["HOROVOD_TIMELINE_JAX_PROFILER"] = {str(tmp_path)!r}
os.environ["HOROVOD_PLATFORM"] = "cpu"
import jax.numpy as jnp
import horovod_tpu as hvd
from horovod_tpu.common import basics
hvd.init()
st = basics.state()
assert st.profiler is not None, "bridge did not open"
jnp.ones(4).block_until_ready()
basics.teardown_distributed(bound_s=2)
assert st.profiler is None, "teardown left the bridge open"
caps = [f for f in os.listdir(os.path.join({str(tmp_path)!r}, "rank0",
        "plugins", "profile"))]
assert caps, "generation-1 capture did not land at teardown"
# simulate the elastic re-init: same process, next generation
st.initialized = False
hvd.init()
assert st.profiler is not None, "re-init did not reopen the bridge"
assert "gen2" in st.profiler._dir, st.profiler._dir
jnp.ones(4).block_until_ready()
hvd.shutdown()
g2 = os.path.join({str(tmp_path)!r}, "gen2", "rank0")
found = [fn for _dp, _dn, fns in os.walk(g2) for fn in fns
         if "xplane" in fn]
assert found, "generation-2 capture did not land"
print("LIFECYCLE-OK")
"""
    out = subprocess.run([sys.executable, "-c", script],
                         capture_output=True, text=True, timeout=240,
                         cwd=REPO)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "LIFECYCLE-OK" in out.stdout
