"""Flight recorder + trace merge/analyze tests (docs/flight-recorder.md).

Unit layer: ring semantics (bounded memory, order, overwrite), the
no-syscall hot-path cost bound (mirror of the metrics registry's
lock-cheap test), atomic JSONL dumps, fatal-signal dumps, NTP-style
clock-offset math, Chrome-trace schema, and the straggler / death
analyzers over synthetic dumps.

Multiprocess layer: the two acceptance scenarios — a ``delay@rank1``
fault-injected straggler the analyzer must rank first with the
injected lateness, and a SIGKILL whose survivors' dumps must merge
into a valid trace and a death report naming the dead rank.
"""

import json
import os
import signal
import socket
import subprocess
import sys
import time

import pytest

from horovod_tpu.runtime import flight
from horovod_tpu.trace.analyze import analyze, format_report
from horovod_tpu.trace.merge import (RankDump, compute_offsets,
                                     load_dumps, merge)
from horovod_tpu.trace.perfetto import chrome_trace

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# Ring semantics
# ---------------------------------------------------------------------------


def test_ring_is_bounded_and_ordered():
    r = flight.FlightRecorder(8)
    for i in range(21):
        r.record("x", i=i)
    snap = r.snapshot()
    assert len(snap) == 8
    assert [e["i"] for e in snap] == list(range(13, 21))
    assert [e["seq"] for e in snap] == list(range(13, 21))
    assert r.recorded_total() == 21
    # memory bound: the slot list never grows past capacity
    assert len(r._slots) == 8


def test_ring_partial_fill_and_both_clocks():
    r = flight.FlightRecorder(16)
    r.record("a", ph="B")
    r.record("b")
    snap = r.snapshot()
    assert [e["kind"] for e in snap] == ["a", "b"]
    assert snap[0]["ph"] == "B" and snap[1]["ph"] == "i"
    for ev in snap:
        assert ev["mono"] > 0 and ev["wall"] > 0


def test_clear_resets_ring_for_next_generation():
    """An elastic re-form dumps the old generation's ring then clears
    it: round numbers restart with the new generation, and a later
    dump carrying both generations' events would merge unrelated
    rounds in the straggler analyzer."""
    r = flight.FlightRecorder(8)
    r.record("round", ph="B", round=5)
    r.clear()
    assert r.snapshot() == [] and r.recorded_total() == 0
    r.record("round", ph="B", round=0)
    assert [e["round"] for e in r.snapshot()] == [0]


def test_record_reentrant_from_signal_context():
    """The fatal-signal handler records/dumps on the main thread; if
    the signal lands while that thread is inside record(), the ring
    lock must be reentrant or the dump deadlocks."""
    r = flight.FlightRecorder(8)
    with r._lock:  # simulate: interrupted mid-record
        r.record("signal", sig="SIGTERM")   # must not deadlock
        assert len(r.snapshot()) == 1


def test_overlapping_wait_spans_counted_and_async_in_trace(tmp_path):
    """Two framework threads blocked on different handles at once: the
    analyzer must count both spans (keyed by handle), and the trace
    writer must emit waits as async b/e pairs (sync B/E on one row
    would be matched stack-wise by Chrome and swap the durations)."""
    _dump(tmp_path, 0, [
        {"kind": "wait", "ph": "B", "handle": 1, "wall": 1.0, "mono": 1.0},
        {"kind": "wait", "ph": "B", "handle": 2, "wall": 1.5, "mono": 1.5},
        {"kind": "wait", "ph": "E", "handle": 1, "wall": 2.0, "mono": 2.0},
        {"kind": "wait", "ph": "E", "handle": 2, "wall": 3.5, "mono": 3.5},
    ], size=1)
    dumps = load_dumps(str(tmp_path))
    report = analyze(dumps, compute_offsets(dumps))
    # 1.0 s (h1) + 2.0 s (h2), not just the last-opened span
    assert abs(report["phases"][0]["blocked_s"] - 3.0) < 1e-6
    trace = chrome_trace(dumps, compute_offsets(dumps))
    waits = [e for e in trace["traceEvents"]
             if e["name"].startswith("wait h")]
    assert {e["ph"] for e in waits} == {"b", "e"}
    assert all("id" in e and "cat" in e for e in waits), waits


def test_zero_capacity_disables_recording():
    r = flight.FlightRecorder(0)
    r.record("x")
    assert r.snapshot() == [] and r.recorded_total() == 0


@pytest.mark.parametrize("kind", ["hot", "hvd_compile"])
def test_record_is_syscall_free_and_bounded(kind):
    """Acceptance: recording performs no syscalls (open/socket banned
    during a burst) and ring memory stays at HOROVOD_FLIGHT_EVENTS
    entries regardless of run length — the PR 6 lock-cheap registry
    bound, applied to the flight ring.  A set-up kind, of which the
    first 512 are held beside the ring, costs no more."""
    import builtins

    r = flight.FlightRecorder(64)
    real_open, real_socket = builtins.open, socket.socket

    def no_open(*a, **k):
        raise AssertionError("open() on the flight-recorder hot path")

    class NoSocket(socket.socket):
        def __init__(self, *a, **k):
            raise AssertionError("socket() on the flight-recorder hot path")

    builtins.open = no_open
    socket.socket = NoSocket
    try:
        t0 = time.perf_counter()
        for i in range(30000):
            r.record(kind, round=i, n_req=2)
        dt = time.perf_counter() - t0
    finally:
        builtins.open = real_open
        socket.socket = real_socket
    assert r.recorded_total() == 30000
    kept = 0 if kind == "hot" else flight._SETUP_KEEP
    assert len(r.snapshot()) == 64 + kept
    assert len(r._slots) == 64  # no allocation growth with run length
    assert len(r._kept) == kept
    # generous bound for a loaded CI image; a hidden syscall per record
    # would blow far past it
    assert dt < 5.0, f"hot path too slow: {dt:.2f}s for 30k records"


def test_set_up_records_survive_a_ring_that_wraps():
    """Set-up's kinds are read at the end of a run, after a launched
    world's background thread has wrapped the ring many times: the
    recorder holds them beside it and ``snapshot()`` merges them by
    ``seq``, each event once."""
    r = flight.FlightRecorder(8)
    r.record("hvd_process", started_wall=1.0)
    r.record("hvd_import", "B", id=1)
    r.record("round", round=0)
    r.record("hvd_init", "B", id=2)
    r.record("hvd_init.backend", "B", id=3)
    r.record("hvd_compile", fun_name="jit(step)")
    # not yet wrapped: nothing twice
    assert [e["seq"] for e in r.snapshot()] == list(range(6))
    for i in range(100):
        r.record("round", round=i)
    snap = r.snapshot()
    assert [e["kind"] for e in snap[:5]] == [
        "hvd_process", "hvd_import", "hvd_init", "hvd_init.backend",
        "hvd_compile"]
    assert [e["seq"] for e in snap] == [0, 1, 3, 4, 5] + list(range(98, 106))
    assert snap[4]["fun_name"] == "jit(step)"
    assert r.recorded_total() == 106 and len(r._slots) == 8
    # a new generation starts with neither
    r.clear()
    assert r.snapshot() == [] and r._kept == []


# ---------------------------------------------------------------------------
# A record for every program compiled
# ---------------------------------------------------------------------------

_COMPILE_PROBE = """
import json, sys
import jax, jax.numpy as jnp
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
from horovod_tpu.common import platform
from horovod_tpu.runtime import flight, metrics
platform.ensure_compile_cache()
platform.ensure_compile_cache()      # listens once, whoever asks again
if sys.argv[1] == "off":
    jax.config.update("jax_enable_compilation_cache", False)

@jax.jit
def inner(x):
    return jnp.tanh(x) * 2

def probe(x):
    return inner(x) + 1

x = jnp.ones(4)
with flight.span("setup") as setup:
    jax.jit(probe).lower(x).compile()
try:
    jax.jit(lambda y: y.nothing_here).lower(x)    # traced, never compiled
except AttributeError:
    pass
jax.jit(probe).lower(jnp.ones(5)).compile()       # outside any span
print(json.dumps({
    "span": setup.id,
    "records": [e for e in flight.recorder().snapshot()
                if e["kind"] == "hvd_compile"],
    "counter": metrics.counter("hvd_compile_seconds_total").series()}))
"""


def _compile_probe(cache_dir: str) -> dict:
    # the cache is where the variable says (common/platform)
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=cache_dir)
    done = subprocess.run([sys.executable, "-c", _COMPILE_PROBE,
                           os.path.basename(cache_dir)],
                          capture_output=True, text=True, timeout=120,
                          env=env, cwd=REPO)
    assert done.returncode == 0, done.stderr[-2000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


def _check_probe(out: dict, cache: str) -> None:
    """Two programs named ``jit(probe)``, one record each, the first
    under the span; the counter holds the seconds of the process's
    records, once."""
    probes = [e for e in out["records"] if e["fun_name"] == "jit(probe)"]
    assert len(probes) == 2, out["records"]
    assert [e["cache"] for e in probes] == [cache, cache]
    assert probes[0]["parent"] == out["span"] and "parent" not in probes[1]
    for e in probes:
        assert e["trace_s"] > 0 and e["lower_s"] > 0 and e["backend_s"] > 0
        # the ring's wall clock, and the trace is where the program began
        assert e["start_wall"] + e["trace_s"] <= e["wall"]
        assert ("retrieval_s" in e) == (cache == "hit")
    # the program's trace is the outermost one, not the sum with the
    # ``jit`` traced inside it, and the abandoned lowering left nothing
    assert not [e for e in out["records"] if "lambda" in e["fun_name"]]
    seconds = {"warm": 0.0, "cold": 0.0}
    for e in out["records"]:
        seconds["warm" if e["cache"] == "hit" else "cold"] += (
            e["trace_s"] + e["lower_s"] + e["backend_s"])
    counted = {"warm": 0.0, "cold": 0.0}
    for series in out["counter"]:
        counted[series["labels"]["path"]] = series["value"]
    assert counted["warm"] == pytest.approx(seconds["warm"], abs=1e-9)
    assert counted["cold"] == pytest.approx(seconds["cold"], abs=1e-9)


def test_compile_record_misses_then_hits_in_a_second_process(tmp_path):
    """Every program compiled leaves one ``hvd_compile`` record:
    ``cache`` ``miss`` in the process that fills a temporary cache
    directory, ``hit`` (with what the retrieval cost) in the next, and
    ``hvd_compile_seconds_total`` holds the same seconds once, by
    path."""
    cache_dir = str(tmp_path / "jax_cache")
    _check_probe(_compile_probe(cache_dir), "miss")
    _check_probe(_compile_probe(cache_dir), "hit")


def test_compile_record_says_uncached_with_the_cache_off(tmp_path):
    _check_probe(_compile_probe(str(tmp_path / "off")), "uncached")


def test_a_programs_trace_is_found_behind_the_traces_its_lowering_makes(
        monkeypatch):
    """The events as JAX sends them for a step whose lowering traces
    hundreds of small ``jit``s after the step's own trace has ended
    (``gpt2-124m.s1024`` on the chip, PR 37): the record's ``trace_s``
    is the step's, the longest of its name, and a trace that no
    compile followed is dropped with the next program's first event."""
    from horovod_tpu.common import platform

    monkeypatch.setattr(flight, "_recorder", flight.FlightRecorder(16))
    monkeypatch.setattr(platform, "_compiling", platform._Compiling())
    send = platform._on_compile_span
    send(platform._TRACE, 50.0, 51.0, fun_name="abandoned")
    send(platform._LOWER, 51.0, 52.0, fun_name="jit(abandoned)")
    send(platform._TRACE, 100.5, 100.75, fun_name="tanh")   # inside step's
    send(platform._TRACE, 100.0, 103.0, fun_name="step")
    for i in range(300):
        send(platform._TRACE, 103.5, 103.5 + 2 ** -10, fun_name=f"small{i}")
    send(platform._TRACE, 103.75, 103.75 + 2 ** -10, fun_name="step")
    send(platform._LOWER, 103.25, 104.25, fun_name="jit(step)")
    platform._on_cache_event(platform._CACHE_ASKED)
    send(platform._BACKEND, 104.5, 106.5, fun_name="jit(step)")
    send(platform._LOWER, 107.0, 107.5, fun_name="jit(cached_trace)")
    send(platform._BACKEND, 107.5, 107.75, fun_name="jit(cached_trace)")
    first, second = [e for e in flight.recorder().snapshot()
                     if e["kind"] == "hvd_compile"]
    assert (first["fun_name"], first["trace_s"], first["lower_s"],
            first["backend_s"], first["cache"], first["start_wall"]) == (
        "jit(step)", 3.0, 1.0, 2.0, "miss", 100.0)
    assert (second["trace_s"], second["lower_s"], second["cache"],
            second["start_wall"]) == (0.0, 0.5, "uncached", 107.0)
    assert platform.compiled_seconds() == 6.75


def test_compile_seconds_reach_the_counter_once_through_the_aot_cache(
        tmp_path, monkeypatch):
    """``aot_cache.compile_or_load`` adds to
    ``hvd_compile_seconds_total`` only what JAX's compile events do not
    see: a miss's ``lower().compile()`` is counted by its
    ``hvd_compile`` record alone, a hit from the ``.aot`` file (which
    compiles nothing) by the ``aot`` record's ``load_s`` alone."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.runtime import aot_cache, metrics

    monkeypatch.setenv("HOROVOD_AOT_CACHE_DIR", str(tmp_path / "aot"))
    monkeypatch.delenv("HOROVOD_AOT_CACHE_MODE", raising=False)
    monkeypatch.setattr(flight, "_recorder", flight.FlightRecorder(256))
    counter = metrics.counter("hvd_compile_seconds_total")
    x = jnp.arange(7.0)

    def build():
        return jax.jit(lambda v: v * 3 - 1)

    def grown_and_recorded(run) -> tuple:
        before, total0 = flight.recorder().recorded_total(), counter.total()
        fn = run()
        assert float(fn(x)[2]) == 5.0
        events = flight.recorder().snapshot()[before:]
        compiled = sum(e["trace_s"] + e["lower_s"] + e["backend_s"]
                       for e in events if e["kind"] == "hvd_compile")
        loaded = sum(e["load_s"] for e in events if e["kind"] == "aot"
                     and e["event"] == "hit")
        return counter.total() - total0, compiled, loaded

    key = ("t_flight_once", (7,), "f32")
    grew, compiled, loaded = grown_and_recorded(
        lambda: aot_cache.compile_or_load(key, build, [x]))
    assert compiled > 0 and loaded == 0
    assert grew == pytest.approx(compiled, abs=1e-9)
    grew, compiled, loaded = grown_and_recorded(
        lambda: aot_cache.compile_or_load(key, build, [x]))
    assert compiled == 0 and loaded > 0
    # load_s is rounded to 0.1 ms in the ring
    assert grew == pytest.approx(loaded, abs=1e-4)


# ---------------------------------------------------------------------------
# Spans
# ---------------------------------------------------------------------------


def test_span_records_a_pair_with_id_and_parent(monkeypatch):
    """``B`` and ``E`` of the kind, the same id on both, the enclosing
    span's id as ``parent`` on ``B``; the fields given on both and what
    the body adds on ``E`` only; siblings share the parent."""
    monkeypatch.setattr(flight, "_recorder", flight.FlightRecorder(32))
    with flight.span("outer", rank=3) as outer:
        with flight.span("first"):
            pass
        with flight.span("second") as second:
            second.fields["n"] = 2
        outer.fields["wall_s"] = 0.5
    evs = [{k: v for k, v in e.items() if k not in ("seq", "mono", "wall")}
           for e in flight.recorder().snapshot()]
    a, b, c = outer.id, outer.id + 1, second.id
    assert evs == [
        {"kind": "outer", "ph": "B", "id": a, "rank": 3},
        {"kind": "first", "ph": "B", "id": b, "parent": a},
        {"kind": "first", "ph": "E", "id": b},
        {"kind": "second", "ph": "B", "id": c, "parent": a},
        {"kind": "second", "ph": "E", "id": c, "n": 2},
        {"kind": "outer", "ph": "E", "id": a, "rank": 3, "wall_s": 0.5}]
    assert len({a, b, c}) == 3
    monos = [e["mono"] for e in flight.recorder().snapshot()]
    assert monos == sorted(monos)


def test_span_survives_an_exception_and_another_thread(monkeypatch):
    """The pair closes when the body raises, the exception passes
    through, and the next span on the thread has no stale parent; a
    span on another thread is no child of this thread's."""
    import threading

    monkeypatch.setattr(flight, "_recorder", flight.FlightRecorder(32))
    with pytest.raises(KeyError):
        with flight.span("breaks"):
            with flight.span("inner"):
                raise KeyError("boom")
    def elsewhere():
        with flight.span("other"):
            pass

    with flight.span("outer"):
        t = threading.Thread(target=elsewhere)
        t.start()
        t.join(10)
        assert not t.is_alive()
    evs = flight.recorder().snapshot()
    assert [(e["kind"], e["ph"]) for e in evs] == [
        ("breaks", "B"), ("inner", "B"), ("inner", "E"), ("breaks", "E"),
        ("outer", "B"), ("other", "B"), ("other", "E"), ("outer", "E")]
    assert "parent" not in evs[4] and "parent" not in evs[5]


def test_span_costs_two_records_and_no_more(monkeypatch):
    """Two ring writes a span, no syscall, and a bound in time of the
    kind ``record()`` is held to."""
    ring = flight.FlightRecorder(64)
    monkeypatch.setattr(flight, "_recorder", ring)
    t0 = time.perf_counter()
    for i in range(10000):
        with flight.span("hot", round=i):
            pass
    dt = time.perf_counter() - t0
    assert ring.recorded_total() == 20000
    assert len(ring._slots) == 64
    assert dt < 5.0, f"span too slow: {dt:.2f}s for 10k spans"


def test_span_is_a_profiler_annotation_once_jax_is_there(monkeypatch):
    """With ``jax`` imported the span holds a ``TraceAnnotation`` of its
    kind open (or the one it was given), so a running capture shows it
    on the trace's clock."""
    import jax

    opened = []

    class Annotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            opened.append(("enter", self.name))

        def __exit__(self, *exc):
            opened.append(("exit", self.name))

    monkeypatch.setattr(flight, "_recorder", flight.FlightRecorder(8))
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    with flight.span("hvd_init.backend"):
        with flight.span("step", Annotation("hvd_step")):
            pass
    assert opened == [("enter", "hvd_init.backend"), ("enter", "hvd_step"),
                      ("exit", "hvd_step"), ("exit", "hvd_init.backend")]


def test_flight_and_its_span_need_no_jax():
    """The module alone, by its file: the standard library is enough to
    import it and to record a span, and neither imports ``jax``."""
    code = (
        "import importlib.util, sys\n"
        f"spec = importlib.util.spec_from_file_location('flight', "
        f"{os.path.join(REPO, 'horovod_tpu', 'runtime', 'flight.py')!r})\n"
        "flight = importlib.util.module_from_spec(spec)\n"
        "spec.loader.exec_module(flight)\n"
        "with flight.span('a'):\n"
        "    pass\n"
        "assert [e['ph'] for e in flight.recorder().snapshot()] "
        "== ['B', 'E']\n"
        "assert not {'jax', 'numpy', 'horovod_tpu'} & set(sys.modules)\n")
    done = subprocess.run([sys.executable, "-S", "-c", code],
                          capture_output=True, text=True, timeout=60)
    assert done.returncode == 0, done.stderr


def test_init_spans_its_phases(monkeypatch):
    """After ``hvd.init()`` the ring holds ``hvd_init`` and, inside it
    and in order, the spans of its phases (a world of one opens no
    ``distributed`` and no ``runtime``); a second call, which brings
    nothing up, records none."""
    import horovod_tpu as hvd

    if hvd.is_initialized():
        hvd.shutdown()
    monkeypatch.setattr(flight, "_recorder", flight.FlightRecorder(64))
    hvd.init()
    try:
        hvd.init()
        evs = [e for e in flight.recorder().snapshot()
               if e["kind"].startswith("hvd_init")]
    finally:
        hvd.shutdown()
    assert [(e["kind"], e["ph"]) for e in evs] == [
        ("hvd_init", "B"),
        ("hvd_init.backend", "B"), ("hvd_init.backend", "E"),
        ("hvd_init.topology", "B"), ("hvd_init.topology", "E"),
        ("hvd_init.meshes", "B"),
        ("hvd_init.topology", "B"), ("hvd_init.topology", "E"),
        ("hvd_init.meshes", "E"),
        ("hvd_init.planes", "B"), ("hvd_init.planes", "E"),
        ("hvd_init", "E")]
    whole, meshes = evs[0]["id"], evs[5]["id"]
    assert "parent" not in evs[0]
    assert [e["parent"] for e in evs[1:] if e["ph"] == "B"] \
        == [whole, whole, whole, meshes, whole]
    assert evs[0]["mono"] <= evs[1]["mono"] and \
        evs[-2]["mono"] <= evs[-1]["mono"]
    # the lifecycle event init() always recorded is inside the planes
    kinds = [e["kind"] for e in flight.recorder().snapshot()]
    assert kinds.index("init") > kinds.index("hvd_init.planes")


# ---------------------------------------------------------------------------
# Dumps
# ---------------------------------------------------------------------------


def test_dump_atomic_jsonl_roundtrip(tmp_path):
    r = flight.FlightRecorder(32)
    r.record("round", ph="B", round=0, n_req=1, names=["t"])
    r.record("round", ph="E", round=0, path="slow", n_resp=1)
    path = str(tmp_path / "flight-r0-g1-p1.jsonl")
    out = r.dump(path, {"rank": 0, "size": 2, "generation": 1,
                        "reason": "test"})
    assert out == path and os.path.exists(path)
    assert not [n for n in os.listdir(tmp_path) if ".tmp" in n]
    d = load_dumps(str(tmp_path))[0]
    assert d.rank == 0 and d.generation == 1 and d.size == 2
    assert d.meta["reason"] == "test" and d.meta["events"] == 2
    assert [e["kind"] for e in d.events] == ["round", "round"]
    # dump is idempotent: a second trigger overwrites the same file
    r.record("abort", ranks=[1])
    r.dump(path, {"rank": 0, "size": 2, "generation": 1,
                  "reason": "later"})
    d = load_dumps(str(tmp_path))[0]
    assert d.meta["reason"] == "later" and len(d.events) == 3


def test_global_dump_respects_env_dir(tmp_path, monkeypatch):
    monkeypatch.delenv("HOROVOD_FLIGHT_DIR", raising=False)
    flight.reset()
    flight.record("x")
    assert flight.dump("nodir") is None  # no dir -> no-op, no crash
    monkeypatch.setenv("HOROVOD_FLIGHT_DIR", str(tmp_path / "sub"))
    path = flight.dump("explicit")
    assert path and os.path.exists(path)
    d = load_dumps(os.path.dirname(path))[0]
    assert d.meta["reason"] == "explicit"
    # the dump trigger itself is on the record
    assert d.events[-1]["kind"] == "dump"
    flight.reset()


def test_flight_events_knob_sizes_global_ring(monkeypatch):
    monkeypatch.setenv("HOROVOD_FLIGHT_EVENTS", "5")
    flight.reset()
    for i in range(9):
        flight.record("k", i=i)
    assert len(flight.recorder().snapshot()) == 5
    monkeypatch.setenv("HOROVOD_FLIGHT_EVENTS", "0")
    flight.reset()
    flight.record("k")
    assert flight.recorder().snapshot() == []
    flight.reset()


def test_sigterm_dumps_ring(tmp_path):
    """A fatal signal dumps the ring before the process dies with the
    signal's own exit status (the launcher keys on it)."""
    script = (
        "import os, signal, time\n"
        "from horovod_tpu.runtime import flight\n"
        "assert flight.install_signal_handlers()\n"
        "flight.record('round', ph='B', round=7)\n"
        "os.kill(os.getpid(), signal.SIGTERM)\n"
        "time.sleep(10)\n")
    env = dict(os.environ)
    env.update({"HOROVOD_FLIGHT_DIR": str(tmp_path),
                "HOROVOD_RANK": "3", "HOROVOD_SIZE": "4",
                "PYTHONPATH": REPO + os.pathsep
                + env.get("PYTHONPATH", "")})
    p = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == -signal.SIGTERM, (p.returncode, p.stderr)
    dumps = load_dumps(str(tmp_path))
    assert len(dumps) == 1, os.listdir(tmp_path)
    d = dumps[0]
    assert d.rank == 3 and d.meta["reason"] == "signal:SIGTERM"
    kinds = [e["kind"] for e in d.events]
    # the ring opens with the process's own start (the package's import)
    assert kinds[:4] == ["hvd_process", "hvd_import", "hvd_import", "round"]
    assert "signal" in kinds


def test_failure_dump_flushes_terminal_metrics(tmp_path, monkeypatch):
    """Satellite regression: the abort/fatal-signal dump path must push
    one LAST KV metrics snapshot (the launcher aggregate otherwise
    keeps serving the final periodic publish, missing the terminal
    abort counters) — the metrics-plane mirror of PR 6's
    timeline-flush fix."""
    from horovod_tpu.common import basics

    published = []

    class FakePublisher:
        def publish(self):
            published.append(1)

    monkeypatch.setattr(basics.state(), "metrics_publisher",
                        FakePublisher())
    monkeypatch.setenv("HOROVOD_FLIGHT_DIR", str(tmp_path))
    flight.reset()
    path = flight.dump_on_failure("ranks_down")
    assert path and os.path.exists(path)
    assert published == [1]
    # no publisher configured: still dumps, still no crash
    monkeypatch.setattr(basics.state(), "metrics_publisher", None)
    assert flight.dump_on_failure("ranks_down") is not None
    flight.reset()


# ---------------------------------------------------------------------------
# Clock alignment
# ---------------------------------------------------------------------------


def _dump(tmp_path, rank, events, gen=1, size=2, **meta):
    r = flight.FlightRecorder(256)
    for ev in events:
        fields = {k: v for k, v in ev.items()
                  if k not in ("kind", "ph", "wall", "mono")}
        r.record(ev["kind"], ph=ev.get("ph", "i"), **fields)
    # overwrite the auto stamps with the scripted clocks
    with r._lock:
        for i, ev in enumerate(events):
            s, _, _, kind, ph, fields = r._slots[i]
            r._slots[i] = (s, ev.get("mono", float(i)),
                           ev.get("wall", float(i)), kind, ph, fields)
    path = str(tmp_path / f"flight-r{rank}-g{gen}-p{100 + rank}.jsonl")
    m = {"rank": rank, "size": size, "generation": gen}
    m.update(meta)
    r.dump(path, m)
    return path


def test_clock_offsets_two_way_ntp_bound(tmp_path):
    """Known true offset + asymmetric delays: the estimate must land
    within the reported bound of the truth, and the bound must equal
    (d1 + d2) / 2."""
    true = 0.8       # rank 1's clock runs 0.8 s behind rank 0's
    d1, d2 = 0.030, 0.010
    # rank 0 observed rank 1's beat: sample = (c0 - c1) + d1
    _dump(tmp_path, 0, [
        {"kind": "clk", "peer": 1, "wall": 100.0 + true + d1,
         "peer_wall": 100.0},
        {"kind": "clk", "peer": 1, "wall": 102.0 + true + d1 + 0.5,
         "peer_wall": 102.0},  # a slower sample: min() must win
    ])
    # rank 1 observed rank 0: sample = (c1 - c0) + d2
    _dump(tmp_path, 1, [
        {"kind": "clk", "peer": 0, "wall": 101.0 - true + d2,
         "peer_wall": 101.0},
    ])
    offsets = compute_offsets(load_dumps(str(tmp_path)))
    info = next(v for v in offsets.values() if v["rank"] == 1)
    assert info["mode"] == "two-way"
    est, bound = info["offset_s"], info["bound_s"]
    assert abs(bound - (d1 + d2) / 2) < 1e-9
    assert abs(est - true) <= bound + 1e-9
    ref = next(v for v in offsets.values() if v["rank"] == 0)
    assert ref["offset_s"] == 0.0 and ref["bound_s"] == 0.0


def test_clock_offsets_one_way_and_none(tmp_path):
    _dump(tmp_path, 0, [{"kind": "init"}])  # no samples at all
    _dump(tmp_path, 1, [
        {"kind": "clk", "peer": 0, "wall": 50.0, "peer_wall": 49.9}])
    offsets = compute_offsets(load_dumps(str(tmp_path)))
    one = next(v for v in offsets.values() if v["rank"] == 1)
    assert one["mode"] == "one-way"
    assert abs(one["offset_s"] - (-0.1)) < 1e-6
    _dump(tmp_path, 2, [{"kind": "init"}], size=3)
    offsets = compute_offsets(load_dumps(str(tmp_path)))
    none = next(v for v in offsets.values() if v["rank"] == 2)
    assert none["mode"] == "none" and none["bound_s"] is None


# ---------------------------------------------------------------------------
# Chrome trace + analyzer over synthetic dumps
# ---------------------------------------------------------------------------


def _synthetic_job(tmp_path):
    """Rank 0 (coordinator) saw 3 rounds; rank 1 arrived ~1 s late in
    each; rank 1's dump is missing (SIGKILL) and rank 0 aborted on it."""
    events = [{"kind": "init", "rank": 0}]
    for rnd in range(3):
        base = 10.0 * (rnd + 1)
        events += [
            {"kind": "round", "ph": "B", "round": rnd,
             "wall": base, "mono": base},
            {"kind": "arrive", "peer": 0, "round": rnd,
             "wall": base + 0.01, "mono": base + 0.01},
            {"kind": "arrive", "peer": 1, "round": rnd,
             "wall": base + 1.01, "mono": base + 1.01},
            {"kind": "round", "ph": "E", "round": rnd, "path": "slow",
             "wall": base + 1.2, "mono": base + 1.2},
            {"kind": "dispatch", "ph": "B", "wall": base + 1.3,
             "mono": base + 1.3},
            {"kind": "dispatch", "ph": "E", "wall": base + 1.5,
             "mono": base + 1.5},
        ]
    events += [
        {"kind": "round", "ph": "B", "round": 3, "wall": 40.0,
         "mono": 40.0},  # left open: rank 1 never arrived
        {"kind": "abort", "ranks": [1], "round": 3, "wall": 45.0,
         "mono": 45.0},
    ]
    return _dump(tmp_path, 0, events, reason="ranks_down")


def test_chrome_trace_schema_and_unfinished_spans(tmp_path):
    _synthetic_job(tmp_path)
    dumps = load_dumps(str(tmp_path))
    trace = chrome_trace(dumps, compute_offsets(dumps))
    evs = trace["traceEvents"]
    assert evs, "empty trace"
    for ev in evs:
        assert {"ts", "pid", "tid", "ph"} <= set(ev), ev
    names = {e["args"]["name"] for e in evs
             if e["ph"] == "M" and e["name"] == "process_name"}
    assert any(n.startswith("rank 0 gen 1") for n in names), names
    # B/E balanced per (pid, tid): the open round 3 was closed
    depth = {}
    for e in evs:
        k = (e["pid"], e["tid"])
        if e["ph"] == "B":
            depth[k] = depth.get(k, 0) + 1
        elif e["ph"] == "E":
            depth[k] = depth.get(k, 0) - 1
            assert depth[k] >= 0, e
    assert all(v == 0 for v in depth.values()), depth
    unfinished = [e for e in evs
                  if (e.get("args") or {}).get("unfinished")]
    assert unfinished, "open round 3 span was not closed at dump time"


def test_chrome_trace_async_ids_scoped_per_rank(tmp_path):
    """Legacy Chrome async events pair globally by (cat, id), not per
    pid — and HandleManager numbering restarts per rank, so two ranks'
    'wait h1' spans must not share an id (the viewer would cross
    them)."""
    wait = [{"kind": "wait", "ph": "B", "handle": 1, "mono": 0.0},
            {"kind": "wait", "ph": "E", "handle": 1, "mono": 1.0}]
    _dump(tmp_path, 0, wait)
    _dump(tmp_path, 1, wait)
    dumps = load_dumps(str(tmp_path))
    trace = chrome_trace(dumps, compute_offsets(dumps))
    ids = {e["pid"]: e["id"] for e in trace["traceEvents"]
           if e["ph"] == "b"}
    assert len(ids) == 2 and len(set(ids.values())) == 2, ids


def test_trace_package_does_not_shadow_merge_submodule():
    import horovod_tpu.trace
    import horovod_tpu.trace.merge as m

    assert callable(m.load_dumps)  # module, not the merge() function
    assert callable(horovod_tpu.trace.merge_dumps)


def test_merge_writes_loadable_trace(tmp_path):
    _synthetic_job(tmp_path)
    out, dumps, offsets = merge(str(tmp_path))
    with open(out) as f:
        trace = json.load(f)
    assert trace["traceEvents"]
    assert "clock_offsets" in trace["otherData"]


def test_merge_empty_dir_raises(tmp_path):
    with pytest.raises(FileNotFoundError):
        merge(str(tmp_path))


def test_analyzer_straggler_ranking(tmp_path):
    _synthetic_job(tmp_path)
    dumps = load_dumps(str(tmp_path))
    report = analyze(dumps, compute_offsets(dumps))
    st = report["stragglers"]
    assert st["rounds"] == 3
    top = st["ranking"][0]
    assert top["rank"] == 1 and top["last_count"] == 3
    assert 2.9 <= top["total_lateness_s"] <= 3.1
    assert 0.9 <= top["max_lateness_s"] <= 1.1
    assert sum(top["hist"].values()) == 3


def test_analyzer_stragglers_never_merge_generations(tmp_path):
    """Rank identities are reassigned at each elastic re-form: gen-1
    "rank 1" (a dead slow host) and gen-2 "rank 1" (an innocent
    replacement) must get SEPARATE ranking entries, not one summed
    "rank 1" blaming the new host for the old host's lateness."""
    def arrivals(rnd, late_by):
        return ([{"kind": "round", "ph": "B", "round": rnd,
                  "mono": 10.0 * rnd}]
                + [{"kind": "arrive", "peer": p, "round": rnd,
                    "mono": 10.0 * rnd + off}
                   for p, off in late_by.items()]
                + [{"kind": "round", "ph": "E", "round": rnd,
                    "mono": 10.0 * rnd + 9.0}])
    # gen 1: rank 1 is 2s late every round (the host that then dies)
    _dump(tmp_path, 0,
          arrivals(0, {0: 0.0, 1: 2.0}) + arrivals(1, {0: 0.0, 1: 2.0}),
          gen=1, reason="reform:2")
    # gen 2: the NEW rank 1 is on time; rank 0 is 0.1s late
    _dump(tmp_path, 0,
          arrivals(0, {0: 0.1, 1: 0.0}) + arrivals(1, {0: 0.1, 1: 0.0}),
          gen=2, reason="explicit")
    dumps = load_dumps(str(tmp_path))
    report = analyze(dumps, compute_offsets(dumps))
    # clock section must keep both generations' entries apart too
    # (rank-only keys would overwrite one with the other)
    assert sorted(report["clock"]) == ["0@g1", "0@g2"], report["clock"]
    st = report["stragglers"]
    by_key = {(r["generation"], r["rank"]): r for r in st["ranking"]}
    assert len(by_key) == 4, st["ranking"]
    assert by_key[(1, 1)]["total_lateness_s"] == pytest.approx(4.0)
    assert by_key[(2, 1)]["total_lateness_s"] == 0.0  # innocent
    assert st["ranking"][0]["generation"] == 1  # worst entry is gen-1
    text = format_report({"stragglers": st})
    assert "rank 1 g1: last-in 2x" in text  # multi-gen labels the gen


def test_analyzer_death_report_names_dead_rank(tmp_path):
    _synthetic_job(tmp_path)
    dumps = load_dumps(str(tmp_path))
    report = analyze(dumps, compute_offsets(dumps))
    deaths = report["deaths"]
    assert deaths["dead"] == [1]
    assert deaths["missing_dumps"] == [1]  # SIGKILL left no dump
    assert deaths["last_round"]["1"] == 2  # never arrived for round 3
    text = format_report(report)
    assert "DEAD rank(s): [1]" in text
    assert "last participated in round 2" in text
    assert "rank 1: last-in 3x" in text


def test_jaxcoord_try_get_fallback_deadline_covers_a_round_trip():
    """Regression for the bug that blinded clock sampling: on jaxlib
    builds without ``key_value_try_get`` the fallback blocking get used
    a 1 ms deadline no real gRPC round trip meets, so PRESENT keys
    read as absent — heartbeat sweeps never observed a beat value and
    liveness silently degraded to absence-only.  The fallback deadline
    must cover an actual round trip."""
    from horovod_tpu.runtime.controller import JaxCoordTransport

    class FakeClient:  # no key_value_try_get attribute
        def __init__(self):
            self.deadlines = []

        def blocking_key_value_get(self, key, ms):
            self.deadlines.append(ms)
            return "beat"

    t = JaxCoordTransport.__new__(JaxCoordTransport)
    t._c = FakeClient()
    assert t.try_get("hvd1/hb/1") == "beat"
    assert t._c.deadlines and t._c.deadlines[0] >= 20, t._c.deadlines


def test_analyzer_step_split(tmp_path):
    """hvd.trace_step() spans land on the record and the analyzer
    reports the per-step comm/compute/blocked split per rank."""
    _dump(tmp_path, 0, [
        {"kind": "step", "ph": "B", "step": 0, "wall": 1.0, "mono": 1.0},
        {"kind": "step", "ph": "E", "step": 0, "wall": 2.0, "mono": 2.0,
         "wall_s": 1.0, "compute_s": 0.7, "comm_s": 0.2,
         "blocked_s": 0.3},
        {"kind": "step", "ph": "B", "step": 1, "wall": 2.0, "mono": 2.0},
        {"kind": "step", "ph": "E", "step": 1, "wall": 4.0, "mono": 4.0,
         "wall_s": 2.0, "compute_s": 1.5, "comm_s": 0.1,
         "blocked_s": 0.5},
    ])
    dumps = load_dumps(str(tmp_path))
    report = analyze(dumps, compute_offsets(dumps))
    p = report["phases"][0]
    assert p["steps"] == 2
    assert abs(p["step_mean_s"] - 1.5) < 1e-6
    assert abs(p["step_max_s"] - 2.0) < 1e-6
    assert abs(p["step_blocked_total_s"] - 0.8) < 1e-6
    assert abs(p["step_compute_total_s"] - 2.2) < 1e-6
    text = format_report(report)
    assert "2 steps" in text


def test_trace_step_records_flight_events(hvd_single):
    """Integration: the live hvd.trace_step() span writes B/E step
    events with the split fields into the global ring."""
    flight.reset()
    with hvd_single.trace_step(step=7):
        time.sleep(0.01)
    evs = [e for e in flight.recorder().snapshot()
           if e["kind"] == "step"]
    assert [e["ph"] for e in evs] == ["B", "E"]
    assert evs[0]["step"] == 7 and evs[1]["step"] == 7
    assert evs[1]["wall_s"] >= 0.01
    for k in ("compute_s", "comm_s", "blocked_s"):
        assert k in evs[1]
    flight.reset()


def test_analyzer_no_false_deaths_without_failure_evidence(tmp_path):
    """A healthy job where only rank 0 dumped explicitly must not read
    as a massacre: missing dumps count as deaths only when surviving
    dumps corroborate an abnormal end (abort event, or a dump whose
    own trigger was a failure path / fatal signal / re-form)."""
    _dump(tmp_path, 0, [{"kind": "init"}], size=4, reason="explicit")
    dumps = load_dumps(str(tmp_path))
    report = analyze(dumps, compute_offsets(dumps))
    assert report["deaths"]["dead"] == [], report["deaths"]
    assert "no rank deaths observed" in format_report(report)
    # ...but the same hole in the dump set IS a death once a survivor
    # dumped on a fatal signal (the launcher's fail-fast teardown)
    _dump(tmp_path, 0, [{"kind": "init"}], size=4,
          reason="signal:SIGTERM")
    dumps = load_dumps(str(tmp_path))
    report = analyze(dumps, compute_offsets(dumps))
    assert report["deaths"]["dead"] == [1, 2, 3], report["deaths"]


def test_trace_cli_merge(tmp_path, capsys):
    from horovod_tpu.trace.__main__ import main

    _synthetic_job(tmp_path)
    assert main(["merge", str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "wrote" in out and "flight-recorder report" in out
    assert os.path.exists(tmp_path / "trace.json")
    assert main(["analyze", str(tmp_path), "--json"]) == 0
    report = json.loads(capsys.readouterr().out)
    assert report["deaths"]["dead"] == [1]


def test_launcher_flight_sweep(tmp_path, capsys):
    from horovod_tpu.run import launcher

    assert launcher._sweep_flight_dir({}, "wrap-up") == []
    _synthetic_job(tmp_path)
    found = launcher._sweep_flight_dir(
        {"HOROVOD_FLIGHT_DIR": str(tmp_path)}, "wrap-up")
    assert len(found) == 1
    err = capsys.readouterr().err
    assert "flight recorder (wrap-up)" in err
    assert "horovod_tpu.trace merge" in err


# ---------------------------------------------------------------------------
# Multiprocess acceptance: straggler attribution + SIGKILL postmortem
# ---------------------------------------------------------------------------


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _spawn_n(script: str, extra_env: dict, np_: int = 2,
             timeout: int = 240):
    port = _free_port()
    procs = []
    for r in range(np_):
        env = dict(os.environ)
        env.update({
            "HOROVOD_PLATFORM": "cpu",
            "HOROVOD_RANK": str(r),
            "HOROVOD_SIZE": str(np_),
            "HOROVOD_LOCAL_RANK": str(r),
            "HOROVOD_LOCAL_SIZE": str(np_),
            "HOROVOD_COORDINATOR_ADDR": f"localhost:{port}",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
            "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", ""),
        })
        env.update(extra_env)
        procs.append(subprocess.Popen(
            [sys.executable, "-c", script], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True))
    outs = []
    for r, p in enumerate(procs):
        try:
            out, _ = p.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise AssertionError(f"rank {r} timed out")
        outs.append(out)
    return procs, outs


_spawn_two = _spawn_n


@pytest.mark.multiprocess
def test_straggler_attribution_2proc(tmp_path):
    """Acceptance: under ``delay@rank1:q/*:1s`` fault injection the
    analyzer must rank rank 1 first, with attributed lateness above
    0.5 s and within 2x of the injected 1 s delay."""
    flight_dir = str(tmp_path / "fl")
    script = r"""
import numpy as np
import jax.numpy as jnp
import horovod_tpu as hvd

hvd.init()
for i in range(3):
    out = hvd.allreduce(jnp.ones(2), op=hvd.Sum, name="t%d" % i)
    assert np.allclose(np.asarray(out), 2.0), out
hvd.dump_flight_recorder()
print("DONE-%d" % hvd.rank(), flush=True)
hvd.shutdown()
"""
    procs, outs = _spawn_two(script, {
        "HOROVOD_FLIGHT_DIR": flight_dir,
        "HOROVOD_FAULT_SPEC": "delay@rank1:q/*:1s",
        # the delayed rank must not be declared dead mid-test
        "HOROVOD_HEARTBEAT_INTERVAL": "0.5",
        "HOROVOD_HEARTBEAT_TIMEOUT_SECONDS": "60",
        # cache off: every round ships explicit requests, so each
        # delayed q/<r>/<rank1> write is a measurable arrival
        "HOROVOD_CACHE_CAPACITY": "0",
    })
    for r, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {r} failed:\n{out}"
        assert f"DONE-{r}" in out, out
    dumps = load_dumps(flight_dir)
    assert {d.rank for d in dumps} == {0, 1}, flight_dir
    report = analyze(dumps, compute_offsets(dumps))
    st = report["stragglers"]
    assert st["rounds"] >= 2, st
    top = st["ranking"][0]
    assert top["rank"] == 1, st["ranking"]
    # injected 1 s per round: attributed lateness in (0.5 s, 2 s)
    assert top["max_lateness_s"] > 0.5, top
    assert top["max_lateness_s"] < 2.0, top
    assert top["last_count"] >= 2, top
    # both ranks' clocks were sampled: offsets carry a measured bound,
    # and — same host, same physical clock — the estimated offset must
    # sit within that bound of the true offset (zero)
    clock = report["clock"]
    two_way = [v for v in clock.values() if v["mode"] == "two-way"]
    assert two_way, clock
    for v in two_way:
        assert v["bound_ms"] is not None
        assert abs(v["offset_ms"]) <= v["bound_ms"] + 1e-6, v


@pytest.mark.multiprocess
def test_straggler_attribution_3proc_blames_only_the_straggler(tmp_path):
    """World > 2 regression: with rank-ordered blocking gets, ranks
    that arrived DURING rank 1's injected delay were stamped when the
    coordinator's wait on rank 1 returned — blaming an innocent higher
    rank.  The fair-poll gather must attribute the lateness to rank 1
    alone."""
    flight_dir = str(tmp_path / "fl")
    script = r"""
import numpy as np
import jax.numpy as jnp
import horovod_tpu as hvd

hvd.init()
for i in range(2):
    out = hvd.allreduce(jnp.ones(2), op=hvd.Sum, name="t%d" % i)
    assert np.allclose(np.asarray(out), 3.0), out
hvd.dump_flight_recorder()
print("DONE-%d" % hvd.rank(), flush=True)
hvd.shutdown()
"""
    procs, outs = _spawn_n(script, {
        "HOROVOD_FLIGHT_DIR": flight_dir,
        "HOROVOD_FAULT_SPEC": "delay@rank1:q/*:1s",
        "HOROVOD_HEARTBEAT_INTERVAL": "0.5",
        "HOROVOD_HEARTBEAT_TIMEOUT_SECONDS": "60",
        "HOROVOD_CACHE_CAPACITY": "0",
    }, np_=3)
    for r, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {r} failed:\n{out}"
    dumps = load_dumps(flight_dir)
    report = analyze(dumps, compute_offsets(dumps))
    ranking = report["stragglers"]["ranking"]
    by_rank = {rec["rank"]: rec for rec in ranking}
    assert ranking[0]["rank"] == 1, ranking
    assert by_rank[1]["max_lateness_s"] > 0.5, by_rank
    # the innocent bystander must NOT inherit rank 1's delay
    assert by_rank[2]["max_lateness_s"] < 0.4, by_rank


@pytest.mark.multiprocess
def test_sigkill_postmortem_2proc(tmp_path):
    """Acceptance: SIGKILL rank 1 mid-job.  The survivor must write a
    dump on the coordinated abort, the dumps must merge into a valid
    Perfetto trace whose clocks agree within the measured bound, and
    the death report must name rank 1 and the last round it
    participated in."""
    flight_dir = str(tmp_path / "fl")
    hb_timeout = 5.0
    script = r"""
import os, signal, sys, time
import numpy as np
import jax.numpy as jnp
import horovod_tpu as hvd

hvd.init()
rank = hvd.rank()
for i in range(2):
    out = hvd.allreduce(jnp.ones(2), op=hvd.Sum, name="warm%d" % i)
    assert np.allclose(np.asarray(out), 2.0), out
if rank == 1:
    print("RANK1-DYING", flush=True)
    os.kill(os.getpid(), signal.SIGKILL)
time.sleep(0.5)
try:
    hvd.allreduce(jnp.ones(2), op=hvd.Sum, name="after-death")
    print("NO-ERROR", flush=True)
except hvd.RanksDownError as e:
    assert 1 in e.ranks, (e.ranks, str(e))
    print("RANKSDOWN-OK", flush=True)
sys.stdout.flush()
os._exit(0)  # skip the shutdown barrier against a dead peer
"""
    procs, outs = _spawn_two(script, {
        "HOROVOD_FLIGHT_DIR": flight_dir,
        "HOROVOD_HEARTBEAT_INTERVAL": "0.5",
        "HOROVOD_HEARTBEAT_TIMEOUT_SECONDS": str(int(hb_timeout)),
        "HOROVOD_CACHE_CAPACITY": "0",
    })
    assert procs[1].returncode == -9, (procs[1].returncode, outs[1])
    assert procs[0].returncode == 0, outs[0]
    assert "RANKSDOWN-OK" in outs[0], outs[0]
    # every survivor wrote a dump; the dead rank could not
    dumps = load_dumps(flight_dir)
    assert {d.rank for d in dumps} == {0}, os.listdir(flight_dir)
    assert dumps[0].meta["reason"] == "ranks_down"
    # merge -> one valid Perfetto-loadable JSON
    out_path, dumps, offsets = merge(flight_dir)
    with open(out_path) as f:
        trace = json.load(f)
    for ev in trace["traceEvents"]:
        assert {"ts", "pid", "tid", "ph"} <= set(ev), ev
    # clock agreement: the survivor holds samples of the dead peer's
    # clock; same-host processes share a clock, so the estimated
    # offset must sit within the measured bound
    report = analyze(dumps, offsets)
    deaths = report["deaths"]
    assert deaths["dead"] == [1], deaths
    assert "last_round" in deaths and deaths["last_round"].get("1") \
        is not None, deaths
    assert int(deaths["last_round"]["1"]) >= 0
    text = format_report(report)
    assert "DEAD rank(s): [1]" in text, text
    # abort forensics on the survivor's ring
    kinds = {e["kind"] for e in dumps[0].events}
    assert "abort" in kinds and "hb_stale" in kinds, kinds
