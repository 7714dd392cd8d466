"""Training-health plane (docs/health.md).

Covers the acceptance bar of the health PR:
  * in-trace stat taps: pre-reduction culprit attribution (rank +
    dtype group) from the packed verdict allgather, update-to-weight
    ratio, skip-step contract (params stay finite, state held);
  * parity proofs: enabling health stats changes no trained parameter
    bit across ZeRO stage 0-3 x overlap x int8/int4/topk;
  * HLO proofs via the PR 12 checker: stats add zero extra full-size
    buffers and exactly one small allgather;
  * the nan:/inf: fault grammar (deterministic gradient poisoning) and
    the 2-proc culprit test over the real negotiated wire;
  * sentinel EWMA hysteresis units (fake clock), monitor dumps, the
    `python -m horovod_tpu.perf health` report, the flight analyzer's
    health section, and the guardrail's loss-primary/residual-fallback
    precedence.
"""

import json
import os
import subprocess
import sys

import numpy as np
import optax
import pytest

import horovod_tpu as hvd  # noqa: F401
import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from horovod_tpu.analysis import hlo_lint as HL
from horovod_tpu.common import config as _config
from horovod_tpu.runtime import faults as F
from horovod_tpu.runtime import flight
from horovod_tpu.runtime import health as H
from horovod_tpu.runtime import metrics as M
import horovod_tpu.optim.distributed as D

N = 8
K = 4


@pytest.fixture(scope="module")
def mesh():
    return Mesh(np.array(jax.devices()[:N]), ("hvd",))


@pytest.fixture(scope="module")
def mesh4():
    # int4's sum-safe headroom (qmax = 7 // n) refuses axes past 7
    # ranks, so int4 parity cells run on a 4-device mesh.
    return Mesh(np.array(jax.devices()[:4]), ("hvd",))


@pytest.fixture(autouse=True)
def _fresh_monitor():
    H.reset()
    F._data_cache = ("", [])
    yield
    H.reset()
    F._data_cache = ("", [])


def _int_params():
    # 31 + 9 = 40 elements: padded-to-8 fused length (40) must differ
    # from the verdict gather's element count (N x 4 = 32), or the
    # HLO-FULLBUF proof could not tell them apart.
    return {"b": jnp.ones((3, 3), jnp.float32),
            "w": jnp.arange(-15.0, 16.0, dtype=jnp.float32)}


# ---------------------------------------------------------------------------
# Knobs / handshake
# ---------------------------------------------------------------------------


def test_health_knobs_registered():
    knobs = _config.knobs()
    for name in ("health", "health_skip_nonfinite", "health_ewma_alpha",
                 "health_sentinel_ratio", "health_trip_steps",
                 "health_clear_steps", "health_dir"):
        assert name in knobs, name
        assert knobs[name].cli, name
        assert knobs[name].config_key, name
    # the program-shaping pair must claim handshake agreement
    for name in ("health", "health_skip_nonfinite"):
        assert any(m in knobs[name].help.lower()
                   for m in ("round-0 handshake",
                             "must agree on every rank")), name


def test_round0_cfg_carries_health(monkeypatch):
    from horovod_tpu.runtime import controller as C

    monkeypatch.delenv("HOROVOD_HEALTH", raising=False)
    monkeypatch.delenv("HOROVOD_HEALTH_SKIP_NONFINITE", raising=False)
    base = C.round0_cfg()
    assert "HOROVOD_HEALTH" in C.ROUND0_KNOB_ENVS
    assert "HOROVOD_HEALTH_SKIP_NONFINITE" in C.ROUND0_KNOB_ENVS
    assert len(base) == len(C.ROUND0_KNOB_ENVS)
    i_health = C.ROUND0_KNOB_ENVS.index("HOROVOD_HEALTH")
    i_skip = C.ROUND0_KNOB_ENVS.index("HOROVOD_HEALTH_SKIP_NONFINITE")
    monkeypatch.setenv("HOROVOD_HEALTH", "1")
    on = C.round0_cfg()
    assert on != base and on[i_health] == 1 and base[i_health] == 0
    monkeypatch.setenv("HOROVOD_HEALTH_SKIP_NONFINITE", "1")
    assert C.round0_cfg()[i_skip] == 1


def test_health_cfg_joins_program_cache_key(monkeypatch):
    from horovod_tpu.ops import xla_exec as X

    monkeypatch.delenv("HOROVOD_HEALTH", raising=False)
    assert X.health_cfg() is None
    monkeypatch.setenv("HOROVOD_HEALTH", "1")
    assert X.health_cfg() == (1, 0)
    monkeypatch.setenv("HOROVOD_HEALTH_SKIP_NONFINITE", "1")
    assert X.health_cfg() == (1, 1)


# ---------------------------------------------------------------------------
# Fault grammar: nan:/inf: gradient poisoning
# ---------------------------------------------------------------------------


def test_nan_inf_spec_grammar():
    rules = F.parse_spec("nan:grad_buffer*,inf@rank1:g*:round2")
    assert rules[0].kind == "nan" and rules[0].pattern == "grad_buffer*"
    assert rules[0].round == 0 and rules[0].remaining is None
    assert rules[1].kind == "inf" and rules[1].only_rank == 1
    assert rules[1].round == 2 and rules[1].remaining == 1
    with pytest.raises(F.FaultSpecError):
        F.parse_spec("nan:g*:roundX")
    with pytest.raises(F.FaultSpecError):
        F.parse_spec("nan")
    with pytest.raises(F.FaultSpecError):
        F.parse_spec("nan@rankZ:g*")


def test_transport_ignores_data_rules():
    class T:
        writes = []

        def set(self, k, v):
            T.writes.append((k, v))

    ft = F.FaultyTransport(T(), rank=0, rules=F.parse_spec("nan:grad*"))
    ft.set("hvd1/q/0/0", "x")
    assert T.writes == [("hvd1/q/0/0", "x")]


class _E:
    def __init__(self, name, tensor):
        self.name = name
        self.tensor = tensor


def test_poison_entries_glob_rank_round(monkeypatch):
    monkeypatch.setenv("HOROVOD_FAULT_SPEC",
                       "nan@rank1:grad_buffer*:round2")
    F._data_cache = ("", [])
    mk = lambda: [_E("grad_buffer.float32.2", jnp.ones(4)),  # noqa: E731
                  _E("other.int32", jnp.ones(4, jnp.int32))]
    # wrong rank: untouched
    out = F.poison_entries(mk(), rank=0, rnd=5)
    assert np.isfinite(np.asarray(out[0].tensor)).all()
    # right rank, round too early: untouched
    out = F.poison_entries(mk(), rank=1, rnd=1)
    assert np.isfinite(np.asarray(out[0].tensor)).all()
    # fires once at the first round >= 2 ...
    out = F.poison_entries(mk(), rank=1, rnd=2)
    a = np.asarray(out[0].tensor)
    assert np.isnan(a[0]) and np.isfinite(a[1:]).all()
    assert np.asarray(out[1].tensor).dtype == np.int32  # ints untouched
    # ... and never again (deterministic single poisoning)
    out = F.poison_entries(mk(), rank=1, rnd=3)
    assert np.isfinite(np.asarray(out[0].tensor)).all()


def test_poison_entries_roundless_every_time(monkeypatch):
    monkeypatch.setenv("HOROVOD_FAULT_SPEC", "inf:grad*")
    F._data_cache = ("", [])
    for rnd in (0, 1, 7):
        out = F.poison_entries([_E("grad_buffer.float32.1",
                                   jnp.ones(3))], rank=0, rnd=rnd)
        assert np.isinf(np.asarray(out[0].tensor)[0])


def test_traced_poison_rank_scoped(monkeypatch, mesh):
    monkeypatch.setenv("HOROVOD_FAULT_SPEC", "nan@rank3:grads*")
    F._data_cache = ("", [])

    def body(x):
        idx = jax.lax.axis_index("hvd")
        return F.traced_poison(x, "grads.float32", idx)

    out = jax.jit(shard_map(body, mesh=mesh, check_vma=False,
                            in_specs=P("hvd"), out_specs=P("hvd")))(
        jnp.ones((N, 4)))
    a = np.asarray(out)
    assert np.isnan(a[3, 0])
    assert np.isfinite(np.delete(a.reshape(-1), 3 * 4)).all()


# ---------------------------------------------------------------------------
# Sentinel hysteresis (fake-clock units)
# ---------------------------------------------------------------------------


def test_sentinel_warmup_and_trip_and_clear():
    s = H.Sentinel("loss_divergence", alpha=0.5, ratio=2.0,
                   trip_steps=3, clear_steps=4)
    # warmup: even huge values cannot breach before WARMUP_SAMPLES
    for _ in range(H.WARMUP_SAMPLES):
        assert s.observe(1.0) is None
    assert not s.active
    # two breaches then recovery: hysteresis holds
    assert s.observe(10.0) is None
    assert s.observe(10.0) is None
    assert s.observe(1.0) is None and not s.active
    # three consecutive breaches trip
    assert s.observe(10.0) is None
    assert s.observe(10.0) is None
    assert s.observe(10.0) == "trip" and s.active
    # EWMA did not chase the divergence
    assert s.mean == pytest.approx(1.0)
    # clears only after clear_steps healthy samples
    for _ in range(3):
        assert s.observe(1.0) is None and s.active
    assert s.observe(1.0) == "clear" and not s.active


def test_sentinel_nonfinite_breaches_immediately():
    s = H.Sentinel("x", alpha=0.1, ratio=4.0, trip_steps=1,
                   clear_steps=2)
    assert s.observe(float("nan")) == "trip"  # warmup does not protect


def test_monitor_loss_sentinel_with_fake_clock(monkeypatch):
    monkeypatch.setenv("HOROVOD_HEALTH_TRIP_STEPS", "2")
    monkeypatch.setenv("HOROVOD_HEALTH_CLEAR_STEPS", "3")
    monkeypatch.setenv("HOROVOD_HEALTH_SENTINEL_RATIO", "3.0")
    t = [100.0]
    m = H.HealthMonitor(clock=lambda: t[0])
    for _ in range(H.WARMUP_SAMPLES):
        m.observe_loss(2.0)
    t[0] = 123.0
    m.observe_loss(50.0)
    assert m.alerts_total() == 0
    m.observe_loss(50.0)
    assert m.active_alerts() == ["loss_divergence"]
    assert m.snapshot()["alert_log"][0]["time"] == 123.0
    for _ in range(3):
        m.observe_loss(2.0)
    assert m.active_alerts() == []
    assert m.alerts_total() == 1  # trips are counted, clears are not


def test_monitor_nonfinite_loss_immediate_alert_then_clears(monkeypatch):
    monkeypatch.setenv("HOROVOD_HEALTH_CLEAR_STEPS", "4")
    m = H.HealthMonitor()
    m.observe_loss(float("nan"))
    assert "loss_nonfinite" in m.active_alerts()
    # the latched alert clears after clear_steps consecutive finite
    # losses — a transient NaN must not pin the alert forever
    for _ in range(3):
        m.observe_loss(1.0)
        assert "loss_nonfinite" in m.active_alerts()
    m.observe_loss(1.0)
    assert "loss_nonfinite" not in m.active_alerts()
    assert m.alerts_total() == 1  # lifetime count keeps the event


def test_nonfinite_alert_clears_after_clean_verdicts(monkeypatch):
    # clear_steps ABOVE the 5-sample loss warmup, so the loss_guard
    # check below observes the alert while it is still latched
    monkeypatch.setenv("HOROVOD_HEALTH_CLEAR_STEPS", "8")
    H.reset()
    poisoned = np.array([[1.0, 4.0, 2.0, 5.0]])
    clean = np.array([[0.0, 4.0, 2.0, 0.0], [1.0, 4.0, 2.0, 0.0]])
    H.publish_verdict(poisoned, idx=None, groups=("float32",))
    m = H.monitor()
    assert "nonfinite" in m.active_alerts()
    # ...and loss_guard reports diverged while it is active
    for _ in range(H.WARMUP_SAMPLES):
        m.observe_loss(1.0)
    assert H.loss_guard()["diverged"] is True
    for _ in range(7):
        H.publish_verdict(clean, idx=0, groups=("float32",))
        assert "nonfinite" in m.active_alerts()
    H.publish_verdict(clean, idx=0, groups=("float32",))
    assert "nonfinite" not in m.active_alerts()
    assert H.loss_guard()["diverged"] is False  # guardrail unpinned
    # a new poisoned verdict re-latches (and recounts the trip)
    H.publish_verdict(poisoned, idx=None, groups=("float32",))
    assert "nonfinite" in m.active_alerts()
    assert m.alerts_total() == 2


def test_negative_loss_baseline_never_ratio_trips(monkeypatch):
    """An ELBO-style negative loss must not false-trip the divergence
    sentinel: against a negative EWMA the ratio threshold would
    collapse to ~0 and ordinary noise around zero would breach."""
    s = H.Sentinel("loss_divergence", alpha=0.3, ratio=4.0,
                   trip_steps=1, clear_steps=2)
    for _ in range(H.WARMUP_SAMPLES):
        assert s.observe(-120.0) is None
    for v in (-80.0, -10.0, -0.001, 0.002, 0.0):
        assert s.observe(v) is None, v
    assert not s.active


# ---------------------------------------------------------------------------
# Verdict publication + report plumbing
# ---------------------------------------------------------------------------


def test_publish_verdict_attribution_and_idx_gate():
    # rows: [rank, sumsq, maxabs, nonfinite] — rank 2 poisoned
    rows = np.array([[0.0, 4.0, 2.0, 0.0],
                     [1.0, 9.0, 3.0, 0.0],
                     [2.0, 1.0, 1.0, 5.0]])
    H.publish_verdict(rows, idx=0, groups=("float32",))
    m = H.monitor()
    snap = m.snapshot()
    assert snap["culprits"] == [{"rank": 2, "group": "float32",
                                 "count": 5.0}]
    assert snap["first_nonfinite"]["rank"] == 2
    assert "nonfinite" in m.active_alerts()
    assert M.gauge("hvd_grad_norm").value(group="all") == \
        pytest.approx(np.sqrt(14.0))
    assert M.gauge("hvd_grad_max_abs").value(group="float32") == 3.0
    assert M.counter("hvd_nonfinite_total").value(
        group="float32", rank="2") == 5.0
    # a mismatching idx (another local device's invocation) is a no-op
    H.publish_verdict(rows, idx=7, groups=("float32",))
    assert M.counter("hvd_nonfinite_total").value(
        group="float32", rank="2") == 5.0
    # flight ring carries the first-nonfinite event
    evs = [e for e in flight.recorder().snapshot()
           if e.get("kind") == "health"]
    assert any(e.get("event") == "first_nonfinite" and
               e.get("culprit") == 2 for e in evs)


def test_wire_tap_verdict_does_not_feed_grad_sentinel():
    """Per-buffer wire verdicts (sentinel=False) publish gauges and
    culprit attribution but must NOT feed the grad-norm EWMA: the
    eager wire fires once per fused buffer, and per-buffer norms of
    different magnitudes would false-trip the divergence sentinel on
    every big buffer of a healthy run."""
    m = H.monitor()
    for _ in range(H.WARMUP_SAMPLES + 3):
        # alternating small/large buffers, all healthy
        H.publish_verdict(np.array([[0.0, 1.0, 1.0, 0.0]]), idx=0,
                          groups=("bfloat16",), sentinel=False)
        H.publish_verdict(np.array([[0.0, 1e6, 1e3, 0.0]]), idx=0,
                          groups=("float32",), sentinel=False)
    assert m.grad.samples == 0  # sentinel never fed
    assert m.active_alerts() == []
    # the per-group gauges still published
    assert M.gauge("hvd_grad_norm").value(group="float32") == 1e3
    # ...and wire verdicts must not advance the clear streak either:
    # with ~K fused buffers per step, per-buffer clean verdicts would
    # shrink the clear hysteresis K-fold
    m.note_nonfinite(1.0, "float32", 0)
    assert "nonfinite" in m.active_alerts()
    for _ in range(100):
        H.publish_verdict(np.array([[0.0, 1.0, 1.0, 0.0]]), idx=0,
                          groups=("float32",), sentinel=False)
    assert "nonfinite" in m.active_alerts()


def test_healthy_run_publishes_no_phantom_alert_series(monkeypatch):
    monkeypatch.setenv("HOROVOD_HEALTH_CLEAR_STEPS", "2")
    H.reset()
    m = H.monitor()
    for _ in range(10):  # well past clear_steps — clears must not
        m.observe_loss(1.0)  # INSERT never-tripped reasons at 0
        H.publish_verdict(np.array([[0.0, 1.0, 1.0, 0.0]]), idx=0,
                          groups=("float32",))
    m.refresh()
    assert M.gauge("hvd_health_alert").series() == []
    view = H.from_metrics_snapshot(M.metrics())
    assert view["alerts_total"] == 0 and view["active_alerts"] == []


def test_eager_nonfinite_alert_clears_via_finite_losses(monkeypatch):
    monkeypatch.setenv("HOROVOD_HEALTH_CLEAR_STEPS", "3")
    H.reset()
    m = H.monitor()
    m.note_nonfinite(2.0, "float32", 1)  # wire verdict latched it
    assert "nonfinite" in m.active_alerts()
    for _ in range(2):
        m.observe_loss(1.0)
        assert "nonfinite" in m.active_alerts()
    m.observe_loss(1.0)  # 3rd finite loss: recovery evidence
    assert "nonfinite" not in m.active_alerts()


def test_nonfinite_alert_does_not_flap_under_persistent_poison(
        monkeypatch):
    """Persistent poisoning + the skip contract keeps the LOSS finite
    while verdicts keep arriving poisoned — the finite-loss streak
    alone must not clear (and re-trip) the nonfinite alert every
    clear_steps losses."""
    monkeypatch.setenv("HOROVOD_HEALTH_CLEAR_STEPS", "3")
    H.reset()
    m = H.monitor()
    for _ in range(12):  # one poisoned verdict + one finite loss/step
        m.note_nonfinite(1.0, "float32", 1)
        m.observe_loss(1.0)
        assert "nonfinite" in m.active_alerts()
    assert m.alerts_total() == 1  # latched once, no flapping
    # poisoning stops: clear_steps further losses with NO new
    # nonfinite event clear it
    for _ in range(3):
        m.observe_loss(1.0)
    assert "nonfinite" not in m.active_alerts()


def test_wire_only_nonfinite_alert_clears_per_round(monkeypatch):
    """Eager jobs that never feed a loss still get the documented
    clear hysteresis: a completed clean negotiation round counts once
    toward CLEAR_STEPS no matter how many fused buffers it dispatched
    (buffers-per-step must not shrink the window)."""
    monkeypatch.setenv("HOROVOD_HEALTH_CLEAR_STEPS", "3")
    H.reset()
    m = H.monitor()
    clean = np.array([[0.0, 1.0, 1.0, 0.0]])
    H.note_wire_round(0)
    m.note_nonfinite(1.0, "float32", 1)
    assert "nonfinite" in m.active_alerts()
    # rounds 1..3 each dispatch SEVERAL clean per-buffer verdicts
    for rnd in (1, 2, 3):
        H.note_wire_round(rnd)
        for _ in range(5):
            H.publish_verdict(clean, idx=0, groups=("float32",),
                              sentinel=False)
        if rnd < 3:
            assert "nonfinite" in m.active_alerts(), rnd
    # rounds 1 and 2 completed clean (finalized at the NEXT marker);
    # round 4's marker finalizes round 3 = the 3rd clean round
    H.note_wire_round(4)
    assert "nonfinite" not in m.active_alerts()
    # a poisoned round resets the streak
    m.note_nonfinite(1.0, "float32", 1)
    H.note_wire_round(5)
    assert "nonfinite" in m.active_alerts()


def test_guardrail_ceiling_zero_outranks_healthy_loss(monkeypatch):
    """HOROVOD_COMPRESSION_MAX_RESIDUAL_RATIO=0 is an explicit
    operator kill switch: a healthy loss trajectory must not bypass
    it."""
    monkeypatch.setenv("HOROVOD_COMPRESSION_MAX_RESIDUAL_RATIO", "0")
    pm = _pm(monkeypatch)
    gauge = M.gauge("hvd_compression_residual_ratio")
    gauge.reset()
    try:
        gauge.set(0.01, bucket="0")
        gauge.set(0.01, bucket="1")
        for _ in range(H.WARMUP_SAMPLES + 1):
            H.observe_loss(1.0)
        assert H.loss_guard()["diverged"] is False
        out = pm._guard({"bucket_compression": "int4:topk"})
        assert out["bucket_compression"] == "int8:int8"
    finally:
        gauge.reset()


def test_load_report_does_not_world_fold_culprits(tmp_path):
    """Every rank's dump carries the SAME allgathered verdict counts;
    the merged report must MAX them, not sum (1 real element must not
    read as world elements)."""
    H.monitor().note_nonfinite(1.0, "float32", 1)
    snap = H.monitor().snapshot()
    for rank in (0, 1):  # identical fleet-wide verdict on both ranks
        per = dict(snap)
        per["meta"] = {"rank": rank, "size": 2, "generation": 1,
                       "reason": "test"}
        with open(tmp_path / f"health-r{rank}-g1.json", "w") as f:
            json.dump(per, f)
    rep = H.load_report(str(tmp_path))
    assert len(rep["ranks"]) == 2
    assert rep["culprits"] == [{"rank": 1, "group": "float32",
                                "count": 1.0}]
    assert rep["alerts_total"] == 1


def test_data_rules_raise_on_malformed_spec(monkeypatch):
    """A typo'd nan:/inf: spec must fail loudly — in the 1-proc
    in-trace regime no FaultyTransport exists to surface the parse
    error, and a silent no-op would turn a detection test vacuous."""
    monkeypatch.setenv("HOROVOD_FAULT_SPEC", "nan:grads*:round_x")
    F._data_cache = ("", [])
    with pytest.raises(F.FaultSpecError):
        F.data_rules()


def test_update_ratio_eager_publish():
    H.tap_update_ratio({"w": jnp.full((4,), 0.5)},
                       {"w": jnp.full((4,), 5.0)})
    assert M.gauge("hvd_update_ratio").value(group="float32") == \
        pytest.approx(0.1)


def test_dump_load_report_cli_round_trip(tmp_path, monkeypatch):
    monkeypatch.setenv("HOROVOD_HEALTH_DIR", str(tmp_path))
    m = H.monitor()
    m.note_nonfinite(3.0, "float32", 1)
    m.observe_grad_norm(12.5)
    m.observe_loss(0.7)
    path = H.dump("test")
    assert path and os.path.exists(path)
    rep = H.load_report(str(tmp_path))
    assert rep["ranks"][0]["last_grad_norm"] == 12.5
    assert rep["culprits"] == [{"rank": 1, "group": "float32",
                                "count": 3.0}]
    text = H.format_report(rep)
    assert "rank 1 / float32" in text and "3 nonfinite" in text
    # CLI surface
    r = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.perf", "health",
         str(tmp_path), "--json"],
        capture_output=True, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, r.stderr[:500]
    out = json.loads(r.stdout)
    assert out["culprits"][0]["rank"] == 1


@pytest.mark.parametrize("form", ["dump", "metrics_snapshot"])
def test_report_reads_one_file_of_each_form_it_knows(tmp_path, form):
    """``perf health <file>``: a single ``health-*.json`` dump and a
    single ``/metrics.json`` snapshot each give one rank's row with its
    nonfinite element count (the third form, a ``bench.py`` result
    line, went with the script in PR 31)."""
    H.publish_verdict(np.array([[1.0, 4.0, 2.0, 7.0]]), idx=None,
                      groups=("bfloat16",))
    H.observe_loss(0.5)
    if form == "dump":
        obj = H.monitor().snapshot()
    else:
        obj = M.metrics()
    path = tmp_path / "one.json"
    path.write_text(json.dumps(obj))
    rep = H.load_report(str(path))
    assert len(rep["ranks"]) == 1
    assert rep["culprits"] == [{"rank": 1, "group": "bfloat16",
                                "count": 7.0}]
    text = H.format_report(rep)
    assert "nonfinite 7" in text and "nonfinite_events" not in text
    assert "rank 1 / bfloat16" in text


def test_from_metrics_snapshot():
    H.publish_verdict(np.array([[1.0, 4.0, 2.0, 7.0]]), idx=None,
                      groups=("bfloat16",))
    H.observe_loss(0.5)
    view = H.from_metrics_snapshot(M.metrics())
    assert view is not None
    assert view["last_loss"] == 0.5
    assert any(c["rank"] == 1 and c["group"] == "bfloat16"
               and c["count"] == 7.0 for c in view["culprits"])
    assert "nonfinite" in view["active_alerts"]


# ---------------------------------------------------------------------------
# Guardrail precedence: loss trajectory primary, residual fallback
# ---------------------------------------------------------------------------


def _pm(monkeypatch, world=8):
    monkeypatch.setenv("HOROVOD_AUTOTUNE", "1")
    monkeypatch.setenv("HOROVOD_ADAPTIVE_COMPRESSION", "1")
    monkeypatch.setenv("HOROVOD_OVERLAP", "1")
    monkeypatch.setenv("HOROVOD_OVERLAP_CHUNKS", "2")
    import horovod_tpu.runtime.parameter_manager as pmmod

    return pmmod.ParameterManager(world=world, hier_possible=False)


def test_guardrail_loss_primary_residual_fallback(monkeypatch):
    pm = _pm(monkeypatch)
    gauge = M.gauge("hvd_compression_residual_ratio")
    gauge.reset()
    try:
        gauge.set(0.9, bucket="0")  # proxy says: pin slot 0 back
        # no loss observed -> the residual proxy governs (fallback)
        assert H.loss_guard() is None
        out = pm._guard({"bucket_compression": "topk:topk"})
        assert out["bucket_compression"] == "int8:topk"
        # healthy loss trajectory -> primary signal overrides the proxy
        for _ in range(H.WARMUP_SAMPLES + 1):
            H.observe_loss(1.0)
        assert H.loss_guard() == {"diverged": False,
                                  "ratio": pytest.approx(1.0),
                                  "samples": H.WARMUP_SAMPLES + 1}
        out = pm._guard({"bucket_compression": "topk:topk"})
        assert out["bucket_compression"] == "topk:topk"
        # diverged loss -> every aggressive slot pinned back
        H.monitor()._raise_alert("loss_divergence", value=99.0)
        out = pm._guard({"bucket_compression": "topk:int4"})
        assert out["bucket_compression"] == "int8:int8"
    finally:
        gauge.reset()


def test_guardrail_nonfinite_pins_back(monkeypatch):
    pm = _pm(monkeypatch)
    for _ in range(H.WARMUP_SAMPLES + 1):
        H.observe_loss(1.0)
    H.monitor().note_nonfinite(1.0, "float32", 0)
    out = pm._guard({"bucket_compression": "int4:topk"})
    assert out["bucket_compression"] == "int8:int8"


# ---------------------------------------------------------------------------
# In-trace taps: attribution, skip, parity, HLO
# ---------------------------------------------------------------------------


def _run_traj(mesh, opt_ctor, steps=3, poison_rank=None,
              poison_step=None, stage=0, t=5.0):
    """Fixed-integer-gradient trajectory under shard_map; returns the
    final params (full tree for every stage)."""
    params = _int_params()
    opt = opt_ctor()

    def body(tv):
        if stage >= 3:
            zp = D.zero3_shard_params(params)
            st = opt.init(zp)
            keys = sorted(params)
            for step in range(steps):
                def loss(z):
                    full = D.zero3_full_params(z)
                    return sum((i + 1.0) * (tv - 3.0) * jnp.sum(full[k])
                               for i, k in enumerate(keys))

                g = jax.grad(loss)(zp)
                upd, st = opt.update(g, st, zp)
                zp = optax.apply_updates(zp, upd)
            return D.zero3_full_params(zp)
        p = dict(params)
        st = opt.init(p)
        for step in range(steps):
            g = {k: jnp.full(v.shape, (i + 1.0) * (tv - 3.0), v.dtype)
                 for i, (k, v) in enumerate(sorted(p.items()))}
            if poison_rank is not None and step == poison_step:
                idx = jax.lax.axis_index("hvd")
                g = {k: jnp.where(
                    (idx == poison_rank)
                    & (jnp.arange(v.size).reshape(v.shape) == 0),
                    jnp.nan, v) for k, v in g.items()}
            upd, st = opt.update(g, st, p)
            p = optax.apply_updates(p, upd)
        return p

    out = jax.jit(shard_map(body, mesh=mesh, check_vma=False,
                            in_specs=P(), out_specs=P()))(
        jnp.float32(t))
    jax.effects_barrier()
    return {k: np.asarray(v) for k, v in out.items()}


def test_intrace_culprit_attribution_and_skip(mesh, monkeypatch):
    monkeypatch.setenv("HOROVOD_HEALTH", "1")
    monkeypatch.setenv("HOROVOD_HEALTH_SKIP_NONFINITE", "1")
    out = _run_traj(mesh, lambda: hvd.DistributedOptimizer(
        optax.sgd(0.1), zero_stage=2), poison_rank=3, poison_step=1)
    assert all(np.isfinite(v).all() for v in out.values())
    snap = H.monitor().snapshot()
    assert snap["culprits"] == [{"rank": 3, "group": "float32",
                                 "count": 2.0}]  # one elem x two leaves
    assert snap["skipped_steps"] == 1
    assert M.counter("hvd_nonfinite_total").value(
        group="float32", rank="3") == 2.0
    assert "nonfinite" in snap["active_alerts"]
    # the skipped step contributed nothing: trajectory equals a clean
    # run of steps-1 updates
    H.reset()
    clean = _run_traj(mesh, lambda: hvd.DistributedOptimizer(
        optax.sgd(0.1), zero_stage=2), steps=2)
    for k in out:
        assert np.array_equal(out[k], clean[k]), k


_PARITY_GRID = [
    # (stage, overlap, mode) — the not-slow corners
    pytest.param(0, False, "none"),
    pytest.param(1, False, "int8"),
    pytest.param(2, True, "none"),
    pytest.param(3, False, "none"),
] + [
    pytest.param(st, ov, mode, marks=pytest.mark.slow)
    for st in (0, 1, 2, 3) for ov in (False, True)
    for mode in ("none", "int8", "int4", "topk")
    if (st, ov, mode) not in ((0, False, "none"), (1, False, "int8"),
                              (2, True, "none"), (3, False, "none"))
]


@pytest.mark.parametrize("stage,overlap,mode", _PARITY_GRID)
def test_stats_on_off_parity_bit_exact(mesh, mesh4, monkeypatch, stage,
                                       overlap, mode):
    """The parity acceptance proof: enabling health stats changes no
    trained parameter bit — the taps are pure observers riding the
    existing program."""
    if mode == "int4":
        mesh = mesh4  # 7 // 8 == 0: int4 refuses the 8-rank axis
    monkeypatch.setenv("HOROVOD_COMPRESSION", mode)
    monkeypatch.setenv("HOROVOD_OVERLAP", "1" if overlap else "0")
    ctor = lambda: hvd.DistributedOptimizer(  # noqa: E731
        optax.sgd(0.1), zero_stage=stage)
    monkeypatch.setenv("HOROVOD_HEALTH", "1")
    on = _run_traj(mesh, ctor, stage=stage)
    monkeypatch.setenv("HOROVOD_HEALTH", "0")
    off = _run_traj(mesh, ctor, stage=stage)
    for k in on:
        assert np.array_equal(on[k], off[k]), (stage, overlap, mode, k)


def _lower_step(mesh, stage):
    params = _int_params()
    opt = hvd.DistributedOptimizer(optax.sgd(0.1), zero_stage=stage)

    def body(t):
        p = dict(params)
        st = opt.init(p)
        g = {k: jnp.full(v.shape, t - 3.0, v.dtype)
             for k, v in sorted(p.items())}
        upd, st = opt.update(g, st, p)
        return optax.apply_updates(p, upd)

    return jax.jit(shard_map(body, mesh=mesh, check_vma=False,
                             in_specs=P(), out_specs=P())).lower(
        jnp.float32(3.0))


def test_hlo_no_extra_full_buffer_one_small_allgather(mesh,
                                                      monkeypatch):
    """The HLO acceptance proof via the PR 12 checker: with health on,
    the stage-2 residency contract still holds (zero extra full-size
    buffers) and exactly ONE new allgather appears — the small packed
    verdict vector."""
    total = 40  # 31 + 9 elements
    padded = total + (-total) % N
    assert padded != N * 4  # the verdict gather must stay tellable
    monkeypatch.setenv("HOROVOD_HEALTH", "0")
    off = _lower_step(mesh, stage=2).as_text("hlo")
    monkeypatch.setenv("HOROVOD_HEALTH", "1")
    on = _lower_step(mesh, stage=2).as_text("hlo")
    # residency: the PR 12 structural checker finds no full-size fused
    # buffer in the health-on program
    findings = HL.check_program(
        on, [HL.no_full_buffer(padded, label="health_on_zero2")])
    assert findings == [], findings
    prog_on, prog_off = HL.parse_hlo(on), HL.parse_hlo(off)
    ag_on = prog_on.by_opcode("all-gather")
    ag_off = prog_off.by_opcode("all-gather")
    assert len(ag_on) == len(ag_off) + 1, (len(ag_on), len(ag_off))
    # ...and the added one is SMALL: the packed per-rank verdict
    # (n x (1 + 3G) floats), nowhere near the fused buffer size
    sizes_off = sorted(s.elems for i in ag_off for s in i.shapes)
    sizes_on = sorted(s.elems for i in ag_on for s in i.shapes)
    added = [e for e in sizes_on]
    for e in sizes_off:
        added.remove(e)
    assert len(added) == 1 and added[0] <= N * 8, (added, sizes_on)


def test_hlo_stage0_single_verdict_allgather(mesh, monkeypatch):
    monkeypatch.setenv("HOROVOD_HEALTH", "0")
    off = HL.parse_hlo(_lower_step(mesh, stage=0).as_text("hlo"))
    monkeypatch.setenv("HOROVOD_HEALTH", "1")
    on = HL.parse_hlo(_lower_step(mesh, stage=0).as_text("hlo"))
    assert len(off.by_opcode("all-gather")) == 0
    assert len(on.by_opcode("all-gather")) == 1


# ---------------------------------------------------------------------------
# Flight analyzer health section
# ---------------------------------------------------------------------------


def test_analyzer_health_section(tmp_path):
    from horovod_tpu.trace.analyze import analyze, format_report
    from horovod_tpu.trace.merge import merge

    r0 = flight.FlightRecorder(64)
    r0.record("round", ph="B", round=0, n_req=1)
    r0.record("round", ph="E", round=0, path="slow", n_resp=1)
    r0.record("round", ph="B", round=1, n_req=1)
    r0.record("health", event="first_nonfinite", culprit=1,
              group="float32", count=2.0)
    r0.record("health", event="sentinel_trip", reason="loss_divergence")
    r0.record("abort", ranks=[1], round=1)
    r0.dump(os.path.join(tmp_path, "flight-r0-g1-p1.jsonl"),
            {"rank": 0, "size": 2, "generation": 1,
             "reason": "ranks_down"})
    r1 = flight.FlightRecorder(64)
    r1.record("round", ph="B", round=0, n_req=1)
    r1.dump(os.path.join(tmp_path, "flight-r1-g1-p2.jsonl"),
            {"rank": 1, "size": 2, "generation": 1})
    _, dumps, offsets = merge(str(tmp_path))
    rep = analyze(dumps, offsets)
    hl = rep["health"]
    assert hl["first_nonfinite"][0]["culprit"] == 1
    assert hl["first_nonfinite"][0]["group"] == "float32"
    assert hl["first_nonfinite"][0]["round"] == 1  # anchored vs rounds
    assert any(t["event"] == "sentinel_trip"
               and t["reason"] == "loss_divergence"
               for t in hl["sentinel_trips"])
    # the timeline interleaves the abort with the health events
    kinds = [r["kind"] for r in hl["timeline"]]
    assert "abort" in kinds and "health" in kinds
    text = format_report(rep)
    assert "training health" in text
    assert "culprit rank 1 / float32" in text
    assert "sentinel TRIP reason=loss_divergence" in text


def test_analyzer_health_section_empty(tmp_path):
    from horovod_tpu.trace.analyze import analyze, format_report
    from horovod_tpu.trace.merge import merge

    r0 = flight.FlightRecorder(16)
    r0.record("round", ph="B", round=0, n_req=1)
    r0.dump(os.path.join(tmp_path, "flight-r0-g1-p1.jsonl"),
            {"rank": 0, "size": 1, "generation": 1})
    _, dumps, offsets = merge(str(tmp_path))
    rep = analyze(dumps, offsets)
    assert rep["health"]["first_nonfinite"] == []
    assert "no nonfinite gradients or" in format_report(rep)


# ---------------------------------------------------------------------------
# 2-proc: culprit attribution over the real negotiated wire
# ---------------------------------------------------------------------------


@pytest.mark.multiprocess
def test_culprit_attribution_2proc(tmp_path):
    """The acceptance scenario: rank 1's gradient payload is poisoned
    at negotiation round >= 2 (deterministic nan: fault rule); BOTH
    ranks' metrics name rank 1 + the float32 dtype group, the merged
    flight trace's health section names it on the aligned clock, and
    with HOROVOD_HEALTH_SKIP_NONFINITE=1 the poisoned step is skipped
    so survivors' params stay finite and identical across ranks."""
    from tests.test_multiprocess import run_ranks

    flight_dir = str(tmp_path / "flight")
    outs = run_ranks("""
        import json
        import optax
        from horovod_tpu.runtime import health as H

        params = {"w": jnp.ones((8,), jnp.float32)}
        opt = hvd.DistributedOptimizer(optax.sgd(0.1))
        state = opt.init(params)
        for step in range(6):
            grads = {"w": jnp.full((8,), 0.5 + rank, jnp.float32)}
            upd, state = opt.update(grads, state, params)
            params = optax.apply_updates(params, upd)
        w = np.asarray(params["w"])
        assert np.isfinite(w).all(), w
        snap = hvd.metrics()["metrics"]
        nf = snap.get("hvd_nonfinite_total", {}).get("series", [])
        by = {(s["labels"].get("rank"), s["labels"].get("group")):
              s["value"] for s in nf}
        assert by.get(("1", "float32"), 0) > 0, (rank, by)
        assert not any(r == "0" for r, _ in by), (rank, by)
        alerts = snap.get("hvd_health_alert", {}).get("series", [])
        assert any(s["labels"].get("reason") == "nonfinite"
                   and s["value"] == 1 for s in alerts), (rank, alerts)
        skips = H.monitor().snapshot()["skipped_steps"]
        assert skips >= 1, skips
        print("HEALTH-%d %s" % (rank, json.dumps(
            {"w": w.tolist(), "culprits": sorted(by)})), flush=True)
        hvd.dump_flight_recorder()
    """, extra_env={
        "HOROVOD_HEALTH": "1",
        "HOROVOD_HEALTH_SKIP_NONFINITE": "1",
        "HOROVOD_FAULT_SPEC": "nan@rank1:grad_buffer*:round2",
        "HOROVOD_FLIGHT_DIR": flight_dir,
    })
    # both ranks converged to the SAME finite params (the skip verdict
    # is consistent: the poisoned reduction is NaN everywhere)
    ws = []
    for out in outs:
        line = [ln for ln in out.splitlines()
                if ln.startswith("HEALTH-")][0]
        ws.append(json.loads(line.split(" ", 1)[1])["w"])
    assert ws[0] == ws[1]
    # the merged flight trace names the culprit on the aligned clock
    from horovod_tpu.trace.analyze import analyze, format_report
    from horovod_tpu.trace.merge import merge

    _, dumps, offsets = merge(flight_dir)
    rep = analyze(dumps, offsets)
    firsts = rep["health"]["first_nonfinite"]
    assert firsts, rep["health"]
    assert all(f["culprit"] == 1 and f["group"] == "float32"
               for f in firsts), firsts
    text = format_report(rep)
    assert "culprit rank 1 / float32" in text
    # per-rank health dumps landed beside the flight rings (health_dir
    # falls back to the flight dir) and the CLI report reads them
    rep2 = H.load_report(flight_dir)
    assert any(c["rank"] == 1 and c["group"] == "float32"
               for c in rep2["culprits"]), rep2
