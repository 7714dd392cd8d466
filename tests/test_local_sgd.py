"""Cross-slice local-SGD / DiLoCo outer loop (docs/local-sgd.md).

Covers the acceptance bar of the local-SGD PR:
  * knob resolution (``HOROVOD_LOCAL_SGD_H`` / outer lr / momentum /
    compression) and the metrics gauge;
  * H=1 / knob-off bit-exact parity with a plain
    ``DistributedOptimizer`` (replicated + ZeRO-1, overlap on/off) —
    the regime can be flipped on without touching code;
  * DiLoCo outer-step math pinned against a NumPy reference (dyadic
    values, bit equality) over the in-trace ('cross','local') mesh;
  * ZeRO 1-3 composition: local-axis sharded runs walk bit-identically
    to the stage-0 regime;
  * single-slice degenerate world: loud warning, no-op outer sync;
  * HLO proofs: the compiled inner program carries ZERO cross-slice
    collectives, the outer program must carry one (positive controls
    both ways + the checked-in must-trip fixture);
  * round-0 handshake: cfg i64s #23-26 + the 2-proc mismatch test per
    entry;
  * simfleet ICI/DCN latency split (back-compat) and the >= H-fold
    cross-round economy scenario;
  * autopilot comm_retune proposing H doubling; goodput outer-sync
    accounting; the elastic commit-boundary helper.
"""

import numpy as np
import optax
import pytest

import jax
import jax.numpy as jnp

import horovod_tpu as hvd  # noqa: F401

from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

import os

from horovod_tpu.analysis import hlo_lint as HL
from horovod_tpu.common import config as _config
from horovod_tpu.common.types import HorovodTpuError
from horovod_tpu.ops.collectives import Adasum
from horovod_tpu.ops.compression import Compression
from horovod_tpu.optim import distributed as D
from horovod_tpu.optim import local_sgd as LS
from horovod_tpu.parallel import mesh as M

CROSS, LOCAL = 2, 4
N = CROSS * LOCAL
PAIR = ("cross", "local")

FIXTURES = os.path.join(os.path.dirname(__file__), "data", "analysis")

LS_ENVS = ("HOROVOD_LOCAL_SGD_H", "HOROVOD_OUTER_LR",
           "HOROVOD_OUTER_MOMENTUM", "HOROVOD_LOCAL_SGD_COMPRESSION")


@pytest.fixture(autouse=True)
def _clean_ls_env(monkeypatch):
    for e in LS_ENVS + ("HOROVOD_COMPRESSION", "HOROVOD_MESH",
                        "HOROVOD_HIERARCHICAL_ALLREDUCE",
                        "HOROVOD_HIERARCHICAL_LOCAL_SIZE"):
        monkeypatch.delenv(e, raising=False)
    yield


@pytest.fixture(scope="module")
def ls_mesh():
    """The two-level ('cross','local') mesh of the regime: 2 slices of
    4 devices — cross groups are the strided columns {0,4},{1,5},..."""
    return M.hierarchical_mesh(jax.devices()[:N], local_size=LOCAL)


@pytest.fixture(scope="module")
def flat_mesh():
    return Mesh(np.array(jax.devices()[:4]), ("hvd",))


# ---------------------------------------------------------------------------
# Knob resolution
# ---------------------------------------------------------------------------


def test_resolved_h(monkeypatch):
    assert LS.resolved_h() == 0
    monkeypatch.setenv("HOROVOD_LOCAL_SGD_H", "4")
    assert LS.resolved_h() == 4
    assert LS.resolved_h(8) == 8  # explicit wins over the knob
    assert LS.resolved_h(-3) == 0  # clamped


def test_knob_defaults():
    assert int(_config.get("local_sgd_h")) == 0
    assert float(_config.get("outer_lr")) == 0.7
    assert float(_config.get("outer_momentum")) == 0.9
    assert str(_config.get("local_sgd_compression") or "") == ""


def test_outer_compression_resolution(monkeypatch):
    assert LS.outer_compression() is Compression.none
    monkeypatch.setenv("HOROVOD_COMPRESSION", "int8")
    assert LS.outer_compression() is Compression.int8  # inherits
    monkeypatch.setenv("HOROVOD_LOCAL_SGD_COMPRESSION", "fp16")
    assert LS.outer_compression() is Compression.fp16  # own knob wins
    assert LS.outer_compression(Compression.bf16) is Compression.bf16


def test_local_sgd_cache_cfg(monkeypatch):
    from horovod_tpu.ops import xla_exec as X

    assert X.local_sgd_cfg() is None
    monkeypatch.setenv("HOROVOD_LOCAL_SGD_H", "4")
    cfg = X.local_sgd_cfg()
    assert cfg == (4, 700000, 900000, "none")
    monkeypatch.setenv("HOROVOD_LOCAL_SGD_COMPRESSION", "int8")
    assert X.local_sgd_cfg()[3] == "int8"


def test_reduction_scope_contract():
    from horovod_tpu.runtime import controller as C

    assert C.reduction_scope("localsgd.local.g0") == "local"
    assert C.reduction_scope("localsgd.cross.sim_g1") == "cross"
    assert C.reduction_scope("grads.dense.kernel") is None


# ---------------------------------------------------------------------------
# Construction: rejections, degenerate world, gauge
# ---------------------------------------------------------------------------


def test_active_regime_rejections():
    with pytest.raises(HorovodTpuError, match="backward_passes_per_step"):
        hvd.LocalSGD(optax.sgd(0.1), h=4, axis_name=PAIR,
                     backward_passes_per_step=2)
    with pytest.raises(HorovodTpuError, match="Adasum"):
        hvd.LocalSGD(optax.sgd(0.1), h=4, axis_name=PAIR, op=Adasum)
    with pytest.raises(TypeError, match="optax"):
        hvd.LocalSGD(object())
    opt = hvd.LocalSGD(optax.sgd(0.1), h=4, axis_name=PAIR,
                       compression=Compression.none)
    with pytest.raises(HorovodTpuError, match="floating"):
        opt.init({"w": jnp.arange(4)})  # int32 params


def test_single_slice_degenerate_warns():
    """A world with no second slice has nothing to outer-sync with: the
    regime must warn loudly and run as plain synchronous training."""
    with pytest.warns(UserWarning, match="single slice"):
        opt = hvd.LocalSGD(optax.sgd(0.1), h=4,
                           compression=Compression.none)
    assert opt.active and opt._degenerate
    p = {"w": jnp.ones(4, jnp.float32)}
    state = opt.init(p)
    assert state.outer is None
    assert not opt.should_sync(4)  # never a boundary
    p2, st2 = opt.outer_sync(p, state)  # no-op
    np.testing.assert_array_equal(np.asarray(p2["w"]), np.asarray(p["w"]))
    assert LS.inner_window_position(st2) is None


def _gauge_value(name):
    from horovod_tpu.runtime import metrics as _metrics

    snap = _metrics.registry().snapshot().get(name)
    if not snap or not snap["series"]:
        return None
    return snap["series"][-1]["value"]


def test_h_gauge_tracks_regime():
    hvd.LocalSGD(optax.sgd(0.1), h=3, axis_name=PAIR,
                 compression=Compression.none)
    assert _gauge_value("hvd_local_sgd_h") == 3
    hvd.LocalSGD(optax.sgd(0.1))  # knob off -> synchronous
    assert _gauge_value("hvd_local_sgd_h") == 0


def test_inner_window_position():
    opt = hvd.LocalSGD(optax.sgd(0.1), h=2, axis_name=PAIR,
                       compression=Compression.none)
    p = {"w": jnp.ones(2, jnp.float32)}
    st = opt.init(p)
    assert LS.is_local_sgd_state(st)
    assert LS.inner_window_position(st) == 0  # at a boundary
    mid = LS.LocalSGDState(st.inner_state, st.outer,
                           jnp.asarray(1, jnp.int32))
    assert LS.inner_window_position(mid) == 1
    assert LS.inner_window_position({"not": "a state"}) is None
    assert LS.inner_window_position(st.inner_state) is None


def test_maybe_outer_sync_fires_on_boundary():
    opt = hvd.LocalSGD(optax.sgd(0.1), h=3, axis_name=PAIR,
                       compression=Compression.none)
    assert [s for s in range(1, 10) if opt.should_sync(s)] == [3, 6, 9]
    calls = []

    def fake_sync(p, st):
        calls.append(1)
        return p, st

    p = {"w": jnp.ones(2, jnp.float32)}
    st = opt.init(p)
    opt.maybe_outer_sync(2, p, st, sync_fn=fake_sync)
    assert not calls  # mid-window: no sync, no ledger entry
    opt.maybe_outer_sync(3, p, st, sync_fn=fake_sync)
    assert calls == [1]


def test_record_outer_sync_accounting():
    from horovod_tpu.perf import goodput as G

    def total(name):
        v = _gauge_value(name)
        return 0.0 if v is None else v

    c0 = total("hvd_outer_sync_total")
    s0 = total("hvd_outer_sync_seconds_total")
    G.record_outer_sync(0.25)
    assert total("hvd_outer_sync_total") == c0 + 1
    assert abs(total("hvd_outer_sync_seconds_total") - s0 - 0.25) < 1e-9


# ---------------------------------------------------------------------------
# H=1 / knob-off parity: bit-exact with a plain DistributedOptimizer
# ---------------------------------------------------------------------------


def _int_params():
    return {"w": jnp.arange(-8.0, 8.0, dtype=jnp.float32),
            "b": jnp.ones((3, 3), jnp.float32)}


def _train(opt, mesh, spec, steps=2):
    params = _int_params()

    def body(t):
        p = dict(params)
        state = opt.init(p)
        for _ in range(steps):
            g = {k: jnp.full(v.shape, (i + 1.0) * (t[0, 0] - 1.0), v.dtype)
                 for i, (k, v) in enumerate(sorted(p.items()))}
            upd, state = opt.update(g, state, p)
            p = optax.apply_updates(p, upd)
        return p["w"].reshape(1, -1), p["b"].reshape(1, -1)

    w, b = jax.jit(shard_map(body, mesh=mesh, check_vma=False,
                             in_specs=spec, out_specs=(spec,) * 2))(
        jnp.arange(mesh.devices.shape[0],
                   dtype=jnp.float32).reshape(-1, 1))
    return np.asarray(w), np.asarray(b)


@pytest.mark.parametrize("overlap", [False, True], ids=["mono", "overlap"])
@pytest.mark.parametrize("stage", [0, 1])
def test_h1_parity_bit_exact(flat_mesh, stage, overlap):
    """The knob-off contract: with H <= 1 a LocalSGD wrapper IS a
    DistributedOptimizer — bit-identical trained params, so flipping
    HOROVOD_LOCAL_SGD_H on a synchronous job is a pure no-op."""
    ref = hvd.DistributedOptimizer(optax.sgd(0.1), axis_name="hvd",
                                   zero_stage=stage, overlap=overlap)
    ls = hvd.LocalSGD(optax.sgd(0.1), axis_name="hvd",
                      zero_stage=stage, overlap=overlap)
    assert not ls.active
    wr, br = _train(ref, flat_mesh, P("hvd"))
    wl, bl = _train(ls, flat_mesh, P("hvd"))
    np.testing.assert_array_equal(wr, wl)
    np.testing.assert_array_equal(br, bl)
    assert not hvd.LocalSGD(optax.sgd(0.1), h=1).active


# ---------------------------------------------------------------------------
# DiLoCo outer math: bit equality with a NumPy reference
# ---------------------------------------------------------------------------


def test_diloco_outer_math_matches_reference(ls_mesh):
    """Two H=2 windows over 2 slices x 4 devices, dyadic values only
    (inner lr .25, outer lr/momentum .5): every reduction and Nesterov
    update is exact in fp32, so the trained params must equal the
    NumPy DiLoCo reference BIT-for-bit on every device."""
    H, STEPS = 2, 4
    opt = hvd.LocalSGD(optax.sgd(0.25), h=H, axis_name=PAIR,
                       outer_lr=0.5, outer_momentum=0.5,
                       compression=Compression.none, zero_stage=0)
    p0 = jnp.arange(8.0, dtype=jnp.float32)

    def body(t):
        r = t[0, 0]
        p = {"w": p0}
        state = opt.init(p)
        for s in range(1, STEPS + 1):
            g = {"w": jnp.full(p0.shape, r + 1.0, jnp.float32)}
            upd, state = opt.update(g, state, p)
            p = optax.apply_updates(p, upd)
            if s % H == 0:
                p, state = opt.outer_sync(p, state)
        return p["w"].reshape(1, 1, -1)

    w = jax.jit(shard_map(body, mesh=ls_mesh, check_vma=False,
                          in_specs=P(*PAIR), out_specs=P(*PAIR)))(
        jnp.arange(N, dtype=jnp.float32).reshape(CROSS, LOCAL))
    w = np.asarray(w)

    # NumPy reference: per-slice inner SGD, outer Nesterov over slices.
    ranks = np.arange(N, dtype=np.float32).reshape(CROSS, LOCAL)
    m = (ranks + 1).mean(axis=1).astype(np.float32)  # slice grad means
    lr_in = np.float32(0.25)
    lr_out = mu = np.float32(0.5)
    p = np.tile(np.arange(8, dtype=np.float32), (CROSS, 1))
    anchor = np.arange(8, dtype=np.float32)
    v = np.zeros(8, np.float32)
    for s in range(1, STEPS + 1):
        p = p - lr_in * m[:, None]
        if s % H == 0:
            red = (anchor[None, :] - p).mean(axis=0).astype(np.float32)
            v = mu * v + red
            upd = red + mu * v
            anchor = (anchor - lr_out * upd).astype(np.float32)
            p = np.tile(anchor, (CROSS, 1))
    assert w.shape == (CROSS, LOCAL, 8)
    for c in range(CROSS):
        for l in range(LOCAL):
            np.testing.assert_array_equal(w[c, l], anchor)


# ---------------------------------------------------------------------------
# ZeRO composition: stages 1-3 over the local axis == stage 0
# ---------------------------------------------------------------------------


def _run_ls_stage(stage, ls_mesh, steps=4, h=2):
    opt = hvd.LocalSGD(optax.sgd(0.25), h=h, axis_name=PAIR,
                       outer_lr=0.5, outer_momentum=0.5,
                       compression=Compression.none, zero_stage=stage)
    p0 = {"w": jnp.arange(16.0, dtype=jnp.float32),
          "b": jnp.full((8,), 2.0, jnp.float32)}
    keys = sorted(p0)

    def body(t):
        r = t[0, 0]
        if stage == 3:
            cur = D.zero3_shard_params(p0, axis_name="local")
            state = opt.init(cur)
            for s in range(1, steps + 1):
                def loss(z):
                    full = D.zero3_full_params(z, axis_name="local")
                    return sum((i + 1.0) * (r + 1.0) * jnp.sum(full[k])
                               for i, k in enumerate(keys))

                g = jax.grad(loss)(cur)
                upd, state = opt.update(g, state, cur)
                cur = optax.apply_updates(cur, upd)
                if s % h == 0:
                    cur, state = opt.outer_sync(cur, state)
            full = D.zero3_full_params(cur, axis_name="local")
        else:
            full = dict(p0)
            state = opt.init(full)
            for s in range(1, steps + 1):
                g = {k: jnp.full(full[k].shape, (i + 1.0) * (r + 1.0),
                                 full[k].dtype)
                     for i, k in enumerate(keys)}
                upd, state = opt.update(g, state, full)
                full = optax.apply_updates(full, upd)
                if s % h == 0:
                    full, state = opt.outer_sync(full, state)
        return (full["w"].reshape(1, 1, -1), full["b"].reshape(1, 1, -1))

    w, b = jax.jit(shard_map(body, mesh=ls_mesh, check_vma=False,
                             in_specs=P(*PAIR),
                             out_specs=(P(*PAIR),) * 2))(
        jnp.arange(N, dtype=jnp.float32).reshape(CROSS, LOCAL))
    return np.asarray(w), np.asarray(b)


def test_zero_stage_composition_parity(ls_mesh):
    """ZeRO 1-3 shard the inner state AND the outer anchors 1/L over
    the local axis; the trained params must still walk bit-identically
    to the stage-0 regime (dyadic data, exact reductions)."""
    base = _run_ls_stage(0, ls_mesh)
    for stage in (1, 2, 3):
        got = _run_ls_stage(stage, ls_mesh)
        for a, g in zip(base, got):
            np.testing.assert_array_equal(a, g)


# ---------------------------------------------------------------------------
# HLO proofs: inner program DCN-silent, outer program must cross
# ---------------------------------------------------------------------------


def _inner_hlo(ls_mesh, stage=0):
    opt = hvd.LocalSGD(optax.sgd(0.1), h=4, axis_name=PAIR,
                       compression=Compression.none, zero_stage=stage)
    params = {"w": jnp.ones((96,), jnp.float32)}

    def body(t):
        state = opt.init(params)
        g = {"w": params["w"] * t[0, 0]}
        upd, _ = opt.update(g, state, params)
        return upd["w"].reshape(1, 1, -1)

    fn = jax.jit(shard_map(body, mesh=ls_mesh, check_vma=False,
                           in_specs=P(*PAIR), out_specs=P(*PAIR)))
    return fn.lower(jnp.zeros((CROSS, LOCAL), jnp.float32)).as_text("hlo")


def _outer_hlo(ls_mesh, stage=0):
    opt = hvd.LocalSGD(optax.sgd(0.1), h=4, axis_name=PAIR,
                       compression=Compression.none, zero_stage=stage)
    params = {"w": jnp.ones((96,), jnp.float32)}

    def body(t):
        state = opt.init(params)
        p = {"w": params["w"] * t[0, 0]}
        p2, _ = opt.outer_sync(p, state)
        return p2["w"].reshape(1, 1, -1)

    fn = jax.jit(shard_map(body, mesh=ls_mesh, check_vma=False,
                           in_specs=P(*PAIR), out_specs=P(*PAIR)))
    return fn.lower(jnp.zeros((CROSS, LOCAL), jnp.float32)).as_text("hlo")


@pytest.mark.parametrize("stage", [0, 1])
def test_inner_program_is_dcn_silent(ls_mesh, stage):
    """THE load-bearing invariant: the compiled inner step carries zero
    cross-slice collectives — every replica group stays inside one
    4-device slice."""
    h = _inner_hlo(ls_mesh, stage=stage)
    assert HL.check_program(h, HL.local_sgd_inner_rules(LOCAL)) == []


def test_outer_program_carries_the_cross_exchange(ls_mesh):
    h = _outer_hlo(ls_mesh)
    assert HL.check_program(h, HL.local_sgd_outer_rules(LOCAL)) == []


def test_hlo_positive_controls(ls_mesh):
    """A checker that cannot fail passes vacuously: the inner rule must
    FLAG the outer program (it crosses slices by design), and the
    outer rule must FLAG the inner program (no cross exchange)."""
    outer = _outer_hlo(ls_mesh)
    hits = HL.check_program(outer, HL.local_sgd_inner_rules(LOCAL))
    assert hits and all(f.rule == "HLO-LOCALSGD-INNER" for f in hits)
    inner = _inner_hlo(ls_mesh)
    hits = HL.check_program(inner,
                            [HL.has_cross_collective(LOCAL)])
    assert hits and all(f.rule == "HLO-LOCALSGD-OUTER" for f in hits)


def test_localsgd_fixture_file():
    bad = HL.check_file(os.path.join(FIXTURES, "bad_localsgd_inner.hlo"))
    assert len(bad) >= 2  # whole-world group AND cross-slice group
    assert all(f.rule == "HLO-LOCALSGD-INNER" for f in bad)


# ---------------------------------------------------------------------------
# Round-0 handshake: cfg i64s #23-26
# ---------------------------------------------------------------------------


def test_local_sgd_rides_round0_cfg(monkeypatch):
    from horovod_tpu.runtime import controller as C

    for e in LS_ENVS:
        assert e in C.ROUND0_KNOB_ENVS
    assert C._local_sgd_codes() == (0, 0, 0, 0)  # regime off: all gated
    base = C.round0_cfg()
    assert tuple(base[-6:-2]) == (0, 0, 0, 0)
    monkeypatch.setenv("HOROVOD_LOCAL_SGD_H", "4")
    monkeypatch.setenv("HOROVOD_OUTER_LR", "0.5")
    cfg = C.round0_cfg()
    assert len(cfg) == len(base)
    assert tuple(cfg[-6:-2]) == C._local_sgd_codes()
    assert cfg[-6] == 4
    assert cfg[-5] == 500000  # micro-units
    assert cfg[-4] == 900000  # default momentum 0.9
    assert cfg[-3] == 0  # mode "none" rides wire code 0
    monkeypatch.setenv("HOROVOD_LOCAL_SGD_COMPRESSION", "int8")
    assert C.round0_cfg()[-3] != 0  # lossy mode: nonzero wire code
    # mesh code stays pinned at -2, control fanout at -1
    assert cfg[-2] == base[-2] and cfg[-1] == base[-1]


@pytest.mark.multiprocess
@pytest.mark.parametrize("env,r0,r1,extra", [
    ("HOROVOD_LOCAL_SGD_H", "4", "2", {}),
    ("HOROVOD_OUTER_LR", "0.5", "0.7", {"HOROVOD_LOCAL_SGD_H": "4"}),
    ("HOROVOD_OUTER_MOMENTUM", "0.8", "0.9",
     {"HOROVOD_LOCAL_SGD_H": "4"}),
    ("HOROVOD_LOCAL_SGD_COMPRESSION", "int8", "fp16",
     {"HOROVOD_LOCAL_SGD_H": "4"}),
])
def test_local_sgd_handshake_mismatch_2proc(env, r0, r1, extra):
    """Each of the four new cfg i64s must fail fast on a cross-rank
    divergence, naming its knob — never deadlock in mismatched
    collective programs at the first boundary one rank thinks is an
    outer sync."""
    from tests.test_multiprocess import run_ranks

    run_ranks("""
        import os
        os.environ["%s"] = "%s" if rank == 0 else "%s"
        try:
            hvd.allreduce(jnp.ones(2), op=hvd.Sum, name="hs")
            raise SystemExit("expected a handshake mismatch error")
        except Exception as e:
            assert "%s" in str(e), e
    """ % (env, r0, r1, env), extra_env=extra)


# ---------------------------------------------------------------------------
# Simfleet: ICI/DCN latency split + the cross-round economy scenario
# ---------------------------------------------------------------------------


def test_latency_model_ici_dcn_split_back_compat():
    from horovod_tpu.runtime.simfleet import LatencyModel

    legacy = LatencyModel(rtt_ms=0.7)
    assert legacy.ici() == legacy.dcn() == 0.7  # pre-split numbers
    split = LatencyModel(ici_rtt_ms=0.05, dcn_rtt_ms=2.5)
    assert split.ici() == 0.05 and split.dcn() == 2.5


def test_local_sgd_scaling_scenario_small_world():
    from horovod_tpu.runtime import simfleet

    a = simfleet.local_sgd_scaling(world=16, fanout=4, h=4, windows=1,
                                   seed=0)
    b = simfleet.local_sgd_scaling(world=16, fanout=4, h=4, windows=1,
                                   seed=0)
    assert a == b, "local-SGD scaling scenario replay drift"
    assert a["sync_cross_rounds"] == a["h"] * 1
    assert a["localsgd_cross_rounds"] == 1
    assert a["cross_round_ratio"] >= a["h"]
    assert a["localsgd_wall_ms"] < a["sync_wall_ms"]
    # the outer round rides the cross-scope name contract
    assert all(t["round"] >= 0 for t in a["outer_trace"])


# ---------------------------------------------------------------------------
# Autopilot + parameter manager: comm_retune proposes doubling H
# ---------------------------------------------------------------------------


def _engine(**kw):
    from horovod_tpu.runtime import autopilot as AP

    base = dict(dry_run=False, clock=lambda: 0.0, cooldown_s=60.0,
                rate_limit=4, rate_window_s=600.0, trip_ticks=1,
                straggler_factor=4.0, straggler_floor_s=0.05,
                burn_threshold=2.0, comm_fraction=0.25, record=False)
    base.update(kw)
    return AP.Autopilot(**base)


def test_comm_retune_proposes_h_doubling(monkeypatch):
    monkeypatch.setenv("HOROVOD_LOCAL_SGD_H", "4")
    ap = _engine()
    act = ap.observe_comm(exposed_s=5.0, compute_s=5.0, now=0.0)
    assert act is not None
    assert act.evidence["proposal"] == {"local_sgd_h": 8}
    monkeypatch.setenv("HOROVOD_LOCAL_SGD_H", "64")
    assert ap.observe_comm(5.0, 5.0, now=100.0) is None  # at the cap


def test_parameter_manager_applies_h(monkeypatch):
    from horovod_tpu.runtime import parameter_manager as PM

    monkeypatch.setenv("HOROVOD_LOCAL_SGD_H", "4")
    PM.apply_params({"local_sgd_h": 8})
    assert int(_config.get("local_sgd_h")) == 8
