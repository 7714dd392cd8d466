"""Adaptive compression stack (docs/compression.md).

Acceptance bar of the int4/top-k PR:
  * int4 — two signed nibbles per wire byte with sum-safe headroom:
    pack/unpack exactness, nibble-wise partial-sum safety, jaxpr proof
    the packed psum payload is HALF the int8 wire's, refusal past 7
    ranks, hierarchical mode packs only the cross-slice hop (asserted
    as analysis.hlo_lint placement verdicts on the lowered HLO; the
    half-width jaxpr regex stays as cross-validation);
  * top-k — fixed-size ``k * (index, value)`` payloads (static shapes),
    jaxpr proof the sparse payload is what crosses the wire, EF
    residual carries exactly the unselected mass;
  * error-feedback telescoping identity for BOTH new modes (replicated
    + sharded + under overlap): the residual equals exactly what the
    wire dropped, so nothing is lost — only deferred;
  * per-bucket modes: knob parsing/cycling, mixed-mode overlap chains
    with layout-stable residuals, program-cache keying;
  * wire-byte accounting: int4 packed bytes and topk index+value
    payloads counted as such (autotuner + wire/logical metrics);
  * the adaptive tuner: mode dims on the GP, comm-exposed objective
    hierarchy, bounded-loss guardrail, and the slow-DCN convergence
    proof (delayed path -> more aggressive mode than baseline);
  * 2-proc negotiated-wire parity per new mode + handshake fail-fast
    on the new cfg i64s.
"""

import re

import numpy as np
import optax
import pytest

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.analysis import hlo_lint as HL
from horovod_tpu.common import config as _config
from horovod_tpu.ops import collectives as coll
from horovod_tpu.ops import compression as compr
from horovod_tpu.ops import overlap as ovl
from horovod_tpu.ops import quantization as q

N, CROSS, LOCAL = 8, 2, 4
N4 = 4  # int4 needs a sum-safe axis (<= 7 ranks)


@pytest.fixture(scope="module")
def mesh():
    return Mesh(np.array(jax.devices()[:N]), ("hvd",))


@pytest.fixture(scope="module")
def mesh4():
    return Mesh(np.array(jax.devices()[:N4]), ("hvd",))


@pytest.fixture(scope="module")
def hmesh():
    return Mesh(np.array(jax.devices()[:N]).reshape(CROSS, LOCAL),
                ("cross", "local"))


def run1d(mesh, fn, x, out_specs=P("hvd")):
    return jax.jit(shard_map(fn, mesh=mesh, check_vma=False,
                             in_specs=P("hvd"), out_specs=out_specs))(x)


# ---------------------------------------------------------------------------
# int4 codec
# ---------------------------------------------------------------------------


def test_int4_roundtrip_exact_on_grid():
    """Integer values in [-7, 7] with block absmax 7 put the scale at
    exactly 1.0 -> the int4 round trip is lossless."""
    x = jnp.asarray((np.arange(512) % 15 - 7), jnp.float32)
    p, scales, meta = q.quantize4_block_scaled(x, block_size=256)
    assert p.shape == (2, 128) and p.dtype == jnp.int8  # half of int8
    back = q.dequantize4_block_scaled(p, scales, meta)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(x))


def test_int4_pack_is_half_the_int8_payload():
    x = jnp.asarray(np.random.default_rng(0).standard_normal(4096),
                    jnp.float32)
    p8, _, _ = q.quantize_block_scaled(x, block_size=256)
    p4, _, _ = q.quantize4_block_scaled(x, block_size=256)
    assert p4.size * p4.dtype.itemsize * 2 == \
        p8.size * p8.dtype.itemsize


def test_int4_nibble_partial_sums_are_safe():
    """The sum-safe headroom contract: adding PACKED bytes of n rank
    payloads (each nibble in [-qmax, qmax], n*qmax <= 7) and unpacking
    equals unpacking each and adding — nibble sums never carry across
    the boundary."""
    rng = np.random.default_rng(1)
    n, qmax = 3, q.sum_safe_qmax4(3)  # 7 // 3 == 2
    qs = rng.integers(-qmax, qmax + 1, (n, 4, 256)).astype(np.float32)
    scales = jnp.ones((4,), jnp.float32)
    packed = [np.asarray(q._quantize_pack4_jnp(jnp.asarray(v), scales,
                                               qmax)).astype(np.int32)
              for v in qs]
    summed = jnp.asarray(sum(packed))
    got = np.asarray(q._unpack4_i32(summed))
    np.testing.assert_array_equal(got, qs.sum(0))


def test_int4_block_must_be_even():
    with pytest.raises(ValueError, match="even"):
        q.quantize4_block_scaled(jnp.zeros((10,)), block_size=5)


def test_int4_refuses_past_seven_ranks(mesh):
    assert q.sum_safe_qmax4(7) == 1
    with pytest.raises(ValueError, match="sum-safe"):
        q.sum_safe_qmax4(8)
    with pytest.raises(ValueError, match="sum-safe"):
        jax.make_jaxpr(shard_map(
            lambda b: q.int4_psum(b[0], "hvd"), mesh=mesh,
            check_vma=False, in_specs=P("hvd"), out_specs=P()))(
                jnp.zeros((N, 256), jnp.float32))


def test_int4_psum_exact_on_grid(mesh4):
    """4-rank qmax = 7 // 4 = 1: per-rank values in {-a, 0, a} with
    block absmax a sit exactly on the scale grid -> lossless."""
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.integers(-1, 2, (N4, 1024)) * 3.0, jnp.float32)
    out = run1d(mesh4, lambda b: q.int4_psum(
        b[0].reshape(-1), "hvd").reshape(1, -1), x)
    for r in range(N4):
        np.testing.assert_array_equal(np.asarray(out)[r],
                                      np.asarray(x).sum(0))


def test_int4_psum_within_bound(mesh4):
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((N4, 2048)), jnp.float32)
    out = run1d(mesh4, lambda b: q.int4_psum(
        b[0].reshape(-1), "hvd", block_size=256).reshape(1, -1), x)
    blockmax = np.abs(np.asarray(x)).reshape(N4, -1, 256).max(
        axis=(0, 2))                       # pmax of per-rank absmax
    scale = blockmax / q.sum_safe_qmax4(N4)
    bound = np.repeat(N4 * scale / 2, 256) + 1e-6
    err = np.abs(np.asarray(out)[0] - np.asarray(x).sum(0))
    assert (err <= bound).all(), (err.max(), bound.max())


def test_int4_wire_half_width_jaxpr(mesh4):
    """Acceptance evidence: the int4 program's psum payload is i8 of
    HALF the element count the int8 program moves (4096 elems, block
    256 -> int8 i8[16,256] vs int4 i8[16,128])."""
    def jx(mode):
        return str(jax.make_jaxpr(shard_map(
            lambda b: q.lossy_psum(b[0].reshape(-1), "hvd", mode,
                                   256),
            mesh=mesh4, check_vma=False, in_specs=P("hvd"),
            out_specs=P()))(jnp.zeros((N4, 4096), jnp.float32)))

    t8, t4 = jx("int8"), jx("int4")
    assert re.search(r"i8\[16,256\].*psum", t8), t8
    assert re.search(r"i8\[16,128\].*psum", t4), t4
    assert not re.search(r"i8\[16,256\].*psum", t4), t4


def test_int4_hierarchical_cross_only_hlo_lint(hmesh):
    """The EQuARX split under int4: only the cross-slice hop carries
    the packed i8 payload — asserted as an analysis.hlo_lint placement
    verdict on the LOWERED HLO (replica-group structure), replacing
    the jaxpr regex: the checker classifies every collective's axis
    from its device groups instead of trusting axis-name spellings."""
    _config.set_knob("hierarchical_allreduce", True)
    try:
        low = jax.jit(shard_map(
            lambda b: coll.quantized_allreduce(
                b[0], axis_name=("cross", "local"), op=coll.Sum,
                mode="int4"),
            mesh=hmesh, check_vma=False,
            in_specs=P(("cross", "local")), out_specs=P())).lower(
                jnp.zeros((N, 1024), jnp.float32))
    finally:
        _config.set_knob("hierarchical_allreduce", False)
    prog = HL.parse_hlo(low.as_text("hlo"))
    assert HL.check_program(prog,
                            HL.hierarchical_lossy_rules(LOCAL)) == []
    # the lossy payload really exists and really rides cross (the rule
    # would pass vacuously on an all-f32 program)
    lossy = [i for i in prog.collectives()
             if any(s.dtype == "s8" for s in i.shapes)]
    assert lossy, "no packed int4 payload found in the lowered program"
    assert all(HL.group_axis_kind(i.replica_groups, LOCAL) == "cross"
               for i in lossy)
    # ...and the two-level split is really there: dense f32
    # collectives still run on the local (ICI) hop — a program that
    # collapsed into one cross-axis s8 psum would pass the placement
    # rule but not this
    assert any(HL.group_axis_kind(i.replica_groups, LOCAL) == "local"
               and any(s.dtype == "f32" for s in i.shapes)
               for i in prog.collectives())


# ---------------------------------------------------------------------------
# top-k codec
# ---------------------------------------------------------------------------


def test_topk_k_is_static_and_capped():
    assert q.topk_k(1000, 0.01) == 10
    assert q.topk_k(10, 0.001) == 1      # floor at 1
    assert q.topk_k(10, 5.0) == 10       # ratio clamped to 1.0
    assert q.topk_k(4096, None) == round(
        4096 * float(_config.get("topk_ratio")))


def test_topk_psum_union_and_residual(mesh):
    """The reduction is the scatter-add of every rank's top-k; the EF
    residual is EXACTLY the unselected local mass (selected entries
    zeroed)."""
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.standard_normal((N, 500)), jnp.float32)

    def body(b):
        out, err = q.topk_psum_with_error(b[0].reshape(-1), "hvd",
                                          ratio=0.1)
        return out.reshape(1, -1), err.reshape(1, -1)

    out, err = run1d(mesh, body, x, out_specs=(P("hvd"), P("hvd")))
    k = q.topk_k(500, 0.1)
    xs = np.asarray(x)
    expect = np.zeros(500, np.float32)
    for r in range(N):
        idx = np.argsort(-np.abs(xs[r]))[:k]
        expect[idx] += xs[r][idx]
        # residual r = local values with the selected zeroed
        resid = xs[r].copy()
        resid[idx] = 0.0
        np.testing.assert_allclose(np.asarray(err)[r], resid, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(out)[0], expect, rtol=1e-5,
                               atol=1e-6)


def test_topk_payload_jaxpr(mesh):
    """Acceptance evidence: the wire carries k (int32 index, fp32
    value) pairs per rank — all_gathers of the k-payload — and no
    dense f32[L] collective exists in the program."""
    L, ratio = 1000, 0.05
    text = str(jax.make_jaxpr(shard_map(
        lambda b: q.topk_psum(b[0].reshape(-1), "hvd", ratio=ratio),
        mesh=mesh, check_vma=False, in_specs=P("hvd"),
        out_specs=P()))(jnp.zeros((N, L), jnp.float32)))
    k = q.topk_k(L, ratio)
    assert re.search(rf"i32\[{k}\]", text), text
    assert re.search(rf"all_gather\[", text), text
    # the dense buffer never rides a collective
    assert not re.search(rf"f32\[{L}\] = (psum|all_gather|all_to_all)",
                         text), text


def test_topk_scatter_segments(mesh):
    rng = np.random.default_rng(6)
    seg = jnp.asarray(rng.standard_normal((N, N, 64)), jnp.float32)

    def body(b):
        shard, err = q.topk_psum_scatter_segments(
            b[0].reshape(N, 64), "hvd", ratio=0.25, with_error=True)
        return shard.reshape(1, -1), err.reshape(1, -1)

    out, _ = run1d(mesh, body, seg, out_specs=(P("hvd"), P("hvd")))
    k = q.topk_k(64, 0.25)
    xs = np.asarray(seg)                   # (owner_rank?, n, 64)
    for owner in range(N):
        expect = np.zeros(64, np.float32)
        for r in range(N):
            row = xs[r, owner]
            idx = np.argsort(-np.abs(row))[:k]
            expect[idx] += row[idx]
        np.testing.assert_allclose(np.asarray(out)[owner], expect,
                                   rtol=1e-5, atol=1e-6)


def test_topk_hierarchical_cross_only_hlo_lint(hmesh):
    """Under the (cross, local) split the sparse (index, value)
    payload moves only on the cross hop; ICI stays dense f32 —
    asserted as an hlo_lint placement verdict on the lowered HLO,
    replacing the jaxpr regex (see the int4 twin above)."""
    _config.set_knob("hierarchical_allreduce", True)
    try:
        low = jax.jit(shard_map(
            lambda b: coll.quantized_allreduce(
                b[0], axis_name=("cross", "local"), op=coll.Sum,
                mode="topk"),
            mesh=hmesh, check_vma=False,
            in_specs=P(("cross", "local")), out_specs=P())).lower(
                jnp.zeros((N, 1024), jnp.float32))
    finally:
        _config.set_knob("hierarchical_allreduce", False)
    prog = HL.parse_hlo(low.as_text("hlo"))
    assert HL.check_program(prog,
                            HL.hierarchical_lossy_rules(LOCAL)) == []
    idx = [i for i in prog.collectives()
           if any(s.dtype == "s32" for s in i.shapes)]
    assert idx, "no sparse index payload found in the lowered program"
    assert all(HL.group_axis_kind(i.replica_groups, LOCAL) == "cross"
               for i in idx)
    # the dense halves still exist on the local hop
    assert any(HL.group_axis_kind(i.replica_groups, LOCAL) == "local"
               for i in prog.collectives())


# ---------------------------------------------------------------------------
# Error-feedback telescoping (the bounded-loss contract)
# ---------------------------------------------------------------------------


def _telescope_identity(mesh_, nranks, mode, steps=5, length=768,
                        overlap=False, sharded=False):
    """EF contract: after k steps of feedback the summed reductions
    equal k * psum(g) - psum(final residual) EXACTLY — the wire loses
    nothing, it only defers.  Checked through the same entry points the
    optimizer uses."""
    rng = np.random.default_rng(8)
    g = jnp.asarray(rng.standard_normal((nranks, length)), jnp.float32)

    def body(b):
        grad = b[0].reshape(-1)
        resid = jnp.zeros_like(grad)
        acc = jnp.zeros_like(grad)
        for _ in range(steps):
            if sharded:
                shard, resid = coll._scatter_flat_buffer(
                    grad + resid, "hvd", quantized=mode,
                    with_error=True, overlap=overlap)
                red = coll._gather_flat_shard(shard, "hvd",
                                              overlap=overlap)
            else:
                red, resid = q.lossy_psum_with_error(
                    grad + resid, "hvd", mode)
            acc = acc + red
        return (acc.reshape(1, -1), resid.reshape(1, -1),
                jax.lax.psum(resid, "hvd").reshape(1, -1))

    acc, _, gresid = run1d(
        mesh_, body, g, out_specs=(P("hvd"), P("hvd"), P("hvd")))
    expect = steps * np.asarray(g).sum(0) - np.asarray(gresid)[0]
    np.testing.assert_allclose(np.asarray(acc)[0], expect, rtol=1e-4,
                               atol=1e-3)


@pytest.mark.parametrize("mode", ["int4", "topk"])
def test_ef_telescoping_replicated(mesh4, mode):
    _telescope_identity(mesh4, N4, mode)


@pytest.mark.parametrize("mode", ["int4", "topk"])
def test_ef_telescoping_sharded(mesh4, mode):
    _telescope_identity(mesh4, N4, mode, sharded=True)


@pytest.mark.parametrize("mode", ["int4", "topk"])
def test_ef_telescoping_sharded_under_overlap(mesh4, mode):
    _config.set_knob("overlap", True)
    _config.set_knob("overlap_chunks", 3)
    try:
        _telescope_identity(mesh4, N4, mode, sharded=True, overlap=True)
    finally:
        _config.set_knob("overlap", False)
        _config.set_knob("overlap_chunks", 4)


def test_int4_optimizer_ef_bound(mesh4):
    """Optimizer-level telescoping bar (the int8 test's int4 sibling):
    after k steps the int4 trajectory is within ~one quantization bound
    of exact, not k bounds."""
    lr, steps = 0.01, 5
    qopt = hvd.DistributedOptimizer(optax.sgd(lr), axis_name="hvd",
                                    sharded=True,
                                    compression=hvd.Compression.int4)
    exact = hvd.DistributedOptimizer(optax.sgd(lr), axis_name="hvd",
                                     sharded=True)
    rng = np.random.default_rng(9)
    per_rank_g = jnp.asarray(rng.standard_normal((N4, 512)), jnp.float32)

    def body(g):
        pq = {"w": jnp.zeros((512,), jnp.float32)}
        pe = dict(pq)
        sq, se = qopt.init(pq), exact.init(pe)
        for _ in range(steps):
            uq, sq = qopt.update({"w": g[0]}, sq, pq)
            pq = optax.apply_updates(pq, uq)
            ue, se = exact.update({"w": g[0]}, se, pe)
            pe = optax.apply_updates(pe, ue)
        return pq["w"].reshape(1, -1), pe["w"].reshape(1, -1)

    got, ref = jax.jit(shard_map(body, mesh=mesh4, check_vma=False,
                                 in_specs=P("hvd"),
                                 out_specs=(P("hvd"),) * 2))(per_rank_g)
    gmax = float(np.abs(np.asarray(per_rank_g)).max())
    one_step = lr * (N4 * gmax / q.sum_safe_qmax4(N4)) / 2 / N4 + 1e-7
    err = np.abs(np.asarray(got) - np.asarray(ref)).max()
    assert err <= 2.5 * one_step, (err, one_step)


@pytest.mark.parametrize("mode", ["int4", "topk"])
def test_zero2_ef_bound(mesh4, mode):
    """The optimizer-level EF bar under ZeRO-2: the stage-2 bucket-piece
    scatter carries the new modes' residual slices, so after k steps
    the lossy trajectory tracks the exact stage-2 one instead of
    drifting k compression errors away."""
    lr, steps = 0.01, 5
    comp = getattr(hvd.Compression, mode)
    qopt = hvd.DistributedOptimizer(optax.sgd(lr), axis_name="hvd",
                                    zero_stage=2, compression=comp)
    exact = hvd.DistributedOptimizer(optax.sgd(lr), axis_name="hvd",
                                     zero_stage=2)
    rng = np.random.default_rng(12)
    per_rank_g = jnp.asarray(rng.standard_normal((N4, 512)), jnp.float32)

    def body(g):
        pq = {"w": jnp.zeros((512,), jnp.float32)}
        pe = dict(pq)
        sq, se = qopt.init(pq), exact.init(pe)
        for _ in range(steps):
            uq, sq = qopt.update({"w": g[0]}, sq, pq)
            pq = optax.apply_updates(pq, uq)
            ue, se = exact.update({"w": g[0]}, se, pe)
            pe = optax.apply_updates(pe, ue)
        return pq["w"].reshape(1, -1), pe["w"].reshape(1, -1)

    got, ref = jax.jit(shard_map(body, mesh=mesh4, check_vma=False,
                                 in_specs=P("hvd"),
                                 out_specs=(P("hvd"),) * 2))(per_rank_g)
    gmax = float(np.abs(np.asarray(per_rank_g)).max())
    if mode == "int4":
        # one telescoped quantization bound, not k of them
        one_step = lr * (N4 * gmax / q.sum_safe_qmax4(N4)) / 2 / N4 + 1e-7
        err = np.abs(np.asarray(got) - np.asarray(ref)).max()
        assert err <= 2.5 * one_step, (err, one_step)
    else:
        # top-k defers mass into the residual: the trajectory gap is
        # bounded by one step's worth of deferred gradient, not k
        err = np.abs(np.asarray(got) - np.asarray(ref)).max()
        assert err <= 2.5 * lr * gmax, (err, lr * gmax)


def test_topk_full_density_is_exact(mesh):
    """ratio=1.0 selects everything: the sparse plumbing must be
    lossless — optimizer parity with the uncompressed trajectory."""
    _config.set_knob("topk_ratio", 1.0)
    try:
        lr, steps = 0.05, 3
        qopt = hvd.DistributedOptimizer(optax.sgd(lr), axis_name="hvd",
                                        compression=hvd.Compression.topk)
        exact = hvd.DistributedOptimizer(optax.sgd(lr), axis_name="hvd")
        rng = np.random.default_rng(10)
        per_rank_g = jnp.asarray(rng.standard_normal((N, 256)),
                                 jnp.float32)

        def body(g):
            pq = {"w": jnp.ones((256,), jnp.float32)}
            pe = dict(pq)
            sq, se = qopt.init(pq), exact.init(pe)
            for _ in range(steps):
                uq, sq = qopt.update({"w": g[0]}, sq, pq)
                pq = optax.apply_updates(pq, uq)
                ue, se = exact.update({"w": g[0]}, se, pe)
                pe = optax.apply_updates(pe, ue)
            return pq["w"].reshape(1, -1), pe["w"].reshape(1, -1)

        got, ref = jax.jit(shard_map(
            body, mesh=mesh, check_vma=False, in_specs=P("hvd"),
            out_specs=(P("hvd"),) * 2))(per_rank_g)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-5, atol=1e-6)
    finally:
        _config.set_knob("topk_ratio", 0.01)


# ---------------------------------------------------------------------------
# The loss of a tiny trainer under a lossy wire
# ---------------------------------------------------------------------------


def _tiny_trainer_final_loss(mesh, compression, seed, steps=100):
    """Mean loss over the 8 ranks' shards after ``steps`` SGD steps of
    a 32-16-1 tanh regression through ``DistributedOptimizer``: each
    rank holds 64 rows of its own, the teacher and the label noise come
    from ``seed``."""
    rows, dim, hid = 64, 32, 16
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((N, rows, dim)).astype(np.float32)
    teacher = rng.standard_normal(dim).astype(np.float32) / np.sqrt(dim)
    y = (np.tanh(x @ teacher)
         + 0.1 * rng.standard_normal((N, rows))).astype(np.float32)
    params = {
        "w1": jnp.asarray(rng.standard_normal((dim, hid)) / np.sqrt(dim),
                          jnp.float32),
        "b1": jnp.zeros((hid,), jnp.float32),
        "w2": jnp.asarray(rng.standard_normal(hid) / np.sqrt(hid),
                          jnp.float32)}
    opt = hvd.DistributedOptimizer(optax.sgd(0.1), axis_name="hvd",
                                   compression=compression)

    def loss_fn(p, xb, yb):
        return jnp.mean((jnp.tanh(xb @ p["w1"] + p["b1"]) @ p["w2"]
                         - yb) ** 2)

    def body(xb, yb):
        xb, yb = xb[0], yb[0]

        def step(_, carry):
            p, st = carry
            upd, st = opt.update(jax.grad(loss_fn)(p, xb, yb), st, p)
            return optax.apply_updates(p, upd), st

        p, _ = jax.lax.fori_loop(0, steps, step, (params, opt.init(params)))
        return jnp.stack([jax.lax.pmean(loss_fn(params, xb, yb), "hvd"),
                          jax.lax.pmean(loss_fn(p, xb, yb), "hvd")])[None]

    first, last = np.asarray(jax.jit(shard_map(
        body, mesh=mesh, check_vma=False, in_specs=(P("hvd"), P("hvd")),
        out_specs=P("hvd")))(jnp.asarray(x), jnp.asarray(y)))[0]
    assert last < 0.5 * first, (first, last)     # it trained at all
    return float(last)


@pytest.fixture(scope="module")
def uncompressed_losses(mesh):
    return [_tiny_trainer_final_loss(mesh, hvd.Compression.none, seed)
            for seed in (0, 1, 2)]


@pytest.mark.parametrize("mode", ["int8", "topk"])
def test_tiny_trainer_loss_stays_in_the_uncompressed_band(
        mesh, uncompressed_losses, mode):
    """The convergence signal ci.sh's gate held to a 1.5 absolute band
    on a CPU ResNet, without a clock and with a band that means
    something: over three seeds the final loss under a lossy wire with
    error feedback is within the uncompressed run's own spread over
    those seeds of the uncompressed loss of the same seed (measured:
    int8 2e-6, topk at 1 % density 2.6e-3, of a spread of 9.6e-3).  A
    mode that drops its residual, or a wire that scales wrongly,
    leaves the band."""
    band = max(uncompressed_losses) - min(uncompressed_losses)
    for seed, want in zip((0, 1, 2), uncompressed_losses):
        got = _tiny_trainer_final_loss(
            mesh, getattr(hvd.Compression, mode), seed)
        assert abs(got - want) <= band, (mode, seed, got, want, band)


# ---------------------------------------------------------------------------
# Per-bucket modes
# ---------------------------------------------------------------------------


def test_parse_bucket_modes_validates():
    assert compr.parse_bucket_modes("int8:int4:topk") == \
        ["int8", "int4", "topk"]
    assert compr.parse_bucket_modes(" INT8 : None ") == ["int8", "none"]
    with pytest.raises(ValueError, match="int2"):
        compr.parse_bucket_modes("int8:int2")


def test_bucket_modes_cycle_and_default():
    _config.set_knob("bucket_compression", "int8:int4")
    try:
        assert compr.bucket_modes(5) == \
            ["int8", "int4", "int8", "int4", "int8"]
    finally:
        _config.set_knob("bucket_compression", "")
    assert compr.bucket_modes(3, default="topk") == ["topk"] * 3


def test_effective_bucket_modes_tracks_overlap():
    _config.set_knob("compression", "int8")
    _config.set_knob("overlap", True)
    _config.set_knob("overlap_chunks", 3)
    try:
        assert compr.effective_bucket_modes() == ["int8"] * 3
        _config.set_knob("bucket_compression", "none:topk")
        assert compr.effective_bucket_modes() == \
            ["none", "topk", "none"]
    finally:
        _config.set_knob("bucket_compression", "")
        _config.set_knob("overlap", False)
        _config.set_knob("overlap_chunks", 4)
        _config.set_knob("compression", "none")
    assert compr.effective_bucket_modes() == ["none"]


def test_mixed_bucket_modes_layout_stable(mesh4):
    """A chain mixing lossy / cast / dense buckets: outputs keep the
    buffer layout, and the EF residual is zero-filled exactly on the
    buckets whose mode carries no residual."""
    rng = np.random.default_rng(11)
    buf = jnp.asarray(rng.standard_normal((N4, 1024)), jnp.float32)
    modes = ["none", "int4", "fp16", "topk"]

    def body(b):
        out, err = ovl.overlapped_flat_reduce(
            b[0].reshape(-1), "hvd", op=coll.Sum, quantized="none",
            with_error=True, chunks=4, modes=modes)
        return out.reshape(1, -1), err.reshape(1, -1)

    out, err = run1d(mesh4, body, buf, out_specs=(P("hvd"), P("hvd")))
    assert out.shape == (N4, 1024)
    # bucket bounds over L = 1024 // N4 = 256 columns, 4 buckets of 64
    e2d = np.asarray(err)[0].reshape(N4, 256)
    exact = np.asarray(buf).sum(0).reshape(N4, 256)
    got = np.asarray(out)[0].reshape(N4, 256)
    # bucket 0 (none) and bucket 2 (fp16) carry no EF residual
    np.testing.assert_array_equal(e2d[:, 0:64], 0.0)
    np.testing.assert_array_equal(e2d[:, 128:192], 0.0)
    # the dense bucket is exact up to ring-order ulps (the ppermute
    # ring sums in rotation order, np.sum in rank order)
    np.testing.assert_allclose(got[:, 0:64], exact[:, 0:64],
                               rtol=1e-5, atol=1e-6)
    # lossy buckets have nonzero residual somewhere
    assert np.abs(e2d[:, 64:128]).max() > 0      # int4
    assert np.abs(e2d[:, 192:256]).max() > 0     # topk


def test_program_cache_key_carries_mode_vector():
    from horovod_tpu.ops import xla_exec

    _config.set_knob("compression", "int8")
    try:
        base = xla_exec._wire_compression(np.dtype("float32"))
        assert base[0] == ("int8",)
        _config.set_knob("overlap", True)
        _config.set_knob("overlap_chunks", 2)
        _config.set_knob("bucket_compression", "int4:topk")
        vec = xla_exec._wire_compression(np.dtype("float32"))
        assert vec[0] == ("int4", "topk")
        assert vec[1] > 0 and vec[2] > 0  # block + ratio both live
        assert base != vec                # distinct program cache keys
        # non-floating payloads never compress
        assert xla_exec._wire_compression(np.dtype("int32"))[0] == \
            ("none",)
    finally:
        _config.set_knob("bucket_compression", "")
        _config.set_knob("overlap", False)
        _config.set_knob("overlap_chunks", 4)
        _config.set_knob("compression", "none")


# ---------------------------------------------------------------------------
# Wire-byte accounting
# ---------------------------------------------------------------------------


def test_payload_wire_bytes_per_mode():
    kw = dict(block=256, ratio=0.01, world=4)
    dense = compr.payload_wire_bytes(1024, 4, "none", **kw)
    assert dense == 4096
    assert compr.payload_wire_bytes(1024, 4, "fp16", **kw) == 2048
    i8 = compr.payload_wire_bytes(1024, 4, "int8", **kw)
    assert i8 == 1024 + 4 * 5            # payload + scales
    i4 = compr.payload_wire_bytes(1024, 4, "int4", **kw)
    assert i4 == 512 + 4 * 5             # HALF the int8 payload
    tk = compr.payload_wire_bytes(1024, 4, "topk", **kw)
    assert tk == 4 * 10 * 8 // 2         # world * k * (idx+val) / 2
    # fp16 payloads don't "compress" to fp16
    assert compr.payload_wire_bytes(1024, 2, "bf16", **kw) == 2048


@pytest.mark.parametrize("mode, wire", [
    ("none", 8480), ("fp16", 4240), ("bf16", 4240),         # 1.0, 0.5, 0.5
    ("int8", 2120 + 4 * 9),     # a byte an element + 9 block scales: 0.254
    ("int4", 1060 + 4 * 9),     # half a byte an element + the scales: 0.129
    ("topk", 8 * 21 * 8 // 2),  # world * k * (idx+val) / 2, k = 21: 0.079
])
def test_wire_bytes_of_a_gradient_tree_per_mode(mode, wire):
    """Wire bytes over logical bytes of one gradient tree, from the
    optimizer's own fused layout (``_shard_layout``: one flat buffer a
    dtype, padded to the world) and the knob-resolved mode vector: the
    count ci.sh's gate stamped as ``*_wire_compression_ratio``, as plain
    numbers.  An int4 payload counted dense, or a widened topk payload,
    moves the ratio here."""
    from horovod_tpu.optim.distributed import _leaf_nbytes, _shard_layout

    grads = {"w": jnp.zeros((300, 7)), "b": jnp.zeros((13,)),
             "s": jnp.zeros(())}
    leaves = jax.tree_util.tree_leaves(grads)
    layout = _shard_layout(leaves, N)
    assert layout.keys == ("float32",)
    # 2,114 elements, 2,120 once padded to a multiple of 8 ranks
    assert _leaf_nbytes(leaves) == 2114 * 4 and layout.padded == (2120,)
    _config.set_knob("compression", mode)
    try:
        got = compr.fused_wire_bytes(
            layout.padded[0], 4, compr.effective_bucket_modes(),
            block=_config.get("quant_block_size"),
            ratio=_config.get("topk_ratio"), world=N)
    finally:
        _config.set_knob("compression", "none")
    assert got == wire                  # of 8,480 logical bytes


def test_background_wire_nbytes_counts_new_modes():
    from types import SimpleNamespace

    from horovod_tpu.runtime.background import BackgroundRuntime
    from horovod_tpu.runtime.controller import Response

    shim = SimpleNamespace(world=4)
    resp = Response(kind="allreduce", names=["g"], shapes=[(1024,)])
    dt = np.dtype("float32")

    def wire(mode, bucket=""):
        _config.set_knob("compression", mode)
        _config.set_knob("bucket_compression", bucket)
        try:
            return BackgroundRuntime._wire_nbytes(shim, resp, dt)
        finally:
            _config.set_knob("compression", "none")
            _config.set_knob("bucket_compression", "")

    assert wire("none") == 4096
    assert wire("int8") == 1024 + 4 * 5
    assert wire("int4") == 512 + 4 * 5
    assert wire("topk") == 4 * 10 * 8 // 2
    # a per-bucket vector splits the payload across its modes
    _config.set_knob("overlap", True)
    _config.set_knob("overlap_chunks", 2)
    try:
        mixed = wire("none", bucket="none:int4")
        assert mixed == 2048 + (256 + 4 * 3)
    finally:
        _config.set_knob("overlap", False)
        _config.set_knob("overlap_chunks", 4)
    # integer payloads stay dense whatever the knob says
    assert BackgroundRuntime._wire_nbytes(
        shim, Response(kind="allreduce", names=["i"], shapes=[(64,)]),
        np.dtype("int32")) == 256


# ---------------------------------------------------------------------------
# The adaptive tuner
# ---------------------------------------------------------------------------


def _pm(monkeypatch, comm_signal=None, **env):
    defaults = {"HOROVOD_AUTOTUNE": "1",
                "HOROVOD_AUTOTUNE_STEPS_PER_SAMPLE": "1",
                "HOROVOD_AUTOTUNE_WARMUP_SAMPLES": "0",
                "HOROVOD_AUTOTUNE_BAYES_OPT_MAX_SAMPLES": "30",
                "HOROVOD_ADAPTIVE_COMPRESSION": "1",
                "HOROVOD_OVERLAP": "1", "HOROVOD_OVERLAP_CHUNKS": "2",
                "HOROVOD_COMPRESSION": "int8"}
    defaults.update(env)
    for k, v in defaults.items():
        monkeypatch.setenv(k, v)
    import horovod_tpu.runtime.parameter_manager as pmmod

    class _Clock:
        t = 0.0

        def monotonic(self):
            _Clock.t += 0.5
            return _Clock.t

    monkeypatch.setattr(pmmod, "time", _Clock())
    return pmmod, pmmod.ParameterManager(world=8, hier_possible=False,
                                         comm_signal=comm_signal)


def test_adaptive_mode_dims_join_the_search(monkeypatch):
    pmmod, pm = _pm(monkeypatch)
    assert pm._mode_slots == 2
    assert list(range(7, 9)) == [d for d in pm._tuned if d >= 7]
    # without the knob, no mode dims
    monkeypatch.setenv("HOROVOD_ADAPTIVE_COMPRESSION", "0")
    pm2 = pmmod.ParameterManager(world=8, hier_possible=False)
    assert pm2._mode_slots == 0
    assert all(d < 7 for d in pm2._tuned)
    # without overlap: one uniform slot
    monkeypatch.setenv("HOROVOD_ADAPTIVE_COMPRESSION", "1")
    monkeypatch.setenv("HOROVOD_OVERLAP", "0")
    pm3 = pmmod.ParameterManager(world=8, hier_possible=False)
    assert pm3._mode_slots == 1


def _drive(pmmod, pm, oracle, max_iter=200):
    """Run the tuner against a deterministic comm-exposed oracle until
    it pins; returns the pinned params."""
    state = oracle["state"]
    for _ in range(max_iter):
        cur = pmmod.unit_to_params(pm._full(pm._current))
        state["modes"] = cur.get("bucket_compression",
                                 "int8:int8").split(":")
        pm.record_bytes(10 * 1024 * 1024)
        pm.tick()
        if pm._pinned:
            break
    assert pm._pinned
    best_x, _ = pm.bo.best()
    return pmmod.unit_to_params(pm._full(best_x))


def test_adaptive_tuner_goes_aggressive_on_delayed_path(monkeypatch,
                                                        tmp_path):
    """The acceptance scenario: bucket 1's hop is slow (delayed DCN) —
    byte cut pays off linearly; bucket 0's hop is fast — aggressive
    modes only add overhead.  The tuner must converge to a MORE
    aggressive mode on the delayed path than the baseline (no-delay)
    run picks, and the CSV log must carry the chosen vector with the
    comm_exposed objective."""
    log = tmp_path / "adaptive.csv"
    log_base = tmp_path / "baseline.csv"  # the ctor truncates its log
    ladder = list(compr.MODE_LADDER)

    def make_oracle(slow: bool):
        state = {"modes": None}

        def signal():
            modes = state["modes"] or ["int8", "int8"]
            i0 = ladder.index(modes[0])
            i1 = ladder.index(modes[1 % len(modes)])
            fast0 = 0.010 + 0.002 * i0          # overhead only
            hop1 = ((0.500 - 0.080 * i1) if slow  # byte cut pays off
                    else 0.010 + 0.002 * i1)
            return fast0 + hop1

        return {"state": state, "signal": signal}

    pmmod, _ = _pm(monkeypatch)
    monkeypatch.setenv("HOROVOD_AUTOTUNE_LOG", str(log))

    slow_oracle = make_oracle(slow=True)
    pm_slow = pmmod.ParameterManager(world=8, hier_possible=False,
                                     comm_signal=slow_oracle["signal"])
    slow_params = _drive(pmmod, pm_slow, slow_oracle)

    base_oracle = make_oracle(slow=False)
    monkeypatch.setenv("HOROVOD_AUTOTUNE_LOG", str(log_base))
    pm_base = pmmod.ParameterManager(world=8, hier_possible=False,
                                     comm_signal=base_oracle["signal"])
    base_params = _drive(pmmod, pm_base, base_oracle)

    slow_modes = slow_params["bucket_compression"].split(":")
    base_modes = base_params["bucket_compression"].split(":")
    # delayed path: strictly more aggressive than int8
    assert ladder.index(slow_modes[1]) > ladder.index("int8"), \
        (slow_modes, base_modes)
    # and more aggressive than what the baseline run picked there
    assert ladder.index(slow_modes[1]) > ladder.index(base_modes[1]), \
        (slow_modes, base_modes)
    # the CSV log proves it (chosen vector + objective column)
    lines = log.read_text().strip().splitlines()
    assert lines[0].startswith("sample,score,objective")
    assert any("comm_exposed" in ln for ln in lines[1:])
    assert any(slow_params["bucket_compression"] in ln
               for ln in lines[1:])


def test_guardrail_pins_back_to_int8(monkeypatch):
    from horovod_tpu.runtime import metrics as _metrics

    pmmod, pm = _pm(monkeypatch)
    gauge = _metrics.gauge(
        "hvd_compression_residual_ratio",
        "Per-bucket EF residual-to-gradient norm ratio.")
    gauge.reset()
    try:
        # slot 1's residual ratio breaches the 0.5 default ceiling
        gauge.set(0.1, bucket="0")
        gauge.set(0.9, bucket="1")
        out = pm._guard({"bucket_compression": "topk:topk"})
        assert out["bucket_compression"] == "topk:int8"
        # raw bucket indices fold onto slots modulo the vector length
        gauge.set(2.0, bucket="2")  # bucket 2 -> slot 0
        out = pm._guard({"bucket_compression": "int4:int8"})
        assert out["bucket_compression"] == "int8:int8"
    finally:
        gauge.reset()


def test_guardrail_ceiling_zero_disables_aggressive(monkeypatch):
    from horovod_tpu.runtime import metrics as _metrics

    monkeypatch.setenv("HOROVOD_COMPRESSION_MAX_RESIDUAL_RATIO", "0")
    pmmod, pm = _pm(monkeypatch)
    gauge = _metrics.gauge(
        "hvd_compression_residual_ratio",
        "Per-bucket EF residual-to-gradient norm ratio.")
    gauge.reset()
    try:
        gauge.set(0.01, bucket="0")
        gauge.set(0.01, bucket="1")
        out = pm._guard({"bucket_compression": "int4:topk"})
        assert out["bucket_compression"] == "int8:int8"
        # unreported slots are left alone (nothing to bound against) —
        # at a world where int4 has sum-safe headroom, so only the
        # ceiling (not the topology clamp) is in play
        gauge.reset()
        pm4 = pmmod.ParameterManager(world=4, hier_possible=False)
        out = pm4._guard({"bucket_compression": "int4:topk"})
        assert out["bucket_compression"] == "int4:topk"
    finally:
        gauge.reset()


def test_comm_signal_hierarchy(monkeypatch):
    from horovod_tpu.runtime import metrics as _metrics
    from horovod_tpu.runtime.parameter_manager import \
        _default_comm_signal

    dev = _metrics.gauge(
        "hvd_device_comm_exposed_seconds",
        "Device-measured comm seconds not hidden under compute.")
    last = _metrics.gauge(
        "hvd_step_phase_seconds_last",
        "Last trace_step() span, split by phase plus wall.")
    dev.reset()
    last.reset()
    try:
        assert _default_comm_signal() is None
        last.set(0.25, phase="blocked")
        assert _default_comm_signal() == 0.25  # subtraction fallback
        dev.set(0.125)
        assert _default_comm_signal() == 0.125  # device truth wins
    finally:
        dev.reset()
        last.reset()


def test_apply_params_exports_bucket_compression(monkeypatch):
    from horovod_tpu.runtime.parameter_manager import apply_params

    monkeypatch.setenv("HOROVOD_BUCKET_COMPRESSION", "")
    apply_params({"bucket_compression": "int8:int4"})
    try:
        assert str(_config.get("bucket_compression")) == "int8:int4"
    finally:
        _config.set_knob("bucket_compression", "")


def test_handshake_codes_for_new_knobs(monkeypatch):
    from horovod_tpu.runtime import controller as ctl

    assert ctl._COMPRESSION_WIRE_CODES["int4"] == 4
    assert ctl._COMPRESSION_WIRE_CODES["topk"] == 5
    monkeypatch.setenv("HOROVOD_BUCKET_COMPRESSION", "")
    assert ctl._bucket_modes_code() == 0
    monkeypatch.setenv("HOROVOD_BUCKET_COMPRESSION", "Int8: int4")
    normalized = ctl._bucket_modes_code()
    monkeypatch.setenv("HOROVOD_BUCKET_COMPRESSION", "int8:int4")
    assert ctl._bucket_modes_code() == normalized  # spelling-stable
    assert {"int8", "int4"} <= ctl._active_wire_modes()


# ---------------------------------------------------------------------------
# Review regressions: eager builder composition + guard blind spots
# ---------------------------------------------------------------------------


def test_eager_cast_composes_with_hierarchical(hmesh, monkeypatch):
    """fp16/bf16 under HOROVOD_HIERARCHICAL_ALLREDUCE must keep the
    two-level ICI/DCN decomposition (cast payload on every hop), not
    silently fall back to a flat psum over both axes."""
    from horovod_tpu.ops import xla_exec

    monkeypatch.setattr(xla_exec, "_hier_mesh", lambda hier: hmesh)
    fn = xla_exec._build_allreduce(
        None, ((1024,),), coll.Sum, N, hier=(CROSS, LOCAL),
        comp=(("fp16",), 0, 0), ov=None)
    text = jax.jit(fn).lower(
        jax.ShapeDtypeStruct((N, 1024), jnp.float32)).as_text()
    # the decomposition survives: local reduce-scatter + local gather
    assert "stablehlo.reduce_scatter" in text, text
    assert "stablehlo.all_gather" in text, text
    # ...and every hop runs at the CAST wire width: the local scatter
    # consumes the f16 payload and the cross all-reduce stays f16
    assert re.search(r"\(tensor<1024xf16>\) -> tensor<256xf16>", text), \
        text
    assert re.search(r"\(tensor<256xf16>\) -> tensor<256xf16>", text), \
        text


def test_eager_lossy_publishes_guard_signal():
    """The eager negotiated wire reduces WITHOUT error feedback, so
    under adaptive compression its dropped mass must still reach the
    guardrail gauge — otherwise the tuner would keep an
    over-aggressive mode on eager frontends forever."""
    from horovod_tpu.optim import distributed as _dist
    from horovod_tpu.ops import xla_exec
    from horovod_tpu.runtime import metrics as _metrics

    _dist._M_RESID_RATIO.reset()
    _config.set_knob("adaptive_compression", True)
    _config.set_knob("topk_ratio", 0.05)
    try:
        mesh = Mesh(np.array(jax.devices()[:N]), ("hvd",))
        fn = xla_exec._build_allreduce(
            mesh, ((512,),), coll.Sum, N, hier=None,
            comp=(("topk",), 0, 50000), ov=None)
        rng = np.random.default_rng(13)
        out = fn(jnp.asarray(rng.standard_normal((N, 512)), jnp.float32))
        jax.block_until_ready(out)
        series = _metrics.registry().snapshot().get(
            "hvd_compression_residual_ratio", {}).get("series", [])
        assert series, "eager lossy program published no guard signal"
        # top-5% density drops most of the norm: the ratio is large
        assert max(s["value"] for s in series) > 0.5, series
    finally:
        _config.set_knob("adaptive_compression", False)
        _config.set_knob("topk_ratio", 0.01)
        _dist._M_RESID_RATIO.reset()


def test_guard_topology_clamps_impossible_modes(monkeypatch):
    """The tuner must never propose a mode the topology cannot run
    (int4 refuses axes past 7 ranks, int8 past 127): the clamp maps it
    to the strongest mode that CAN run instead of aborting the job at
    the adaptive retrace."""
    pmmod, _ = _pm(monkeypatch)
    pm8 = pmmod.ParameterManager(world=8, hier_possible=False)
    out = pm8._guard({"bucket_compression": "int4:topk"})
    assert out["bucket_compression"] == "int8:topk"
    pm200 = pmmod.ParameterManager(world=200, hier_possible=False)
    out = pm200._guard({"bucket_compression": "int8:int4"})
    assert out["bucket_compression"] == "fp16:fp16"
    # a proposal that also turns the hierarchical split on quantizes
    # the (small) cross axis — exempt
    monkeypatch.setattr(pmmod.ParameterManager, "_quantized_axis_size",
                        lambda self: 2)
    out = pm8._guard({"bucket_compression": "int4:topk",
                      "hierarchical_allreduce": True})
    assert out["bucket_compression"] == "int4:topk"


def test_handshake_validates_quant_knobs_under_adaptive(monkeypatch):
    """With the adaptive knob on the tuner can broadcast any lossy mode
    later (block size / topk ratio do NOT ride its proposals), so the
    round-0 handshake must validate them up front instead of
    normalizing them away under HOROVOD_COMPRESSION=none."""
    from horovod_tpu.runtime import controller as _ctrl

    _config.set_knob("compression", "none")
    _config.set_knob("adaptive_compression", False)
    try:
        assert _ctrl._active_wire_modes() == {"none"}
        _config.set_knob("adaptive_compression", True)
        modes = _ctrl._active_wire_modes()
        assert {"int8", "int4", "topk"} <= modes
    finally:
        _config.set_knob("adaptive_compression", False)
        _config.set_knob("compression", "none")


def test_residual_ratio_reported_with_integer_leaf(mesh4):
    """A grads pytree carrying an integer leaf (bypasses the lossy
    wire, zero residual) must not blind the guardrail: the float pairs
    still publish."""
    from horovod_tpu.optim import distributed as _dist
    from horovod_tpu.runtime import metrics as _metrics

    _dist._M_RESID_RATIO.reset()
    _config.set_knob("adaptive_compression", True)
    try:
        def body(b):
            g = b[0].reshape(-1)
            red, resid = q.lossy_psum_with_error(g, "hvd", "topk")
            _dist._maybe_report_residual_ratio(
                {"w": resid, "step": jnp.zeros((4,), jnp.float32)},
                {"w": red, "step": jnp.zeros((4,), jnp.int32)},
                "hvd")
            return red.reshape(1, -1)

        rng = np.random.default_rng(14)
        out = run1d(mesh4, body,
                    jnp.asarray(rng.standard_normal((N4, 256)),
                                jnp.float32), out_specs=P("hvd"))
        jax.block_until_ready(out)
        series = _metrics.registry().snapshot().get(
            "hvd_compression_residual_ratio", {}).get("series", [])
        assert series, "mixed-dtype pytree blinded the guardrail"
    finally:
        _config.set_knob("adaptive_compression", False)
        _dist._M_RESID_RATIO.reset()


def test_fused_wire_bytes_shared_accounting():
    """One accounting for tuner scoring and metrics: the helper
    splits shares exactly like the overlap chain and sums per-mode."""
    total = compr.fused_wire_bytes(
        1000, 4, ["none", "int4"], block=256, ratio=0.01, world=2)
    assert total == (500 * 4) + compr.payload_wire_bytes(
        500, 4, "int4", block=256, ratio=0.01, world=2)
    # uneven split: first bucket takes the extra element
    total3 = compr.fused_wire_bytes(
        7, 4, ["none", "none", "none"], block=256, ratio=0.01, world=2)
    assert total3 == 7 * 4


# ---------------------------------------------------------------------------
# 2-proc negotiated wire (the ci.sh adaptive-compression stage)
# ---------------------------------------------------------------------------


@pytest.mark.multiprocess
def test_int4_negotiated_parity_2proc():
    """int4 over the negotiated eager wire: 2-rank qmax = 7 // 2 = 3,
    so values in {-3..3} with block absmax 3 are scale-exact; integer
    dtypes bypass the packed wire entirely."""
    from tests.test_multiprocess import run_ranks

    run_ranks("""
        base = (np.arange(1024) % 7 - 3).astype(np.float32)
        x = jnp.asarray(base * (1 if rank == 0 else -1))
        s = hvd.allreduce(x, op=hvd.Sum, name="i4.z")
        assert np.array_equal(np.asarray(s), np.zeros(1024)), s
        s2 = hvd.allreduce(jnp.asarray(base), op=hvd.Sum, name="i4.d")
        assert np.array_equal(np.asarray(s2), base * 2), s2
        si = hvd.allreduce(jnp.full((16,), 7, jnp.int32), op=hvd.Sum,
                           name="i4.i")
        assert np.array_equal(np.asarray(si), np.full(16, 14)), si
        print("INT4-2PROC-OK", flush=True)
    """, extra_env={"HOROVOD_COMPRESSION": "int4"})


@pytest.mark.multiprocess
def test_topk_negotiated_parity_2proc():
    """top-k over the negotiated eager wire: full density (ratio 1.0)
    must be exact; sparse density keeps at most 2k nonzeros."""
    from tests.test_multiprocess import run_ranks

    run_ranks("""
        import os
        base = np.linspace(-4.0, 4.0, 512).astype(np.float32)
        s = hvd.allreduce(jnp.asarray(base), op=hvd.Sum, name="tk.full")
        assert np.allclose(np.asarray(s), base * 2, atol=1e-6), s
        os.environ["HOROVOD_TOPK_RATIO"] = "0.05"
        # knob change joins the program key on BOTH ranks in lockstep
        s2 = hvd.allreduce(jnp.asarray(base), op=hvd.Sum, name="tk.sp")
        nz = int((np.asarray(s2) != 0).sum())
        assert 0 < nz <= 2 * max(1, round(512 * 0.05)), nz
        print("TOPK-2PROC-OK", flush=True)
    """, extra_env={"HOROVOD_COMPRESSION": "topk",
                    "HOROVOD_TOPK_RATIO": "1.0"})


@pytest.mark.multiprocess
def test_compression_handshake_mismatch_2proc():
    """Rank-divergent topk ratio / bucket vector: the round-0 cfg
    handshake must fail fast (payload shapes are part of the
    negotiated wire) instead of deadlocking."""
    from tests.test_multiprocess import run_ranks

    run_ranks("""
        import os
        os.environ["HOROVOD_TOPK_RATIO"] = \
            "0.01" if rank == 0 else "0.02"
        os.environ["HOROVOD_BUCKET_COMPRESSION"] = \
            "int8:topk" if rank == 0 else "topk:int8"
        try:
            hvd.allreduce(jnp.ones(8), op=hvd.Sum, name="hs")
            raise SystemExit("expected a handshake mismatch error")
        except Exception as e:
            msg = str(e)
            assert ("HOROVOD_TOPK_RATIO" in msg
                    or "HOROVOD_BUCKET_COMPRESSION" in msg), msg
        print("MISMATCH-OK", flush=True)
    """, extra_env={"HOROVOD_COMPRESSION": "topk"})
