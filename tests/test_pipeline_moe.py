"""Pipeline (GPipe over 'pp') and MoE (expert-parallel over 'ep')
correctness on the 8-device mesh.  Both are TPU extensions beyond the
reference (SURVEY §2.7); validated against single-device golden models.
"""

import functools
import json
import pathlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from horovod_tpu.parallel import moe
from horovod_tpu.parallel.pipeline import (gpipe, interleaved_schedule,
                                           interleaved_stage_split,
                                           pipeline)

NSTAGES = 8
M, MB, F = 4, 2, 3  # microbatches, microbatch size, features


@pytest.fixture(scope="module")
def mesh():
    return Mesh(np.array(jax.devices()[:NSTAGES]), ("pp",))


def test_gpipe_matches_sequential(mesh):
    rng = np.random.RandomState(0)
    w = jnp.asarray(rng.randn(NSTAGES, F, F).astype(np.float32)) * 0.5
    b = jnp.asarray(rng.randn(NSTAGES, F).astype(np.float32)) * 0.1
    x = jnp.asarray(rng.randn(M, MB, F).astype(np.float32))

    def stage(params, h):
        wp, bp = params
        return jnp.tanh(h @ wp[0] + bp[0])

    def per_rank(wp, bp, xin):
        return gpipe(stage, (wp, bp), xin, "pp")

    fn = jax.jit(shard_map(per_rank, mesh=mesh, check_vma=False,
                           in_specs=(P("pp"), P("pp"), P()),
                           out_specs=P()))
    out = np.asarray(fn(w, b, x))

    expected = np.asarray(x)
    for s in range(NSTAGES):
        expected = np.tanh(expected @ np.asarray(w[s]) + np.asarray(b[s]))
    np.testing.assert_allclose(out, expected, rtol=1e-5, atol=1e-6)


def test_gpipe_trains(mesh):
    """Pipeline is differentiable end-to-end: a few SGD steps reduce a
    regression loss."""
    rng = np.random.RandomState(1)
    w = jnp.asarray(rng.randn(NSTAGES, F, F).astype(np.float32)) * 0.3
    x = jnp.asarray(rng.randn(M, MB, F).astype(np.float32))
    target = jnp.asarray(rng.randn(M, MB, F).astype(np.float32))

    def stage(wp, h):
        return jnp.tanh(h @ wp[0])

    def per_rank(wp, xin, tgt):
        def loss(wl):
            out = gpipe(stage, wl, xin, "pp")
            return jnp.mean((out - tgt) ** 2)

        l, g = jax.value_and_grad(loss)(wp)
        return l.reshape(1), g

    fn = jax.jit(shard_map(per_rank, mesh=mesh, check_vma=False,
                           in_specs=(P("pp"), P(), P()),
                           out_specs=(P(), P("pp"))))
    losses = []
    for _ in range(5):
        l, g = fn(w, x, target)
        losses.append(float(l[0]))
        assert np.isfinite(np.asarray(g)).all()
        w = w - 0.2 * g
    assert losses[-1] < losses[0], losses


IP, IV, IM = 4, 2, 8  # interleaved: ranks, virtual chunks, microbatches


def _check_schedule(P, V, M):
    """Validity invariants: ready-respecting, each (chunk, mb) exactly
    once, chunks on their owner ranks."""
    steps, run = interleaved_schedule(P, V, M)
    done = {}
    for t, row in enumerate(run):
        assert len(row) == P
        for p, item in enumerate(row):
            if item is None:
                continue
            c, mb = item
            assert 0 <= c < P * V and 0 <= mb < M
            assert c % P == p  # chunk lives on its owner rank
            assert item not in done
            if c > 0:  # activation produced strictly earlier
                assert done[(c - 1, mb)] < t
            done[item] = t
    assert len(done) == P * V * M
    return steps


def test_interleaved_schedule_valid_and_shorter():
    """Greedy schedule is ready-respecting, covers every (chunk, mb)
    exactly once, and beats GPipe's bubble: M*V + P - 1 chunk-steps vs
    (M + P - 1) * V (VERDICT r4 #5: step-count improvement at P=4,
    M=8)."""
    steps = _check_schedule(IP, IV, IM)
    assert steps == IM * IV + IP - 1 == 19
    assert steps < (IM + IP - 1) * IV == 22


def test_interleaved_schedule_property_grid():
    """Validity holds across the (P, V, M) grid, including M < P and
    V=1 (which must reproduce GPipe's M + P - 1 length); at M >= P the
    greedy schedule stays work-optimal-plus-fill."""
    for P in (1, 2, 3, 4):
        for V in (1, 2, 3):
            for M in (1, 2, 4, 8):
                steps = _check_schedule(P, V, M)
                if V == 1:
                    assert steps == M + P - 1, (P, V, M, steps)
                if M >= P:
                    assert steps == M * V + P - 1, (P, V, M, steps)


@pytest.fixture(scope="module")
def imesh():
    return Mesh(np.array(jax.devices()[:IP]), ("pp",))


def _interleaved_params(rng, scale=0.5):
    """(P, V, 1, F, F) weight stack: [p, v] holds chunk v*P + p (one
    layer per chunk, D = P*V layers total), laid out by the canonical
    `interleaved_stage_split` helper."""
    w_layers = jnp.asarray(
        rng.randn(IP * IV, 1, F, F).astype(np.float32) * scale)
    stacked = jnp.stack([
        interleaved_stage_split(w_layers.reshape(IP * IV, F, F), IP, IV, p)
        for p in range(IP)])
    return w_layers, stacked


def test_interleaved_matches_sequential(imesh):
    rng = np.random.RandomState(4)
    w_layers, stacked = _interleaved_params(rng)
    x = jnp.asarray(rng.randn(IM, MB, F).astype(np.float32))

    def stage(wp, h):
        return jnp.tanh(h @ wp[0])

    def per_rank(wp, xin):
        return pipeline(stage, wp[0], xin, "pp",
                        schedule="interleaved", n_virtual=IV)

    fn = jax.jit(shard_map(per_rank, mesh=imesh, check_vma=False,
                           in_specs=(P("pp"), P()), out_specs=P()))
    out = np.asarray(fn(stacked, x))

    expected = np.asarray(x)
    for c in range(IP * IV):
        expected = np.tanh(expected @ np.asarray(w_layers[c, 0]))
    np.testing.assert_allclose(out, expected, rtol=1e-5, atol=1e-6)


def test_interleaved_trains(imesh):
    """Interleaved pipeline is differentiable: SGD reduces a regression
    loss and grads reach every chunk."""
    rng = np.random.RandomState(5)
    _, stacked = _interleaved_params(rng, scale=0.3)
    x = jnp.asarray(rng.randn(IM, MB, F).astype(np.float32))
    target = jnp.asarray(rng.randn(IM, MB, F).astype(np.float32))

    def stage(wp, h):
        return jnp.tanh(h @ wp[0])

    def per_rank(wp, xin, tgt):
        def loss(wl):
            out = pipeline(stage, wl[0], xin, "pp",
                           schedule="interleaved", n_virtual=IV)
            return jnp.mean((out - tgt) ** 2)

        l, g = jax.value_and_grad(loss)(wp)
        return l.reshape(1), g

    fn = jax.jit(shard_map(per_rank, mesh=imesh, check_vma=False,
                           in_specs=(P("pp"), P(), P()),
                           out_specs=(P(), P("pp"))))
    w = stacked
    losses = []
    for _ in range(5):
        l, g = fn(w, x, target)
        g_np = np.asarray(g)
        assert np.isfinite(g_np).all()
        # every chunk's weights receive gradient signal
        assert (np.abs(g_np).reshape(IP * IV, -1).max(axis=1) > 0).all()
        losses.append(float(l[0]))
        w = w - 0.2 * g
    assert losses[-1] < losses[0], losses


def test_remat_grads_match(imesh):
    """remat=True recomputes stage internals in backward; gradients
    must be bit-for-bit the same math (fp-noise tolerance) for both
    schedules."""
    rng = np.random.RandomState(6)
    w_layers, stacked = _interleaved_params(rng, scale=0.3)
    x = jnp.asarray(rng.randn(IM, MB, F).astype(np.float32))
    target = jnp.asarray(rng.randn(IM, MB, F).astype(np.float32))

    def one_layer(wp, h):  # interleaved chunk: wp (1, F, F)
        return jnp.tanh(h @ wp[0])

    def two_layers(wp, h):  # gpipe stage: wp (2, F, F), layers in order
        return jnp.tanh(jnp.tanh(h @ wp[0]) @ wp[1])

    def grads(schedule, stage, w, n_virtual, remat):
        def per_rank(wp, xin, tgt):
            def loss(wl):
                out = pipeline(stage, wl[0], xin, "pp",
                               schedule=schedule, n_virtual=n_virtual,
                               remat=remat)
                return jnp.mean((out - tgt) ** 2)

            return jax.grad(loss)(wp)

        fn = jax.jit(shard_map(per_rank, mesh=imesh, check_vma=False,
                               in_specs=(P("pp"), P(), P()),
                               out_specs=P("pp")))
        return np.asarray(fn(w, x, target))

    # interleaved: stacked (P, V, 1, F, F); gpipe: rank p holds layers
    # (2p, 2p+1) contiguously
    w_gpipe = jnp.asarray(np.asarray(w_layers).reshape(IP, 2, F, F))

    gi = grads("interleaved", one_layer, stacked, IV, remat=False)
    gi_r = grads("interleaved", one_layer, stacked, IV, remat=True)
    np.testing.assert_allclose(gi_r, gi, rtol=1e-6, atol=1e-7)

    gg = grads("gpipe", two_layers, w_gpipe, 1, remat=False)
    gg_r = grads("gpipe", two_layers, w_gpipe, 1, remat=True)
    np.testing.assert_allclose(gg_r, gg, rtol=1e-6, atol=1e-7)


EP = 8
T, DIM, FFH = 32, 8, 16
E_LOCAL = 2
E = EP * E_LOCAL
TOP_K, SCALE = 4, 2.5


@pytest.fixture(scope="module")
def ep_mesh():
    return Mesh(np.array(jax.devices()[:EP]), ("ep",))


FORMS = ("swiglu", "relu2")


def _layer_params(seed, first=0, held=None, shared=True, form="swiglu",
                  n_experts=E):
    """A layer's weights, float32: the router and bias over all
    ``n_experts`` experts, the experts ``first .. first + held`` (all of
    them where ``held`` is not given), the shared one.  ``form`` "relu2"
    leaves the gate matrices out: two-matrix experts."""
    rng = np.random.RandomState(seed)
    held = n_experts if held is None else held
    p = {"router": rng.randn(DIM, n_experts) * 0.5,
         "bias": rng.randn(n_experts) * 0.1,
         "experts": {"w_gate": rng.randn(n_experts, DIM, FFH) * 0.3,
                     "w_up": rng.randn(n_experts, DIM, FFH) * 0.3,
                     "w_down": rng.randn(n_experts, FFH, DIM) * 0.3},
         "shared": {"w_gate": rng.randn(DIM, FFH) * 0.3,
                    "w_up": rng.randn(DIM, FFH) * 0.3,
                    "w_down": rng.randn(FFH, DIM) * 0.3}}
    p["experts"] = {name: a[first:first + held]
                    for name, a in p["experts"].items()}
    if form == "relu2":
        del p["experts"]["w_gate"], p["shared"]["w_gate"]
    if not shared:
        del p["shared"]
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), p)


_SPECS = {"router": P(), "bias": P(),
          "experts": {"w_gate": P("ep"), "w_up": P("ep"), "w_down": P("ep")},
          "shared": {"w_gate": P(), "w_up": P(), "w_down": P()}}


def test_moe_matches_reference(ep_mesh, monkeypatch):
    """Over ``ep`` = 8, two experts a rank: tokens gathered, each rank's
    share, a reduce-scatter back.  Every rank's tokens get what the
    plain reference gives for the whole layer (all 16 experts and the
    shared one), in chunks of 16 rows so that a rank loops more than
    once; every pair is computed (the counts add up to tokens x k)."""
    monkeypatch.setattr(moe, "CHUNK_ROWS", 16)
    params = _layer_params(2)
    x = jnp.asarray(np.random.RandomState(3).randn(EP, T, DIM), jnp.float32)

    def per_rank(xb, p):
        out, pairs = moe.moe_layer(xb[0], p, top_k=TOP_K, scale=SCALE,
                                   axis_name="ep")
        return out[None], pairs

    out, pairs = jax.jit(shard_map(
        per_rank, mesh=ep_mesh, check_vma=False,
        in_specs=(P("ep"), _SPECS), out_specs=(P("ep"), P("ep"))))(x, params)
    assert int(pairs.sum()) == EP * T * TOP_K
    ref = moe.moe_reference(x.reshape(EP * T, DIM), params, top_k=TOP_K,
                            scale=SCALE)
    # float32 on both sides; the chunks' order of the sums apart
    np.testing.assert_allclose(np.asarray(out).reshape(EP * T, DIM),
                               np.asarray(ref), rtol=1e-4, atol=1e-5)


def test_moe_grads_flow(ep_mesh, monkeypatch):
    """The gradients of tokens, router, held experts and shared expert
    through the exchange and the chunk loop's own backward pass are the
    plain reference's; the selection bias gets none."""
    monkeypatch.setattr(moe, "CHUNK_ROWS", 16)
    params = _layer_params(4)
    x = jnp.asarray(np.random.RandomState(5).randn(EP, T, DIM), jnp.float32)

    def per_rank(xb, p):
        def loss(x_, p_):
            out, _ = moe.moe_layer(x_, p_, top_k=TOP_K, scale=SCALE,
                                   axis_name="ep")
            return jnp.sum(out ** 2)

        gx, gp = jax.grad(loss, argnums=(0, 1))(xb[0], p)
        # a replicated weight's gradient is the sum over the ranks, as
        # make_train_step reduces it; an expert's stays with its holder
        gp = {name: (g if name == "experts" else jax.lax.psum(g, "ep"))
              for name, g in gp.items()}
        return gx[None], gp

    gx, gp = jax.jit(shard_map(
        per_rank, mesh=ep_mesh, check_vma=False,
        in_specs=(P("ep"), _SPECS), out_specs=(P("ep"), _SPECS)))(x, params)
    rx, rp = jax.grad(
        lambda x_, p_: jnp.sum(moe.moe_reference(
            x_, p_, top_k=TOP_K, scale=SCALE) ** 2),
        argnums=(0, 1))(x.reshape(EP * T, DIM), params)
    assert not np.asarray(gp["bias"]).any()
    assert np.abs(np.asarray(gp["experts"]["w_gate"])).max() > 0
    got = jax.tree_util.tree_leaves((gx.reshape(EP * T, DIM), gp))
    want = jax.tree_util.tree_leaves((rx, rp))
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=2e-4,
                                   atol=2e-5)


@pytest.mark.parametrize("form, n_experts, top_k, scale, shares", [
    *((form, E, TOP_K, SCALE, (E, E // 4)) for form in FORMS),
    ("swiglu", 128, 8, 2.826, (16,))],
    ids=[*FORMS, "top-8-of-128-in-16-shares"])
def test_the_shares_of_a_layer_add_up_to_the_layer(form, n_experts, top_k,
                                                   scale, shares):
    """One chip at a time, no exchange: the 16 shares of a 16-expert
    layer (one expert each, told which), with the shared expert, which
    every chip computes alike, counted once, add up to what the uncut
    reference gives; so do 4 shares of 4, and the 16 shares of 8 of a
    128-expert layer routed top-8 at scale 2.826 (the window / full
    attention expert configuration's cut).  What a share leaves out is
    exactly the other experts' part.  SwiGLU experts and two-matrix
    relu^2 ones: the layer and the reference read the form from the
    weights."""
    x = jnp.asarray(np.random.RandomState(6).randn(T, DIM), jnp.float32)
    whole = _layer_params(7, form=form, n_experts=n_experts)
    ref = moe.moe_reference(x, whole, top_k=top_k, scale=scale)
    shared = getattr(moe, form)(x, whole["shared"])
    for held in (n_experts // n for n in shares):
        total, sent = shared, 0
        for first in range(0, n_experts, held):
            share = _layer_params(7, first, held, shared=False, form=form,
                                  n_experts=n_experts)
            out, pairs = moe.moe_layer(x, share, top_k=top_k, scale=scale,
                                       first=first)
            part = moe.moe_reference(x, share, top_k=top_k, scale=scale,
                                     first=first)
            np.testing.assert_allclose(np.asarray(out), np.asarray(part),
                                       rtol=1e-4, atol=1e-5)
            total, sent = total + out, sent + int(pairs.sum())
        assert sent == T * top_k            # no pair dropped, none twice
        np.testing.assert_allclose(np.asarray(total), np.asarray(ref),
                                   rtol=1e-4, atol=1e-5)


def test_routing_is_dropless_under_any_skew():
    """A router that sends every token to the same k experts: the held
    ones compute tokens x k pairs, more than a chunk, none dropped."""
    rng = np.random.RandomState(8)
    params = _layer_params(9, shared=False)
    params["router"] = jnp.zeros((DIM, E))
    params["bias"] = jnp.asarray(
        np.where(np.arange(E) < TOP_K, 1.0, 0.0), jnp.float32)
    x = jnp.asarray(rng.randn(3 * T, DIM), jnp.float32)
    patch = pytest.MonkeyPatch()
    patch.setattr(moe, "CHUNK_ROWS", 32)
    try:
        out, pairs = moe.moe_layer(x, params, top_k=TOP_K, scale=SCALE)
    finally:
        patch.undo()
    assert pairs.tolist() == [3 * T] * TOP_K + [0] * (E - TOP_K)
    ref = moe.moe_reference(x, params, top_k=TOP_K, scale=SCALE)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4,
                               atol=1e-5)


@pytest.mark.parametrize("form", FORMS)
@pytest.mark.parametrize("case", ("larger", "equal", "divisor", "no_divisor"))
def test_any_chunk_size_gives_the_one_chunk_result(monkeypatch, form, case):
    """A share that holds 4 of 16 experts, its held pairs in chunks
    larger than, equal to, a divisor of and no divisor of their number:
    the loop runs ``ceil(held pairs / rows)`` trips in each pass (the
    chunks are counted as they run), and the share and every gradient
    are the one-chunk result to float32 rounding and the plain
    reference's.  Both forms of expert."""
    params = _layer_params(17, first=4, held=4, shared=False, form=form)
    x = jnp.asarray(np.random.RandomState(19).randn(T, DIM), jnp.float32)

    def loss(layer):
        return jax.value_and_grad(
            lambda x_, p_: jnp.sum(layer(x_, p_, top_k=TOP_K, scale=SCALE,
                                         first=4) ** 2),
            argnums=(0, 1))(x, params)

    def share(*args, **kw):
        return moe.moe_layer(*args, **kw)[0]

    held = int(moe.moe_layer(x, params, top_k=TOP_K, scale=SCALE,
                             first=4)[1].sum())
    assert held % 2 == 0 and 6 <= held <= T * TOP_K - 3
    rows = {"larger": held + 3, "equal": held, "divisor": held // 2,
            "no_divisor": held // 2 + 1}[case]
    assert held + 3 <= moe.chunk_rows(T * TOP_K, 4, E) == T * TOP_K // 2
    one = loss(share)                   # one chunk holds all the held pairs
    trips, real = [], moe._chunk

    def counted(x_, w, weights, plan, index, rows_):
        assert rows_ == rows
        jax.debug.callback(lambda i: trips.append(int(i)), index)
        return real(x_, w, weights, plan, index, rows_)

    monkeypatch.setattr(moe, "CHUNK_ROWS", rows)
    monkeypatch.setattr(moe, "_chunk", counted)
    got = loss(share)
    jax.effects_barrier()
    assert sorted(trips) == sorted(2 * list(range(-(-held // rows))))
    want = loss(functools.partial(moe.moe_reference, held=4))
    for g, o, w in zip(*map(jax.tree_util.tree_leaves, (got, one, want))):
        np.testing.assert_allclose(np.asarray(g), np.asarray(o), rtol=1e-5,
                                   atol=1e-6)
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=2e-4,
                                   atol=2e-5)


def _cell_shapes(config):
    """``(tokens x k, held, router width)`` of a step of the benchmark's
    expert cell of that configuration."""
    root = pathlib.Path(__file__).parents[1] / "benchmark"
    cfg = json.loads((root / "configs" / f"{config}.json").read_text())
    job = json.loads((root / "traffic" / "s8192.epshare.json").read_text())
    return (job["batch_per_chip"] * job["seq"] * cfg["num_experts_per_tok"],
            cfg["n_routed_experts"], cfg["router_width"])


def test_the_hybrid_cells_balanced_layer_is_one_trip_with_room():
    """Arithmetic only, at the shapes of the benchmark's hybrid cell
    (16,384 tokens a step, top-6 with 8 held of 128): the 6,144 pairs a
    balanced router sends the held experts lie at least 15 % clear of a
    trip boundary, so a seed's few per cent do not change the trips,
    and one trip is half the 16,384 rows of a whole chunk."""
    pairs, held, width = _cell_shapes("nemotron-twotower-30b-a3b")
    expected = pairs * held // width
    assert (pairs, expected) == (16384 * 6, 6144)
    rows = moe.chunk_rows(pairs, held, width)
    assert 1.15 * expected <= rows == 16384 // 2


@pytest.mark.parametrize("config", ("nemotron-twotower-30b-a3b",
                                    "joyai-llm-flash"))
def test_no_load_runs_more_rows_than_whole_chunks_would(config):
    """Arithmetic only, at the shapes of the benchmark's expert cells:
    a chunk is ``CHUNK_ROWS`` rows in equal parts (2 and 1: the latent
    cell's share and a third does not fit twice), so whatever the held
    experts are sent, from nothing to every pair, the trips' rows are
    at most those of whole chunks."""
    pairs, held, width = _cell_shapes(config)
    rows = moe.chunk_rows(pairs, held, width)
    assert moe.CHUNK_ROWS % rows == 0
    assert moe.CHUNK_ROWS // rows == {8: 2, 16: 1}[held]
    load = np.arange(0, pairs + 1, 61)
    run = -(-load // rows) * rows
    whole = -(-load // moe.CHUNK_ROWS) * moe.CHUNK_ROWS
    assert (run <= whole).all()


def test_more_ranks_than_the_router_has_experts_raises(ep_mesh):
    params = _layer_params(10)      # 16 experts a rank x 8 ranks > 16

    def per_rank(xb, p):
        return moe.moe_layer(xb[0], p, top_k=TOP_K, scale=SCALE,
                             axis_name="ep")[0][None]

    replicated = jax.tree_util.tree_map(lambda _: P(), params)
    with pytest.raises(Exception, match="exceed the router"):
        jax.jit(shard_map(per_rank, mesh=ep_mesh, check_vma=False,
                          in_specs=(P("ep"), replicated),
                          out_specs=P("ep")))(jnp.zeros((EP, T, DIM)), params)


def _leaves_rows_unwritten(real):
    """``lax.ragged_dot`` as the TPU runs it: a row of the left operand
    that belongs to no group is not written, in the product and in the
    left operand's gradient.  NaN stands for what the buffer held."""
    def product(a, b, sizes, **kw):
        @jax.custom_vjp
        def prod(a, b, sizes):
            dead = jnp.arange(a.shape[0]) >= jnp.sum(sizes)
            return jnp.where(dead[:, None], jnp.nan, real(a, b, sizes, **kw))

        def bwd(res, ct):
            a, b, sizes = res
            da, db = jax.vjp(lambda a, b: real(a, b, sizes, **kw), a, b)[1](ct)
            dead = jnp.arange(a.shape[0]) >= jnp.sum(sizes)
            return (jnp.where(dead[:, None], jnp.nan, da), db,
                    np.zeros(sizes.shape, jax.dtypes.float0))

        prod.defvjp(lambda a, b, sizes: (prod(a, b, sizes), (a, b, sizes)),
                    bwd)
        return prod(a, b, sizes)

    return product


@pytest.mark.parametrize("form", FORMS)
def test_rows_that_belong_to_no_group_are_never_read(monkeypatch, form):
    """A share that holds 4 of 16 experts: most rows of its one chunk
    lie past the last held pair.  With those rows of every grouped
    product and of its left gradient poisoned, the share and all its
    gradients are what they are unpoisoned: finite, and equal.  Both
    forms of expert."""
    params = _layer_params(11, first=4, held=4, shared=False, form=form)
    x = jnp.asarray(np.random.RandomState(12).randn(T, DIM), jnp.float32)

    def loss(x_, p_):
        out, _ = moe.moe_layer(x_, p_, top_k=TOP_K, scale=SCALE, first=4)
        return jnp.sum(out ** 2)

    want = jax.value_and_grad(loss, argnums=(0, 1))(x, params)
    monkeypatch.setattr(jax.lax, "ragged_dot",
                        _leaves_rows_unwritten(jax.lax.ragged_dot))
    got = jax.value_and_grad(loss, argnums=(0, 1))(x, params)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_count_all_gives_the_pairs_sent_to_every_expert():
    """``count_all``: the second value is over all ``E`` experts, held
    or not; its held columns are what the share computes, and it adds
    up to tokens x k."""
    params = _layer_params(13, first=4, held=4, shared=False)
    x = jnp.asarray(np.random.RandomState(14).randn(T, DIM), jnp.float32)
    out, held = moe.moe_layer(x, params, top_k=TOP_K, scale=SCALE, first=4)
    same, loads = moe.moe_layer(x, params, top_k=TOP_K, scale=SCALE, first=4,
                                count_all=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(same))
    assert loads.shape == (E,) and int(loads.sum()) == T * TOP_K
    np.testing.assert_array_equal(np.asarray(loads[4:8]), np.asarray(held))


def test_the_balance_rule_evens_the_load_out():
    """``settle_bias``: an expert sent more pairs than the mean loses
    ``rate``, one sent fewer gains it, an expert at the mean keeps its
    bias; over rounds a lopsided router's busiest expert comes down to
    the mean (the bias selects only: the weights stay the scores')."""
    bias = jnp.zeros((2, 4))
    loads = jnp.asarray([[10, 2, 6, 6], [1, 1, 1, 21]], jnp.int32)
    np.testing.assert_allclose(
        np.asarray(moe.settle_bias(bias, loads, 0.5)),
        [[-0.5, 0.5, 0.0, 0.0], [0.5, 0.5, 0.5, -0.5]])
    params = _layer_params(15, shared=False)
    params["router"] = params["router"].at[:, 0].mul(4.0)   # a favourite
    x = jnp.asarray(np.random.RandomState(16).randn(8 * T, DIM), jnp.float32)

    def loads_of(bias):
        ids, _ = moe.route(x, params["router"], bias, TOP_K, SCALE)
        return moe.pairs_per_expert(ids, 0, E)

    bias = params["bias"]
    before = loads_of(bias)
    for _ in range(200):
        bias = moe.settle_bias(bias, loads_of(bias), 0.01)
    after = loads_of(bias)
    assert float(before.max() / before.mean()) > 1.5
    assert float(after.max() / after.mean()) < 1.15
