"""The kinds of a ``layer_pattern`` that the short-convolution expert
configurations (``lfm2_moe``) are made of: ``C``, a gated short
convolution ``(C * conv(B * x)) W_out`` over three projections of the
stream, and ``R``, ungated grouped-query attention with QK-norm and
rotary positions over the whole causal past, beside ``D`` and ``E``.  The
configuration's checks, the stacks, the mesh axes each kind refuses, the
convolution against a direct NumPy computation forward and backward, the
attention kind against the indexed kind keeping every key, training over
``dp`` with the experts shared out, and the ``hvd_conv_mix`` records.
(The stack against the benchmark's plain reference:
tests/benchmark_suite/test_benchmark_conv_moe.py.)"""

import dataclasses
import re

import numpy as np
import optax
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from horovod_tpu.models import blocks
from horovod_tpu.models.transformer import (TransformerConfig, init_params,
                                            loss_and_routing, make_train_step,
                                            param_specs, record_attention,
                                            record_conv, shard_params)
from horovod_tpu.parallel.mesh import make_mesh

CFG = TransformerConfig(
    vocab=64, d_model=32, n_heads=4, head_dim=8, max_seq=32, dtype="float32",
    tied_head=False, layer_pattern="CDRECE", n_kv_heads=2, d_ff=48,
    n_experts=16, experts_held=4, experts_per_token=4, d_expert=16,
    rope_theta=1e6, norm_eps=1e-5)


def _data(mesh, cfg, batch=4, seq=32, seed=0):
    rng = np.random.RandomState(seed)
    sh = NamedSharding(mesh, P("dp", "sp"))
    return tuple(jax.device_put(jnp.asarray(
        rng.randint(0, cfg.vocab, (batch, seq)), jnp.int32), sh)
        for _ in range(2))


def _one_chip(fn, *args):
    """``fn(*args)`` under ``shard_map`` on a mesh of one device, where
    the mixers can ask for their axes."""
    from jax import shard_map

    mesh = make_mesh(dp=1, pp=1, tp=1, sp=1, devices=jax.devices()[:1])
    return jax.jit(shard_map(fn, mesh=mesh, check_vma=False,
                             in_specs=P(), out_specs=P()))(*args)


def _loss(cfg, params):
    from jax import lax

    mesh = make_mesh(dp=1, pp=1, tp=1, sp=1, devices=jax.devices()[:1])
    tokens, targets = _data(mesh, cfg)
    return float(_one_chip(
        lambda p, a, b: lax.psum(loss_and_routing(p, a, b, cfg)[0],
                                 ("dp", "sp")), params, tokens, targets))


# ---------------------------------------------------------------------------
# Configuration, stacks, refusals
# ---------------------------------------------------------------------------


def test_the_pattern_decides_the_stacks():
    """``C`` and ``R`` have stacks of their own: the in-projection to
    three times the hidden size, the taps, the out-projection; the
    attention's four matrices and its two per-head gains; the specs name
    what the parameters hold."""
    params = init_params(np.random.RandomState(0), CFG)
    assert set(params) == {"embed", "ln_f", "head", "conv", "rattn", "dense",
                           "moe"}
    assert set(params["conv"]) == {"ln", "w_in", "conv_w", "w_out"}
    assert params["conv"]["w_in"].shape == (2, 32, 3 * 32)
    assert params["conv"]["conv_w"].shape == (2, 3, 32)
    assert params["conv"]["w_out"].shape == (2, 32, 32)
    assert set(params["rattn"]) == {"ln", "wq", "wk", "wv", "wo", "q_norm",
                                    "k_norm"}
    assert params["rattn"]["wk"].shape == (1, 32, 2 * 8)
    assert params["rattn"]["q_norm"].shape == (1, 8)
    # the taps at a framework's default variance, a third of 1 / taps
    taps = init_params(np.random.RandomState(0), dataclasses.replace(
        CFG, d_model=256, n_heads=32))["conv"]["conv_w"]
    assert float(jnp.std(taps)) == pytest.approx(1 / 3, rel=0.05)
    assert jax.tree_util.tree_structure(
        jax.tree_util.tree_map(lambda a: 0, params)) \
        == jax.tree_util.tree_structure(jax.tree_util.tree_map(
            lambda s: 0, param_specs(CFG), is_leaf=lambda s: isinstance(s, P)))
    assert blocks.STACK_OF["C"] == "conv" and blocks.STACK_OF["R"] == "rattn"
    assert "R" in blocks.ATTENTION_KINDS and "C" not in blocks.ATTENTION_KINDS
    # the older kinds' stacks draw the same weights as before the gains
    # were shared out into _init_normed_gqa
    gated = init_params(np.random.RandomState(0), dataclasses.replace(
        CFG, layer_pattern="GD"))["gattn"]
    rng = np.random.RandomState(0)
    rng.randn(64, 32), rng.randn(32, 64)              # embedding, head
    for name, shape in (("wq", (1, 32, 32)), ("wk", (1, 32, 16)),
                        ("wv", (1, 32, 16)), ("wo", (1, 32, 32)),
                        ("wg", (1, 32, 32))):
        want = rng.randn(*shape) * shape[1] ** -0.5
        np.testing.assert_allclose(gated[name], want, rtol=1e-6)


@pytest.mark.parametrize("change,message", [
    (dict(layer_pattern="CQ"), "layer_pattern holds .*'C'.*: \\['Q'\\]"),
    (dict(head_dim=7), "'R' layers need an even head_dim"),
    (dict(n_kv_heads=3), "no multiple of n_kv_heads"),
])
def test_configuration_checks(change, message):
    with pytest.raises(ValueError, match=message):
        dataclasses.replace(CFG, **change)


@pytest.mark.parametrize("axes,pattern,message", [
    (dict(tp=2), "CE", "a gated short convolution under tp > 1 is not "
                       "supported: the in-projection's B | C | x parts"),
    (dict(sp=2), "CE", "a gated short convolution under sp > 1 is not "
                       "supported: a chunk's first outputs need the last "
                       "taps - 1 inputs"),
    (dict(tp=2), "RE", "grouped-query attention with QK-norm under tp > 1"),
    (dict(sp=2), "RE", "grouped-query attention with QK-norm under sp > 1"),
])
def test_a_kind_refuses_the_axes_it_is_not_built_for(axes, pattern, message):
    """``NotImplementedError`` with the reason, at trace time."""
    cfg = dataclasses.replace(CFG, layer_pattern=pattern)
    mesh = make_mesh(**{"dp": 1, "pp": 1, "tp": 1, "sp": 1, **axes},
                     devices=jax.devices()[:2])
    step = make_train_step(cfg, mesh, optax.sgd(0.1))
    params = init_params(np.random.RandomState(0), cfg)
    tokens, targets = _data(mesh, cfg)
    with pytest.raises(NotImplementedError,
                       match=re.escape(message)) as raised:
        step(params, optax.sgd(0.1).init(params), tokens, targets)
    assert "is not supported: " in str(raised.value)


# ---------------------------------------------------------------------------
# The gated short convolution against NumPy
# ---------------------------------------------------------------------------


def _numpy_conv(h, w_in, taps_w, w_out, cotangent):
    """The sub-layer and its gradients in float64, written out by hand:
    ``[B | C | x] = h W_in``, ``u = B * x``, ``v_t = sum_k w_k u_{t - T +
    1 + k}`` (nothing before the first step), ``y = C * v``, ``out = y
    W_out``; and, for the loss ``sum(out * cotangent)``, the gradients
    of ``h``, ``W_in``, the taps and ``W_out``."""
    h, w_in, taps_w, w_out, g = (np.asarray(a, np.float64) for a in
                                 (h, w_in, taps_w, w_out, cotangent))
    d = w_out.shape[0]
    taps, steps = taps_w.shape[0], h.shape[1]
    proj = h @ w_in
    b, c, x = proj[..., :d], proj[..., d:2 * d], proj[..., 2 * d:]
    u = b * x
    v = np.zeros_like(u)
    for t in range(steps):
        for k in range(taps):
            s = t - (taps - 1) + k
            if s >= 0:
                v[:, t] += taps_w[k] * u[:, s]
    y = c * v
    out = y @ w_out
    # backward
    d_w_out = np.einsum("bld,ble->de", y, g)
    dy = g @ w_out.T
    dc, dv = dy * v, dy * c
    du = np.zeros_like(u)
    d_taps = np.zeros_like(taps_w)
    for t in range(steps):
        for k in range(taps):
            s = t - (taps - 1) + k
            if s >= 0:
                du[:, s] += taps_w[k] * dv[:, t]
                d_taps[k] += np.sum(dv[:, t] * u[:, s], axis=0)
    d_proj = np.concatenate([du * x, dc, du * b], axis=-1)
    return out, {"h": d_proj @ w_in.T,
                 "w_in": np.einsum("bld,ble->de", h, d_proj),
                 "conv_w": d_taps, "w_out": d_w_out}


@pytest.mark.parametrize("taps", [blocks.CONV_TAPS, 5])
def test_the_convolution_against_numpy_forward_and_backward(taps):
    """``blocks.conv_mix`` at float32, a sequence of 24 and 8 channels:
    its output and the gradients of its input and of its three weights
    equal the hand-written NumPy computation's, at the taps
    ``init_params`` draws and at as many as ``conv_w`` holds."""
    cfg = dataclasses.replace(CFG, d_model=8, layer_pattern="C")
    rng = np.random.RandomState(3)
    h = jnp.asarray(rng.randn(2, 24, 8), jnp.float32)
    lp = {"w_in": jnp.asarray(rng.randn(8, 24) / 3, jnp.float32),
          "conv_w": jnp.asarray(rng.randn(taps, 8), jnp.float32),
          "w_out": jnp.asarray(rng.randn(8, 8) / 3, jnp.float32)}
    cotangent = jnp.asarray(rng.randn(2, 24, 8), jnp.float32)

    def loss(h, lp):
        return jnp.sum(blocks.conv_mix(cfg, lp, h) * cotangent)

    def run(h, lp):
        with jax.default_matmul_precision("highest"):
            return (blocks.conv_mix(cfg, lp, h),
                    jax.grad(loss, argnums=(0, 1))(h, lp))

    out, (dh, dlp) = _one_chip(run, h, lp)
    want, grads = _numpy_conv(h, lp["w_in"], lp["conv_w"], lp["w_out"],
                              cotangent)
    np.testing.assert_allclose(out, want, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(dh, grads["h"], rtol=1e-4, atol=1e-4)
    for name in ("w_in", "conv_w", "w_out"):
        np.testing.assert_allclose(dlp[name], grads[name], rtol=1e-4,
                                   atol=1e-4, err_msg=name)


def test_the_convolution_is_causal():
    """An output reads its own step and the two before: a change at step
    10 moves steps 10, 11 and 12 alone."""
    cfg = dataclasses.replace(CFG, d_model=8, layer_pattern="C")
    rng = np.random.RandomState(4)
    h = rng.randn(1, 24, 8).astype(np.float32)
    lp = {"w_in": jnp.asarray(rng.randn(8, 24), jnp.float32),
          "conv_w": jnp.asarray(rng.randn(3, 8), jnp.float32),
          "w_out": jnp.asarray(rng.randn(8, 8), jnp.float32)}
    moved = h.copy()
    moved[0, 10] += 1.0
    a, b = (np.asarray(_one_chip(lambda x, lp: blocks.conv_mix(cfg, lp, x),
                                 jnp.asarray(x), lp)) for x in (h, moved))
    changed = np.abs(a - b).max(axis=-1)[0] > 1e-6
    assert changed.nonzero()[0].tolist() == [10, 11, 12]


def test_causal_conv_takes_its_bias_or_none():
    """Without a bias the convolution is the one with a zero bias, and
    the state-space layer's call with its bias is unchanged."""
    rng = np.random.RandomState(5)
    x = jnp.asarray(rng.randn(2, 16, 6), jnp.float32)
    w = jnp.asarray(rng.randn(4, 6), jnp.float32)
    bias = jnp.asarray(rng.randn(6), jnp.float32)
    np.testing.assert_array_equal(blocks.causal_conv(x, w),
                                  blocks.causal_conv(x, w, jnp.zeros(6)))
    with_bias = blocks.causal_conv(x, w, bias)
    np.testing.assert_allclose(with_bias - blocks.causal_conv(x, w),
                               jnp.broadcast_to(bias, x.shape), atol=1e-5)


def test_each_part_of_the_convolution_is_computed():
    """Either gate zeroed, or the taps zeroed, silences the sub-layer;
    the two gates swapped change it (``B`` and ``C`` are not
    interchangeable: ``C`` multiplies after the convolution)."""
    cfg = dataclasses.replace(CFG, d_model=8, layer_pattern="C")
    rng = np.random.RandomState(6)
    h = jnp.asarray(rng.randn(1, 16, 8), jnp.float32)
    w_in = rng.randn(8, 24).astype(np.float32)
    lp = {"w_in": w_in, "conv_w": rng.randn(3, 8).astype(np.float32),
          "w_out": rng.randn(8, 8).astype(np.float32)}

    def out(**change):
        return np.asarray(_one_chip(
            lambda h, lp: blocks.conv_mix(cfg, lp, h), h,
            {**lp, **change}))

    whole = out()
    assert np.abs(whole).max() > 0.1
    for cols in (slice(0, 8), slice(8, 16), slice(16, 24)):
        zeroed = w_in.copy()
        zeroed[:, cols] = 0
        assert np.abs(out(w_in=zeroed)).max() == 0.0
    assert np.abs(out(conv_w=np.zeros((3, 8), np.float32))).max() == 0.0
    swapped = np.concatenate([w_in[:, 8:16], w_in[:, :8], w_in[:, 16:]], 1)
    assert np.abs(out(w_in=swapped) - whole).max() > 0.1


# ---------------------------------------------------------------------------
# The attention kind
# ---------------------------------------------------------------------------


def test_rotary_attention_is_the_indexed_kind_keeping_every_key():
    """``R`` and ``I`` share the projections, the per-head norms, rotary
    and the key/value broadcast (``blocks._normed_heads``): with an
    indexer that keeps every earlier key, ``I`` is ``R`` to rounding."""
    cfg = dataclasses.replace(CFG, layer_pattern="RE")
    params = init_params(np.random.RandomState(7), cfg)
    indexed = dataclasses.replace(cfg, layer_pattern="IE", index_heads=2,
                                  index_head_dim=8, index_topk=32)
    extra = init_params(np.random.RandomState(7), indexed)["dsa"]
    both = {**{k: v for k, v in params.items() if k != "rattn"},
            "dsa": {**params["rattn"],
                    **{k: extra[k] for k in ("wq_idx", "wk_idx", "ww_idx")}}}
    assert _loss(indexed, both) == pytest.approx(_loss(cfg, params),
                                                 rel=1e-6)


def test_qk_norm_and_positions_are_computed():
    """The gains of the per-head norms and the rotary frequency move the
    loss; so does the order of the key/value heads."""
    params = init_params(np.random.RandomState(8), CFG)
    base = _loss(CFG, params)
    rattn = params["rattn"]
    for changed in ({**rattn, "q_norm": 2 * rattn["q_norm"]},
                    {**rattn, "k_norm": 0.5 * rattn["k_norm"]}):
        assert abs(_loss(CFG, {**params, "rattn": changed}) - base) > 1e-4
    assert abs(_loss(dataclasses.replace(CFG, rope_theta=10.0), params)
               - base) > 1e-4
    swapped = {**rattn, "wk": jnp.concatenate(
        [rattn["wk"][..., 8:], rattn["wk"][..., :8]], axis=-1)}
    assert abs(_loss(CFG, {**params, "rattn": swapped}) - base) > 1e-4


# ---------------------------------------------------------------------------
# Training, records
# ---------------------------------------------------------------------------


def test_trains_over_dp_with_the_experts_shared_out():
    """Two chips, each holding 4 of the 16 experts and its own
    sequences, every sub-layer recomputed: the loss falls."""
    mesh = make_mesh(dp=2, pp=1, tp=1, sp=1, devices=jax.devices()[:2])
    cfg = dataclasses.replace(CFG, remat=True)
    opt = optax.adam(1e-2)
    params = shard_params(init_params(np.random.RandomState(0), cfg, ep=2),
                          cfg, mesh)
    state = opt.init(params)
    step = make_train_step(cfg, mesh, opt)
    tokens, targets = _data(mesh, cfg, batch=4)
    losses = []
    for _ in range(8):
        params, state, loss = step(params, state, tokens, targets)
        losses.append(float(loss))
    assert np.isfinite(losses).all() and losses[-1] < losses[0] - 0.1


def test_conv_records():
    """One ``hvd_conv_mix`` record a convolution layer, by its place in
    the pattern; the attention layer is recorded as every attention kind
    is, with no window."""
    from horovod_tpu.runtime import flight

    before = len([e for e in flight.recorder().snapshot()
                  if e["kind"] == "hvd_conv_mix"])
    records = record_conv(CFG, batch=4)
    assert [r["layer"] for r in records] == [0, 4]
    for record in records:
        assert (record["seq"], record["channels"], record["taps"],
                record["impl"]) == (32, 32, 3, "xla")
        assert record["required_bytes"] == 4 * 4 * 32 * 32 * 4
    assert len([e for e in flight.recorder().snapshot()
                if e["kind"] == "hvd_conv_mix"]) == before + 2
    attention, = record_attention(CFG, batch=4)
    assert (attention["layer"], attention["layer_kind"],
            attention["window"]) == (2, "R", 0)


def test_conv_record_at_the_benchmarks_sizes():
    """8,192 tokens twice at a hidden size of 2,048 in bf16: 134 MB a
    pass a layer."""
    cfg = TransformerConfig(
        vocab=64, d_model=2048, n_heads=32, head_dim=64, n_kv_heads=8,
        max_seq=8192, tied_head=False, layer_pattern="CRCC")
    records = record_conv(cfg, batch=2)
    assert [r["layer"] for r in records] == [0, 2, 3]
    assert records[0]["required_bytes"] == 4 * 2 * 8192 * 2048 * 2 \
        == 268_435_456


def test_docs_name_the_scopes_and_the_record():
    """``docs/perf.md`` says where each new name is set."""
    import os

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(repo, "docs", "perf.md"), encoding="utf-8") as f:
        text = f.read()
    for name in ("hvd_conv", "hvd_conv_mix", "hvd_rattn", "blocks.conv_mix",
                 "transformer.record_conv"):
        assert f"`{name}`" in text, name
