"""The kinds of a ``layer_pattern`` that the window / full attention
expert configurations (``afmoe``) are made of: ``S`` and ``G`` gated
grouped-query attention with an RMSNorm on each head's q and k — ``S``
under the sliding window with rotary positions, ``G`` over the whole past
with none — ``D`` a dense SwiGLU FFN, a norm after a sub-layer, and the
embedding's multiplier.  The configuration's checks, the stacks, the mesh
axes each kind refuses, what each statement of the configuration changes
in the loss, training over ``dp`` with the experts shared out, and the
``hvd_attn_window`` records.  (The stack against the benchmark's plain
reference: tests/benchmark_suite/test_benchmark_swa_moe.py.)"""

import dataclasses

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
                                            shard_params)
from horovod_tpu.parallel.mesh import make_mesh

CFG = TransformerConfig(
    vocab=64, d_model=32, n_heads=4, head_dim=8, max_seq=32, dtype="float32",
    tied_head=False, layer_pattern="SDSEGE", n_kv_heads=2, window=8,
    post_norm=True, embed_scale=32 ** 0.5, d_ff=48, n_experts=8,
    experts_held=4, experts_per_token=2, d_expert=16, shared_experts=1,
    routed_scale=2.826, norm_eps=1e-5)


def _data(mesh, cfg, batch=4, seq=32, seed=0):
    rng = np.random.RandomState(seed)
    sh = NamedSharding(mesh, P("dp", "sp"))
    return tuple(jax.device_put(jnp.asarray(
        rng.randint(0, cfg.vocab, (batch, seq)), jnp.int32), sh)
        for _ in range(2))


def _loss(cfg, params=None, axes=None, seed=0):
    """The global loss of one batch on a mesh of ``axes``."""
    from jax import lax, shard_map

    axes = {"dp": 1, "pp": 1, "tp": 1, "sp": 1, **(axes or {})}
    mesh = make_mesh(**axes, devices=jax.devices()[:int(np.prod(
        list(axes.values())))])
    if params is None:
        params = init_params(np.random.RandomState(seed), cfg)
    tokens, targets = _data(mesh, cfg)
    fn = shard_map(
        lambda p, a, b: lax.psum(loss_and_routing(p, a, b, cfg)[0],
                                 ("dp", "sp")),
        mesh=mesh, check_vma=False,
        in_specs=(param_specs(cfg), P("dp", "sp"), P("dp", "sp")),
        out_specs=P())
    return float(jax.jit(fn)(params, tokens, targets))


def test_the_pattern_decides_the_stacks():
    """A row a layer of the kind in each kind's stack, a gain after the
    sub-layer in every stack, the embedding drawn at 1 / its multiplier;
    every leaf has a spec."""
    assert CFG.n_layers == 6 and CFG.n_expert_layers == 2
    params = init_params(np.random.RandomState(0), CFG, ep=2)
    assert set(params) == {"embed", "ln_f", "head", "swa", "gattn", "dense",
                           "moe"}
    gated = {"ln", "ln_post", "wq", "wk", "wv", "wg", "wo", "q_norm",
             "k_norm"}
    assert set(params["swa"]) == set(params["gattn"]) == gated
    assert params["swa"]["wq"].shape == (2, 32, 32)
    assert params["swa"]["wk"].shape == (2, 32, 16)
    assert params["swa"]["wg"].shape == (2, 32, 32)
    assert params["gattn"]["q_norm"].shape == (1, 8)
    assert set(params["dense"]) == {"ln", "ln_post", "w_gate", "w_up",
                                    "w_down"}
    assert params["dense"]["w_up"].shape == (1, 32, 48)
    assert params["moe"]["ln_post"].shape == (2, 32)
    assert params["moe"]["experts"]["w_gate"].shape == (2, 2 * 4, 32, 16)
    assert float(jnp.std(params["embed"])) == pytest.approx(32 ** -0.5,
                                                            rel=0.1)
    assert jax.tree_util.tree_structure(params) \
        == jax.tree_util.tree_structure(
            param_specs(CFG), is_leaf=lambda s: isinstance(s, P))
    # without the statements the stacks are the older kinds' own
    plain = init_params(np.random.RandomState(0), dataclasses.replace(
        CFG, layer_pattern="*E", post_norm=False))
    assert "ln_post" not in plain["attn"] and "wg" not in plain["attn"]


@pytest.mark.parametrize("change,message", [
    (dict(layer_pattern="SXE"), r"layer_pattern holds 'M', '\*', 'E', 'S', "
                                r"'G', 'D', 'I': \['X'\]"),
    (dict(window=0), "'S' layers need window >= 1"),
    (dict(n_kv_heads=3), "no multiple"),
    (dict(layer_pattern="GE", n_kv_heads=3), "no multiple"),
    (dict(head_dim=7), "even head_dim"),
])
def test_configuration_checks(change, message):
    with pytest.raises(ValueError, match=message):
        dataclasses.replace(CFG, **change)


def test_the_kinds_come_from_one_table():
    assert set(blocks.STACK_OF) == set("M*ESGDI")
    assert TransformerConfig._check_pattern.__doc__.count("STACK_OF") == 1
    # a pattern of full layers alone needs no window
    dataclasses.replace(CFG, layer_pattern="GD", window=0)


@pytest.mark.parametrize("axes,pattern,message", [
    (dict(pp=2), "SDGE", "a layer pattern under pp > 1"),
    (dict(tp=2), "SE", "gated grouped-query attention under tp > 1"),
    (dict(sp=2), "SE", "gated grouped-query attention under sp > 1"),
    (dict(tp=2), "GE", "gated grouped-query attention under tp > 1"),
    (dict(sp=2), "GE", "gated grouped-query attention under sp > 1"),
    (dict(tp=2), "DE", "a pattern's dense FFN under tp > 1"),
])
def test_a_kind_refuses_the_axes_it_is_not_built_for(axes, pattern, message):
    """``NotImplementedError`` with the reason, at trace time."""
    cfg = dataclasses.replace(CFG, layer_pattern=pattern)
    mesh = make_mesh(**{"dp": 1, "pp": 1, "tp": 1, "sp": 1, **axes},
                     devices=jax.devices()[:2])
    step = make_train_step(cfg, mesh, optax.sgd(0.1))
    params = init_params(np.random.RandomState(0), cfg)
    tokens, targets = _data(mesh, cfg)
    with pytest.raises(NotImplementedError, match=message) as raised:
        step(params, optax.sgd(0.1).init(params), tokens, targets)
    assert "is not supported: " in str(raised.value)


def test_the_dense_ffn_and_the_post_norm_run_over_sp():
    """Both work on a token alone: a sequence split over two chips gives
    the loss of one chip."""
    cfg = dataclasses.replace(CFG, layer_pattern="DEDE")
    assert _loss(cfg, axes=dict(sp=2)) == pytest.approx(_loss(cfg), rel=1e-5)


def _with(path, change):
    """The parameters with the leaf at ``path`` changed."""
    params = init_params(np.random.RandomState(0), CFG)
    stack, name = path
    params[stack] = {**params[stack], name: change(params[stack][name])}
    return params


def test_each_statement_of_the_configuration_is_computed():
    """What the model file states, one at a time, against the loss of
    the whole: the window's length, the gate, the two per-head norms'
    gains, the gain after a sub-layer, the multiplier; and a window of
    at least the sequence is no window."""
    whole = _loss(CFG)
    for change in (dict(window=7), dict(window=9), dict(embed_scale=1.0),
                   dict(post_norm=False), dict(rope_theta=100.0)):
        params = init_params(np.random.RandomState(0), CFG)
        if not change.get("post_norm", True):
            params = {name: ({k: v for k, v in part.items()
                              if k != "ln_post"}
                             if isinstance(part, dict) else part)
                      for name, part in params.items()}
        assert abs(_loss(dataclasses.replace(CFG, **change), params=params)
                   - whole) > 1e-4, change
    for path in (("swa", "wg"), ("gattn", "wg"), ("swa", "q_norm"),
                 ("gattn", "k_norm"), ("dense", "ln_post"),
                 ("moe", "ln_post"), ("swa", "ln_post")):
        assert abs(_loss(CFG, params=_with(path, lambda a: 0.5 * a + 0.1))
                   - whole) > 1e-5, path
    assert _loss(dataclasses.replace(CFG, window=32)) \
        == _loss(dataclasses.replace(CFG, window=4096))
    # the gate is a sigmoid of h W_g: with W_g = 0 it is one half
    # everywhere, which the norm after the sub-layer takes out again
    halved = _loss(CFG, params=_with(("gattn", "wg"), lambda a: 0 * a))
    assert abs(halved - whole) > 1e-5


def test_full_layers_take_no_positions_and_window_layers_do():
    """Shift every position by a constant: rotary attention depends on
    differences alone, so nothing moves, but only where rotary is applied
    to q and k alike; a ``G`` layer reads no position at all."""
    h = jnp.asarray(np.random.RandomState(1).randn(2, 32, 32), jnp.float32)
    params = init_params(np.random.RandomState(0), CFG)
    lp = jax.tree_util.tree_map(lambda a: a[0], params["swa"])
    pos = jnp.arange(32)

    def run(sliding, positions):
        from jax import shard_map
        mesh = make_mesh(dp=1, pp=1, tp=1, sp=1, devices=jax.devices()[:1])
        return shard_map(
            lambda h: blocks.gated_gqa(CFG, lp, h, positions, sliding),
            mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False)(h)

    np.testing.assert_allclose(np.asarray(run(True, pos)),
                               np.asarray(run(True, pos + 100)),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(run(False, pos)),
                                  np.asarray(run(False, pos + 100)))
    assert np.abs(np.asarray(run(True, pos) - run(False, pos))).max() > 1e-3


def test_rotary_in_the_half_rotation_layout():
    """Pairs ``(x[i], x[i + d/2])`` turned by position x theta^(-2i/d):
    the interleaved layout under a permutation of the columns."""
    x = jnp.asarray(np.random.RandomState(2).randn(1, 5, 2, 8), jnp.float32)
    pos = jnp.asarray([0, 1, 2, 7, 30])
    got = np.asarray(blocks.rotary(x, pos, 10000.0, halves=True))
    order = np.asarray([0, 4, 1, 5, 2, 6, 3, 7])       # halves -> pairs
    paired = np.asarray(blocks.rotary(x[..., order], pos, 10000.0))
    np.testing.assert_allclose(got[..., order], paired, rtol=1e-6, atol=1e-6)
    angle = 30 * 10000.0 ** (-2 / 8)
    np.testing.assert_allclose(
        got[0, 4, 0, 1], x[0, 4, 0, 1] * np.cos(angle)
        - x[0, 4, 0, 5] * np.sin(angle), rtol=1e-5)
    np.testing.assert_array_equal(got[0, 0], np.asarray(x[0, 0]))


def test_trains_over_dp_with_the_experts_shared_out():
    """dp 2: the batch split, each rank holding 4 of a layer's 8
    experts; every sub-layer recomputed, an expert layer keeping what it
    gave its post-norm.  The loss falls and the expert layers report
    the pairs sent to each of all their experts."""
    cfg = dataclasses.replace(CFG, remat=True)
    mesh = make_mesh(dp=2, pp=1, tp=1, sp=1, devices=jax.devices()[:2])
    params = shard_params(init_params(np.random.RandomState(0), cfg, ep=2),
                          cfg, mesh)
    opt = optax.adam(1e-2)
    state = opt.init(params)
    step = make_train_step(cfg, mesh, opt)
    tokens, targets = _data(mesh, cfg)
    losses = []
    for _ in range(6):
        params, state, loss = step(params, state, tokens, targets)
        losses.append(float(loss))
    assert losses[-1] < losses[0] and all(np.isfinite(losses))
    # recomputed or not: the same loss and the same gradients
    plain = dataclasses.replace(cfg, remat=False)
    assert _loss(cfg) == pytest.approx(_loss(plain), rel=1e-6)


def test_attention_records_at_the_benchmarks_sizes():
    """One ``hvd_attn_window`` record an attention layer: at 16,384 in
    1024 x 1024 tiles a window of 2,048 leaves 45 of 256 tile pairs
    live and masks 30, and the forward call walks a band of 3 tiles a
    row, 48 steps for 256; the backward kernels' tiles are cut to 512
    x 512 under it (150 live, 60 masked, a band of 5, 160 steps for
    1,024).  Without a window 136 and 16 of 256, every pair walked,
    either pass."""
    from horovod_tpu.runtime import flight

    cfg = TransformerConfig(
        vocab=64, d_model=32, n_heads=32, head_dim=128, n_kv_heads=4,
        max_seq=16384, tied_head=False, layer_pattern="SDSEGE", window=2048,
        n_experts=8, experts_held=4, experts_per_token=2, d_expert=16)
    records = record_attention(cfg, batch=1)
    assert [(r["layer"], r["layer_kind"], r["window"]) for r in records] \
        == [(0, "S", 2048), (2, "S", 2048), (4, "G", 0)]
    keys = ("block_q", "block_k", "grid", "band", "live", "masked")
    for r in records:
        assert r["seq"] == 16384
        forward = tuple(r[k] for k in keys)
        backward = tuple(r["bwd_" + k] for k in keys)
        if r["window"]:
            assert forward == (1024, 1024, 48, 3, 45, 30)
            assert backward == (512, 512, 160, 5, 150, 60)
        else:
            assert forward == backward == (1024, 1024, 256, 0, 136, 16)
    written = [e for e in flight.recorder().snapshot()
               if e["kind"] == "hvd_attn_window"]
    assert len(written) >= 3 and written[-1]["live"] == 136
    assert written[-3]["grid"] == 48 and written[-3]["bwd_grid"] == 160
