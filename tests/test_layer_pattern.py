"""A ``layer_pattern``: each layer ONE pre-normed sub-layer with one
residual — a Mamba-2 mixer, grouped-query attention without positions,
or the expert layer alone — its weights stacked per kind.  The
configuration's checks, the mesh axes each kind refuses, training on a
``dp`` mesh with the experts shared out, and that a configuration
without a pattern imports nothing of it."""

import dataclasses
import os
import subprocess
import sys

import numpy as np
import optax
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from horovod_tpu.models.transformer import (TransformerConfig, init_params,
                                            loss_and_routing, make_train_step,
                                            param_specs, shard_params)
from horovod_tpu.parallel.mesh import make_mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = TransformerConfig(
    vocab=64, d_model=32, n_heads=4, head_dim=8, max_seq=32, dtype="float32",
    tied_head=False, layer_pattern="MEM*E", n_kv_heads=2, ssm_heads=4,
    ssm_head_dim=8, ssm_groups=2, ssm_state=16, ssm_chunk=8, n_experts=8,
    experts_held=4, experts_per_token=2, d_expert=16, shared_experts=2,
    routed_scale=2.5, expert_form="relu2", norm_eps=1e-5)


def _data(mesh, cfg, batch=4, seq=32, seed=0):
    rng = np.random.RandomState(seed)
    sh = NamedSharding(mesh, P("dp", "sp"))
    return tuple(jax.device_put(jnp.asarray(
        rng.randint(0, cfg.vocab, (batch, seq)), jnp.int32), sh)
        for _ in range(2))


def test_the_pattern_decides_the_depth_and_the_stacks():
    """``n_layers`` is the pattern's length; a row a layer of the kind
    in each kind's stack; two-matrix experts; every leaf has a spec."""
    assert CFG.layer_pattern == ("M", "E", "M", "*", "E")
    assert CFG.n_layers == 5 and CFG.n_expert_layers == 2
    assert not CFG.gpt2_block and CFG.n_dense == 0
    params = init_params(np.random.RandomState(0), CFG, ep=2)
    assert params["ssm"]["w_in"].shape == (2, 32, 32 + 96 + 4)
    assert params["attn"]["wk"].shape == (1, 32, 2 * 8)
    assert set(params["moe"]["experts"]) == {"w_up", "w_down"}
    assert params["moe"]["experts"]["w_up"].shape == (2, 2 * 4, 32, 16)
    assert set(params["moe"]["shared"]) == {"w_up", "w_down"}
    assert "pos" not in params and "layers" not in params
    # the time step's bias is what softplus maps onto [0.001, 0.1], the
    # decay rate's logarithm that of [1, 16]
    dt = np.asarray(jax.nn.softplus(params["ssm"]["dt_bias"]))
    assert (dt > 0.9e-3).all() and (dt < 0.11).all()
    rate = np.exp(np.asarray(params["ssm"]["a_log"]))
    assert (rate >= 1).all() and (rate <= 16).all()
    assert jax.tree_util.tree_structure(params) \
        == jax.tree_util.tree_structure(
            param_specs(CFG), is_leaf=lambda s: isinstance(s, P))


def test_rescale_depth_shrinks_every_sub_layers_out_projection():
    """Drawn for a stream of ``rescale_depth`` layers: the mixer's, the
    attention's and both kinds of expert's out-projections are the
    fan-in scaled draw divided by its square root; nothing else moves."""
    plain = init_params(np.random.RandomState(3), CFG, ep=2)
    deep = init_params(np.random.RandomState(3),
                       dataclasses.replace(CFG, rescale_depth=16), ep=2)
    shrunk = {("ssm", "w_out"), ("attn", "wo"), ("moe", "experts", "w_down"),
              ("moe", "shared", "w_down")}
    for path, leaf in jax.tree_util.tree_leaves_with_path(plain):
        keys = tuple(k.key for k in path)
        other = deep
        for k in keys:
            other = other[k]
        np.testing.assert_allclose(
            np.asarray(other), np.asarray(leaf) * (0.25 if keys in shrunk
                                                   else 1.0), rtol=1e-6)


@pytest.mark.parametrize("change,message", [
    (dict(layer_pattern="MXE"), "layer_pattern holds"),
    (dict(tied_head=True), "tied_head=False"),
    (dict(n_experts=0), "'E' layers need"),
    (dict(n_kv_heads=3), "no multiple"),
    (dict(ssm_groups=3), "'M' layers need"),
    (dict(expert_form="gelu"), "expert_form"),
])
def test_configuration_checks(change, message):
    with pytest.raises(ValueError, match=message):
        dataclasses.replace(CFG, **change)


def test_the_uniform_stack_still_asks_for_swiglu_dense_layers():
    """The check that read "expert layers are SwiGLU" is now one of the
    expert's form; the uniform stack's own rule is said as what it is."""
    with pytest.raises(ValueError, match="uniform stack"):
        TransformerConfig(n_experts=4, experts_held=4, experts_per_token=2,
                          d_expert=8)
    cfg = TransformerConfig(vocab=64, d_model=16, n_heads=2, head_dim=8,
                            n_layers=2, mlp="swiglu", n_experts=4,
                            experts_held=4, experts_per_token=2, d_expert=8,
                            shared_experts=1, n_dense_layers=1,
                            expert_form="relu2")
    moe = init_params(np.random.RandomState(0), cfg)["moe"]
    assert set(moe["experts"]) == set(moe["shared"]) == {"w_up", "w_down"}


@pytest.mark.parametrize("axes,pattern,message", [
    (dict(pp=2), "MEM*E", "a layer pattern under pp > 1"),
    (dict(tp=2), "ME", "a state-space layer under tp > 1"),
    (dict(sp=2), "ME", "a state-space layer under sp > 1"),
    (dict(tp=2), "*E", "grouped-query attention under tp > 1"),
    (dict(sp=2), "*E", "grouped-query attention under sp > 1"),
])
def test_a_kind_refuses_the_axes_it_is_not_built_for(axes, pattern, message):
    """``NotImplementedError`` with the reason, at trace time; an expert
    layer alone refuses nothing but ``pp``."""
    cfg = dataclasses.replace(CFG, layer_pattern=pattern)
    mesh = make_mesh(**{"dp": 1, "pp": 1, "tp": 1, "sp": 1, **axes},
                     devices=jax.devices()[:2])
    step = make_train_step(cfg, mesh, optax.sgd(0.1))
    params = init_params(np.random.RandomState(0), cfg)
    tokens, targets = _data(mesh, cfg)
    with pytest.raises(NotImplementedError, match=message) as raised:
        step(params, optax.sgd(0.1).init(params), tokens, targets)
    assert "is not supported: " in str(raised.value)


def test_trains_over_dp_with_the_experts_shared_out():
    """dp 2: the batch split, each rank holding 4 of a layer's 8
    experts; every layer recomputed.  The loss falls and the layers
    report: the pairs sent to each of an expert layer's experts, alike
    on both ranks, the least log-decay of each state-space layer."""
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
    assert np.isfinite(losses).all() and losses[-1] < losses[0] - 0.1, losses

    specs = param_specs(cfg)
    reports = jax.jit(jax.shard_map(
        lambda p, a, b: loss_and_routing(p, a, b, cfg)[1], mesh=mesh,
        check_vma=False, in_specs=(specs, P("dp", "sp"), P("dp", "sp")),
        out_specs={"loads": P(), "least_log_decay": P("dp")}))(
        params, tokens, targets)
    pairs = np.asarray(reports["loads"])
    assert pairs.shape == (2, 8)
    # every token's 2 choices fall on one of the 8 experts: all computed
    # (a rank routes the gathered tokens of both ranks)
    assert (pairs.sum(axis=1) == 4 * 32 * 2).all()
    assert np.asarray(reports["least_log_decay"]).shape == (2 * 2,)
    assert (np.asarray(reports["least_log_decay"]) < 0).all()


def test_a_configuration_without_a_pattern_imports_nothing_of_it():
    """Importing the transformer, and building and tracing the GPT-2
    block, imports neither ``models/blocks.py`` nor the scan's module."""
    code = (
        "import sys, numpy as np, jax\n"
        "from horovod_tpu.models import transformer as T\n"
        "cfg = T.TransformerConfig(vocab=64, d_model=16, n_heads=2, "
        "head_dim=8, n_layers=1, d_ff=32, max_seq=8)\n"
        "T.init_params(np.random.RandomState(0), cfg); T.param_specs(cfg)\n"
        "new = [m for m in sys.modules if m.endswith(('ops.ssm_scan', "
        "'models.blocks'))]\n"
        "assert not new, new\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""))
    proc = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]


@pytest.mark.parametrize("pattern", ["MEM*E", "SDSEGE"])
def test_transformer_lm_example_takes_a_layer_pattern(pattern):
    """The user's entry point outside the harness: ``--layer-pattern``
    with ``--experts`` on a dp mesh; the hybrid kinds, and the window /
    full attention ones with a norm after every sub-layer."""
    env = dict(os.environ, HOROVOD_PLATFORM="cpu", HOROVOD_SIZE="1",
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""))
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "examples", "transformer_lm.py"),
         "--dp", "2", "--steps", "2", "--d-model", "32", "--seq", "16",
         "--batch", "4", "--layer-pattern", pattern, "--experts", "2"],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, (proc.stdout, proc.stderr[-3000:])
    assert "loss" in proc.stdout.lower()
