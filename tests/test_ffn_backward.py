"""SwiGLU's backward rule (``moe.swiglu``, a ``jax.custom_vjp``) against
the formula it replaced, differentiated by JAX's own rules: the rule
rounds ``hidden``, ``d_gate`` and ``d_up`` to the compute dtype once, in
one elementwise pass, where the products at default precision rounded
their f32 operands on the chip.  At small widths, with leading batch
dimensions: the same cotangents as the plain formula rounded at those
places; within bfloat16's tolerance of the all-f32 formula, and no
farther from it than JAX's rule; equal to JAX's rule where the compute
dtype is float32; the same under ``blocks._remat_layer``'s checkpoint
policy and in the gradient of a toy pattern with a dense sub-layer and a
shared expert.  On the CPU: what the chip's compiler makes of the rule is
``tests/test_ffn_backward_aot.py``'s.
"""

import dataclasses
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from horovod_tpu.models import blocks
from horovod_tpu.models.transformer import TransformerConfig
from horovod_tpu.parallel import moe
from horovod_tpu.parallel.mesh import make_mesh
from horovod_tpu.parallel.ring_attention import KEPT_NAMES

D, F = 32, 48
LEADS = [(16,), (2, 8), (2, 3, 4)]
NAMES = ("x", "w_gate", "w_up", "w_down")


def plain(x, w):
    """``moe.swiglu``'s forward, differentiated by JAX's own rules: the
    formula as it stood before the rule."""
    gate = jnp.dot(x, w["w_gate"], preferred_element_type=jnp.float32)
    up = jnp.dot(x, w["w_up"], preferred_element_type=jnp.float32)
    hidden = (jax.nn.silu(gate) * up).astype(x.dtype)
    return jnp.dot(hidden, w["w_down"], preferred_element_type=jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _cotangent_rounded(v, dtype):
    """``v``; its cotangent rounded to ``dtype``."""
    return v


_cotangent_rounded.defvjp(
    lambda v, dtype: (v, None),
    lambda dtype, _, g: (g.astype(dtype).astype(g.dtype),))


def rounded(x, w, dtype):
    """The plain formula in float32 on operands that hold ``dtype``'s
    values, rounded to ``dtype`` where the rule rounds: ``hidden`` (and
    so its cotangent) and the cotangents of ``gate`` and ``up``."""
    gate = _cotangent_rounded(x @ w["w_gate"], dtype)
    up = _cotangent_rounded(x @ w["w_up"], dtype)
    hidden = (jax.nn.silu(gate) * up).astype(dtype).astype(jnp.float32)
    return hidden @ w["w_down"]


def _case(lead, dtype, seed=0):
    """``(x, w, g)``: the input and the weights in ``dtype``, drawn at
    the scales of ``blocks._init_swiglu``, and an f32 cotangent."""
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(*lead, D), dtype)
    w = {"w_gate": rng.randn(D, F) * D ** -0.5,
         "w_up": rng.randn(D, F) * D ** -0.5,
         "w_down": rng.randn(F, D) * F ** -0.5}
    w = {name: jnp.asarray(a, dtype) for name, a in w.items()}
    g = jnp.asarray(rng.randn(*lead, D), jnp.float32)
    return x, w, g


def _cotangents(f, x, w, g):
    """``(out, {name: cotangent})`` of ``f`` at ``(x, w)`` under ``g``."""
    out, vjp = jax.vjp(f, x, w)
    dx, dw = jax.jit(vjp)(g)
    return out, {"x": dx, **dw}


def _apart(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return np.linalg.norm(a - b) / np.linalg.norm(b)


@pytest.mark.parametrize("lead", LEADS, ids=str)
def test_the_rule_is_the_formula_rounded_where_it_rounds(lead):
    """The same numbers as the plain formula rounded at the rule's
    places, to f32 accumulation noise: a cotangent is rounded to
    bfloat16 once, and where the noise straddles a rounding boundary the
    two fall one unit in the last place apart."""
    x, w, g = _case(lead, jnp.bfloat16)
    out, got = _cotangents(moe.swiglu, x, w, g)
    wide = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), (x, w))
    want_out, want = _cotangents(
        lambda x_, w_: rounded(x_, w_, jnp.bfloat16), *wide, g)
    np.testing.assert_array_equal(out, plain(x, w))
    np.testing.assert_allclose(out, want_out, rtol=1e-5, atol=1e-6)
    for name in NAMES:
        assert got[name].dtype == jnp.bfloat16, name
        assert got[name].shape == want[name].shape, name
        ours = np.asarray(got[name], np.float32)
        theirs = np.asarray(want[name].astype(jnp.bfloat16), np.float32)
        # one bfloat16 unit in the last place of the larger
        ulp = 2.0 ** (np.floor(np.log2(np.maximum(
            np.abs(ours), np.abs(theirs)) + 1e-30)) - 7)
        assert np.all(np.abs(ours - theirs) <= ulp), name
        assert np.mean(ours == theirs) >= 0.99, name


@pytest.mark.parametrize("lead", LEADS, ids=str)
def test_within_bfloat16_of_the_float32_formula_as_jax_rule_is(lead):
    """Rounded at other places than JAX's rule rounds, to the same
    precision: each cotangent within bfloat16's tolerance of the all-f32
    formula's, and at most half as far again from it as JAX's rule on
    the same bfloat16 operands — whose products read their f32 operands
    whole on the CPU, where the chip's round them (measured here: 0.0023
    to 0.0032 against 0.0023 to 0.0030)."""
    x, w, g = _case(lead, jnp.bfloat16)
    got = _cotangents(moe.swiglu, x, w, g)[1]
    theirs = _cotangents(plain, x, w, g)[1]
    wide = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), (x, w))
    exact = _cotangents(plain, *wide, g)[1]
    for name in NAMES:
        ours_apart = _apart(got[name], exact[name])
        assert ours_apart <= 2e-2, (name, ours_apart)
        assert ours_apart <= 1.5 * _apart(theirs[name], exact[name]), name


@pytest.mark.parametrize("lead", LEADS, ids=str)
def test_float32_is_jax_rule(lead):
    """Where the compute dtype is float32 every rounding is the
    identity: the rule gives JAX's own cotangents."""
    x, w, g = _case(lead, jnp.float32)
    out, got = _cotangents(moe.swiglu, x, w, g)
    want_out, want = _cotangents(plain, x, w, g)
    np.testing.assert_allclose(out, want_out, rtol=1e-6, atol=1e-6)
    for name in NAMES:
        assert got[name].dtype == jnp.float32, name
        assert _apart(got[name], want[name]) <= 1e-6, name


def _vjp_jaxpr(f, x, w, g):
    return jax.make_jaxpr(lambda x_, w_, g_: jax.vjp(f, x_, w_)[1](g_))(
        x, w, g).jaxpr


def test_the_backward_pass_keeps_the_inputs_and_reads_rounded_operands():
    """The rule's jaxpr: the forward's three products, whose inputs and
    f32 ``gate`` and ``up`` it keeps, so that nothing is computed again
    where no checkpoint is; the backward's ``d_hidden`` and five more,
    which read what one barrier hands on: the three operands in the
    compute dtype."""
    from test_pallas_attention import _primitives

    x, w, g = _case((2, 8), jnp.bfloat16)
    fwd = _primitives(jax.make_jaxpr(moe.swiglu)(x, w).jaxpr)
    assert fwd.count("dot_general") == 3
    bwd = _vjp_jaxpr(moe.swiglu, x, w, g)
    names = _primitives(bwd)
    assert names.count("dot_general") == 3 + 6, names
    barriers = [eqn for eqn in _walk(bwd)
                if eqn.primitive.name == "optimization_barrier"]
    assert len(barriers) == 1
    assert [v.aval.dtype for v in barriers[0].outvars] == [jnp.bfloat16] * 3
    assert [v.aval.shape for v in barriers[0].outvars] == [(2, 8, F)] * 3


def _walk(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _walk(sub)


def test_a_checkpoints_replay_computes_gate_and_up_once():
    """Under ``blocks._remat_layer``'s policy, which names neither
    ``gate`` nor ``up``, the checkpoint's replay computes the two again
    and the rule computes nothing of its own: 3 forward, 2 replayed, 6
    backward, as JAX's rule ran there."""
    from test_pallas_attention import _primitives

    x, w, g = _case((2, 8), jnp.bfloat16)
    policy = jax.checkpoint_policies.save_only_these_names(
        *KEPT_NAMES, blocks.KEPT_EXPERT_OUT, blocks.KEPT_SELECTION)
    for f in (moe.swiglu, plain):
        names = _primitives(_vjp_jaxpr(jax.checkpoint(f, policy=policy),
                                       x, w, g))
        assert names.count("dot_general") == 3 + 2 + 6, (f, names)


@pytest.fixture
def plain_in_its_place(monkeypatch):
    """Puts :func:`plain` in the rule's place when called.  JAX keeps
    what it traced of a checkpointed layer by the layer's function, so
    its caches are emptied both ways: else the swap would not be seen,
    nor its undoing by the tests after."""
    def put():
        monkeypatch.setattr(moe, "swiglu", plain)
        jax.clear_caches()

    yield put
    monkeypatch.undo()
    jax.clear_caches()


def _assert_apart(got, want, limit, dtype):
    """Every leaf within ``limit`` of ``want``'s (rel. l2); in bfloat16
    some leaf apart at all, which shows that the plain formula ran."""
    leaves = list(zip(jax.tree_util.tree_leaves_with_path(got),
                      jax.tree_util.tree_leaves(want)))
    for (path, a), b in leaves:
        if np.linalg.norm(np.asarray(b, np.float32)):
            assert _apart(a, b) <= limit, path
    if dtype == "bfloat16":
        assert any(np.any(np.asarray(a) != np.asarray(b))
                   for (_, a), b in leaves)


CFG = TransformerConfig(
    vocab=64, d_model=D, n_heads=4, head_dim=8, max_seq=16,
    dtype="bfloat16", tied_head=False, layer_pattern="DE", d_ff=F,
    n_experts=8, experts_held=8, experts_per_token=2, d_expert=16,
    shared_experts=1, routed_scale=2.5, norm_eps=1e-5)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_under_the_layers_checkpoint_policy(dtype, plain_in_its_place):
    """A dense sub-layer through ``blocks._remat_layer``, whose replay
    keeps only the layer's input: the rule's gradients are the plain
    formula's there too, equal in float32, to bfloat16's tolerance."""
    cfg = dataclasses.replace(CFG, dtype=dtype, layer_pattern="D")
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(2, 8, D), jnp.float32)
    lp = {"ln": jnp.asarray(1 + 0.1 * rng.randn(D), jnp.float32),
          **_case((1,), jnp.float32, seed=4)[1]}
    ct = jnp.asarray(rng.randn(2, 8, D), jnp.float32)

    mesh = make_mesh(dp=1, pp=1, tp=1, sp=1, devices=jax.devices()[:1])

    def grads():
        # the sub-layer asks the mesh's axes whether it is split
        return jax.jit(shard_map(jax.grad(lambda x_, lp_: jnp.sum(
            blocks._remat_layer(cfg, "D", lp_, x_, None)[0] * ct),
            argnums=(0, 1)), mesh=mesh, check_vma=False,
            in_specs=(P(), P()), out_specs=P()))(x, lp)

    got = grads()
    plain_in_its_place()
    _assert_apart(got, grads(), 1e-6 if dtype == "float32" else 2e-2, dtype)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_a_pattern_with_a_dense_layer_and_a_shared_expert(
        dtype, plain_in_its_place):
    """``jax.grad`` of a toy pattern's loss, a dense sub-layer and an
    expert sub-layer with a shared SwiGLU expert, every sub-layer
    recomputed: the loss and every gradient as with the plain formula in
    the rule's place, equal in float32, to bfloat16's tolerance."""
    from test_transformer import _loss_and_grads

    cfg = dataclasses.replace(CFG, dtype=dtype, remat=True)
    loss, grads = _loss_and_grads(cfg)[2:]
    plain_in_its_place()
    want, want_grads = _loss_and_grads(cfg)[2:]
    assert abs(float(loss) - float(want)) <= 1e-6 * abs(float(want))
    _assert_apart(grads, want_grads, 1e-5 if dtype == "float32" else 2e-2,
                  dtype)
    shared = grads["moe"]["shared"]
    assert all(np.linalg.norm(np.asarray(a, np.float32)) > 0
               for a in jax.tree_util.tree_leaves(shared))
