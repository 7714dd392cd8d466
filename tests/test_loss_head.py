"""The loss head's own rule (``transformer._table_nll``) against the
formulation it replaced, kept here as the reference: f32 logits,
``log_softmax``, the targets' gather, differentiated by JAX's rules.
The same negative log likelihood and the same gradients to the stream,
the final norm's gain and the table, tied and untied, at GPT-2's
vocabulary and at a multiple of 128, plain and under
``jax.checkpoint``; and ``loss_fn`` with an MTP head on a toy latent
configuration gives the loss and the gradient norm it gave with that
reference in the head's place.  On the CPU: what the chip's compiler
makes of the rule is ``tests/test_loss_head_aot.py``'s.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from horovod_tpu.models import transformer
from horovod_tpu.models.transformer import TransformerConfig

BATCH, SEQ, WIDTH = 2, 8, 32
# rel. l2 of a gradient against the reference's.  float32 rounds
# nothing; bfloat16 is held to what the stack's other bfloat16
# comparison allows all leaves together (test_transformer._REMAT_APART)
APART = {"float32": 1e-6, "bfloat16": 2e-2}


def reference_head_nll(cfg, x, gain, table, targets):
    """``transformer._head_nll`` as it stood before PR 39."""
    cd = cfg.compute_dtype
    x = transformer._rmsnorm(x, gain, cfg.norm_eps)
    logits = (x.astype(cd) @ (table.astype(cd).T if cfg.tied_head
                              else table.astype(cd))).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]


def _head_case(dtype, tied, vocab, seed=0):
    cfg = TransformerConfig(vocab=vocab, d_model=WIDTH, n_heads=4,
                            head_dim=8, n_layers=1, d_ff=64, max_seq=SEQ,
                            dtype=dtype, tied_head=tied)
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(BATCH, SEQ, WIDTH), cfg.compute_dtype)
    gain = jnp.asarray(1 + 0.1 * rng.randn(WIDTH), jnp.float32)
    table = jnp.asarray(
        rng.randn(*((vocab, WIDTH) if tied else (WIDTH, vocab)))
        * (0.3 if tied else 0.3 / np.sqrt(WIDTH)), jnp.float32)
    targets = rng.randint(0, vocab, (BATCH, SEQ))
    targets[0, 0], targets[0, 1] = 0, vocab - 1   # the table's ends
    # an uneven cotangent a row, as the MTP head's mask gives
    weight = jnp.asarray(rng.rand(BATCH, SEQ), jnp.float32)
    return cfg, (x, gain, table), jnp.asarray(targets, jnp.int32), weight


def _nll_and_grads(head, cfg, operands, targets, weight):
    nll = jax.jit(lambda *ops: head(cfg, *ops, targets))(*operands)
    grads = jax.jit(jax.grad(
        lambda *ops: jnp.sum(head(cfg, *ops, targets) * weight),
        argnums=(0, 1, 2)))(*operands)
    return nll, grads


def _apart(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return np.linalg.norm(a - b) / np.linalg.norm(b)


@pytest.mark.parametrize("replayed", [False, True],
                         ids=["plain", "checkpoint"])
@pytest.mark.parametrize("vocab", [50257, 1024])
@pytest.mark.parametrize("tied", [True, False], ids=["tied", "untied"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_rule_gives_the_reference_nll_and_gradients(dtype, tied, vocab,
                                                        replayed):
    cfg, operands, targets, weight = _head_case(dtype, tied, vocab)
    head = (transformer._remat_head_nll if replayed
            else transformer._head_nll)
    nll, grads = _nll_and_grads(head, cfg, operands, targets, weight)
    want, want_grads = _nll_and_grads(reference_head_nll, cfg, operands,
                                      targets, weight)
    assert nll.dtype == jnp.float32 and nll.shape == (BATCH, SEQ)
    # the logits are the same numbers on both sides, so the loss differs
    # by f32 rounding of a sum over the row in either type
    np.testing.assert_allclose(nll, want, rtol=1e-6, atol=2e-6)
    for got, ref, name in zip(grads, want_grads, ("stream", "gain", "table")):
        assert got.dtype == ref.dtype and got.shape == ref.shape, name
        assert _apart(got, ref) <= APART[dtype], name


@pytest.mark.parametrize("tied", [True, False], ids=["tied", "untied"])
def test_a_target_at_either_end_of_the_table(tied):
    """Vocabulary row 0 and the last one (50,256: the row beside the
    padding of a 128-lane tile) as every position's target: the picked
    logit is that row's, and the table's gradient, that row's own with
    it, is the reference's."""
    cfg, (x, gain, table), _, _ = _head_case("float32", tied, 50257)
    for row in (0, cfg.vocab - 1):
        targets = jnp.full((BATCH, SEQ), row, jnp.int32)
        nll = transformer._head_nll(cfg, x, gain, table, targets)
        np.testing.assert_allclose(
            nll, reference_head_nll(cfg, x, gain, table, targets),
            rtol=1e-6, atol=2e-6)
        d_nll, d_ref = (jax.grad(lambda t: jnp.sum(
            head(cfg, x, gain, t, targets)))(table)
            for head in (transformer._head_nll, reference_head_nll))
        assert _apart(d_nll, d_ref) <= 1e-6
        on_row = d_nll[row] if tied else d_nll[:, row]
        assert _apart(on_row, d_ref[row] if tied else d_ref[:, row]) <= 1e-6


def test_the_gradient_gathers_and_scatters_nothing():
    """The jaxpr of the head's gradient holds the three products and no
    gather out of the logits, so no scatter into their shape either: the
    target's logit is a compare-and-sum inside a reduction, its
    transpose a compare inside the products' operand."""
    from test_pallas_attention import _primitives

    cfg, operands, targets, weight = _head_case("bfloat16", True, 1024)
    names = _primitives(jax.make_jaxpr(jax.grad(
        lambda *ops: jnp.sum(transformer._head_nll(cfg, *ops, targets)
                             * weight), argnums=(0, 1, 2)))(*operands).jaxpr)
    assert not {"gather", "scatter", "scatter-add"} & set(names)
    assert names.count("dot_general") == 3


@pytest.mark.parametrize("remat", [True, False], ids=["remat", "kept"])
def test_loss_fn_with_an_mtp_head_gives_the_parents_loss_and_norm(
        remat, monkeypatch):
    """The main head and the MTP module's go through one rule: with the
    reference put in its place (``loss_fn`` finds both names in the
    module when it runs) the loss and the global gradient norm are the
    same to float32 rounding."""
    from test_transformer import LATENT, _loss_and_grads

    cfg = dataclasses.replace(LATENT, remat=remat)
    loss, grads = _loss_and_grads(cfg)[2:]
    monkeypatch.setattr(transformer, "_head_nll", reference_head_nll)
    monkeypatch.setattr(transformer, "_remat_head_nll", jax.checkpoint(
        reference_head_nll, static_argnums=(0,)))
    want, want_grads = _loss_and_grads(cfg)[2:]

    def norm(tree):
        return float(np.sqrt(sum(np.sum(np.square(np.asarray(g)))
                                 for g in jax.tree_util.tree_leaves(tree))))

    assert abs(float(loss) - float(want)) <= 1e-6 * abs(float(want))
    assert abs(norm(grads) - norm(want_grads)) <= 1e-6 * norm(want_grads)
    for (path, got), ref in zip(
            jax.tree_util.tree_leaves_with_path(grads),
            jax.tree_util.tree_leaves(want_grads)):
        ref = np.asarray(ref)
        assert (np.linalg.norm(np.asarray(got) - ref)
                <= 1e-5 * max(np.linalg.norm(ref), 1e-6)), path
