"""The indexer's selection as one Pallas kernel (``ops/pallas_index``),
interpreted on the CPU: its packed words against the XLA form's
(``blocks.select_keys`` on the CPU) bit for bit — random inputs at two
tilings, short rows and a block wholly below ``topk``, two sequences,
whole-number inputs whose rows tie at their threshold — its threshold
against ``blocks._kth_largest``, its name by TPU lowering, which path
``blocks.index_impl`` picks, and the two fields the ``hvd_dsa_select``
record gains."""

import dataclasses
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from horovod_tpu.models import blocks
from horovod_tpu.models.transformer import TransformerConfig, record_selection
from horovod_tpu.ops import pallas_index
from horovod_tpu.ops.pallas_attention import unpack_keep

CFG = TransformerConfig(
    vocab=64, d_model=32, n_heads=4, head_dim=8, max_seq=256,
    dtype="float32", tied_head=False, layer_pattern="IE", n_kv_heads=2,
    index_heads=2, index_head_dim=8, index_topk=80, rope_sections=(1, 1, 2),
    n_experts=4, experts_held=4, experts_per_token=2, d_expert=16,
    router="softmax")


def _layer(cfg, batch, seq, seed=0, whole=False):
    """Indexer weights and a stream: normal, or small whole numbers."""
    rng = np.random.RandomState(seed)
    heads, size = cfg.index_heads, cfg.index_head_dim
    shapes = {"wq_idx": (32, heads * size), "wk_idx": (32, size),
              "ww_idx": (32, heads)}
    draw = ((lambda shape: rng.randint(-1, 2, shape)) if whole
            else (lambda shape: rng.randn(*shape)))
    lp = {name: jnp.asarray(draw(shape), jnp.float32)
          for name, shape in shapes.items()}
    return lp, jnp.asarray(draw((batch, seq, 32)), jnp.float32)


def _both(cfg, lp, h, block_q, block_k):
    """``(kernel's words, the XLA form's words)`` as numpy."""
    topk = min(cfg.index_topk, h.shape[1])

    def kernel(lp, h):
        q, k, w = blocks.index_inputs(cfg, lp, h)
        return pallas_index.index_select(jnp.swapaxes(q, 1, 2), k, w,
                                         topk=topk, block_q=block_q,
                                         block_k=block_k)

    xla = jax.jit(lambda lp, h: blocks.select_keys(cfg, lp, h))
    return np.asarray(jax.jit(kernel)(lp, h)), np.asarray(xla(lp, h))


@pytest.mark.parametrize("seq,batch,topk,block_q,block_k,dtype", [
    # a block wholly below topk, one astride it, two sequences
    (256, 2, 80, 64, 128, "float32"),
    # bf16 products, four heads of 16, K tiles of two lane tiles
    (512, 1, 64, 128, 256, "bfloat16"),
    # every row short: no score at all
    (256, 1, 300, 128, 128, "float32"),
])
def test_the_words_are_the_xla_forms(seq, batch, topk, block_q, block_k,
                                     dtype):
    """Bit for bit, and every row keeps ``min(t + 1, topk)`` keys."""
    cfg = dataclasses.replace(CFG, max_seq=seq, index_topk=topk, dtype=dtype)
    if dtype == "bfloat16":
        cfg = dataclasses.replace(cfg, index_heads=4, index_head_dim=16)
    lp, h = _layer(cfg, batch, seq, seed=seq + topk)
    got, want = _both(cfg, lp, h, block_q, block_k)
    assert got.shape == want.shape == (batch, seq, 128)
    assert (got == want).all()
    kept = np.asarray(unpack_keep(jnp.asarray(got), seq)).sum(-1)
    assert (kept == np.minimum(np.arange(seq) + 1, topk)).all()


def test_a_tie_goes_to_the_lower_key_as_in_the_xla_form():
    """``test_dsa_pattern``'s whole-number inputs (an indexer of 4 heads
    of 16, every score a whole number of eighths) at 256 tokens: rows
    tie at their threshold, zeros of either sign among the scores, and
    the kernel keeps the same first keys of the level ones."""
    cfg = dataclasses.replace(CFG, index_heads=4, index_head_dim=16,
                              index_topk=16)
    lp, h = _layer(cfg, 2, 256, seed=3, whole=True)
    q, k, w = blocks.index_inputs(cfg, lp, h)
    terms = np.asarray(w[..., None] * jax.nn.relu(
        jnp.einsum("bqjd,bkd->bqjk", q, k)))
    assert np.signbit(terms[terms == 0]).any()
    assert (~np.signbit(terms[terms == 0])).any()
    scores = terms.sum(axis=2) * 0.25
    tied = [np.sum(np.sort(row[:t + 1])[::-1][15] == row[:t + 1]) > 1
            for batch in scores for t, row in enumerate(batch) if t >= 16]
    assert np.sum(tied) >= 100
    got, want = _both(cfg, lp, h, 64, 128)
    assert (got == want).all()


def _threshold_call(order, k, bk):
    """``pallas_index._threshold`` over (rows, n) int32 orders, in a
    kernel of its own: ``(threshold, reach)`` of each row."""
    rows, n = order.shape

    def kernel(order_ref, least_ref, reach_ref):
        least, reach = pallas_index._threshold(
            lambda c: order_ref[c], n // bk, k, rows, bk)
        least_ref[...] = least[:, :1]
        reach_ref[...] = reach[:, :1]

    tiles = jnp.swapaxes(order.reshape(rows, n // bk, bk), 0, 1)
    return pl.pallas_call(
        kernel, interpret=True,
        out_shape=[jax.ShapeDtypeStruct((rows, 1), jnp.int32),
                   jax.ShapeDtypeStruct((rows, 1), jnp.float32)])(tiles)


@pytest.mark.parametrize("k", [1, 7, 256])
def test_the_threshold_is_the_kth_largest(k):
    """The signed search over the order form gives ``_kth_largest``'s
    unsigned threshold with the top bit turned, duplicates, both ends of
    the range and a row of one value among them; ``reach`` counts the
    keys at or above it."""
    rng = np.random.RandomState(4)
    keys = rng.randint(0, 2 ** 32, (8, 256), dtype=np.uint64).astype(
        np.uint32)
    keys[0, :100] = keys[0, 0]
    keys[1] = 0
    keys[2] = 2 ** 32 - 1
    keys[3, ::2] = keys[3, 1]
    order = jnp.asarray((keys ^ np.uint32(1 << 31)).view(np.int32))
    least, reach = _threshold_call(order, k, 128)
    want = np.asarray(blocks._kth_largest(jnp.asarray(keys), k))
    got = np.asarray(least)[:, 0].view(np.uint32) ^ np.uint32(1 << 31)
    assert (got == want).all()
    assert (np.asarray(reach)[:, 0] == (keys >= want[:, None]).sum(-1)).all()
    assert np.asarray(reach)[1, 0] == 256         # every key reaches 0


def test_the_order_form_is_the_xla_forms_with_the_top_bit_turned():
    """``order_of`` on every class of float32: signed zeros, the
    infinities, subnormals, the largest and least finite numbers."""
    values = np.array([0.0, -0.0, np.inf, -np.inf, 1e-45, -1e-45, 1.0,
                       -1.0, 3.4e38, -3.4e38, 0.5, -2.0], np.float32)
    got = np.asarray(pallas_index.order_of(jnp.asarray(values)))
    # the XLA form's, on the same backend (which may flush subnormals)
    scores = jnp.asarray(values)
    bits = jax.lax.bitcast_convert_type(
        jnp.where(scores == 0.0, 0.0, scores), jnp.uint32)
    unsigned = np.asarray(jnp.where(bits >> 31 == 0,
                                    bits | jnp.uint32(1 << 31), ~bits))
    assert (got.view(np.uint32) == unsigned ^ np.uint32(1 << 31)).all()
    assert (np.argsort(got, kind="stable")
            == np.argsort(unsigned, kind="stable")).all()
    assert got[values == -np.inf][0] == pallas_index.HIDDEN


def test_select_keys_hands_the_kernel_its_operands(monkeypatch):
    """Where ``index_impl`` picks the kernel, ``select_keys`` gives the
    XLA form's words: the heads laid out as the kernel reads them, the
    default tiles."""
    cfg = dataclasses.replace(CFG, max_seq=512, index_topk=96)
    lp, h = _layer(cfg, 1, 512, seed=9)
    want = np.asarray(jax.jit(
        lambda lp, h: blocks.select_keys(cfg, lp, h))(lp, h))
    monkeypatch.setattr(blocks, "index_impl", lambda cfg, lc: "kernel")
    got = np.asarray(jax.jit(
        lambda lp, h: blocks.select_keys(cfg, lp, h))(lp, h))
    assert (got == want).all()


def test_the_kernel_is_named_by_tpu_lowering():
    """Lowered for a TPU (no chip, no TPU library): one Mosaic call,
    ``hvd_index_select`` — an ``hvd_*`` name, and not a flash kernel's."""
    q = jax.ShapeDtypeStruct((1, 16, 1024, 64), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((1, 1024, 64), jnp.bfloat16)
    w = jax.ShapeDtypeStruct((1, 1024, 16), jnp.float32)
    text = jax.jit(lambda q, k, w: pallas_index.index_select(
        q, k, w, topk=256, interpret=False)).trace(q, k, w).lower(
        lowering_platforms=("tpu",)).as_text(debug_info=True)
    assert re.findall(r'kernel_name = "(\w+)"', text) == ["hvd_index_select"]


KEYE = TransformerConfig(
    vocab=64, d_model=2048, n_heads=32, head_dim=128, n_kv_heads=4,
    max_seq=16384, tied_head=False, layer_pattern="I", index_heads=16,
    index_head_dim=64, index_topk=2048, rope_sections=(16, 24, 24))


def test_the_path_by_what_the_program_can_observe(monkeypatch):
    """The XLA form on the CPU, for a chunk the kernel cannot tile and
    where ``attn_impl`` forces XLA; the kernel on a TPU otherwise."""
    assert blocks.index_impl(KEYE, 16384) == "xla"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert blocks.index_impl(KEYE, 16384) == "kernel"
    assert blocks.index_impl(dataclasses.replace(KEYE, attn_impl="pallas"),
                             16384) == "kernel"
    assert blocks.index_impl(dataclasses.replace(KEYE, attn_impl="xla"),
                             16384) == "xla"
    for lc in (64, 384, 16384 + 256):
        assert blocks.index_impl(KEYE, lc) == "xla", lc


def test_the_scored_pairs():
    """A block scores its rows against the key tiles up to its last row,
    and a block below ``topk`` scores nothing: 16,384 tokens, top 2,048
    in 256 x 512 tiles."""
    assert pallas_index.scored_pairs(16384, 2048) == 256 * 512 * sum(
        -(-(256 * (i + 1)) // 512) for i in range(8, 64)) == 135_790_592
    assert pallas_index.scored_pairs(1024, 2048) == 0
    assert pallas_index.scored_pairs(1024, 1, 256, 1024) == 4 * 256 * 1024


def _words(batch, seq):
    rows = np.minimum(np.arange(seq) + 1, 2048)
    counts = np.clip(rows[:, None] - 32 * np.arange(512)[None, :], 0, 32)
    words = (np.left_shift(np.uint64(1), counts.astype(np.uint64))
             - 1).astype(np.uint32).view(np.int32)
    return np.repeat(words[None, None, :seq], batch, axis=1)


def test_the_records_say_which_path_selected(monkeypatch):
    """``index_impl`` and ``index_pairs`` in the ``hvd_dsa_select``
    record: the XLA form scores every key of every row, ``seq²`` a
    sequence; the kernel the tiles up to each block's last row."""
    record, = record_selection(KEYE, _words(2, 16384))
    assert record["index_impl"] == "xla"
    assert record["index_pairs"] == 2 * 16384 ** 2
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    record, = record_selection(KEYE, _words(2, 16384))
    assert record["index_impl"] == "kernel"
    assert record["index_pairs"] == 2 * 135_790_592


def test_the_docs_name_the_kernel_and_the_records_fields():
    """``docs/perf.md`` says where the kernel's name is set and what the
    record's two fields hold."""
    import os

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(repo, "docs", "perf.md"), encoding="utf-8") as f:
        text = f.read()
    for name in ("hvd_index_select", "ops/pallas_index.py", "index_impl",
                 "index_pairs", "blocks.index_impl"):
        assert f"`{name}`" in text, name
