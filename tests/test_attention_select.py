"""A selection as a third bound of attention, beside the causal one and
by data: query ``i`` sees key ``j`` iff ``j <= i`` and bit ``j`` of its
row of packed words is set.  The packed form; the three Pallas kernels
(interpret mode) and the XLA block step against a masked softmax written
from the mask itself, values and the three gradients position by
position — rows with fewer keys than the selection keeps, a selection
whose ties were broken to the lower key, one equal to the causal mask
(then the plain call), one that keeps keys a query cannot see, several
heads on one row of words, tiles in the second span of the words; the
refusals; the kernels' names."""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from horovod_tpu.ops import pallas_attention as pa
from horovod_tpu.parallel.ring_attention import (blockwise_attention,
                                                 reference_attention,
                                                 ring_attention,
                                                 xla_block_step)

B, H, L, D, TILE, TOPK = 2, 3, 256, 16, 128, 16


def _operands(seed, lq=L, lk=L, bh=B * H, d=D):
    rng = np.random.RandomState(seed)
    mk = lambda n: jnp.asarray(rng.randn(bh, n, d), jnp.float32) * 0.5
    return mk(lq), mk(lk), mk(lk), mk(lq) * 0.2


def _top_keys(scores, topk, q_offset=0):
    """(B, Lq, Lk) bool: the ``topk`` earlier keys of largest score, a
    tie to the lower key (a stable sort), every earlier key where they
    are fewer."""
    lq, lk = scores.shape[1:]
    causal = (q_offset + np.arange(lq))[:, None] >= np.arange(lk)[None, :]
    order = np.argsort(-np.where(causal, scores, -np.inf), axis=-1,
                       kind="stable")[..., :topk]
    mask = np.zeros(scores.shape, bool)
    np.put_along_axis(mask, order, True, axis=-1)
    return mask & causal


def _selection(case: str, seed: int = 0, lq=L, lk=L, q_offset=0):
    """(B, Lq, Lk) bool of one of the cases."""
    rng = np.random.RandomState(100 + seed)
    scores = rng.randn(B, lq, lk)
    causal = np.broadcast_to(
        (q_offset + np.arange(lq))[:, None] >= np.arange(lk)[None, :],
        scores.shape)
    if case == "top_keys":
        return _top_keys(scores, TOPK, q_offset)
    if case == "ties":
        # three values only: every row ties at its threshold
        return _top_keys(np.round(scores), TOPK, q_offset)
    if case == "causal_mask":
        return causal.copy()
    if case == "keeps_later_keys":
        # bits set past the diagonal: the causal bound still holds (a
        # query keeps itself, so that no row is empty)
        return (rng.rand(B, lq, lk) < 0.3) | np.eye(lq, lk, q_offset, bool)
    raise AssertionError(case)


CASES = ("top_keys", "ties", "causal_mask", "keeps_later_keys")


def _masked_softmax(q, k, v, mask, q_offset=0):
    """The golden model: every score, the mask as given and the causal
    bound from positions, a row that sees no key gives 0.  ``mask``:
    (B, Lq, Lk) for the BH / B heads that follow each other."""
    qpos = q_offset + jnp.arange(q.shape[1])[:, None]
    seen = jnp.repeat(jnp.asarray(mask), q.shape[0] // mask.shape[0],
                      axis=0) & (qpos >= jnp.arange(k.shape[1])[None, :])
    s = jnp.einsum("bqd,bkd->bqk", q, k) / (q.shape[-1] ** 0.5)
    s = jnp.where(seen, s, -jnp.inf)
    top = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.where(seen, jnp.exp(s - jnp.where(jnp.isfinite(top), top, 0.0)),
                  0.0)
    den = jnp.sum(p, axis=-1, keepdims=True)
    return jnp.einsum("bqk,bkd->bqd", p / jnp.where(den == 0, 1.0, den), v)


def _kernels(q, k, v, dout, keep, q_offset=0, bq=TILE, bk=TILE):
    """``(out, dq, dk, dv)`` of a one-step ring through the three
    kernels, as ``_ring_flash`` calls them."""
    tiles = dict(causal=True, block_q=bq, block_k=bk, interpret=True,
                 keep=keep)
    out, lse, _ = pa.flash_fwd_step(q, k, v, None, q_offset, 0, last=True,
                                    **tiles)
    delta = jnp.sum(dout * out, axis=-1)
    dq = pa.flash_bwd_dq(q, k, v, dout, lse, delta, q_offset, 0, **tiles)
    dk, dv = pa.flash_bwd_dkv(q, k, v, dout, lse, delta, q_offset, 0,
                              **tiles)
    return out, dq, dk, dv


def _golden(q, k, v, dout, mask, q_offset=0):
    out, vjp = jax.vjp(
        lambda q, k, v: _masked_softmax(q, k, v, mask, q_offset), q, k, v)
    return (out, *vjp(dout))


# ---------------------------------------------------------------------------
# The packed form
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("lk", [64, 256, 4096, 4100, 8192])
def test_packing_is_undone_by_unpacking(lk):
    """128 words a span of 4,096 keys, whatever is left of the last
    span padded with 0 bits."""
    mask = np.random.RandomState(lk).rand(2, 3, lk) < 0.4
    words = pa.pack_keep(jnp.asarray(mask))
    assert words.dtype == jnp.int32
    assert words.shape == (2, 3, 128 * -(-lk // 4096))
    assert (np.asarray(pa.unpack_keep(words, lk)) == mask).all()
    # a row's set bits are its kept keys: nothing past the last key
    assert int(jnp.sum(jax.lax.population_count(words))) == mask.sum()


def test_a_key_has_one_bit_of_one_word():
    """Key ``s`` is bit ``(s % 4096) // 128`` of word ``(s // 4096) * 128
    + s % 128``: the 8 bits a 1024-key tile takes of each word lie side
    by side."""
    for key in (0, 127, 128, 1023, 1024, 4095, 4096, 5000):
        mask = np.zeros((1, 8192), bool)
        mask[0, key] = True
        words = np.asarray(pa.pack_keep(jnp.asarray(mask))).view(np.uint32)
        word, bit = (key // 4096) * 128 + key % 128, (key % 4096) // 128
        assert words[0, word] == 1 << bit, key
        assert np.count_nonzero(words) == 1, key
    assert all(pa.keep_tiles_ok(bk) for bk in (128, 256, 512, 1024, 4096))
    assert not any(pa.keep_tiles_ok(bk) for bk in (8, 64, 192, 8192))


# ---------------------------------------------------------------------------
# The kernels and the XLA step against the golden model
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("case", CASES)
def test_kernels_match_a_masked_softmax(case):
    """Four tiles, two rows of words for six heads: the value and the
    three gradients at every position."""
    q, k, v, dout = _operands(1)
    mask = _selection(case)
    got = _kernels(q, k, v, dout, pa.pack_keep(jnp.asarray(mask)))
    want = _golden(q, k, v, dout, mask)
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(a, b, atol=2e-5, rtol=1e-5,
                                   err_msg=f"{case} {name}")
    if case in ("top_keys", "ties"):
        kept = mask.sum(axis=-1)
        assert (kept == np.minimum(np.arange(L) + 1, TOPK)).all()


def test_a_selection_equal_to_the_causal_mask_is_the_plain_call():
    """Every bit a query can see set: the kernels give what they give
    with no selection, to the last bit of the sum's order."""
    q, k, v, dout = _operands(2)
    keep = pa.pack_keep(jnp.asarray(_selection("causal_mask")))
    for a, b in zip(_kernels(q, k, v, dout, keep),
                    _kernels(q, k, v, dout, None)):
        np.testing.assert_allclose(a, b, atol=1e-6, rtol=1e-6)


def test_tiles_in_the_words_second_span():
    """Queries 7,168 .. 8,191 of a sequence of 8,192 in 1024 x 1024
    tiles: the K tiles from the fifth on read their 8 bits a word from
    the second block of 128 words, the first four theirs from bits 0, 8,
    16, 24 of the first."""
    lq, lk, offset = 1024, 8192, 7168
    q, k, v, dout = _operands(3, lq, lk, bh=2, d=8)
    rng = np.random.RandomState(7)
    mask = _top_keys(rng.randn(1, lq, lk), 64, offset)
    got = _kernels(q, k, v, dout, pa.pack_keep(jnp.asarray(mask)),
                   q_offset=offset, bq=1024, bk=1024)
    want = _golden(q, k, v, dout, mask, offset)
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(a, b, atol=2e-5, rtol=1e-5, err_msg=name)


@pytest.mark.parametrize("case", CASES)
def test_xla_step_blockwise_and_reference_match_the_golden_model(case):
    q, k, v, _ = _operands(4)
    mask = _selection(case)
    keep = pa.pack_keep(jnp.asarray(mask))
    want = _masked_softmax(q, k, v, mask)
    m, l, o = xla_block_step(
        q, k, v, jnp.full((B * H, L), -jnp.inf), jnp.zeros((B * H, L)),
        jnp.zeros((B * H, L, D)), 0, 0, causal=True, keep=jnp.asarray(mask))
    np.testing.assert_allclose(o / jnp.where(l == 0, 1, l)[..., None], want,
                               atol=2e-5, rtol=1e-5)
    # (B, L, H, D) for the public functions
    unpacked = [t.reshape(B, H, L, D).transpose(0, 2, 1, 3)
                for t in (q, k, v, want)]
    for fn in (reference_attention,
               lambda *a, **kw: blockwise_attention(*a, block_k=64, **kw)):
        np.testing.assert_allclose(fn(*unpacked[:3], keep=keep), unpacked[3],
                                   atol=2e-5, rtol=1e-5)


@pytest.mark.parametrize("impl", ["pallas", "xla"])
@pytest.mark.parametrize("recomputed", [False, True])
def test_ring_attention_carries_the_selection_through_both_passes(
        impl, recomputed):
    """``ring_attention(keep=...)`` on a ring of one, under
    ``jax.checkpoint`` with the policy of a recomputed layer too: the
    value and the gradients of q, k and v are the reference's, the
    backward kernels reading the forward pass's selection; the selection
    takes no gradient."""
    from horovod_tpu.parallel.ring_attention import (KEPT_NAMES,
                                                     KEPT_SELECTION)

    rng = np.random.RandomState(5)
    q, k, v = (jnp.asarray(rng.randn(B, L, H, D), jnp.float32) * 0.5
               for _ in range(3))
    keep = pa.pack_keep(jnp.asarray(_selection("top_keys", 5)))
    mesh = Mesh(np.array(jax.devices()[:1]), ("sp",))

    def attend(q, k, v):
        return ring_attention(q, k, v, "sp", impl=impl, keep=keep,
                              recomputed=recomputed)

    if recomputed:
        attend = jax.checkpoint(
            attend, policy=jax.checkpoint_policies.save_only_these_names(
                *KEPT_NAMES, KEPT_SELECTION))

    def loss(fn):
        return lambda q, k, v: jnp.sum(jnp.sin(fn(q, k, v)))

    ring = jax.jit(shard_map(attend, mesh=mesh, in_specs=(P(),) * 3,
                             out_specs=P(), check_vma=False))
    reference = lambda q, k, v: reference_attention(q, k, v, keep=keep)
    np.testing.assert_allclose(ring(q, k, v), reference(q, k, v), atol=2e-5)
    got = jax.grad(loss(ring), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(reference), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=3e-5, rtol=1e-4)


# ---------------------------------------------------------------------------
# Where a selection cannot run
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kwargs,reason", [
    (dict(causal=False), "causal=True"),
    (dict(window=8), "window=None"),
    (dict(layout="zigzag"), "layout='contiguous'"),
])
def test_a_selection_needs_the_causal_bound_alone(kwargs, reason):
    rng = np.random.RandomState(6)
    q = jnp.asarray(rng.randn(1, 64, 2, 8), jnp.float32)
    keep = pa.pack_keep(jnp.ones((1, 64, 64), bool))
    mesh = Mesh(np.array(jax.devices()[:1]), ("sp",))
    with pytest.raises(ValueError, match="a selection") as raised:
        shard_map(lambda q: ring_attention(q, q, q, "sp", keep=keep, **kwargs),
                  mesh=mesh, in_specs=P(), out_specs=P(),
                  check_vma=False)(q)
    assert reason in str(raised.value)


def test_a_selection_does_not_run_over_a_ring():
    """A row's words are over the whole sequence's keys: ``sp`` 2
    raises, with the reason."""
    rng = np.random.RandomState(6)
    q = jnp.asarray(rng.randn(1, 64, 2, 8), jnp.float32)
    keep = pa.pack_keep(jnp.ones((1, 32, 32), bool))
    mesh = Mesh(np.array(jax.devices()[:2]), ("sp",))
    with pytest.raises(ValueError, match="sp = 1"):
        shard_map(lambda q: ring_attention(q, q, q, "sp", keep=keep),
                  mesh=mesh, in_specs=P(None, "sp"), out_specs=P(None, "sp"),
                  check_vma=False)(q)


def test_the_kernels_refuse_what_they_cannot_read():
    """Tiles that are no whole lanes of bits, a window beside the
    selection, words of another shape: ``ValueError`` from the entry
    point; ``ring_attention`` asked for the kernels at such a chunk says
    so, and left to choose takes the XLA step."""
    q, k, v, _ = _operands(8, 64, 64)
    keep = pa.pack_keep(jnp.ones((B, 64, 64), bool))
    call = lambda **kw: pa.flash_fwd_step(q, k, v, None, 0, 0, last=True,
                                          interpret=True, **kw)
    with pytest.raises(ValueError, match="multiple of 128"):
        call(keep=keep, block_q=64, block_k=64)
    q, k, v, _ = _operands(8, 128, 128)
    keep = pa.pack_keep(jnp.ones((B, 128, 128), bool))
    with pytest.raises(ValueError, match="window=None"):
        call(keep=keep, window=8, block_q=128, block_k=128)
    with pytest.raises(ValueError, match="got"):
        call(keep=keep[:, :64], block_q=128, block_k=128)
    with pytest.raises(ValueError, match="int32"):
        call(keep=keep.astype(jnp.uint32), block_q=128, block_k=128)
    mesh = Mesh(np.array(jax.devices()[:1]), ("sp",))
    x = jnp.ones((1, 64, 2, 8), jnp.float32)
    words = pa.pack_keep(jnp.ones((1, 64, 64), bool))
    with pytest.raises(ValueError, match="of 128 under a selection"):
        shard_map(lambda x: ring_attention(x, x, x, "sp", impl="pallas",
                                           keep=words),
                  mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False)(x)


# ---------------------------------------------------------------------------
# Names
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("selected", [False, True])
def test_a_selected_calls_kernels_carry_names_of_their_own(selected):
    """Lowered for a TPU (no chip, no TPU library): ``hvd_flash_fwd_sel``,
    ``hvd_flash_bwd_dq_sel`` and ``hvd_flash_bwd_dkv_sel`` under a
    selection, the plain names without one, so that whole-name readers
    of the plain and the windowed kernels do not see them."""
    bh, l, d = 4, 256, 64
    x = jax.ShapeDtypeStruct((bh, l, d), jnp.bfloat16)
    row = jax.ShapeDtypeStruct((bh, l), jnp.float32)
    words = jax.ShapeDtypeStruct((2, l, 128), jnp.int32)
    end = "_sel" if selected else ""

    def all_three(q, k, v, do, lse, delta, keep):
        tiles = dict(interpret=False, keep=keep if selected else None)
        return (pa.flash_fwd_step(q, k, v, None, 0, 0, last=True, **tiles),
                pa.flash_bwd_dq(q, k, v, do, lse, delta, 0, 0, **tiles),
                pa.flash_bwd_dkv(q, k, v, do, lse, delta, 0, 0, **tiles))

    text = jax.jit(all_three).trace(x, x, x, x, row, row, words).lower(
        lowering_platforms=("tpu",)).as_text(debug_info=True)
    assert set(re.findall(r'kernel_name = "(\w+)"', text)) == {
        "hvd_flash_fwd" + end, "hvd_flash_bwd_dq" + end,
        "hvd_flash_bwd_dkv" + end}


def test_without_a_selection_the_calls_trace_what_they_traced():
    """``keep=None`` adds no operand, no operation and no residual: the
    jaxpr of a differentiated ``ring_attention`` call is the one of a
    call that does not name the argument."""
    mesh = Mesh(np.array(jax.devices()[:1]), ("sp",))
    x = jnp.ones((1, 256, 2, 16), jnp.float32)

    def grad_of(**kw):
        fn = shard_map(lambda x: ring_attention(x, x, x, "sp", impl="pallas",
                                                **kw),
                       mesh=mesh, in_specs=P(), out_specs=P(),
                       check_vma=False)
        return str(jax.make_jaxpr(jax.grad(lambda x: jnp.sum(fn(x))))(x))

    assert grad_of(keep=None) == grad_of()
