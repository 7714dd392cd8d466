"""A sliding window as a second bound beside the causal one: query ``i``
sees key ``j`` iff ``0 <= i - j < window``.  The three Pallas kernels
(interpret mode) and the XLA block step against a masked softmax written
from positions, values and the three gradients, at windows smaller than
a tile, of exactly a tile, across several tiles and at least the
sequence (the causal call, bit for bit), with non-zero global offsets;
the tile counts against a count over positions; the band a windowed
call's grid walks and the index maps' clamps; the kernels' names."""

import math

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

BH, L, D, TILE = 3, 64, 16, 16
# smaller than a tile, a tile, across several tiles, the sequence, more
WINDOWS = (5, 16, 40, 64, 100)


def _operands(seed, lq=L, lk=L):
    rng = np.random.RandomState(seed)
    mk = lambda n: jnp.asarray(rng.randn(BH, n, D), jnp.float32) * 0.5
    return mk(lq), mk(lk), mk(lk), mk(lq) * 0.2


def _masked_softmax(q, k, v, q_offset, k_offset, window):
    """The golden model: every score, the mask from positions, a row
    that sees no key gives 0."""
    qpos = q_offset + jnp.arange(q.shape[1])[:, None]
    kpos = k_offset + jnp.arange(k.shape[1])[None, :]
    seen = qpos >= kpos
    if window is not None:
        seen = seen & (qpos - kpos < window)
    s = jnp.einsum("bqd,bkd->bqk", q, k) / (q.shape[-1] ** 0.5)
    s = jnp.where(seen, s, -jnp.inf)
    top = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.where(seen, jnp.exp(s - jnp.where(jnp.isfinite(top), top, 0.0)),
                  0.0)
    den = jnp.sum(p, axis=-1, keepdims=True)
    return jnp.einsum("bqk,bkd->bqd", p / jnp.where(den == 0, 1.0, den), v)


def _kernels(q, k, v, dout, q_offset, k_offset, window, bq=TILE, bk=TILE):
    """``(out, lse, dq, dk, dv)`` of a one-step ring through the three
    kernels, as ``_ring_flash`` calls them."""
    tiles = dict(causal=True, block_q=bq, block_k=bk, interpret=True,
                 window=window)
    out, lse, _ = pa.flash_fwd_step(q, k, v, None, q_offset, k_offset,
                                    last=True, **tiles)
    delta = jnp.sum(dout * out, axis=-1)
    dq = pa.flash_bwd_dq(q, k, v, dout, lse, delta, q_offset, k_offset,
                         **tiles)
    dk, dv = pa.flash_bwd_dkv(q, k, v, dout, lse, delta, q_offset, k_offset,
                              **tiles)
    return out, lse, dq, dk, dv


@pytest.mark.parametrize("offsets", [(0, 0), (48, 48), (64, 0), (40, 8)],
                         ids=["origin", "shifted", "later-block", "askew"])
@pytest.mark.parametrize("window", WINDOWS)
def test_kernels_match_a_masked_softmax(window, offsets):
    """Values and dQ, dK, dV at square and rectangular tiles.  A K
    block a whole sequence back (a ring's later step) leaves rows that
    see no key: 0 and -inf, and gradients exactly zero."""
    q_offset, k_offset = offsets
    q, k, v, dout = _operands(7 + window)
    want, vjp = jax.vjp(lambda *a: _masked_softmax(
        *a, q_offset, k_offset, window), q, k, v)
    wants = (want, *vjp(dout))
    for bq, bk in ((TILE, TILE), (32, 8), (8, 32)):
        out, lse, *grads = _kernels(q, k, v, dout, q_offset, k_offset,
                                    window, bq, bk)
        for got, wanted in zip((out, *grads), wants):
            np.testing.assert_allclose(np.asarray(got), np.asarray(wanted),
                                       rtol=2e-4, atol=2e-5)
        unseen = ~np.asarray(jnp.isfinite(lse))
        last = q_offset + np.arange(L) - k_offset
        np.testing.assert_array_equal(unseen[0], (last < 0)
                                      | (last - (L - 1) >= window))
        assert not np.asarray(out)[unseen].any()
        assert not np.asarray(grads[0])[unseen].any()


@pytest.mark.parametrize("window", [64, 100])
def test_a_window_of_the_sequence_is_the_causal_call_bit_for_bit(window):
    q, k, v, dout = _operands(3)
    plain = _kernels(q, k, v, dout, 0, 0, None)
    for got, want in zip(_kernels(q, k, v, dout, 0, 0, window), plain):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("window", WINDOWS)
def test_xla_step_and_blockwise_match_the_reference(window):
    """``xla_block_step`` (what the CPU mesh and the tests' stacks run)
    through ``blockwise_attention`` against ``reference_attention``,
    both of which take the window."""
    rng = np.random.RandomState(window)
    q, k, v = (jnp.asarray(rng.randn(2, L, 2, D), jnp.float32) * 0.5
               for _ in range(3))
    want = reference_attention(q, k, v, window=window)
    got = blockwise_attention(q, k, v, block_k=TILE, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)
    # and the golden model above is the same function
    packed = lambda t: t.transpose(0, 2, 1, 3).reshape(4, L, D)
    mine = _masked_softmax(packed(q), packed(k), packed(v), 0, 0, window)
    np.testing.assert_allclose(
        np.asarray(mine.reshape(2, 2, L, D).transpose(0, 2, 1, 3)),
        np.asarray(want), rtol=2e-4, atol=2e-5)
    if window >= L:
        np.testing.assert_array_equal(
            np.asarray(want), np.asarray(reference_attention(q, k, v)))


@pytest.mark.parametrize("impl", ["pallas", "xla"])
@pytest.mark.parametrize("window", [5, 40])
def test_ring_attention_carries_the_window_through_both_passes(impl, window):
    """``ring_attention(..., window=)`` on one device, either path:
    values and the gradients of q, k and v against the reference's."""
    rng = np.random.RandomState(11)
    q, k, v, w = (jnp.asarray(rng.randn(2, L, 2, D), jnp.float32) * 0.5
                  for _ in range(4))
    mesh = Mesh(np.array(jax.devices()[:1]), ("sp",))

    def ring(q, k, v):
        return shard_map(
            lambda q, k, v: ring_attention(q, k, v, "sp", impl=impl,
                                           window=window),
            mesh=mesh, in_specs=P(None, "sp"), out_specs=P(None, "sp"),
            check_vma=False)(q, k, v)

    loss = lambda fn: lambda *a: jnp.sum(fn(*a) * w)
    want = jax.value_and_grad(loss(lambda *a: reference_attention(
        *a, window=window)), argnums=(0, 1, 2))(q, k, v)
    got = jax.value_and_grad(loss(ring), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-4)


@pytest.mark.parametrize("window,forward,backward", [
    (None, 1024, 1024), (512, 1024, 512), (1024, 1024, 512),
    (2048, 1024, 512), (3072, 1024, 512), (4096, 1024, 1024),
    (8192, 1024, 1024), (16384, 1024, 1024)])
def test_a_window_cuts_the_backward_kernels_tiles_to_its_band(
        window, forward, backward):
    """``_block_sizes`` at the benchmark's chunk (16,384 tokens, heads
    of 128, bf16): the forward kernel keeps the chunk's tiles whatever
    the window; the backward kernels take the largest edge on the
    ladder within a quarter of the window, never under 512; and no
    window, no cut."""
    from horovod_tpu.parallel.ring_attention import _block_sizes

    assert _block_sizes(16384, 16384, 128, 2, 128, window) \
        == (forward, forward)
    assert _block_sizes(16384, 16384, 128, 2, 128, window, True) \
        == (backward, backward)
    # a chunk that has no such tile keeps its own, and the VMEM bound
    # still steps a wide head's tiles down
    assert _block_sizes(384, 384, 128, 2, 128, window, True) == (128, 128)
    assert _block_sizes(8192, 8192, 1024, 4, None, window or 64, True) \
        == (512, 512)


def test_ring_attention_with_the_two_passes_at_tiles_of_their_own():
    """A chunk of 1,024 under a window of 300: the forward kernel runs
    one 1024 x 1024 tile a head, the backward kernels 512 x 512 tiles
    over a band of two; values and gradients against the reference."""
    from horovod_tpu.parallel.ring_attention import _block_sizes

    n, window = 1024, 300
    assert (_block_sizes(n, n, D, 4, D, window),
            _block_sizes(n, n, D, 4, D, window, True)) \
        == ((1024, 1024), (512, 512))
    rng = np.random.RandomState(13)
    q, k, v, w = (jnp.asarray(rng.randn(1, n, 2, D), jnp.float32) * 0.5
                  for _ in range(4))
    mesh = Mesh(np.array(jax.devices()[:1]), ("sp",))

    def ring(q, k, v):
        return shard_map(
            lambda q, k, v: ring_attention(q, k, v, "sp", impl="pallas",
                                           window=window),
            mesh=mesh, in_specs=P(None, "sp"), out_specs=P(None, "sp"),
            check_vma=False)(q, k, v)

    loss = lambda fn: lambda *a: jnp.sum(fn(*a) * w)
    want = jax.value_and_grad(loss(lambda *a: reference_attention(
        *a, window=window)), argnums=(0, 1, 2))(q, k, v)
    got = jax.value_and_grad(loss(ring), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-4)


def test_ring_attention_window_over_a_ring_of_two():
    """sp 2, the XLA step: the mask is on global positions, so a window
    that reaches into the other chip's chunk is computed in full (whole
    ring steps behind it are not skipped)."""
    rng = np.random.RandomState(5)
    q, k, v = (jnp.asarray(rng.randn(2, L, 2, D), jnp.float32) * 0.5
               for _ in range(3))
    mesh = Mesh(np.array(jax.devices()[:2]), ("sp",))
    got = shard_map(
        lambda q, k, v: ring_attention(q, k, v, "sp", impl="xla", window=40),
        mesh=mesh, in_specs=P(None, "sp"), out_specs=P(None, "sp"),
        check_vma=False)(q, k, v)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(reference_attention(q, k, v, window=40)),
        rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("kwargs", [dict(causal=False, window=8),
                                    dict(window=0),
                                    dict(window=8, layout="zigzag")])
def test_a_window_needs_the_causal_bound_and_the_contiguous_layout(kwargs):
    q = jnp.zeros((1, 16, 1, 8), jnp.float32)
    mesh = Mesh(np.array(jax.devices()[:1]), ("sp",))
    with pytest.raises(ValueError, match="sliding window"):
        shard_map(lambda q: ring_attention(q, q, q, "sp", **kwargs),
                  mesh=mesh, in_specs=P(None, "sp"), out_specs=P(None, "sp"),
                  check_vma=False)(q)
    with pytest.raises(ValueError, match="sliding window"):
        pa.flash_fwd_step(q[:, :, 0], q[:, :, 0], q[:, :, 0], None, 0, 0,
                          causal=False, window=4, last=True, interpret=True)


# ---------------------------------------------------------------------------
# Which tile pairs are live, which build a mask, which are fetched
# ---------------------------------------------------------------------------

CASES = [(128, 128, 32, 16, 0, 0, 8), (128, 128, 16, 32, 0, 0, 16),
         (128, 128, 16, 16, 0, 0, 40), (128, 128, 32, 16, 128, 0, 100),
         (128, 128, 16, 32, 0, 128, 24), (64, 128, 16, 64, 40, 8, 33),
         (96, 64, 8, 32, 0, 24, 1), (128, 128, 16, 16, 0, 0, 500)]


def _seen(lq, lk, qo, ko, window):
    apart = (qo + np.arange(lq))[:, None] - (ko + np.arange(lk))[None, :]
    return (apart >= 0) & (apart < window)


@pytest.mark.parametrize("case", CASES)
def test_tile_counts_with_a_window_match_a_count_over_positions(case):
    """grid / live / masked as the kernels' predicates decide them,
    against the mask itself: a pair is live iff any position in it is
    visible — so a pair called dead has no unmasked position — and
    builds a mask iff, being live, any is hidden."""
    lq, lk, bq, bk, qo, ko, window = case
    tiles = _seen(lq, lk, qo, ko, window).reshape(lq // bq, bq, lk // bk, bk)
    some, every = tiles.any(axis=(1, 3)), tiles.all(axis=(1, 3))
    assert pa.causal_tile_counts(lq, lk, bq, bk, qo, ko, window) == (
        some.size, int(some.sum()), int((some & ~every).sum()))
    for iq in range(lq // bq):
        for ik in range(lk // bk):
            live = pa._tile_live(qo + iq * bq, ko + ik * bk, bq, bk, window)
            assert bool(live) == bool(some[iq, ik]), (iq, ik)


def test_tile_counts_of_the_benchmarks_window_cell():
    """Seq 16,384 in 1024 x 1024 tiles under a window of 2,048: three
    tiles a row, the diagonal's and the trailing edge's masked; the 45
    live tiles cover 47.2 M slots for the 31,458,304 pairs the window
    leaves."""
    assert pa.causal_tile_counts(16384, 16384, 1024, 1024,
                                 window=2048) == (256, 45, 30)
    assert pa.causal_tile_counts(16384, 16384, 1024, 1024) == (256, 136, 16)
    assert pa.causal_tile_counts(8192, 8192, 1024, 1024) == (64, 36, 8)
    pairs = 2048 * 2049 // 2 + (16384 - 2048) * 2048
    assert pairs == 31_458_304 == int(_seen(16384, 16384, 0, 0, 2048).sum())
    assert 45 * 1024 * 1024 == 47_185_920


def _known_multiple(qo, ko):
    """Something the offsets' difference is a multiple of, as a ring
    knows its chunk: a power of two (any, where they are equal)."""
    return math.gcd(abs(qo - ko), 1024)


@pytest.mark.parametrize("case", CASES)
def test_index_maps_send_a_dead_step_to_a_live_tile(case):
    """Under a window the grid walks a band: step ``t`` of a Q row
    stands for K tile ``first + t`` (of a K column in
    ``flash_bwd_dkv``, for Q tile ``first + t``).  Every live tile of
    a row is named by exactly one step, the one that stands for it; a
    step that is not live — past the row's last live tile or past the
    block — names the row's last live block, so a row's steps name a
    run of blocks without a gap and fetch each live block once and no
    dead one (a row with no live tile names one block throughout); and
    the band's static length is never shorter than a row's live run,
    with nothing known of the offsets and with what a ring knows of
    them."""
    lq, lk, bq, bk, qo, ko, window = case
    nq, nk = lq // bq, lk // bk
    tiles = _seen(lq, lk, qo, ko, window).reshape(nq, bq, nk, bk)
    live = tiles.any(axis=(1, 3))
    offs = np.asarray([qo, ko], np.int32)
    _, kv_row = pa._q_major_maps(bq, bk, True, nk, window)
    q_row, _ = pa._k_major_maps(bq, bk, True, nq, window)
    for multiple in (1, _known_multiple(qo, ko)):
        band_k, band_q = pa.band_steps(bq, bk, window, nq, nk, multiple)
        assert 1 <= band_k <= nk and 1 <= band_q <= nq
        for iq in range(nq):
            first = int(pa._first_k_tile(offs, iq, bq, bk, window))
            named = [int(kv_row(0, iq, t, offs)[1]) for t in range(band_k)]
            _check_band(named, first, live[iq])
        for ik in range(nk):
            first = int(pa._first_q_tile(offs, ik, bq, bk))
            named = [int(q_row(0, ik, t, offs)[1]) for t in range(band_q)]
            _check_band(named, first, live[:, ik])
    tight = pa.band_steps(bq, bk, window, nq, nk, _known_multiple(qo, ko))
    assert all(a <= b for a, b in zip(
        tight, pa.band_steps(bq, bk, window, nq, nk)))


def _check_band(named: list, first: int, live) -> None:
    """``named[t]`` is the block step ``t`` fetches, ``first + t`` the
    tile it stands for (and the kernel body computes, or skips)."""
    where = np.flatnonzero(live)
    assert all(0 <= n < len(live) for n in named)
    if not where.size:
        assert len(set(named)) == 1
        return
    lo, hi = where[0], where[-1]
    assert live[lo:hi + 1].all()              # the live tiles are one run
    assert first == lo and len(named) >= hi - lo + 1
    stood_for = [first + t for t in range(len(named))]
    assert [t for t in stood_for if t <= hi] == list(range(lo, hi + 1))
    for tile, n in zip(stood_for, named):
        assert n == min(tile, hi), (tile, named)


@pytest.mark.parametrize("edge,known,steps,band", [
    (1024, 16384, 48, 3), (512, 16384, 160, 5), (256, 16384, 576, 9),
    (1024, 1, 64, 4), (512, 1, 192, 6), (256, 1, 640, 10)])
def test_walked_steps_at_the_benchmarks_sizes(edge, known, steps, band):
    """Seq 16,384 under a window of 2,048: a head's call walks the
    band's steps a row — 48 at 1024 x 1024 tiles where it walked 256 —
    one more a row where nothing is known of the offsets (``askew``
    above); without a window every tile pair, and ``band`` 0.  The
    band is the least that holds a row's live run at every row."""
    seq, window = 16384, 2048
    n = seq // edge
    assert pa.walked_steps(seq, seq, edge, edge, window, known) \
        == (steps, band)
    assert pa.band_steps(edge, edge, window, n, n, known) == (band, band)
    assert pa.walked_steps(seq, seq, edge, edge) == (n * n, 0)
    assert pa.walked_steps(seq, seq, edge, edge)[0] \
        == pa.causal_tile_counts(seq, seq, edge, edge)[0]
    # from positions, a row of tiles at a time
    live = np.stack([_seen(edge, seq, iq * edge, 0, window).reshape(
        edge, n, edge).any(axis=(0, 2)) for iq in range(n)])
    assert live.sum(axis=1).max() == live.sum(axis=0).max() \
        == pa.band_steps(edge, edge, window, n, n, seq)[0]
    # a window of the sequence or more: the band is every tile
    assert pa.walked_steps(seq, seq, edge, edge, seq, known) == (n * n, n)


@pytest.mark.parametrize("offsets", [(192, 0), (0, 64), (1024, 0)],
                         ids=["behind-the-window", "in-the-future",
                              "far-behind"])
@pytest.mark.parametrize("tiles", [(16, 16), (32, 8), (8, 32)])
def test_a_row_whose_whole_band_is_dead(offsets, tiles):
    """A K block that no query of the chunk sees (a ring's step behind
    the window, or ahead of the chunk): every step of every band is
    dead.  The last step gives 0 and -inf, a middle step hands the
    state it was given through unchanged, and dQ, dK, dV are exactly
    zero (the scratch is started and written on the band's first and
    last step whatever lies between)."""
    q_offset, k_offset = offsets
    window, (bq, bk) = 40, tiles
    q, k, v, dout = _operands(21)
    assert not _seen(L, L, q_offset, k_offset, window).any()
    out, lse, dq, dk, dv = _kernels(q, k, v, dout, q_offset, k_offset,
                                    window, bq, bk)
    for got in (out, dq, dk, dv):
        assert not np.asarray(got).any()
    assert np.isneginf(np.asarray(lse)).all()
    rng = np.random.RandomState(2)
    state = (jnp.asarray(rng.randn(BH, L), jnp.float32),
             jnp.asarray(rng.rand(BH, L) + 0.5, jnp.float32),
             jnp.asarray(rng.randn(BH, L, D), jnp.float32))
    through = pa.flash_fwd_step(q, k, v, state, q_offset, k_offset,
                                causal=True, block_q=bq, block_k=bk,
                                interpret=True, window=window)
    for got, want in zip(through, state):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_without_a_window_the_maps_are_the_causal_ones():
    """``window=None`` clamps to the last live tile alone, as before
    there was a window."""
    offs = np.asarray([0, 0], np.int32)
    _, kv_row = pa._q_major_maps(16, 16, True, 8)
    assert [int(kv_row(0, 3, ik, offs)[1]) for ik in range(8)] \
        == [0, 1, 2, 3, 3, 3, 3, 3]
    q_row, _ = pa._k_major_maps(16, 16, True, 8)
    assert [int(q_row(0, 3, iq, offs)[1]) for iq in range(8)] \
        == [3, 3, 3, 3, 4, 5, 6, 7]


# ---------------------------------------------------------------------------
# Names
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("window", [None, 64])
def test_a_windowed_calls_kernels_carry_names_of_their_own(window):
    """Lowered for a TPU (no chip, no TPU library): ``hvd_flash_fwd_win``,
    ``hvd_flash_bwd_dq_win`` and ``hvd_flash_bwd_dkv_win`` under a
    window, the plain names without one, so that a trace tells the
    window layers' kernel time from the full layer's."""
    import re

    bh, l, d = 2, 256, 64
    x = jax.ShapeDtypeStruct((bh, l, d), jnp.bfloat16)
    row = jax.ShapeDtypeStruct((bh, l), jnp.float32)
    end = "" if window is None else "_win"

    def all_three(q, k, v, do, lse, delta):
        tiles = dict(interpret=False, window=window)
        return (pa.flash_fwd_step(q, k, v, None, 0, 0, last=True, **tiles),
                pa.flash_bwd_dq(q, k, v, do, lse, delta, 0, 0, **tiles),
                pa.flash_bwd_dkv(q, k, v, do, lse, delta, 0, 0, **tiles))

    text = jax.jit(all_three).trace(x, x, x, x, row, row).lower(
        lowering_platforms=("tpu",)).as_text(debug_info=True)
    assert set(re.findall(r'kernel_name = "(\w+)"', text)) == {
        "hvd_flash_fwd" + end, "hvd_flash_bwd_dq" + end,
        "hvd_flash_bwd_dkv" + end}
