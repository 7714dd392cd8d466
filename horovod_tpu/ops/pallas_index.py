"""Pallas TPU kernel: an indexer's selection of keys, from the scores to
the packed words, over the keys each block of queries can see.

One entry point, :func:`index_select`: for each block of ``block_q``
query rows of a sequence it computes the indexer's scores

    ``I[t, s] = sum_j w[t, j] relu(q[t, j] . k[s]) * scale``

over the key tiles up to the block's causal bound, keeps them in VMEM in
an order-preserving int32 form, finds each row's ``topk``-th largest
score exactly by 32 counting passes over those tiles, and packs the kept
keys' bits in :func:`pallas_attention.pack_keep`'s layout: every key
above the threshold, of the keys level with it the first by position,
and no key later than the row.  Only the words leave VMEM.  A row ``t <
topk`` keeps every key ``s <= t``; a block whose every row is such
computes no score at all, and fetches no key.

The same selection as ``models/blocks.select_keys``'s XLA form, which
scores every key of the sequence and masks what a query cannot see: the
products of compute-type operands accumulated in float32, the weighted
relu sum over the heads and the scale in float32, ``-0.0`` folded onto
``0.0``, what a query cannot see below every score, and the search and
the tie-break exact.

Grid ``(B, nq, nk)``, the key tiles innermost: step ``ik`` of a row
block scores key tile ``ik`` into the block's scratch where the block
sees it (the key map clamps the dead steps onto the block's last live
tile, so they fetch nothing), and the block's last step searches and
packs, a group of ``SEARCH_ROWS`` rows at a time: the rows' running
threshold and counts then stay in vector registers through each pass.
A group whose rows hold a tie at their threshold ranks its level keys by
an MXU product with a triangle of ones (an inclusive prefix count a key
tile, carried across tiles); a group without one keeps every level key.

Compiled by Mosaic on a TPU backend; interpreted elsewhere
(``common.platform.pallas_interpret``), so the same kernel code is
exercised by the CPU test mesh.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.common.platform import pallas_interpret
from horovod_tpu.ops.pallas_attention import (KEEP_LANES, KEEP_SPAN,
                                              VMEM_BUDGET, keep_tiles_ok)

KERNEL_NAME = "hvd_index_select"

# query rows a grid row scores and searches, keys a grid step scores
BLOCK_Q = 256
BLOCK_K = 512
# rows searched together: a group's threshold and counts are (rows, 128)
# and stay in vector registers through a pass
SEARCH_ROWS = 128

_SIGN = -(1 << 31)
# the order-preserving int32 form of -inf: what a query cannot see
HIDDEN = (0xFF800000 ^ 0x7FFFFFFF) - (1 << 32)


def order_of(scores):
    """float32 -> int32, order kept under a signed comparison: ``-0.0``
    folded onto ``0.0``, a non-negative number's bits as they are and a
    negative number's with every bit but the sign turned.  The signed
    twin of the XLA form's uint32 order (its value with the top bit
    turned)."""
    scores = jnp.where(scores == 0.0, 0.0, scores)
    bits = lax.bitcast_convert_type(scores, jnp.int32)
    return jnp.where(bits >= 0, bits, bits ^ 0x7FFFFFFF)


def vmem_bytes(lc: int, bq: int, bk: int, heads: int, size: int,
               itemsize: int) -> int:
    """What a grid step may hold in VMEM: the block's int32 scores over
    the whole chunk, the double-buffered blocks (the q block's and the
    key tile's lanes padded to 128), and a few f32 (bq, bk) tiles of the
    head sum; a quarter of headroom."""
    lanes = max(size, 128)
    blocks = 2 * (heads * bq * lanes * itemsize + size * bk * itemsize
                  + bq * 128 * 4 + bq * KEEP_LANES * -(-lc // KEEP_SPAN) * 4)
    return (lc * bq * 4 + blocks + 6 * bq * bk * 4) * 5 // 4


def tiles_fit(lc: int, heads: int, size: int, itemsize: int,
              block_q: int = BLOCK_Q, block_k: int = BLOCK_K) -> bool:
    """Whether a chunk of ``lc`` queries is a whole number of the
    kernel's row blocks and key tiles, and its scores of a row block fit
    :data:`VMEM_BUDGET`."""
    return (lc % block_q == 0 and lc % block_k == 0
            and keep_tiles_ok(block_k) and block_q % SEARCH_ROWS == 0
            and vmem_bytes(lc, block_q, block_k, heads, size, itemsize)
            <= VMEM_BUDGET)


def scored_pairs(lc: int, topk: int, block_q: int = BLOCK_Q,
                 block_k: int = BLOCK_K) -> int:
    """The (query, key) pairs the kernel scores for one sequence: every
    row of a block that holds a row ``t >= topk`` against every key of
    the tiles up to the block's last row.  16,384 tokens, top 2,048,
    256 x 512 tiles: 135,790,592, where the causal pairs are 134,225,920
    and the XLA form scores 268,435,456."""
    return sum(block_q * block_k * -(-(first + block_q) // block_k)
               for first in range(0, lc, block_q) if first + block_q > topk)


def _count(load, tiles, hit, rows: int, bk: int):
    """How many keys of each row ``hit`` finds over key tiles ``[0,
    tiles)`` (``load(c)``: tile ``c``'s (rows, bk) block; ``hit``: a
    (rows, 128) lane tile -> its mask), (rows, 128) alike in every
    lane."""
    def tile(c, count):
        block = load(c)
        for lane in range(0, bk, 128):
            count = count + jnp.where(hit(block[:, lane:lane + 128]),
                                      1.0, 0.0)
        return count

    count = lax.fori_loop(0, tiles, tile,
                          jnp.zeros((rows, 128), jnp.float32))
    return jnp.broadcast_to(jnp.sum(count, axis=1, keepdims=True),
                            (rows, 128))


def _threshold(load, tiles, k: int, rows: int, bk: int):
    """Each row's ``k``-th largest int32 order over key tiles ``[0,
    tiles)`` (``load`` as :func:`_count`'s), exactly: the largest ``t``
    that at least ``k`` keys reach, built bit by bit from the top of the
    unsigned form, a count over the row a bit.  Returns ``(threshold,
    reach)``, each (rows, 128) alike in every lane: ``reach`` is how
    many keys reach the threshold."""
    def bit(i, carry):
        least, reach = carry
        trial = ((least ^ _SIGN) | jnp.left_shift(1, 31 - i)) ^ _SIGN
        count = _count(load, tiles, lambda part: part >= trial, rows, bk)
        reached = count >= k
        return (jnp.where(reached, trial, least),
                jnp.where(reached, count, reach))

    # every key reaches the unsigned 0
    every = jnp.asarray(tiles * bk, jnp.float32)
    return lax.fori_loop(0, 32, bit, (
        jnp.full((rows, 128), _SIGN, jnp.int32),
        jnp.full((rows, 128), every, jnp.float32)))


def _pack(words_ref, rows_at, tiles, keep_of, carry, *, rows: int, bk: int,
          spans: int):
    """Write rows ``rows_at`` of the block's words: for each span of
    :data:`KEEP_SPAN` keys, the bits of its tiles below ``tiles``
    (``keep_of(c, carry)``: tile ``c``'s keep masks, one (rows, 128) a
    lane tile, and the carry it hands the next tile), 0 past them."""
    per_span = KEEP_SPAN // bk
    for span in range(spans):
        first = span * per_span
        last = jnp.clip(tiles, first, first + per_span)

        def tile(c, state):
            word, carried = state
            keep, carried = keep_of(c, carried)
            base = (c - first) * (bk // KEEP_LANES)
            for i, part in enumerate(keep):
                word = word | jnp.where(part, jnp.left_shift(1, base + i), 0)
            return word, carried

        word, carry = lax.fori_loop(
            first, last, tile,
            (jnp.zeros((rows, KEEP_LANES), jnp.int32), carry))
        words_ref[0, rows_at, span * KEEP_LANES:(span + 1) * KEEP_LANES] \
            = word


def _select_rows(order_ref, words_ref, group, first_row, *, topk: int,
                 rows: int, bk: int, spans: int):
    """Search and pack the rows ``first_row + [0, rows)`` of the block,
    group ``group`` of its scratch."""
    rows_at = pl.ds(pl.multiple_of(group * rows, rows), rows)
    tiles = lax.div(first_row + rows - 1, bk) + 1
    qpos = first_row + lax.broadcasted_iota(jnp.int32, (rows, KEEP_LANES), 0)
    short = qpos < topk
    pack = functools.partial(_pack, words_ref, rows_at, tiles, rows=rows,
                             bk=bk, spans=spans)

    def causal(c, carry):
        kpos = c * bk + lax.broadcasted_iota(jnp.int32, (rows, KEEP_LANES),
                                             1)
        return [kpos + lane <= qpos
                for lane in range(0, bk, KEEP_LANES)], carry

    @pl.when(first_row + rows <= topk)
    def _():
        pack(causal, 0)

    @pl.when(first_row + rows > topk)
    def _():
        def load(c):
            return order_ref[c, rows_at, :]

        least, reach = _threshold(load, tiles, topk, rows, bk)
        needed = topk - _count(load, tiles, lambda part: part > least,
                               rows, bk)
        # a row past topk with more keys level with its threshold than it
        # needs: the first of them by position are kept
        tied = jnp.max(jnp.where((reach > topk) & jnp.logical_not(short),
                                 1.0, 0.0)) > 0.0

        def lanes(c, keep_lane):
            """Tile ``c``'s keep masks, a lane tile each: every key of a
            short row that is not later than it, else ``keep_lane(block,
            lane)``."""
            block = order_ref[c, rows_at, :]
            return [(short & seen)
                    | (jnp.logical_not(short) & keep_lane(block, lane))
                    for lane, seen in zip(range(0, bk, KEEP_LANES),
                                          causal(c, None)[0])]

        @pl.when(jnp.logical_not(tied))
        def _():
            pack(lambda c, carry: (lanes(c, lambda block, lane: block[
                :, lane:lane + KEEP_LANES] >= least), carry), 0)

        @pl.when(tied)
        def _():
            # ones where the key of the row is at or before the key of the
            # column: a level mask times it counts each key's level keys
            # up to and including itself
            ones = jnp.where(
                lax.broadcasted_iota(jnp.int32, (bk, bk), 0)
                <= lax.broadcasted_iota(jnp.int32, (bk, bk), 1),
                1.0, 0.0).astype(jnp.bfloat16)

            def ranked(c, before):
                block = order_ref[c, rows_at, :]
                level = jnp.concatenate(
                    [jnp.where(block[:, lane:lane + KEEP_LANES] == least,
                               1.0, 0.0)
                     for lane in range(0, bk, KEEP_LANES)], axis=1)
                rank = lax.dot_general(
                    level.astype(jnp.bfloat16), ones,
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)

                def keep_lane(block, lane):
                    part = block[:, lane:lane + KEEP_LANES]
                    return (part > least) | (
                        (part == least)
                        & (before + rank[:, lane:lane + KEEP_LANES]
                           <= needed))

                after = before + jnp.broadcast_to(rank[:, bk - 1:],
                                                  (rows, KEEP_LANES))
                return lanes(c, keep_lane), after

            pack(ranked, jnp.zeros((rows, KEEP_LANES), jnp.float32))


def _index_kernel(q_ref, kt_ref, w_ref, words_ref, order_ref, *, topk: int,
                  heads: int, scale: float, bq: int, bk: int, rows: int,
                  spans: int):
    """Grid (B, nq, nk).  ``q_ref`` (1, heads, bq, d), ``kt_ref`` (1, d,
    bk): key tile ``ik`` transposed, ``w_ref`` (1, bq, heads) f32;
    ``words_ref`` (1, bq, W) int32 out; ``order_ref`` (nk, bq, bk) int32
    scratch: the block's scores in order form, a key tile a row."""
    iq, ik = pl.program_id(1), pl.program_id(2)
    first = iq * bq
    tiles = lax.div(first + bq - 1, bk) + 1

    @pl.when((first + bq > topk) & (ik < tiles))
    def _():
        weights = w_ref[0]
        keys = kt_ref[0]
        total = None
        for j in range(heads):
            products = lax.dot_general(
                q_ref[0, j], keys, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)     # (bq, bk)
            term = weights[:, j:j + 1] * jax.nn.relu(products)
            total = term if total is None else total + term
        order = order_of(total * scale)
        crossed = ik * bk + (bk - 1) > first

        @pl.when(crossed)
        def _():
            qpos = first + lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = ik * bk + lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            order_ref[ik] = jnp.where(kpos <= qpos, order, HIDDEN)

        @pl.when(jnp.logical_not(crossed))
        def _():
            order_ref[ik] = order

    @pl.when(ik == pl.num_programs(2) - 1)
    def _():
        @pl.loop(0, bq // rows)
        def _(group):
            _select_rows(order_ref, words_ref, group, first + group * rows,
                         topk=topk, rows=rows, bk=bk, spans=spans)


@functools.partial(jax.jit, inline=True, static_argnames=(
    "topk", "block_q", "block_k", "interpret"))
def index_select(q, k, w, *, topk: int, block_q: int = BLOCK_Q,
                 block_k: int = BLOCK_K, interpret: bool | None = None):
    """The packed selection of an indexer's ``topk`` keys a query.

    q: (B, H, L, D) the ``H`` index heads' queries in the compute type;
    k: (B, L, D) the one key head; w: (B, L, H) float32 head weights
    (scaled as the caller wants them).  Returns (B, L, W) int32,
    :func:`pallas_attention.pack_keep`'s layout: query ``t`` keeps the
    ``topk`` keys ``s <= t`` of largest ``sum_j w[t, j] relu(q[t, j] .
    k[s]) / sqrt(D)``, a tie going to the lower ``s``, and every key
    ``s <= t`` where ``t < topk``.  Forward only: no gradient is
    defined (the caller stops it)."""
    b, heads, lc, d = q.shape
    bq, bk = min(block_q, lc), min(block_k, lc)
    rows = min(SEARCH_ROWS, bq)
    if lc % bq or lc % bk or bq % rows or not keep_tiles_ok(bk):
        raise ValueError(f"the index kernel tiles {lc} queries in blocks of "
                         f"{bq} rows and {bk} keys: neither may leave a "
                         f"remainder, and the key tile is a multiple of "
                         f"{KEEP_LANES} that divides {KEEP_SPAN}")
    nq, nk = lc // bq, lc // bk
    spans = -(-lc // KEEP_SPAN)
    kernel = functools.partial(
        _index_kernel, topk=min(topk, lc), heads=heads, scale=d ** -0.5,
        bq=bq, bk=bk, rows=rows, spans=spans)

    def key_tile(bi, iq, ik):
        # the block's last live tile on its dead steps; tile 0 where the
        # block computes no score
        last = lax.div(iq * bq + bq - 1, bk)
        return bi, 0, jnp.where(iq * bq + bq > topk, jnp.minimum(ik, last), 0)

    return pl.pallas_call(
        kernel,
        name=KERNEL_NAME,
        grid=(b, nq, nk),
        in_specs=[
            pl.BlockSpec((1, heads, bq, d), lambda bi, iq, ik: (bi, 0, iq, 0)),
            pl.BlockSpec((1, d, bk), key_tile),
            pl.BlockSpec((1, bq, heads), lambda bi, iq, ik: (bi, iq, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, spans * KEEP_LANES),
                               lambda bi, iq, ik: (bi, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, lc, spans * KEEP_LANES),
                                       jnp.int32),
        scratch_shapes=[pltpu.VMEM((nk, bq, bk), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=max(32 << 20, vmem_bytes(
                lc, bq, bk, heads, d, jnp.dtype(q.dtype).itemsize))),
        interpret=pallas_interpret(interpret),
    )(q, jnp.swapaxes(k, 1, 2), w)
