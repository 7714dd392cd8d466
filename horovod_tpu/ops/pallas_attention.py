"""Pallas TPU kernels: the flash-attention forward step and the two
saved-LSE backward kernels.

Three entry points (:func:`flash_fwd_step`, :func:`flash_bwd_dq`,
:func:`flash_bwd_dkv`), none differentiable by itself: the one caller,
``parallel/ring_attention``'s ring-level VJP, pairs them, and hands
them tiles it derives from the chunk length, the window and
:data:`VMEM_BUDGET`.  This module imports nothing from ``parallel/``.

The hot op of ring attention (SURVEY §5.7 — a new TPU capability, absent
from the reference): one online-softmax accumulation of a local Q chunk
against one KV block, carrying the running (max, denominator, numerator)
state between ring steps so `lax.ppermute` KV rotation overlaps the MXU
work.  The kernel tiles Q×K into blocks as large as the chunk allows (up
to 1024×1024, ``ring_attention._pick_block``: a grid step costs about
half a microsecond whatever its tile, so small tiles are all fixed
cost), keeps softmax state in fp32 VMEM scratch across the innermost
K-grid dimension, and applies block-level causal masking from *global*
sequence offsets (the carried state is what makes it composable with
the ring — a plain fused attention kernel could not resume from a
previous block's state).  A sliding ``window`` (a static Python int) is
a second bound on the same predicate: query ``i`` sees key ``j`` iff
``0 <= i - j < window``, the causal bound and the window's trailing
edge.  A tile pair the mask hides whole — past the diagonal or behind
the window — is neither computed nor fetched (the index maps clamp a
row's dead steps onto its last live tile), and only the pairs the
diagonal or the trailing edge crosses build the mask
(:func:`causal_tile_counts` says how many of each).  Under a window the
grid does not walk the square at all: its innermost dimension is the
band, the static count of tiles a row's window can touch
(:func:`band_steps`; 3 of 16 at 1024×1024 tiles, a window of 2,048 and
16,384 tokens), and step ``t`` of a row stands for tile ``first + t``,
``first`` read from the prefetched offsets, so the band's length is
static and its place is not (:func:`walked_steps` says how many steps a
call walks).  A dead step in the middle of a row costs 0.15 µs a
kernel on a v5e, one that ends a row 2.2 µs, because the next row's
blocks are then fetched behind no work (``PERF.md`` section 6, PR 36):
the band ends every row but the first few on a live tile.  A windowed
call's kernels carry names of their own (``hvd_flash_fwd_win`` ...), so
that a trace tells them from the plain calls; without a window the
kernels trace what they traced before there was one.

A selection (``keep``) is a third bound, by data and not by position:
one bit a (query, key) pair, shared by the heads of a batch row, packed
32 keys to an int32 word (:func:`pack_keep` has the layout, chosen so
that a (bq, 128) block of words unpacks onto a K tile with shifts and
lane-aligned joins alone).  A pair is seen iff the causal bound and its
bit allow it.  Which tiles are live is still decided from positions —
at 16,384 tokens and 2,048 kept keys a row no 1024 x 1024 tile is
without a kept key — and every live tile builds its mask from the
words; the kernels are then named ``hvd_flash_fwd_sel`` ... .  Without
a selection the kernels trace what they trace without one.

The state goes through HBM only between ring steps.  At the ends of the
ring the kernel does the state's work where the state is, in VMEM
(:func:`flash_fwd_step`): the first step is handed no (m, l, o) and
starts its scratch at (-inf, 0, 0); the last step writes o / l and
lse = m + log l instead of the state.  The backward kernels keep fp32
accumulators in VMEM and write the type the caller asks for.  A ring of
one step — every cell of the benchmark — has only ends: three kernel
calls, no state, no fp32 gradient in HBM.

Compiled by Mosaic on a TPU backend; interpreted elsewhere
(``common.platform.pallas_interpret``), so the same kernel code is
exercised by the CPU test mesh.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.common.platform import pallas_interpret

_NEG_INF = float("-inf")

# The carried per-row softmax state (running max m, denominator l)
# travels as ONE native (sublane, lane)=(8, 128) f32 tile per row-block:
# lanes 0..63 replicate m, lanes 64..127 replicate l.  Mosaic cannot
# lower a (1, bq) per-row block, and XLA pads any narrower minor dim
# back to 128 in HBM anyway — packing both scalars into a single
# 128-lane buffer is what actually halves the carried-state traffic
# (one tile read+write per block instead of two).
_M_LANE = 0
_L_LANE = 64

# Tiles are sized so that a kernel asks for at most about half of the
# 128 MiB of VMEM a v5e (or v4, v6e) TensorCore has.
VMEM_BUDGET = 64 << 20

# A selection's packed form: a row of KEEP_LANES int32 words covers
# KEEP_SPAN keys, bit ``b`` of word ``c`` standing for key ``b *
# KEEP_LANES + c`` of the span — so the ``bk / KEEP_LANES`` bits that a
# K tile of ``bk`` keys takes of each word lie side by side, and the
# tile's (bq, bk) mask is that many shifts of one (bq, KEEP_LANES) block
# joined along the lanes.
KEEP_LANES = 128
KEEP_SPAN = 32 * KEEP_LANES


def pack_keep(mask):
    """(..., Lq, Lk) bool -> (..., Lq, W) int32, ``W = KEEP_LANES *
    ceil(Lk / KEEP_SPAN)``: key ``s`` of a row is bit ``(s % KEEP_SPAN)
    // KEEP_LANES`` of word ``(s // KEEP_SPAN) * KEEP_LANES + s %
    KEEP_LANES``; the bits past ``Lk`` are 0."""
    *lead, lk = mask.shape
    spans = -(-lk // KEEP_SPAN)
    bits = jnp.pad(mask, [(0, 0)] * len(lead) + [(0, spans * KEEP_SPAN - lk)])
    bits = bits.reshape(*lead, spans, 32, KEEP_LANES).astype(jnp.uint32)
    words = jnp.sum(bits << jnp.arange(32, dtype=jnp.uint32)[:, None],
                    axis=-2, dtype=jnp.uint32)
    return jax.lax.bitcast_convert_type(
        words.reshape(*lead, spans * KEEP_LANES), jnp.int32)


def unpack_keep(words, lk: int):
    """:func:`pack_keep`'s inverse: (..., Lq, W) int32 -> (..., Lq, lk)
    bool."""
    *lead, width = words.shape
    spans = width // KEEP_LANES
    words = jax.lax.bitcast_convert_type(words, jnp.uint32).reshape(
        *lead, spans, 1, KEEP_LANES)
    bits = (words >> jnp.arange(32, dtype=jnp.uint32)[:, None]) & 1
    return bits.reshape(*lead, spans * KEEP_SPAN)[..., :lk] != 0


def keep_tiles_ok(bk: int) -> bool:
    """Whether K tiles of ``bk`` keys can read a packed selection: whole
    lanes of bits, and a tile never astride two spans."""
    return bk % KEEP_LANES == 0 and KEEP_SPAN % bk == 0


def _keep_block(ik, bk: int):
    """The block of words, along the last axis, that K tile ``ik``'s
    bits lie in."""
    return jax.lax.div(ik * bk, KEEP_SPAN)


def _keep_mask(keep_ref, ik, bk: int):
    """K tile ``ik``'s (bq, bk) bool mask from the (bq, KEEP_LANES)
    block of words that holds its bits."""
    words = keep_ref[0]
    first = jax.lax.div(jax.lax.rem(ik * bk, KEEP_SPAN), KEEP_LANES)
    lanes = [jax.lax.shift_right_logical(
        words, jnp.full_like(words, first + j)) & 1
        for j in range(bk // KEEP_LANES)]
    return jnp.concatenate(lanes, axis=1) != 0


def tile_vmem_bytes(bq: int, bk: int, d: int, itemsize: int,
                    dv: int | None = None) -> int:
    """What one grid step of the hungriest of the three kernels (dK/dV)
    may hold in VMEM at a (bq, bk) tile, q/k head size ``d``, v head
    size ``dv`` (``d`` where not given) and
    ``itemsize``-byte operands: the kernels' ``vmem_limit_bytes``, and
    what ``ring_attention`` keeps under :data:`VMEM_BUDGET` when it
    picks tiles.  Held to what Mosaic allocates when it compiles the
    kernels for a v5e (PR 25: 10 MiB at 1024×1024, d 64, bf16, where
    this says 18; 36 at 2048×2048 where this says 61; 23 at 1024×1024,
    d 256, f32 where this says 35)."""
    n = max(bq, bk)
    dd = d + (d if dv is None else dv)      # a q/k-sized and a v-sized block
    # Mosaic streams the elementwise chain between the products: what
    # stays is two f32 (bq, bk) tiles (scores, dP) and one in the
    # operand dtype for the next product (measured: 9 bytes an element)
    tiles = bq * bk * (2 * 4 + itemsize)
    # q, dO, k, v; the packed row state; two f32 blocks in or out (o, or
    # dk and dv) — each double-buffered by the pipeline
    blocks = 2 * (2 * n * dd * itemsize + n * 128 * 4 + n * dd * 4)
    scratch = 2 * n * 128 * 4 + n * dd * 4
    return (tiles + blocks + scratch) * 5 // 4     # a quarter of headroom


def _tile_live(q_start, k_start, bq: int, bk: int | None = None,
               window: int | None = None):
    """A (Q tile, K tile) pair is live iff the mask on global positions
    leaves it any probability: its first key is no later than its last
    query and, under a ``window``, its last key no further back than
    ``window - 1`` from its first query.  Python ints or traced
    scalars."""
    live = k_start <= q_start + (bq - 1)
    if window is not None:
        live = live & (k_start + (bk - 1) >= q_start - (window - 1))
    return live


def _tile_diagonal(q_start, k_start, bk: int):
    """The pair needs the causal mask iff its last key is later than
    its first query; a live pair that does not lies wholly in the past.
    (A dead pair always "needs" it.)"""
    return k_start + (bk - 1) > q_start


def _tile_trailing(q_start, k_start, bq: int, window: int):
    """The pair needs the window's mask iff its first key lies further
    back than ``window - 1`` from its last query: the window's trailing
    edge crosses it (or, of a dead pair, lies past it)."""
    return k_start < q_start + (bq - 1) - (window - 1)


def causal_tile_counts(lq: int, lk: int, bq: int, bk: int,
                       q_offset: int = 0, k_offset: int = 0,
                       window: int | None = None):
    """``(grid, live, masked)`` tile pairs of one head's causal call,
    under a sliding ``window`` where one is given (query ``i`` sees key
    ``j`` iff ``0 <= i - j < window``): how many grid steps there are,
    how many do any work, and how many of those build the mask (the
    diagonal crosses them, or the window's trailing edge).  Seq 8192 in
    1024×1024 tiles: 64 / 36 / 8; in 128×128 tiles 4,096 / 2,080 / 64.
    Seq 16,384 in 1024×1024 tiles: 256 / 136 / 16, and under a window
    of 2,048 256 / 45 / 30."""
    grid = live = masked = 0
    for q_start in range(q_offset, q_offset + lq, bq):
        for k_start in range(k_offset, k_offset + lk, bk):
            grid += 1
            if _tile_live(q_start, k_start, bq, bk, window):
                live += 1
                masked += bool(
                    _tile_diagonal(q_start, k_start, bk)
                    or (window is not None
                        and _tile_trailing(q_start, k_start, bq, window)))
    return grid, live, masked


def band_steps(bq: int, bk: int, window: int | None, nq: int, nk: int,
               offset_multiple: int = 1):
    """``(K tiles, Q tiles)``: the innermost grid dimension of the
    forward and dQ kernels and of the dK/dV kernel.  Without a window
    every tile, ``(nk, nq)``; under one the static lengths of the band
    that is walked in their place.  A Q row's ``bq + window - 1`` keys
    touch at most that many K tiles wherever the row's first key falls
    in a tile, and a K column's ``bk + window - 1`` queries that many Q
    tiles: ``ceil((bq + window - 2) / bk) + 1`` with nothing known of
    the offsets (4 at 1024 / 1024 / 2,048), one fewer where ``q_offset
    - k_offset`` is known to be a multiple of ``offset_multiple`` that
    the tiles share (3: a row's first key then falls on a known place
    in its tile).  Never more than the tiles there are."""
    if window is None:
        return nk, nq
    g = math.gcd(offset_multiple, bq, bk)
    # the latest place in its tile that a row's first key, a column's
    # first query, can fall on, and the positions that follow it
    keys = (bk - g) + (1 - window) % g + bq + window - 1
    queries = (bq - g) + bk + window - 1
    return min(nk, -(-keys // bk)), min(nq, -(-queries // bq))


def walked_steps(lq: int, lk: int, bq: int, bk: int,
                 window: int | None = None, offset_multiple: int = 1):
    """``(steps, band)`` of one head's forward or dQ call: the grid
    steps it walks, ``nq x nk`` without a window and ``nq x band`` under
    one, ``band`` (0 without a window) being the K tiles of
    :func:`band_steps`.  (The dK/dV call walks ``nk`` columns of the
    band's Q tiles: the same count at square tiles.)  Seq 16,384 in
    1024 x 1024 tiles: 256 and 0; under a window of 2,048 48 and 3 at
    offsets that are multiples of a tile, 64 and 4 at any."""
    nq, nk = lq // bq, lk // bk
    band = band_steps(bq, bk, window, nq, nk, offset_multiple)[0]
    return nq * band, 0 if window is None else band


def _first_k_tile(off_ref, iq, bq: int, bk: int, window: int):
    """Where Q row ``iq``'s band starts: the K tile that holds the
    first key its first query sees (tile 0 where that key lies before
    the block; past the block's last tile where the whole block lies
    behind the window)."""
    first_q = off_ref[0] - off_ref[1] + iq * bq - (window - 1)
    return jax.lax.div(jnp.maximum(first_q, 0), bk)


def _first_q_tile(off_ref, ik, bq: int, bk: int):
    """The first Q tile that sees K column ``ik``: the one that holds
    the query at the column's first key (tile 0 where that lies before
    the chunk).  Where a windowed column's band starts."""
    first_k = off_ref[1] - off_ref[0] + ik * bk
    return jax.lax.div(jnp.maximum(first_k, 0), bq)


def _on_live_tile(off_ref, iq, ik, bq: int, bk: int, causal: bool, body,
                  window: int | None = None, inside=None, keep_ref=None):
    """Run ``body(mask)`` for tile pair (iq, ik): not at all where the
    mask hides the whole pair, with the (bq, bk) bool mask where the
    diagonal or the window's trailing edge crosses it, and with ``None``
    where nothing is hidden (no score is -inf there, so the body drops
    its guards too).  ``inside``: under a window, whether the band's
    step stands for a tile of the block at all.  ``keep_ref``: the
    block of a selection's words that holds the tile's bits; every live
    pair then builds its mask from them, and the pairs the diagonal
    crosses from the positions too."""
    if not causal:
        body(None)
        return
    q_start = off_ref[0] + iq * bq
    k_start = off_ref[1] + ik * bk
    masked = _tile_diagonal(q_start, k_start, bk)
    if keep_ref is not None:
        live = _tile_live(q_start, k_start, bq)

        @pl.when(live & masked)
        def _():
            qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            body(_keep_mask(keep_ref, ik, bk) & (qpos >= kpos))

        @pl.when(live & jnp.logical_not(masked))
        def _():
            body(_keep_mask(keep_ref, ik, bk))

        return
    live = _tile_live(q_start, k_start, bq, bk, window)
    if window is not None:
        masked = masked | _tile_trailing(q_start, k_start, bq, window)
        live = live & inside

    @pl.when(live & masked)
    def _():
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        seen = qpos >= kpos
        if window is not None:
            seen = seen & (qpos - kpos < window)
        body(seen)

    # without a window a pair that needs no mask is live
    @pl.when(jnp.logical_not(masked) if window is None
             else live & jnp.logical_not(masked))
    def _():
        body(None)


def _on_band_tile(off_ref, row, step, bq: int, bk: int, n: int,
                  causal: bool, body, window: int | None = None,
                  q_major: bool = True, keep_ref=None):
    """:func:`_on_live_tile` for step ``step`` of Q row ``row`` over its
    ``n`` K tiles (forward, dQ), or with ``q_major`` false of K column
    ``row`` over its ``n`` Q tiles (dK/dV): tile ``step`` without a
    window, and under one the ``step``-th tile of the row's band."""
    tile, inside = step, None
    if window is not None:
        first = (_first_k_tile(off_ref, row, bq, bk, window) if q_major
                 else _first_q_tile(off_ref, row, bq, bk))
        tile = first + step
        inside = tile < n
    iq, ik = (row, tile) if q_major else (tile, row)
    _on_live_tile(off_ref, iq, ik, bq, bk, causal, body, window, inside,
                  keep_ref)


def _q_major_maps(bq: int, bk: int, causal: bool, nk: int,
                  window: int | None = None):
    """Index maps ``(q_row, kv_row)`` of the grid the forward and dQ
    kernels share: (B*H, nq, nk), and under a ``window`` (B*H, nq,
    band), step ``t`` of a row standing for K tile ``first + t``
    (:func:`_first_k_tile`).  The K/V map clamps the tile to the Q
    row's last live one (and to the block's last), so that the dead
    steps after it name a block that is in VMEM and fetch nothing of
    their own; a band's first step is live wherever any of the row's
    is."""
    def q_row(b, iq, ik, off_ref):
        return b, iq, 0

    def kv_row(b, iq, ik, off_ref):
        if window is not None:
            ik = jnp.minimum(_first_k_tile(off_ref, iq, bq, bk, window) + ik,
                             nk - 1)
        if causal:
            last_q = off_ref[0] - off_ref[1] + (iq + 1) * bq - 1
            ik = jnp.minimum(ik, jax.lax.div(jnp.maximum(last_q, 0), bk))
        return b, ik, 0

    return q_row, kv_row


def _k_major_maps(bq: int, bk: int, causal: bool, nq: int,
                  window: int | None = None):
    """Index maps ``(q_row, kv_row)`` of the dK/dV kernel's grid:
    (B*H, nk, nq), and under a ``window`` (B*H, nk, band), step ``t``
    of a column standing for Q tile ``first + t``
    (:func:`_first_q_tile`).  Without a window the Q map clamps ``iq``
    up to the K column's first live tile: the dead steps before it
    fetch that tile's blocks once, and no others.  Under one it clamps
    the band's tile down to the column's last live one: the last query
    that still sees the column's last key."""
    def q_row(b, ik, iq, off_ref):
        if causal:
            first = _first_q_tile(off_ref, ik, bq, bk)
        if window is not None:
            last_k = (off_ref[1] - off_ref[0] + (ik + 1) * bk - 1
                      + (window - 1))
            iq = jnp.minimum(jnp.minimum(first + iq, nq - 1),
                             jax.lax.div(jnp.maximum(last_k, 0), bq))
        elif causal:
            iq = jnp.maximum(iq, jnp.minimum(first, nq - 1))
        return b, iq, 0

    def kv_row(b, ik, iq, off_ref):
        return b, ik, 0

    return q_row, kv_row


def _keep_row(q_row, kv_row, bk: int, heads: int):
    """The index map of a selection's words beside a grid's ``(q_row,
    kv_row)``: the row of words of the ``heads`` heads' batch row, the
    Q tile that ``q_row`` names, and the block of words that holds the
    bits of the K tile that ``kv_row`` names — the maps' own clamps, so
    a dead step fetches no words of its own either."""
    def keep_row(*step):
        return (step[0] // heads, q_row(*step)[1],
                _keep_block(kv_row(*step)[1], bk))

    return keep_row


def _kernel_name(name: str, window: int | None, keep=None) -> str:
    if keep is not None:
        return name + "_sel"
    return name if window is None else name + "_win"


def _check_keep(causal: bool, window: int | None, keep, bh: int, lq: int,
                lk: int, bk: int) -> int:
    """The heads that share one row of the selection ``keep`` ((B, Lq,
    W) int32, :func:`pack_keep`'s); 1 without one."""
    if keep is None:
        return 1
    if not causal or window is not None:
        raise ValueError("a selection is a bound beside the causal one and "
                         "has no window beside it: it needs causal=True and "
                         f"window=None, got causal={causal}, window={window}")
    if not keep_tiles_ok(bk):
        raise ValueError(f"K tiles of {bk} keys cannot read a packed "
                         f"selection: a multiple of {KEEP_LANES} that "
                         f"divides {KEEP_SPAN}")
    width = KEEP_LANES * -(-lk // KEEP_SPAN)
    if (keep.ndim != 3 or keep.shape[1:] != (lq, width)
            or bh % keep.shape[0] or keep.dtype != jnp.int32):
        raise ValueError(f"a selection for {lq} queries on {lk} keys is "
                         f"(B, {lq}, {width}) int32 with B dividing "
                         f"{bh}, got {keep.shape} {keep.dtype}")
    return bh // keep.shape[0]


def _check_window(causal: bool, window: int | None) -> None:
    if window is not None and (not causal or window < 1):
        raise ValueError(f"a sliding window is a bound beside the causal "
                         f"one: it needs causal=True and window >= 1, got "
                         f"causal={causal}, window={window}")


def _compiler_params(bq: int, bk: int, d: int, dv: int, dtype):
    # the two outer dimensions are independent work items, only the
    # innermost carries scratch state — telling Mosaic lets it overlap
    # DMA with MXU work across grid steps instead of serializing the
    # whole grid
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        # never less than Mosaic's own default, which small tiles had
        vmem_limit_bytes=max(16 << 20, tile_vmem_bytes(
            bq, bk, d, jnp.dtype(dtype).itemsize, dv)))


def _flash_step_kernel(off_ref, q_ref, k_ref, v_ref, *refs, causal: bool,
                       scale: float, bq: int, bk: int, nk: int, first: bool,
                       last: bool, window: int | None = None,
                       selected: bool = False):
    """Grid: (B*H, nq, nk) — nk innermost so (m_s, l_s, acc) scratch
    carries across the K blocks of one Q block; under a ``window``
    (B*H, nq, band), the scratch started and finished on the band's
    first and last step.  ``refs`` are the block of a selection's
    words (where the call is ``selected``), the
    carried state in (packed m|l, o; none on a ring's ``first`` step,
    where scratch starts at -inf, 0, 0), the results and the scratch.
    A middle step's results are the state out: m_s and l_s repacked
    into one tile, so HBM sees a single state buffer, and the
    unnormalized numerator.  The ``last`` step's are the row's lse =
    m + log l in every lane of the tile (-inf for a row that saw no
    key) and the normalized o / l (0 for such a row), in fp32 and, if
    the caller asked, rounded to another type beside it.  Entry and
    exit run on every row, also one whose every tile is dead: it hands
    the carried state through unchanged."""
    keep_ref = None
    if selected:
        keep_ref, *refs = refs
    if not first:
        mli_ref, oi_ref, *refs = refs
    stat_ref, *o_refs, m_s, l_s, acc = refs
    step = pl.program_id(2)
    steps = pl.num_programs(2)

    @pl.when(step == 0)
    def _():
        if first:
            m_s[:, :] = jnp.full_like(m_s, _NEG_INF)
            l_s[:, :] = jnp.zeros_like(l_s)
            acc[:, :] = jnp.zeros_like(acc)
        else:
            ml = mli_ref[0]
            m_s[:, :] = ml[:, _M_LANE][:, None] + jnp.zeros_like(m_s)
            l_s[:, :] = ml[:, _L_LANE][:, None] + jnp.zeros_like(l_s)
            acc[:, :] = oi_ref[0].astype(jnp.float32)

    def accumulate(mask):
        s = jax.lax.dot_general(
            q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (bq, bk)
        if mask is not None:
            s = jnp.where(mask, s, _NEG_INF)
        m_prev = m_s[:, 0]                             # (bq,)
        l_prev = l_s[:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        # Fully-masked rows keep m == -inf; exp against a finite
        # stand-in.
        m_safe = (m_new if mask is None
                  else jnp.where(jnp.isfinite(m_new), m_new, 0.0))
        p = jnp.exp(s - m_safe[:, None])
        if mask is not None:
            p = jnp.where(mask, p, 0.0)
        alpha = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_safe),
                          0.0)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1)
        pv = jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)        # (bq, dv)
        m_s[:, :] = m_new[:, None] + jnp.zeros_like(m_s)
        l_s[:, :] = l_new[:, None] + jnp.zeros_like(l_s)
        acc[:, :] = acc[:, :] * alpha[:, None] + pv

    _on_band_tile(off_ref, pl.program_id(1), step, bq, bk, nk, causal,
                  accumulate, window, keep_ref=keep_ref)

    @pl.when(step == steps - 1)
    def _():
        if last:
            l = l_s[:, :]                   # lane-replicated, as m_s
            seen = l > 0.0
            stat_ref[0] = jnp.where(
                seen, m_s[:, :] + jnp.log(jnp.where(seen, l, 1.0)),
                _NEG_INF)
            den = l_s[:, 0]
            out = acc[:, :] / jnp.where(den == 0.0, 1.0, den)[:, None]
            for o_ref in o_refs:
                o_ref[0] = out.astype(o_ref.dtype)
        else:
            stat_ref[0] = jnp.concatenate(
                [m_s[:, :_L_LANE], l_s[:, _L_LANE:]], axis=1)
            o_refs[0][0] = acc[:, :]


def _tiles(block_q, block_k, lq, lk):
    bq = min(block_q, lq)
    bk = min(block_k, lk)
    if lq % bq or lk % bk:
        raise ValueError(f"block sizes ({bq}, {bk}) must divide the "
                         f"sequence chunks ({lq}, {lk})")
    return bq, bk


def _pack_rows(a, b, bh, lq):
    """Two per-row f32 scalars (m|l for the forward kernel, lse|delta
    for the backward kernels) as one (BH, Lq, 128) tile buffer: ``a``
    in lanes 0..63, ``b`` in lanes 64..127."""
    return jnp.concatenate(
        [jnp.broadcast_to(a[..., None], (bh, lq, _L_LANE)),
         jnp.broadcast_to(b[..., None], (bh, lq, 128 - _L_LANE))],
        axis=-1)


# The three entry points are jitted so that a model of n layers traces
# and lowers each kernel once a shape, not n times (gpt2-124m.s8192's
# warm set-up on the chip's host: 45-53 s without; PERF.md, PR 29), and
# inlined, so that the caller's program holds the kernels and no call:
# with a call left in it, XLA kept other buffers in VMEM around the
# kernels and the expert cell's compiled step gave NaN (PERF.md,
# section 7).
_entry_point = functools.partial(jax.jit, inline=True)


@_entry_point(static_argnames=(
    "causal", "block_q", "block_k", "last", "interpret", "window",
    "offset_multiple"))
def flash_fwd_step(q, k, v, state, q_offset, k_offset, *,
                   causal: bool = True, block_q: int = 128,
                   block_k: int = 128, last: bool = False,
                   interpret: bool | None = None,
                   window: int | None = None, offset_multiple: int = 1,
                   keep=None):
    """One step of a ring's forward pass: attend local Q against one
    KV block.

    q: (BH, Lq, D); k: (BH, Lk, D); v: (BH, Lk, Dv), Dv any size (a
    latent-attention head has 192 for q/k and 128 for v).
    q_offset / k_offset: global positions of q[:,0]/k[:,0] (traced OK).
    ``window``: a static sliding window on those positions (query ``i``
    sees key ``j`` iff ``0 <= i - j < window``), or None.  Under one the
    grid walks a row's band of K tiles and not all ``nk``
    (:func:`band_steps`), the band one tile shorter where the caller
    knows ``q_offset - k_offset`` to be a multiple of
    ``offset_multiple`` (static; a ring's offsets are multiples of its
    chunk) that the tiles share.
    ``keep``: a selection, (B, Lq, W) int32 (:func:`pack_keep`'s) with
    B dividing BH, a row for the BH / B heads that follow each other:
    query ``i`` sees key ``j`` iff ``j <= i`` and bit ``j`` of its row
    is set; with ``causal`` and no ``window``.  None: no such bound.
    ``state``: the carried ``(m, l, o)`` (m, l: (BH, Lq) fp32 running
    max / denominator; o: (BH, Lq, Dv) fp32 unnormalized numerator),
    or None on the ring's first step — the kernel then starts from
    (-inf, 0, 0) in scratch and reads no state from HBM.  Returns the
    updated ``(m, l, o)``; on the ``last`` step instead
    ``(out, lse, out_q)``: the normalized (BH, Lq, Dv) fp32 result
    o / l (0 for a row that saw no key), the (BH, Lq) fp32
    lse = m + log l (-inf for such a row), and ``out`` rounded to q's
    type (``out`` itself where that is fp32), all computed where the
    state is, in VMEM.  A one-step ring is first and last at once and
    its state never exists in HBM.  Forward only.
    """
    bh, lq, d = q.shape
    _, lk, dv = v.shape
    bq, bk = _tiles(block_q, block_k, lq, lk)
    interpret = pallas_interpret(interpret)
    scale = 1.0 / (d ** 0.5)
    offs = jnp.asarray([q_offset, k_offset], jnp.int32)
    first = state is None

    _check_window(causal, window)
    heads = _check_keep(causal, window, keep, bh, lq, lk, bk)
    nq, nk = lq // bq, lk // bk
    kernel = functools.partial(_flash_step_kernel, causal=causal,
                               scale=scale, bq=bq, bk=bk, nk=nk, first=first,
                               last=last, window=window)
    if keep is not None:
        kernel = functools.partial(kernel, selected=True)
    q_row, kv_row = _q_major_maps(bq, bk, causal, nk, window)
    steps = band_steps(bq, bk, window, nq, nk, offset_multiple)[0]
    operands = [q, k, v]
    in_specs = [
        pl.BlockSpec((1, bq, d), q_row),      # q
        pl.BlockSpec((1, bk, d), kv_row),     # k
        pl.BlockSpec((1, bk, dv), kv_row),    # v
    ]
    if keep is not None:
        operands.append(keep)
        in_specs.append(pl.BlockSpec(
            (1, bq, KEEP_LANES), _keep_row(q_row, kv_row, bk, heads)))
    if not first:
        m, l, o = state
        operands += [_pack_rows(m, l, bh, lq), o]
        in_specs += [
            pl.BlockSpec((1, bq, 128), q_row),    # m|l
            pl.BlockSpec((1, bq, dv), q_row),     # o
        ]

    o_block = pl.BlockSpec((1, bq, dv), q_row)
    o_shapes = [jax.ShapeDtypeStruct((bh, lq, dv), jnp.float32)]
    if last and q.dtype != jnp.float32:
        o_shapes.append(jax.ShapeDtypeStruct((bh, lq, dv), q.dtype))

    stat, *o = pl.pallas_call(
        kernel,
        name=_kernel_name("hvd_flash_fwd", window, keep),
        # the offsets are prefetched scalars: the K/V index map reads
        # them to skip the fetch of dead tiles
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bh, nq, steps),
            in_specs=in_specs,
            # m|l and o, or lse and o / l
            out_specs=[pl.BlockSpec((1, bq, 128), q_row)]
            + [o_block] * len(o_shapes),
            scratch_shapes=[
                pltpu.VMEM((bq, 128), jnp.float32),   # running max
                pltpu.VMEM((bq, 128), jnp.float32),   # running denominator
                pltpu.VMEM((bq, dv), jnp.float32),    # numerator accumulator
            ]),
        out_shape=[jax.ShapeDtypeStruct((bh, lq, 128), jnp.float32)]
        + o_shapes,
        compiler_params=_compiler_params(bq, bk, d, dv, q.dtype),
        interpret=interpret,
    )(offs, *operands)
    if last:
        return o[0], stat[..., _M_LANE], o[-1]
    return stat[..., _M_LANE], stat[..., _L_LANE], o[0]


def _recomputed_p_ds(q, k, v, do, ld, mask, scale):
    """One tile's softmax probabilities from the saved per-row LSE, and
    dS = P ∘ (dP − delta) · scale — what both backward kernels start
    from.  The full score matrix is never materialized."""
    lse = ld[:, _M_LANE]
    delta = ld[:, _L_LANE]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale  # (bq, bk)
    if mask is None:
        # every key of the tile is visible to every row: lse is finite
        p = jnp.exp(s - lse[:, None])
    else:
        # fully-masked rows carry lse = -inf
        seen = jnp.isfinite(lse)
        p = jnp.where(mask & seen[:, None],
                      jnp.exp(s - jnp.where(seen, lse, 0.0)[:, None]), 0.0)
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)        # (bq, bk)
    return p, p * (dp - delta[:, None]) * scale


def _flash_bwd_dq_kernel(off_ref, q_ref, k_ref, v_ref, do_ref, ld_ref,
                         *refs, causal: bool, scale: float,
                         bq: int, bk: int, nk: int,
                         window: int | None = None):
    """dQ backward: grid (B*H, nq, nk), under a ``window`` (B*H, nq,
    band), innermost so dq_acc carries across the K blocks of one Q
    block (zero for a row whose every tile is dead).  ``refs``: the
    block of a selection's words where there is one, dQ out, its
    accumulator."""
    *keep_ref, dq_ref, dq_acc = refs
    keep_ref = keep_ref[0] if keep_ref else None
    step = pl.program_id(2)
    steps = pl.num_programs(2)

    @pl.when(step == 0)
    def _():
        dq_acc[:, :] = jnp.zeros_like(dq_acc)

    def accumulate(mask):
        k = k_ref[0]                                   # (bk, d)
        _, ds = _recomputed_p_ds(q_ref[0], k, v_ref[0], do_ref[0],
                                 ld_ref[0], mask, scale)
        dq_acc[:, :] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)        # (bq, d)

    _on_band_tile(off_ref, pl.program_id(1), step, bq, bk, nk, causal,
                  accumulate, window, keep_ref=keep_ref)

    @pl.when(step == steps - 1)
    def _():
        dq_ref[0] = dq_acc[:, :].astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(off_ref, q_ref, k_ref, v_ref, do_ref, ld_ref,
                          *refs, causal: bool,
                          scale: float, bq: int, bk: int, nq: int,
                          window: int | None = None):
    """dK/dV backward: grid (B*H, nk, nq), under a ``window`` (B*H, nk,
    band), innermost so the dk/dv accumulators carry across the Q
    blocks of one KV block.  ``refs``: the block of a selection's words
    where there is one, dK and dV out, their accumulators."""
    *keep_ref, dk_ref, dv_ref, dk_acc, dv_acc = refs
    keep_ref = keep_ref[0] if keep_ref else None
    step = pl.program_id(2)
    steps = pl.num_programs(2)

    @pl.when(step == 0)
    def _():
        dk_acc[:, :] = jnp.zeros_like(dk_acc)
        dv_acc[:, :] = jnp.zeros_like(dv_acc)

    def accumulate(mask):
        q = q_ref[0]                                   # (bq, d)
        do = do_ref[0]                                 # (bq, dv)
        p, ds = _recomputed_p_ds(q, k_ref[0], v_ref[0], do, ld_ref[0],
                                 mask, scale)
        dv_acc[:, :] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)        # (bk, dv)
        dk_acc[:, :] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)        # (bk, d)

    _on_band_tile(off_ref, pl.program_id(1), step, bq, bk, nq, causal,
                  accumulate, window, q_major=False, keep_ref=keep_ref)

    @pl.when(step == steps - 1)
    def _():
        dk_ref[0] = dk_acc[:, :].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:, :].astype(dv_ref.dtype)


@_entry_point(static_argnames=(
    "causal", "block_q", "block_k", "out_dtype", "interpret", "window",
    "offset_multiple"))
def flash_bwd_dq(q, k, v, do, lse, delta, q_offset, k_offset, *,
                 causal: bool = True, block_q: int = 128,
                 block_k: int = 128, out_dtype=jnp.float32,
                 interpret: bool | None = None,
                 window: int | None = None, offset_multiple: int = 1,
                 keep=None):
    """Flash-attention dQ for one (local Q, one KV block) pair.

    q: (BH, Lq, D); k: (BH, Lk, D); v: (BH, Lk, Dv); do: (BH, Lq, Dv)
    upstream grad in
    the matmul dtype; lse: (BH, Lq) fp32 saved log-sum-exp rows
    (m + log l from the forward); delta: (BH, Lq) fp32 rowsum(dO * O).
    Returns (BH, Lq, D) in ``out_dtype`` — the dQ contribution of this
    KV block: fp32 where the caller sums over ring steps, the operands'
    type in a one-step ring (the accumulator is fp32 in VMEM either
    way and is rounded once, as it is written out).  ``window``,
    ``offset_multiple`` and ``keep`` (the selection the forward pass
    ran under): as :func:`flash_fwd_step`'s.
    """
    bh, lq, d = q.shape
    _, lk, dv = v.shape
    bq, bk = _tiles(block_q, block_k, lq, lk)
    interpret = pallas_interpret(interpret)
    scale = 1.0 / (d ** 0.5)
    offs = jnp.asarray([q_offset, k_offset], jnp.int32)
    ld = _pack_rows(lse, delta, bh, lq)
    _check_window(causal, window)
    heads = _check_keep(causal, window, keep, bh, lq, lk, bk)
    nq, nk = lq // bq, lk // bk
    kernel = functools.partial(_flash_bwd_dq_kernel, causal=causal,
                               scale=scale, bq=bq, bk=bk, nk=nk,
                               window=window)
    q_row, kv_row = _q_major_maps(bq, bk, causal, nk, window)
    steps = band_steps(bq, bk, window, nq, nk, offset_multiple)[0]
    selection = [] if keep is None else [(keep, pl.BlockSpec(
        (1, bq, KEEP_LANES), _keep_row(q_row, kv_row, bk, heads)))]

    return pl.pallas_call(
        kernel,
        name=_kernel_name("hvd_flash_bwd_dq", window, keep),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bh, nq, steps),
            in_specs=[
                pl.BlockSpec((1, bq, d), q_row),      # q
                pl.BlockSpec((1, bk, d), kv_row),     # k
                pl.BlockSpec((1, bk, dv), kv_row),    # v
                pl.BlockSpec((1, bq, dv), q_row),     # do
                pl.BlockSpec((1, bq, 128), q_row),    # ld
            ] + [spec for _, spec in selection],
            out_specs=pl.BlockSpec((1, bq, d), q_row),
            scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((bh, lq, d), out_dtype),
        compiler_params=_compiler_params(bq, bk, d, dv, q.dtype),
        interpret=interpret,
    )(offs, q, k, v, do, ld, *(words for words, _ in selection))


@_entry_point(static_argnames=(
    "causal", "block_q", "block_k", "out_dtype", "interpret", "window",
    "offset_multiple"))
def flash_bwd_dkv(q, k, v, do, lse, delta, q_offset, k_offset, *,
                  causal: bool = True, block_q: int = 128,
                  block_k: int = 128, out_dtype=jnp.float32,
                  interpret: bool | None = None,
                  window: int | None = None, offset_multiple: int = 1,
                  keep=None):
    """Flash-attention (dK, dV) for one (local Q, one KV block) pair.

    Same contract as :func:`flash_bwd_dq`; returns
    ((BH, Lk, D), (BH, Lk, Dv)) in ``out_dtype`` — this Q chunk's
    contribution to the block's dK/dV (callers in a longer ring
    accumulate in fp32 while rotating).  Under a ``window`` the grid
    walks a K column's band of Q tiles.
    """
    bh, lq, d = q.shape
    _, lk, dv = v.shape
    bq, bk = _tiles(block_q, block_k, lq, lk)
    interpret = pallas_interpret(interpret)
    scale = 1.0 / (d ** 0.5)
    offs = jnp.asarray([q_offset, k_offset], jnp.int32)
    ld = _pack_rows(lse, delta, bh, lq)
    _check_window(causal, window)
    heads = _check_keep(causal, window, keep, bh, lq, lk, bk)
    nq, nk = lq // bq, lk // bk
    kernel = functools.partial(_flash_bwd_dkv_kernel, causal=causal,
                               scale=scale, bq=bq, bk=bk, nq=nq,
                               window=window)
    q_row, kv_row = _k_major_maps(bq, bk, causal, nq, window)
    steps = band_steps(bq, bk, window, nq, nk, offset_multiple)[1]
    selection = [] if keep is None else [(keep, pl.BlockSpec(
        (1, bq, KEEP_LANES), _keep_row(q_row, kv_row, bk, heads)))]

    return pl.pallas_call(
        kernel,
        name=_kernel_name("hvd_flash_bwd_dkv", window, keep),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bh, nk, steps),
            in_specs=[
                pl.BlockSpec((1, bq, d), q_row),      # q
                pl.BlockSpec((1, bk, d), kv_row),     # k
                pl.BlockSpec((1, bk, dv), kv_row),    # v
                pl.BlockSpec((1, bq, dv), q_row),     # do
                pl.BlockSpec((1, bq, 128), q_row),    # ld
            ] + [spec for _, spec in selection],
            out_specs=[
                pl.BlockSpec((1, bk, d), kv_row),
                pl.BlockSpec((1, bk, dv), kv_row),
            ],
            scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                            pltpu.VMEM((bk, dv), jnp.float32)]),
        out_shape=[
            jax.ShapeDtypeStruct((bh, lk, d), out_dtype),
            jax.ShapeDtypeStruct((bh, lk, dv), out_dtype),
        ],
        compiler_params=_compiler_params(bq, bk, d, dv, q.dtype),
        interpret=interpret,
    )(offs, q, k, v, do, ld, *(words for words, _ in selection))
