"""Ring attention: sequence/context parallelism over a mesh axis.

Absent from the reference (SURVEY §5.7 — it predates the technique);
built here as a first-class TPU capability: the sequence dimension is
sharded over the ``sp`` mesh axis, and each device computes blockwise
(flash-style, online-softmax) attention against its local KV block
while KV blocks rotate around the ring with `lax.ppermute` — the
rotation overlaps with the attention compute of the previous block, so
ICI transfer hides behind the MXU (Liu et al., "Ring Attention with
Blockwise Transformers", and the jax-ml scaling-book collective recipe).

Two paths over the same packed (B*H, L, D) operands, chosen from the
shape alone: ``impl="xla"`` is the pure-JAX online-softmax step
(XLA fuses it well — the safe fallback everywhere), and
``impl="pallas"`` is the hand-tiled flash kernel
(:mod:`horovod_tpu.ops.pallas_attention`) that keeps softmax state in
VMEM scratch and feeds the MXU with aligned blocks, its tiles picked
from the chunk length and the VMEM budget (:func:`_block_sizes`).  The
default picks by score-block size on TPU (:func:`auto_impl`); a chunk
length with no aligned divisor raises when pallas was asked for and
logs once when the pick was automatic.  The pallas path is
differentiable through a ring-level custom VJP: the forward saves only
(q, k, v, out, lse) and the backward is a second ring pass over
hand-written saved-LSE flash backward kernels, with dK/dV accumulators
rotating alongside KV — no O(Lq·Lk) score block is ever materialized
in either direction.

What the Pallas ring's ends do.  The softmax state (m, l, o) and the
fp32 dK/dV accumulators exist in HBM only between steps: the first
forward step starts the state in the kernel and the last finishes it
there (``out`` and ``lse`` come out of the kernel, ``out`` also in the
operands' type); the first backward step's contributions are the
accumulators; K and V stop rotating once the last step holds its block,
and only dK and dV make the ``sp``-th rotation that brings each block's
gradient home.  A ring of one (``sp`` = 1, a Python int at trace time)
has only ends: one forward call, the two backward kernels writing the
operands' type, no loop, no ``ppermute``, nothing carried.

What a recomputed block keeps.  Told that its caller is ``recomputed``,
the forward rule names its residuals ``out`` and ``lse``
(:data:`KEPT_NAMES`), so a ``jax.checkpoint`` whose policy saves those
names (``transformer._remat_block``) keeps them from the forward pass,
and takes what it returns from the kept ``out``: the replay in the
backward pass then holds no forward kernel at all.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from horovod_tpu.common import logging as _log

# The forward rule's residuals ``out`` (fp32) and ``lse`` under
# ``jax.ad_checkpoint.checkpoint_name``, given where the caller's block
# is recomputed: what that block's policy keeps beside its input.
KEPT_NAMES = ("hvd_attn_out", "hvd_attn_lse")
# A selection (``keep``) under that same mechanism: the caller that makes
# one in a recomputed block names it so, and its policy keeps it, so
# that the backward kernels read the selection the forward pass ran
# under and nothing makes it a second time.
KEPT_SELECTION = "hvd_dsa_keep"


def xla_block_step(q, k, v, m, l, o, q_offset, k_offset, *,
                   causal: bool, window: int | None = None, keep=None):
    """One online-softmax accumulation in the packed layout.

    q: (BH, Lq, D); k: (BH, Lk, D); v: (BH, Lk, Dv), Dv any size;
    m/l: (BH, Lq) fp32 running max/denominator; o: (BH, Lq, Dv) fp32
    unnormalized numerator.
    q_offset/k_offset: global positions of q[:, 0] / k[:, 0].
    ``window``: query ``i`` sees key ``j`` iff ``0 <= i - j < window``
    (a second bound beside the causal one; None: the causal bound alone).
    ``keep``: (B, Lq, Lk) bool with B dividing BH, this block's part of
    a selection, one row for the BH / B heads that follow each other: a
    third bound, query ``i`` sees key ``j`` only where it is set.
    Matmuls stay in the input dtype (bf16-friendly), softmax state fp32.
    """
    lq, lk = q.shape[1], k.shape[1]
    scale = 1.0 / (q.shape[-1] ** 0.5)
    s = jnp.einsum("bqd,bkd->bqk", q, k).astype(jnp.float32) * scale
    if causal:
        qpos = q_offset + jnp.arange(lq)
        kpos = k_offset + jnp.arange(lk)
        seen = qpos[:, None] >= kpos[None, :]
        if window is not None:
            seen = seen & (qpos[:, None] - kpos[None, :] < window)
        s = jnp.where(seen, s, -jnp.inf)
    if keep is not None:
        rows = keep.shape[0]
        s = jnp.where(keep[:, None], s.reshape(rows, -1, lq, lk),
                      -jnp.inf).reshape(s.shape)
    m_cur = jnp.max(s, axis=-1)                      # (BH, Lq)
    m_new = jnp.maximum(m, m_cur)
    # guard fully-masked rows (max = -inf)
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.exp(s - m_safe[..., None])
    p = jnp.where(jnp.isfinite(s), p, 0.0)
    alpha = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
    l_new = l * alpha + jnp.sum(p, axis=-1)
    pv = jnp.einsum("bqk,bkd->bqd", p.astype(v.dtype), v).astype(jnp.float32)
    o_new = o * alpha[..., None] + pv
    return m_new, l_new, o_new


_warned_untiled: set = set()


_TILE_LADDER = (1024, 512, 256, 128, 64, 32, 16, 8)


def _pick_block(n: int) -> int | None:
    """Largest tile on the ladder dividing n — up to 1024, because a
    grid step of the kernels costs about half a microsecond whatever
    its tile (``PERF.md``, PR 25).  None if there is none —
    ``ring_attention`` then raises for an explicit ``impl="pallas"``
    and logs once for an automatic pick."""
    return next((c for c in _TILE_LADDER if n % c == 0), None)


# Under a window the backward kernels' tiles are cut to the band: the
# largest edge on the ladder within a quarter of the window, and never
# under this one (measured on a v5e at head size 128, bf16, ``PERF.md``
# section 6, PR 36: at a window of 2,048 dQ and dK/dV take 7.60 and
# 9.55 ms a call of 16,384 tokens in 1024 x 1024 tiles, 6.89 and 8.86
# in 512 x 512, 11.6 and 14.5 in 256 x 256, which are all fixed cost;
# at 4,096 they are faster at 1024, at 1,024 at 512).  The forward
# kernel keeps the chunk's tiles: its row statistics cost by the step,
# and every window measured runs it fastest at 1024.
_WINDOW_EDGE = 512


def _block_sizes(lc: int, lk: int, d: int, itemsize: int,
                 dv: int | None = None, window: int | None = None,
                 backward: bool = False):
    """(block_q, block_k) for the Pallas kernels at q/k head size ``d``,
    v head size ``dv`` (``d`` where not given) and
    ``itemsize``-byte operands: the largest tile on the ladder for
    each side — for the ``backward`` kernels under a ``window`` no
    larger than the band's edge (:data:`_WINDOW_EDGE`) — stepped down
    the ladder (K first) while the kernels would ask for more than
    ``VMEM_BUDGET``.  Returns (None, _) when no aligned tiling exists
    for the Q chunk."""
    from horovod_tpu.ops.pallas_attention import (VMEM_BUDGET,
                                                  tile_vmem_bytes)

    bq, bk = _pick_block(lc), _pick_block(lk)
    if backward and window is not None and bq and bk:
        edge = max(_WINDOW_EDGE, next(
            (c for c in _TILE_LADDER if 4 * c <= window), 0))
        bq, bk = min(bq, edge), min(bk, edge)
    while (bq and bk and max(bq, bk) > 8
           and tile_vmem_bytes(bq, bk, d, itemsize, dv) > VMEM_BUDGET):
        if bk >= bq:
            bk //= 2
        else:
            bq //= 2
    return bq, bk


# One ring step's fp32 score and softmax bytes up to which auto_impl
# picks XLA.  The threshold is older than the kernels it chooses
# between: on a v5e one call's forward and backward at
# (16, 1024, 12, 64), which it sends to XLA, takes 6.2 ms through the
# kernels and 7.7 through XLA (``PERF.md``, PR 29); seq 2048 and 4096
# are not measured, and moving it waits for a benchmark cell on each
# side (``ROADMAP.md`` S4 a).
XLA_SCORE_BYTES = 4 << 30


def auto_impl(batch: int, heads: int, seq_q: int,
              seq_k: int | None = None) -> str:
    """Which attention impl the auto heuristic picks for one ring step
    of this shape on TPU.  The XLA step materializes fp32 scores plus
    an fp32 softmax transient, hence 8 bytes per score element."""
    seq_k = seq_q if seq_k is None else seq_k
    score_bytes = 8 * batch * heads * seq_q * seq_k
    return "xla" if score_bytes <= XLA_SCORE_BYTES else "pallas"


def _ring_offsets(j, axis_name, lc, causal):
    """Global positions of the local Q chunk and of ring step j's KV
    block.  They feed only the causal mask: a non-causal trace holds no
    axis_index chain, and neither does a ring of one."""
    sp = lax.axis_size(axis_name)
    if not causal or sp == 1:
        return 0, 0
    idx = lax.axis_index(axis_name)
    return idx * lc, ((idx - j) % sp) * lc


def _ring_rotate(axis_name, *blocks):
    sp = lax.axis_size(axis_name)
    rot = [(i, (i + 1) % sp) for i in range(sp)]
    return tuple(lax.ppermute(x, axis_name, rot) for x in blocks)


def _ring_flash_fwd_impl(qp, kp, vp, axis_name, causal, tiles,
                         window=None, keep=None):
    """Pallas ring forward, returning (normalized fp32 out, lse, out in
    the operands' type).

    qp/kp: packed (B*H, Lc, D), vp: (B*H, Lc, Dv).  lse = m + log(l)
    per row — the one
    O(L) residual the saved-LSE backward needs (fully-masked rows keep
    lse = -inf).  The softmax state goes through HBM only between
    steps: the first step's kernel starts it, the last step's finishes
    it, and KV stops rotating once the last step holds its block.  A
    ring of one is one kernel call and nothing else.
    """
    from horovod_tpu.ops.pallas_attention import flash_fwd_step

    sp = lax.axis_size(axis_name)
    lc = qp.shape[1]
    bq, bk = tiles[0]

    def step(j, state, kj, vj, last=False):
        qo, ko = _ring_offsets(j, axis_name, lc, causal)
        # a ring's offsets are multiples of its chunk
        # (a selection runs a ring of one: the whole of it is this step's)
        return flash_fwd_step(qp, kj, vj, state, qo, ko, causal=causal,
                              block_q=bq, block_k=bk, last=last,
                              window=window, offset_multiple=lc, keep=keep)

    if sp == 1:
        return step(0, None, kp, vp, last=True)

    def middle(j, carry):
        *state, kj, vj = carry
        return (*step(j, state, kj, vj), *_ring_rotate(axis_name, kj, vj))

    carry = (*step(0, None, kp, vp), *_ring_rotate(axis_name, kp, vp))
    *state, kj, vj = lax.fori_loop(1, sp - 1, middle, carry)
    return step(sp - 1, state, kj, vj, last=True)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _ring_flash(qp, kp, vp, axis_name, causal, tiles, recomputed,
                window=None, keep=None):
    """Differentiable Pallas ring attention on packed (B*H, Lc, D)
    operands, returning (B*H, Lc, Dv) in their type: forward saves only
    (q, k, v, out, lse), ``out`` in fp32 (``delta`` = rowsum(dO ∘ out)
    reads it); backward is a
    second ring pass over the saved-LSE flash backward kernels
    (:func:`horovod_tpu.ops.pallas_attention.flash_bwd_dq` / ``_dkv``),
    with dK/dV accumulators rotating alongside KV so each block's
    gradient arrives home after the full cycle (in a ring of one it is
    home already: nothing rotates).  Nothing O(Lq·Lk) is
    ever materialized.

    ``recomputed`` says the call stands in a block that is run again in
    the backward pass under a policy that keeps :data:`KEPT_NAMES`.  The
    forward rule then gives ``out`` and ``lse`` those names and returns
    the kept ``out`` rounded to the operands' type, which is what the
    kernel's own third result is, bit for bit: the replay is left with
    nothing that reads the kernel and runs none.  Both halves are
    needed: with the rule returning the kernel's result the replay
    runs the kernel for it, names or no names.

    ``tiles``: ``((block_q, block_k) of the forward kernel, of the two
    backward kernels)``.  ``window`` (static) is the sliding window on
    global positions that all three kernels mask and skip tiles by, or
    None.  ``keep``: a packed selection ((B, Lc, W) int32) that all
    three kernels mask by, the backward ones reading the forward pass's,
    or None; it takes no gradient."""
    return _ring_flash_fwd_impl(qp, kp, vp, axis_name, causal, tiles,
                                window, keep)[2]


def _ring_flash_fwd(qp, kp, vp, axis_name, causal, tiles, recomputed,
                    window, keep):
    out, lse, out_q = _ring_flash_fwd_impl(qp, kp, vp, axis_name, causal,
                                           tiles, window, keep)
    if recomputed:
        out, lse = map(checkpoint_name, (out, lse), KEPT_NAMES)
        out_q = out.astype(qp.dtype)
    return out_q, (qp, kp, vp, out, lse, keep)


def _ring_flash_bwd(axis_name, causal, tiles, recomputed, window, res,
                    dout):
    from horovod_tpu.ops.pallas_attention import (flash_bwd_dkv,
                                                  flash_bwd_dq)

    qp, kp, vp, out, lse, keep = res
    sp = lax.axis_size(axis_name)
    lc = qp.shape[1]
    bq, bk = tiles[1]
    # dout comes in the operands' type, the products' (bf16-safe); out
    # and delta are fp32
    delta = jnp.sum(dout.astype(jnp.float32) * out, axis=-1)   # (BH, Lc)

    def step(j, kj, vj, out_dtype=jnp.float32):
        """This step's (dQ, dK, dV) contributions."""
        qo, ko = _ring_offsets(j, axis_name, lc, causal)
        tiles = dict(causal=causal, block_q=bq, block_k=bk,
                     out_dtype=out_dtype, window=window, offset_multiple=lc,
                     keep=keep)
        return (flash_bwd_dq(qp, kj, vj, dout, lse, delta, qo, ko, **tiles),
                *flash_bwd_dkv(qp, kj, vj, dout, lse, delta, qo, ko,
                               **tiles))

    if sp == 1:
        # every block is at home and nothing is summed over steps: the
        # kernels round their fp32 accumulators once, as they write
        dq, dk, dv = step(0, kp, vp, qp.dtype)
        return dq, dk.astype(kp.dtype), dv.astype(vp.dtype), None

    def middle(j, carry):
        dq, kj, vj, dkj, dvj = carry
        dq_p, dk_p, dv_p = step(j, kj, vj)
        # KV and its gradient accumulators rotate together
        return (dq + dq_p, *_ring_rotate(axis_name, kj, vj, dkj + dk_p,
                                         dvj + dv_p))

    # the first step's contributions are the accumulators
    dq, dk, dv = step(0, kp, vp)
    dq, kj, vj, dkj, dvj = lax.fori_loop(
        1, sp - 1, middle, (dq, *_ring_rotate(axis_name, kp, vp, dk, dv)))
    dq_p, dk_p, dv_p = step(sp - 1, kj, vj)
    # KV stays where the last step used it; its gradient's sp-th
    # rotation brings each block's home
    dk, dv = _ring_rotate(axis_name, dkj + dk_p, dvj + dv_p)
    return ((dq + dq_p).astype(qp.dtype), dk.astype(kp.dtype),
            dv.astype(vp.dtype), None)


_ring_flash.defvjp(_ring_flash_fwd, _ring_flash_bwd)


def ring_attention(q, k, v, axis_name: str = "sp", causal: bool = True,
                   impl: str | None = None, layout: str = "contiguous",
                   recomputed: bool = False, window: int | None = None,
                   keep=None):
    """Multi-head attention with the sequence sharded over ``axis_name``.

    q, k: (B, Lc, H, D), v: (B, Lc, H, Dv) — the local sequence chunk
    (global L = Lc * sp); Dv may differ from D (latent attention: 192
    and 128).  Returns (B, Lc, H, Dv).  Must run inside shard_map/pjit with
    ``axis_name`` a mesh axis; with axis size 1 it degrades to plain
    blockwise attention.  ``impl``: "pallas" | "xla" | None (auto:
    pallas on TPU, xla elsewhere).  ``recomputed``: the caller's block
    is run again in the backward pass under a policy that keeps
    :data:`KEPT_NAMES` (the Pallas path's ``out`` and ``lse``; see
    :func:`_ring_flash`).  The XLA path has no names: a recomputed block
    keeps only its input there.

    ``window``: a sliding window on global positions, a static Python
    int: query ``i`` sees key ``j`` iff ``0 <= i - j < window`` (itself
    and the ``window - 1`` before it), a second bound beside the causal
    one, which it needs.  Both paths mask by it; the kernels also skip
    and do not fetch the tile pairs it hides, and carry names of their
    own (``hvd_flash_fwd_win`` ...).  Over a ring every step still
    runs: whole steps behind the window are not skipped.  ``None``: the
    causal bound alone, traced as before there was a window.

    ``keep``: a selection, a third bound that is data and not
    positions: (B, Lc, W) int32, one bit a (query, key) pair in the
    packed form of ``pallas_attention.pack_keep``, one row of words a
    query, shared by all heads; query ``i`` sees key ``j`` iff ``j <=
    i`` and its bit is set.  Both paths mask by it (the kernels then
    carry the names ``hvd_flash_fwd_sel`` ...), the backward pass by
    the forward pass's; it takes no gradient.  It needs ``causal``, no
    ``window``, the contiguous layout and an ``axis_name`` of size 1: a
    row's bits are over the keys of the whole sequence, and which of
    them a ring step's block holds is not built.  ``None``: no such
    bound, traced as before there was one.

    ``layout``: how the global sequence maps onto ranks.

    * ``"contiguous"`` — rank i holds tokens [i*Lc, (i+1)*Lc).  Simple,
      but causal masking leaves early ranks mostly idle: in ring step j
      every rank whose KV block comes from a later chunk masks the
      whole block yet still pays the matmuls.
    * ``"zigzag"`` — rank i holds half-chunks i and 2*sp-1-i of the
      2*sp-way split (use :func:`zigzag_shard` /
      :func:`zigzag_unshard` on the host, or feed data pre-sharded
      this way).  Causal work is balanced: each rank skips the same
      number of fully-masked half-block pairs per ring pass
      (``lax.cond`` skips their matmuls entirely), so wall-clock drops
      toward ~half of contiguous for causal attention at large sp —
      the zigzag context-parallel schedule used by modern
      long-context trainers.  Zigzag runs the XLA block step.
    """
    if layout not in ("contiguous", "zigzag"):
        raise ValueError(f"ring_attention layout must be 'contiguous' or "
                         f"'zigzag', got {layout!r}")
    if window is not None and (not causal or window < 1
                               or layout != "contiguous"):
        raise ValueError("a sliding window needs causal=True, window >= 1 "
                         f"and the contiguous layout, got causal={causal}, "
                         f"window={window}, layout={layout!r}")
    if keep is not None:
        for wrong, why in (
                (not causal, "it is a bound beside the causal one (a "
                 "query's kept keys lie in its past): causal=True"),
                (window is not None, "a window beside it is not built into "
                 "the kernels: window=None (clear the bits instead)"),
                (layout != "contiguous", "the zigzag layout's half-chunks "
                 "would each need their own columns of the words: "
                 "layout='contiguous'"),
                (lax.axis_size(axis_name) > 1, "a row's words are over the "
                 "whole sequence's keys, and a ring step's block of them "
                 f"is not cut out: {axis_name} = 1")):
            if wrong:
                raise ValueError("a selection (keep) cannot run here: "
                                 + why)
    if layout == "zigzag":
        return _ring_attention_zigzag(q, k, v, axis_name, causal)
    sp = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    b, lc, h, d = q.shape
    dv = v.shape[-1]

    asked = impl is not None
    if impl is None:
        impl = (auto_impl(b, h, lc)
                if jax.default_backend() == "tpu" else "xla")
    if impl not in ("pallas", "xla"):
        raise ValueError(f"ring_attention impl must be 'pallas' or 'xla', "
                         f"got {impl!r}")

    if impl == "pallas":
        from horovod_tpu.ops.pallas_attention import keep_tiles_ok

        # ring KV blocks are lc long too; the forward kernel's tiles and
        # the backward kernels'
        tiles = tuple(_block_sizes(lc, lc, d, q.dtype.itemsize, dv, window,
                                   backward) for backward in (False, True))
        if None in tiles[0] or (keep is not None and not all(
                keep_tiles_ok(bk) for _, bk in tiles)):
            msg = (f"sequence chunk {lc} has no tile size the Pallas "
                   "attention kernel can use (a multiple of 8 dividing "
                   "it" + ("" if keep is None else
                           ", of 128 under a selection") + ")")
            if asked:
                raise ValueError(msg + "; impl='pallas' was asked for")
            if lc not in _warned_untiled:
                _warned_untiled.add(lc)
                _log.warning(msg + "; using the XLA block step")
            impl = "xla"
    if impl == "pallas":
        # ring-level saved-LSE VJP — backward runs the hand-written
        # flash backward kernels, O(L) residuals
        qp = q.transpose(0, 2, 1, 3).reshape(b * h, lc, d)
        kp = k.transpose(0, 2, 1, 3).reshape(b * h, lc, d)
        vp = v.transpose(0, 2, 1, 3).reshape(b * h, lc, dv)
        out = _ring_flash(qp, kp, vp, axis_name, causal, tiles, recomputed,
                          window, keep)
        return out.reshape(b, h, lc, dv).transpose(0, 2, 1, 3)

    qp = q.transpose(0, 2, 1, 3).reshape(b * h, lc, d)
    kp = k.transpose(0, 2, 1, 3).reshape(b * h, lc, d)
    vp = v.transpose(0, 2, 1, 3).reshape(b * h, lc, dv)
    m0 = jnp.full((b * h, lc), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b * h, lc), jnp.float32)
    o0 = jnp.zeros((b * h, lc, dv), jnp.float32)
    rot = [(i, (i + 1) % sp) for i in range(sp)]
    if keep is not None:
        from horovod_tpu.ops.pallas_attention import unpack_keep

        keep = unpack_keep(keep, lc)

    def step(j, carry):
        m, l, o, kj, vj = carry
        # Current KV block originated at rank (idx - j) mod sp; the
        # causal mask works on GLOBAL positions.  Offsets feed only
        # that mask, so the non-causal trace skips the axis_index
        # chain (see _ring_flash_fwd_impl).
        qo, ko = (idx * lc, ((idx - j) % sp) * lc) if causal else (0, 0)
        m, l, o = xla_block_step(qp, kj, vj, m, l, o, qo, ko,
                                 causal=causal, window=window, keep=keep)
        # Rotate KV around the ring (overlaps next block's compute).
        kj = lax.ppermute(kj, axis_name, rot)
        vj = lax.ppermute(vj, axis_name, rot)
        return m, l, o, kj, vj

    m, l, o, _, _ = lax.fori_loop(0, sp, step, (m0, l0, o0, kp, vp))
    l = jnp.where(l == 0.0, 1.0, l)
    out = (o / l[..., None]).reshape(b, h, lc, dv).transpose(0, 2, 1, 3)
    return out.astype(q.dtype)


def blockwise_attention(q, k, v, causal: bool = True,
                        block_k: int = 512, window: int | None = None,
                        keep=None):
    """Single-device flash-style attention: online softmax over KV
    blocks, O(L * block_k) memory instead of the O(L^2) score matrix.
    q/k/v: (B, L, H, D); returns (B, L, H, D).  The local building
    block Ulysses runs after its head-scatter.  ``window`` and ``keep``:
    as :func:`ring_attention`'s (every block is still visited)."""
    b, l_, h, d = q.shape
    bk = min(block_k, l_)
    while l_ % bk:
        bk //= 2
    n_blocks = l_ // bk

    qp = q.transpose(0, 2, 1, 3).reshape(b * h, l_, d)
    kp = k.transpose(0, 2, 1, 3).reshape(b * h, l_, d)
    vp = v.transpose(0, 2, 1, 3).reshape(b * h, l_, d)
    m0 = jnp.full((b * h, l_), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b * h, l_), jnp.float32)
    o0 = jnp.zeros((b * h, l_, d), jnp.float32)
    if keep is not None:
        from horovod_tpu.ops.pallas_attention import unpack_keep

        keep = unpack_keep(keep, l_)

    def step(j, carry):
        m, l, o = carry
        kj = lax.dynamic_slice_in_dim(kp, j * bk, bk, axis=1)
        vj = lax.dynamic_slice_in_dim(vp, j * bk, bk, axis=1)
        kept = (None if keep is None else
                lax.dynamic_slice_in_dim(keep, j * bk, bk, axis=2))
        return xla_block_step(qp, kj, vj, m, l, o, 0, j * bk,
                              causal=causal, window=window, keep=kept)

    m, l, o = lax.fori_loop(0, n_blocks, step, (m0, l0, o0))
    l = jnp.where(l == 0.0, 1.0, l)
    out = (o / l[..., None]).reshape(b, h, l_, d).transpose(0, 2, 1, 3)
    return out.astype(q.dtype)


def _zigzag_order(n: int, sp: int) -> list[int]:
    """Token permutation global→zigzag for a length-n sequence: the
    2*sp-way split c0..c(2sp-1) becomes [c0, c(2sp-1), c1, c(2sp-2), …]
    so a plain contiguous sp-way shard hands rank i (ci, c(2sp-1-i))."""
    if n % (2 * sp):
        raise ValueError(
            f"sequence length {n} must be a multiple of 2*sp={2 * sp}")
    h = n // (2 * sp)
    order = []
    for i in range(sp):
        order.extend(range(i * h, (i + 1) * h))
        order.extend(range((2 * sp - 1 - i) * h, (2 * sp - i) * h))
    return order


def zigzag_shard(x, sp: int, axis: int = 1):
    """Reorder a GLOBAL sequence axis into zigzag rank order.  Apply on
    the host before `device_put`; invert with :func:`zigzag_unshard`."""
    order = _zigzag_order(x.shape[axis], sp)
    return jnp.take(x, jnp.asarray(order), axis=axis)


def zigzag_unshard(x, sp: int, axis: int = 1):
    """Inverse of :func:`zigzag_shard` (gathered output → global order)."""
    order = _zigzag_order(x.shape[axis], sp)
    inverse = [0] * len(order)
    for pos, src in enumerate(order):
        inverse[src] = pos
    return jnp.take(x, jnp.asarray(inverse), axis=axis)


def _zigzag_chunks(rank, sp):
    """Global half-chunk ids held by ``rank`` (front, back)."""
    return rank, 2 * sp - 1 - rank


def _ring_attention_zigzag(q, k, v, axis_name: str, causal: bool):
    """Zigzag-layout ring attention (XLA block step).

    Each rank's local Lc tokens are half-chunks (front=chunk idx,
    back=chunk 2sp-1-idx) of the 2*sp-way global split.  Each ring step
    evaluates the 4 (q-half × kv-half) pairs; a pair is, statically per
    chunk-id relation, either fully visible (no mask), diagonal
    (masked), or fully masked — the last is skipped with ``lax.cond``
    so its matmuls never execute.  Across ranks the skip counts are
    equal, which is the whole point of the zigzag layout.
    """
    sp = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    b, lc, h, d = q.shape
    if lc % 2:
        raise ValueError("zigzag layout needs an even local chunk length")
    half = lc // 2

    qp = q.transpose(0, 2, 1, 3).reshape(b * h, lc, d)
    kp = k.transpose(0, 2, 1, 3).reshape(b * h, lc, d)
    vp = v.transpose(0, 2, 1, 3).reshape(b * h, lc, d)
    m0 = jnp.full((b * h, lc), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b * h, lc), jnp.float32)
    o0 = jnp.zeros((b * h, lc, d), jnp.float32)
    rot = [(i, (i + 1) % sp) for i in range(sp)]

    def pair_step(qh, kh, vh, m, l, o, qc, kc):
        """One (q-half, kv-half) pair; qc/kc are global chunk ids."""
        if not causal:
            return xla_block_step(qh, kh, vh, m, l, o, 0, 0, causal=False)

        def full(args):
            qh, kh, vh, m, l, o = args
            return xla_block_step(qh, kh, vh, m, l, o, 0, 0, causal=False)

        def diag(args):
            qh, kh, vh, m, l, o = args
            # same chunk: plain causal mask at offset 0
            return xla_block_step(qh, kh, vh, m, l, o, 0, 0, causal=True)

        def skip(args):
            _, _, _, m, l, o = args
            return m, l, o

        branch = jnp.where(qc > kc, 0, jnp.where(qc == kc, 1, 2))
        return lax.switch(branch, [full, diag, skip],
                          (qh, kh, vh, m, l, o))

    def step(j, carry):
        m, l, o, kj, vj = carry
        src = (idx - j) % sp
        q_front, q_back = _zigzag_chunks(idx, sp)
        k_front, k_back = _zigzag_chunks(src, sp)
        halves = ((slice(None, half), q_front), (slice(half, None), q_back))
        kv_halves = ((slice(None, half), k_front),
                     (slice(half, None), k_back))
        for qs, qc in halves:
            for ks, kc in kv_halves:
                mh, lh, oh = pair_step(
                    qp[:, qs], kj[:, ks], vj[:, ks],
                    m[:, qs], l[:, qs], o[:, qs], qc, kc)
                m = m.at[:, qs].set(mh)
                l = l.at[:, qs].set(lh)
                o = o.at[:, qs].set(oh)
        kj = lax.ppermute(kj, axis_name, rot)
        vj = lax.ppermute(vj, axis_name, rot)
        return m, l, o, kj, vj

    m, l, o, _, _ = lax.fori_loop(0, sp, step, (m0, l0, o0, kp, vp))
    l = jnp.where(l == 0.0, 1.0, l)
    out = (o / l[..., None]).reshape(b, h, lc, d).transpose(0, 2, 1, 3)
    return out.astype(q.dtype)


def reference_attention(q, k, v, causal: bool = True,
                        window: int | None = None, keep=None):
    """Dense single-device attention for tests: (B, L, H, D) global.
    ``window``: query ``i`` sees key ``j`` iff ``0 <= i - j < window``
    (with ``causal``), the golden model of the kernels' second bound;
    ``keep``: a packed selection ((B, L, W) int32, as
    :func:`ring_attention`'s), of their third."""
    b, l_, h, d = q.shape
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) / (d ** 0.5)
    if causal:
        mask = jnp.tril(jnp.ones((l_, l_), bool))
        if window is not None:
            mask = mask & ~jnp.tril(jnp.ones((l_, l_), bool), -window)
        s = jnp.where(mask[None, None], s, -jnp.inf)
    if keep is not None:
        from horovod_tpu.ops.pallas_attention import unpack_keep

        s = jnp.where(unpack_keep(keep, l_)[:, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v).astype(q.dtype)
