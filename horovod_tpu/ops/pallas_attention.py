"""Pallas TPU kernel: blockwise (flash) attention accumulation step.

The hot op of ring attention (SURVEY §5.7 — a new TPU capability, absent
from the reference): one online-softmax accumulation of a local Q chunk
against one KV block, carrying the running (max, denominator, numerator)
state between ring steps so `lax.ppermute` KV rotation overlaps the MXU
work.  The kernel tiles Q×K into MXU-sized blocks, keeps softmax state
in fp32 VMEM scratch across the innermost K-grid dimension, and applies
block-level causal masking from *global* sequence offsets (the carried
state is what makes it composable with the ring — a plain fused
attention kernel could not resume from a previous block's state).

Compiled by Mosaic on a TPU backend; interpreted elsewhere
(``common.platform.pallas_interpret``), so the same kernel code is
exercised by the CPU test mesh.
"""

from __future__ import annotations

import functools

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


def _flash_step_kernel(off_ref, q_ref, k_ref, v_ref, mli_ref, oi_ref,
                       mlo_ref, oo_ref, m_s, l_s, acc,
                       *, causal: bool, scale: float, bq: int, bk: int):
    """Grid: (B*H, nq, nk) — nk innermost so (m_s, l_s, acc) scratch
    carries across the K blocks of one Q block.  The packed m|l HBM
    tile is unpacked into lane-replicated VMEM scratch on entry and
    repacked on exit, so the per-iteration math matches the classic
    two-buffer layout while HBM sees a single state buffer."""
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _():
        ml = mli_ref[0]
        m_s[:, :] = ml[:, _M_LANE][:, None] + jnp.zeros_like(m_s)
        l_s[:, :] = ml[:, _L_LANE][:, None] + jnp.zeros_like(l_s)
        acc[:, :] = oi_ref[0].astype(jnp.float32)

    q = q_ref[0]                                   # (bq, d)
    k = k_ref[0]                                   # (bk, d)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale  # (bq, bk)

    if causal:
        q_start = off_ref[0] + pl.program_id(1) * bq
        k_start = off_ref[1] + ik * bk
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(qpos >= kpos, s, _NEG_INF)

    m_prev = m_s[:, 0]                             # (bq,)
    l_prev = l_s[:, 0]
    m_cur = jnp.max(s, axis=-1)
    m_new = jnp.maximum(m_prev, m_cur)
    # Fully-masked rows keep m == -inf; exp against a finite stand-in.
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.exp(s - m_safe[:, None])
    p = jnp.where(jnp.isfinite(s), p, 0.0)
    alpha = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_safe), 0.0)
    l_new = l_prev * alpha + jnp.sum(p, axis=-1)
    pv = jax.lax.dot_general(
        p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)        # (bq, d)
    m_s[:, :] = m_new[:, None] + jnp.zeros_like(m_s)
    l_s[:, :] = l_new[:, None] + jnp.zeros_like(l_s)
    acc[:, :] = acc[:, :] * alpha[:, None] + pv

    @pl.when(ik == nk - 1)
    def _():
        mlo_ref[0] = jnp.concatenate(
            [m_s[:, :_L_LANE], l_s[:, _L_LANE:]], axis=1)
        oo_ref[0] = acc[:, :].astype(oo_ref.dtype)


def _flash_block_step_impl(q, k, v, m, l, o, q_offset, k_offset,
                           causal, block_q, block_k, interpret):
    bh, lq, d = q.shape
    _, lk, _ = k.shape
    bq = min(block_q, lq)
    bk = min(block_k, lk)
    if lq % bq or lk % bk:
        raise ValueError(f"block sizes ({bq}, {bk}) must divide the "
                         f"sequence chunks ({lq}, {lk})")
    interpret = pallas_interpret(interpret)
    scale = 1.0 / (d ** 0.5)
    offs = jnp.asarray([q_offset, k_offset], jnp.int32)
    ml = jnp.concatenate(
        [jnp.broadcast_to(m[..., None], (bh, lq, _L_LANE)),
         jnp.broadcast_to(l[..., None], (bh, lq, 128 - _L_LANE))],
        axis=-1)

    kernel = functools.partial(_flash_step_kernel, causal=causal,
                               scale=scale, bq=bq, bk=bk)
    grid = (bh, lq // bq, lk // bk)
    mlo, oo = pl.pallas_call(
        kernel,
        name="hvd_flash_fwd",
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),               # offsets
            pl.BlockSpec((1, bq, d), lambda b, iq, ik: (b, iq, 0)),   # q
            pl.BlockSpec((1, bk, d), lambda b, iq, ik: (b, ik, 0)),   # k
            pl.BlockSpec((1, bk, d), lambda b, iq, ik: (b, ik, 0)),   # v
            pl.BlockSpec((1, bq, 128), lambda b, iq, ik: (b, iq, 0)),  # m|l
            pl.BlockSpec((1, bq, d), lambda b, iq, ik: (b, iq, 0)),   # o
        ],
        out_specs=[
            pl.BlockSpec((1, bq, 128), lambda b, iq, ik: (b, iq, 0)),
            pl.BlockSpec((1, bq, d), lambda b, iq, ik: (b, iq, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, lq, 128), jnp.float32),
            jax.ShapeDtypeStruct((bh, lq, d), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),   # running max
            pltpu.VMEM((bq, 128), jnp.float32),   # running denominator
            pltpu.VMEM((bq, d), jnp.float32),     # numerator accumulator
        ],
        # b/iq are independent work items, only the K dimension carries
        # scratch state — telling Mosaic lets it overlap DMA with MXU
        # work across grid steps instead of serializing the whole grid.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(offs, q, k, v, ml, o)
    return mlo[..., _M_LANE], mlo[..., _L_LANE], oo


def _flash_bwd_dq_kernel(off_ref, q_ref, k_ref, v_ref, do_ref, ld_ref,
                         dq_ref, dq_acc, *, causal: bool, scale: float,
                         bq: int, bk: int):
    """dQ backward: grid (B*H, nq, nk), nk innermost so dq_acc carries
    across the K blocks of one Q block.  Scores are recomputed per
    (bq, bk) tile from the saved per-row LSE — the full score matrix is
    never materialized (the whole point vs the XLA-remat VJP)."""
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _():
        dq_acc[:, :] = jnp.zeros_like(dq_acc)

    q = q_ref[0]                                   # (bq, d)
    k = k_ref[0]                                   # (bk, d)
    v = v_ref[0]                                   # (bk, d)
    do = do_ref[0]                                 # (bq, d)
    ld = ld_ref[0]                                 # (bq, 128) lse|delta
    lse = ld[:, _M_LANE]
    delta = ld[:, _L_LANE]

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale  # (bq, bk)
    if causal:
        q_start = off_ref[0] + pl.program_id(1) * bq
        k_start = off_ref[1] + ik * bk
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(qpos >= kpos, s, _NEG_INF)
    # p = softmax row = exp(s - lse); fully-masked rows carry lse=-inf
    p = jnp.where(jnp.isfinite(s) & jnp.isfinite(lse)[:, None],
                  jnp.exp(s - jnp.where(jnp.isfinite(lse), lse,
                                        0.0)[:, None]), 0.0)
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)        # (bq, bk)
    ds = p * (dp - delta[:, None]) * scale
    dq_acc[:, :] += jax.lax.dot_general(
        ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)        # (bq, d)

    @pl.when(ik == nk - 1)
    def _():
        dq_ref[0] = dq_acc[:, :]


def _flash_bwd_dkv_kernel(off_ref, q_ref, k_ref, v_ref, do_ref, ld_ref,
                          dk_ref, dv_ref, dk_acc, dv_acc, *, causal: bool,
                          scale: float, bq: int, bk: int):
    """dK/dV backward: grid (B*H, nk, nq), nq innermost so the dk/dv
    accumulators carry across the Q blocks of one KV block."""
    iq = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(iq == 0)
    def _():
        dk_acc[:, :] = jnp.zeros_like(dk_acc)
        dv_acc[:, :] = jnp.zeros_like(dv_acc)

    q = q_ref[0]                                   # (bq, d)
    k = k_ref[0]                                   # (bk, d)
    v = v_ref[0]                                   # (bk, d)
    do = do_ref[0]                                 # (bq, d)
    ld = ld_ref[0]
    lse = ld[:, _M_LANE]
    delta = ld[:, _L_LANE]

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale  # (bq, bk)
    if causal:
        q_start = off_ref[0] + iq * bq
        k_start = off_ref[1] + pl.program_id(1) * bk
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(qpos >= kpos, s, _NEG_INF)
    p = jnp.where(jnp.isfinite(s) & jnp.isfinite(lse)[:, None],
                  jnp.exp(s - jnp.where(jnp.isfinite(lse), lse,
                                        0.0)[:, None]), 0.0)
    dv_acc[:, :] += jax.lax.dot_general(
        p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)        # (bk, d)
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)        # (bq, bk)
    ds = p * (dp - delta[:, None]) * scale
    dk_acc[:, :] += jax.lax.dot_general(
        ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)        # (bk, d)

    @pl.when(iq == nq - 1)
    def _():
        dk_ref[0] = dk_acc[:, :]
        dv_ref[0] = dv_acc[:, :]


def _pack_ld(lse, delta, bh, lq):
    """Pack per-row lse|delta into one (BH, Lq, 128) f32 tile buffer —
    same single-state-buffer trick as the forward's m|l packing."""
    return jnp.concatenate(
        [jnp.broadcast_to(lse[..., None], (bh, lq, _L_LANE)),
         jnp.broadcast_to(delta[..., None], (bh, lq, 128 - _L_LANE))],
        axis=-1)


def flash_bwd_dq(q, k, v, do, lse, delta, q_offset, k_offset, *,
                 causal: bool = True, block_q: int = 128,
                 block_k: int = 128, interpret: bool | None = None):
    """Flash-attention dQ for one (local Q, one KV block) pair.

    q: (BH, Lq, D); k/v: (BH, Lk, D); do: (BH, Lq, D) upstream grad in
    the matmul dtype; lse: (BH, Lq) fp32 saved log-sum-exp rows
    (m + log l from the forward); delta: (BH, Lq) fp32 rowsum(dO * O).
    Returns fp32 (BH, Lq, D) — the dQ contribution of this KV block
    (sum over ring steps at the caller).
    """
    bh, lq, d = q.shape
    _, lk, _ = k.shape
    bq = min(block_q, lq)
    bk = min(block_k, lk)
    if lq % bq or lk % bk:
        raise ValueError(f"block sizes ({bq}, {bk}) must divide the "
                         f"sequence chunks ({lq}, {lk})")
    interpret = pallas_interpret(interpret)
    scale = 1.0 / (d ** 0.5)
    offs = jnp.asarray([q_offset, k_offset], jnp.int32)
    ld = _pack_ld(lse, delta, bh, lq)
    kernel = functools.partial(_flash_bwd_dq_kernel, causal=causal,
                               scale=scale, bq=bq, bk=bk)
    return pl.pallas_call(
        kernel,
        name="hvd_flash_bwd_dq",
        grid=(bh, lq // bq, lk // bk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, bq, d), lambda b, iq, ik: (b, iq, 0)),   # q
            pl.BlockSpec((1, bk, d), lambda b, iq, ik: (b, ik, 0)),   # k
            pl.BlockSpec((1, bk, d), lambda b, iq, ik: (b, ik, 0)),   # v
            pl.BlockSpec((1, bq, d), lambda b, iq, ik: (b, iq, 0)),   # do
            pl.BlockSpec((1, bq, 128), lambda b, iq, ik: (b, iq, 0)),  # ld
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, iq, ik: (b, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, lq, d), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(offs, q, k, v, do, ld)


def flash_bwd_dkv(q, k, v, do, lse, delta, q_offset, k_offset, *,
                  causal: bool = True, block_q: int = 128,
                  block_k: int = 128, interpret: bool | None = None):
    """Flash-attention (dK, dV) for one (local Q, one KV block) pair.

    Same contract as :func:`flash_bwd_dq`; returns fp32
    ((BH, Lk, D), (BH, Lk, D)) — this Q chunk's contribution to the
    block's dK/dV (ring callers accumulate while rotating).
    """
    bh, lq, d = q.shape
    _, lk, _ = k.shape
    bq = min(block_q, lq)
    bk = min(block_k, lk)
    if lq % bq or lk % bk:
        raise ValueError(f"block sizes ({bq}, {bk}) must divide the "
                         f"sequence chunks ({lq}, {lk})")
    interpret = pallas_interpret(interpret)
    scale = 1.0 / (d ** 0.5)
    offs = jnp.asarray([q_offset, k_offset], jnp.int32)
    ld = _pack_ld(lse, delta, bh, lq)
    kernel = functools.partial(_flash_bwd_dkv_kernel, causal=causal,
                               scale=scale, bq=bq, bk=bk)
    return pl.pallas_call(
        kernel,
        name="hvd_flash_bwd_dkv",
        grid=(bh, lk // bk, lq // bq),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, bq, d), lambda b, ik, iq: (b, iq, 0)),   # q
            pl.BlockSpec((1, bk, d), lambda b, ik, iq: (b, ik, 0)),   # k
            pl.BlockSpec((1, bk, d), lambda b, ik, iq: (b, ik, 0)),   # v
            pl.BlockSpec((1, bq, d), lambda b, ik, iq: (b, iq, 0)),   # do
            pl.BlockSpec((1, bq, 128), lambda b, ik, iq: (b, iq, 0)),  # ld
        ],
        out_specs=[
            pl.BlockSpec((1, bk, d), lambda b, ik, iq: (b, ik, 0)),
            pl.BlockSpec((1, bk, d), lambda b, ik, iq: (b, ik, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, lk, d), jnp.float32),
            jax.ShapeDtypeStruct((bh, lk, d), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(offs, q, k, v, do, ld)


# The block step below is forward-only; its VJP is the XLA block
# step's (same math, rematerialized from the inputs).  It remains the
# ``attn_pallas_bwd="remat"`` escape hatch; the default pallas path now
# runs the ring-level saved-LSE VJP in ring_attention, whose backward
# is the two hand-written kernels above (no full score materialization
# — the XLA-remat VJP needed the whole fp32 score block per ring step,
# which OOM'd HBM at (seq 4096, b 4) on v5e).
@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9, 10, 11))
def _flash_block_step_diff(q, k, v, m, l, o, q_offset, k_offset,
                           causal, block_q, block_k, interpret):
    return _flash_block_step_impl(q, k, v, m, l, o, q_offset, k_offset,
                                  causal, block_q, block_k, interpret)


def _flash_fwd(q, k, v, m, l, o, q_offset, k_offset,
               causal, block_q, block_k, interpret):
    out = _flash_block_step_impl(q, k, v, m, l, o, q_offset, k_offset,
                                 causal, block_q, block_k, interpret)
    return out, (q, k, v, m, l, o, q_offset, k_offset)


def _flash_bwd(causal, block_q, block_k, interpret, res, ct):
    from horovod_tpu.parallel.ring_attention import xla_block_step

    q, k, v, m, l, o, q_offset, k_offset = res
    _, vjp = jax.vjp(
        lambda q_, k_, v_, m_, l_, o_: xla_block_step(
            q_, k_, v_, m_, l_, o_, q_offset, k_offset, causal=causal),
        q, k, v, m, l, o)
    dq, dk, dv, dm, dl, do = vjp(ct)
    return dq, dk, dv, dm, dl, do, None, None


_flash_block_step_diff.defvjp(_flash_fwd, _flash_bwd)


def flash_block_step(q, k, v, m, l, o, q_offset, k_offset, *,
                     causal: bool = True, block_q: int = 128,
                     block_k: int = 128, interpret: bool | None = None):
    """One ring-attention accumulation: attend local Q against one KV
    block, updating carried online-softmax state.

    q: (BH, Lq, D); k, v: (BH, Lk, D); m, l: (BH, Lq) fp32 running
    max / denominator; o: (BH, Lq, D) fp32 unnormalized numerator.
    q_offset / k_offset: global positions of q[:,0]/k[:,0] (traced OK).
    Returns updated (m, l, o).  Differentiable: the backward pass is
    the XLA online-softmax step's VJP over the saved inputs.
    """
    return _flash_block_step_diff(q, k, v, m, l, o,
                                  jnp.asarray(q_offset, jnp.int32),
                                  jnp.asarray(k_offset, jnp.int32),
                                  causal, block_q, block_k, interpret)
