"""Block-scaled lossy wire codecs (EQuARX-style int8, packed int4,
top-k sparsification).

EQuARX ("Efficient Quantized AllReduce in XLA", PAPERS.md) shows that a
block-scaled symmetric int8 wire format inside the allreduce cuts
cross-slice (DCN) bytes ~4x with negligible accuracy loss.  This module
is that wire format plus the scale-aware reductions that ride it:

* **Wire format** — the flat fp32 payload is split into blocks of
  ``HOROVOD_QUANT_BLOCK_SIZE`` elements (default 256); each block
  carries an fp32 scale (symmetric absmax / qmax) and int8 values, i.e.
  ~4x fewer wire bytes plus a 1/64 scale sidecar.

* **Scale-aware reduction** (:func:`quantized_psum`) — ranks first
  agree on per-block scales via a (tiny) ``pmax`` of block absmaxes,
  then quantize with ``qmax = 127 // axis_size`` headroom so the int8
  **sum accumulates exactly in int8 without overflow**, ``psum`` the
  int8 payload (the only full-size transfer — XLA lowers it to an s8
  all-reduce), and dequantize with the shared scales.  Per-element
  error is bounded by ``axis_size * blockmax / (2 * (127 //
  axis_size))`` — tight for the small cross-slice axes (2-8) this is
  designed for, which is why :func:`hierarchical quantized allreduce
  <horovod_tpu.ops.collectives.hierarchical_allreduce>` keeps the
  intra-slice (ICI) hops in full precision and quantizes only the
  cross-slice (DCN) psum, matching EQuARX's two-level design.

* **Error feedback** (:func:`quantized_psum_with_error`,
  :class:`ErrorFeedback` state in the DistributedOptimizer) — the local
  quantization residual ``x - dequant(quant(x))`` is carried to the
  next step and re-injected, so compression error averages out over
  steps instead of accumulating (1-bit-Adam-style EF; the convergence
  test in ``tests/test_quantization.py`` shows the running mean of the
  compressed reduction converging to the exact one).

* **Pallas kernels** — fused quantize / dequantize TPU kernels keep the
  int8 conversion in VMEM (no HBM round-trip between absmax, scale and
  cast); the pure-jnp path is selected off-TPU, the same pattern as
  :mod:`horovod_tpu.ops.pallas_attention`.  ``HOROVOD_QUANT_PALLAS=1``
  forces the kernels (interpret mode off-TPU, test hook; a block size
  they cannot tile then raises), ``0`` forces the jnp path.

Two more lossy codecs ride the same per-block-scale + error-feedback
contract (docs/compression.md's mode ladder):

* **int4** (:func:`int4_psum`, :func:`int4_psum_scatter_segments`) —
  two signed nibbles packed per int8 wire byte (halves pairing: element
  ``i`` of a block pairs with element ``i + block/2``), so the dense
  payload is half of int8's.  Sum-safe headroom ``qmax = 7 // n`` keeps
  every per-nibble partial sum in ``[-7, 7]``; a packed-byte sum then
  never carries across the nibble boundary (``16*hi + lo`` sums
  nibble-wise exactly), so the packed payload rides an ordinary int8
  ``psum``/``psum_scatter``/ppermute ring unchanged.  Past 7 ranks no
  headroom exists — refuse loudly, like int8 past 127 (hierarchical
  mode keeps the quantized axis small).  Fused Pallas pack/unpack
  kernels with a bit-identical jnp fallback, selected exactly like the
  int8 kernels.

* **top-k** (:func:`topk_psum`, :func:`topk_psum_scatter_segments`) —
  per-payload magnitude top-k with a FIXED-size ``k = max(1,
  round(ratio * n_elems))`` index+value payload (``HOROVOD_TOPK_RATIO``)
  so shapes stay static for XLA.  The reduction gathers every rank's
  sparse ``(int32 index, fp32 value)`` pairs (``all_gather`` for
  allreduce, ``all_to_all`` routing each segment row to its shard owner
  for reduce-scatter) and scatter-adds them densely; unselected entries
  land in the error-feedback residual (Deep-Gradient-Compression-style
  memory), so nothing is lost — only deferred.

:func:`lossy_psum` / :func:`lossy_psum_scatter_segments` dispatch on
the mode string (``int8 | int4 | topk``) — the single entry point the
collectives, the overlap engine's per-bucket schedule, and the ZeRO
bucket pipelines share.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from horovod_tpu.common import config as _config
from horovod_tpu.common import logging as _log
from horovod_tpu.common.platform import pallas_interpret

DEFAULT_BLOCK_SIZE = 256
_QMAX = 127  # symmetric int8: values in [-127, 127] (-128 unused)

# Pallas tile geometry: int8 native tiling is (32, 128) on TPU, so row
# tiles are 32 blocks and the block size must be lane-aligned.
_ROW_TILE = 32
_LANES = 128


def resolve_block_size(block_size: int | None = None) -> int:
    if block_size is None:
        block_size = int(_config.get("quant_block_size"))
    return block_size if block_size > 0 else DEFAULT_BLOCK_SIZE


def sum_safe_qmax(n: int) -> int:
    """Largest per-rank magnitude such that an n-rank int8 sum cannot
    overflow: n * (127 // n) <= 127.  Raises past 127 ranks — there is
    no overflow-safe int8 headroom left, and wrapping would corrupt
    gradients silently."""
    n = max(int(n), 1)
    qmax = _QMAX // n
    if qmax < 1:
        raise ValueError(
            f"int8 quantized reduction over {n} ranks cannot be made "
            f"sum-safe (127 // {n} == 0); reduce the quantized axis — "
            "e.g. HOROVOD_HIERARCHICAL_ALLREDUCE=1 so only the small "
            "cross-slice axis rides int8 — or use fp16/bf16.")
    return qmax


class QuantMeta(NamedTuple):
    """Host-side metadata to undo blocking/padding."""
    shape: tuple
    dtype: jnp.dtype
    length: int      # valid elements before padding
    block: int


def _to_blocks(x, block: int):
    """Flatten to (nblocks, block) fp32 with zero padding."""
    flat = x.astype(jnp.float32).reshape(-1)
    length = flat.shape[0]
    pad = (-length) % block
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), jnp.float32)])
    return flat.reshape(-1, block), length


def _from_blocks(x2d, meta: QuantMeta):
    flat = x2d.reshape(-1)[:meta.length]
    return flat.reshape(meta.shape).astype(meta.dtype)


def block_absmax(x2d):
    """Per-block absolute maximum, shape (nblocks,) fp32."""
    return jnp.max(jnp.abs(x2d), axis=1)


# ---------------------------------------------------------------------------
# jnp reference implementation
# ---------------------------------------------------------------------------


def _quantize_jnp(x2d, scales, qmax: int):
    inv = jnp.where(scales > 0, 1.0 / jnp.where(scales > 0, scales, 1.0),
                    0.0)
    q = jnp.clip(jnp.round(x2d * inv[:, None]), -qmax, qmax)
    return q.astype(jnp.int8)


def _dequantize_jnp(q2d, scales):
    return q2d.astype(jnp.float32) * scales[:, None]


# ---------------------------------------------------------------------------
# Pallas kernels (TPU): quantize / dequantize without an HBM round-trip
# ---------------------------------------------------------------------------


def _quant_kernel(x_ref, s_ref, q_ref, *, qmax: int):
    """One row-tile: q = clip(round(x / scale)).  Scales arrive
    lane-replicated (R, 128) — same single-tile state packing as the
    attention kernels (a (R, 1) minor dim is not lowerable)."""
    x = x_ref[...]                      # (R, B) f32
    s = s_ref[:, 0]                     # (R,)
    inv = jnp.where(s > 0, 1.0 / jnp.where(s > 0, s, 1.0), 0.0)
    q = jnp.clip(jnp.round(x * inv[:, None]), -qmax, qmax)
    q_ref[...] = q.astype(jnp.int8)


def _dequant_kernel(q_ref, s_ref, x_ref):
    q = q_ref[...]                      # (R, B) i8 (or i32 partial sums)
    s = s_ref[:, 0]
    x_ref[...] = q.astype(jnp.float32) * s[:, None]


def _pallas_mode() -> str:
    return str(_config.get("quant_pallas")).strip().lower()


_warned_unaligned: set = set()


def _use_pallas(block: int, lanes: int = _LANES) -> bool:
    """Kernel or jnp path for one block size.  The kernels tile blocks
    over whole 128-lane rows (``lanes`` = the alignment the block must
    have): an unaligned block raises when the kernels were asked for
    (``HOROVOD_QUANT_PALLAS=1``) and is logged once when the choice was
    automatic — never a wordless switch to the reference."""
    mode = _pallas_mode()
    if mode in ("0", "off", "jnp", "false"):
        return False
    forced = mode in ("1", "on", "force", "true")
    if not forced and jax.default_backend() != "tpu":
        return False
    if block % lanes:
        msg = (f"quantization block size {block} is not a multiple of "
               f"{lanes}: the Pallas codec kernels cannot tile it")
        if forced:
            raise ValueError(
                msg + " (HOROVOD_QUANT_PALLAS=1 asked for them; use an "
                "aligned HOROVOD_QUANT_BLOCK_SIZE)")
        if (block, lanes) not in _warned_unaligned:
            _warned_unaligned.add((block, lanes))
            _log.warning(msg + "; using the jnp codec")
        return False
    return True


def _pad_rows(x2d, rows: int):
    pad = (-x2d.shape[0]) % rows
    if pad:
        x2d = jnp.concatenate(
            [x2d, jnp.zeros((pad,) + x2d.shape[1:], x2d.dtype)])
    return x2d, pad


def _replicate_scales(scales):
    return jnp.broadcast_to(scales[:, None], (scales.shape[0], _LANES))


@functools.partial(jax.jit, static_argnums=(2, 3))
def _quantize_pallas_call(x2d, scales, qmax: int, interpret: bool):
    from jax.experimental import pallas as pl

    nb, block = x2d.shape
    x2d, pad = _pad_rows(x2d, _ROW_TILE)
    srep, _ = _pad_rows(_replicate_scales(scales), _ROW_TILE)
    rows = x2d.shape[0]
    q = pl.pallas_call(
        functools.partial(_quant_kernel, qmax=qmax),
        grid=(rows // _ROW_TILE,),
        in_specs=[
            pl.BlockSpec((_ROW_TILE, block), lambda i: (i, 0)),
            pl.BlockSpec((_ROW_TILE, _LANES), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((_ROW_TILE, block), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, block), jnp.int8),
        interpret=interpret,
    )(x2d, srep)
    return q[:nb] if pad else q


@functools.partial(jax.jit, static_argnums=(2,))
def _dequantize_pallas_call(q2d, scales, interpret: bool):
    from jax.experimental import pallas as pl

    nb, block = q2d.shape
    q2d, pad = _pad_rows(q2d, _ROW_TILE)
    srep, _ = _pad_rows(_replicate_scales(scales), _ROW_TILE)
    rows = q2d.shape[0]
    x = pl.pallas_call(
        _dequant_kernel,
        grid=(rows // _ROW_TILE,),
        in_specs=[
            pl.BlockSpec((_ROW_TILE, block), lambda i: (i, 0)),
            pl.BlockSpec((_ROW_TILE, _LANES), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((_ROW_TILE, block), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, block), jnp.float32),
        interpret=interpret,
    )(q2d, srep)
    return x[:nb] if pad else x


def quantize_values(x2d, scales, qmax: int = _QMAX):
    """int8 values for blocked fp32 ``x2d`` under given per-block
    scales (Pallas on TPU, jnp elsewhere)."""
    if _use_pallas(x2d.shape[1]):
        interpret = pallas_interpret()
        return _quantize_pallas_call(x2d, scales, int(qmax), interpret)
    return _quantize_jnp(x2d, scales, qmax)


def dequantize_values(q2d, scales):
    """fp32 values for blocked int8 (or int partial-sum) ``q2d``."""
    if _use_pallas(q2d.shape[1]):
        interpret = pallas_interpret()
        return _dequantize_pallas_call(q2d, scales, interpret)
    return _dequantize_jnp(q2d, scales)


# ---------------------------------------------------------------------------
# Standalone compressor surface (local quantize -> dequantize round trip)
# ---------------------------------------------------------------------------


def quantize_block_scaled(x, block_size: int | None = None,
                          qmax: int = _QMAX):
    """Local block-scaled quantization: ``(q2d int8, scales fp32,
    meta)``.  ``dequantize_block_scaled`` undoes it within
    ``scales / 2`` absolute error per element (<= blockmax / 254 at
    qmax=127, i.e. well under the documented 2/127 per-block bound)."""
    block = resolve_block_size(block_size)
    x2d, length = _to_blocks(x, block)
    scales = block_absmax(x2d) / qmax
    q = quantize_values(x2d, scales, qmax)
    meta = QuantMeta(tuple(x.shape), x.dtype, length, block)
    return q, scales, meta


def dequantize_block_scaled(q2d, scales, meta: QuantMeta):
    return _from_blocks(dequantize_values(q2d, scales), meta)


# ---------------------------------------------------------------------------
# Scale-aware in-trace reductions (the wire)
# ---------------------------------------------------------------------------


def _axis_prod(axis_name) -> int:
    names = (axis_name,) if isinstance(axis_name, str) else tuple(axis_name)
    n = 1
    for a in names:
        n *= lax.axis_size(a)
    return n


def _shared_scales(x2d, axis_name, n: int):
    """Per-block scales every rank agrees on: pmax of local absmaxes
    (a 1/block_size-sized fp32 collective) over ``qmax`` headroom so
    the int8 sum cannot overflow."""
    qmax = sum_safe_qmax(n)
    shared = lax.pmax(block_absmax(x2d), axis_name)
    return shared / qmax, qmax


def quantized_psum(x, axis_name, block_size: int | None = None):
    """Sum of ``x`` over ``axis_name`` with an int8 wire payload.

    Wire: one fp32 ``pmax`` of per-block absmaxes (#elements /
    block_size) + one int8 ``psum`` of the full payload — ~4x fewer
    bytes than an fp32 psum.  Exact when every rank's values are
    multiples of the shared per-block scale; otherwise bounded by
    ``n * scale / 2`` per element (``scale = n-pmax blockmax /
    (127 // n)``)."""
    out, _ = _quantized_psum_impl(x, axis_name, block_size,
                                  with_error=False)
    return out


def quantized_psum_with_error(x, axis_name, block_size: int | None = None):
    """Like :func:`quantized_psum`, additionally returning this rank's
    local compression residual ``x - dequant(quant(x))`` (fp32, shape
    of ``x``) for error feedback."""
    return _quantized_psum_impl(x, axis_name, block_size, with_error=True)


def _quantized_psum_impl(x, axis_name, block_size, with_error: bool):
    n = _axis_prod(axis_name)
    block = resolve_block_size(block_size)
    meta_dtype = x.dtype
    x2d, length = _to_blocks(x, block)
    meta = QuantMeta(tuple(x.shape), meta_dtype, length, block)
    if n == 1:
        err = jnp.zeros(x.shape, jnp.float32) if with_error else None
        return x, err
    scales, qmax = _shared_scales(x2d, axis_name, n)
    q = quantize_values(x2d, scales, qmax)
    qsum = lax.psum(q, axis_name)              # int8 wire; no overflow
    out2d = dequantize_values(qsum, scales)
    out = _from_blocks(out2d, meta)
    err = None
    if with_error:
        local = dequantize_values(q, scales)
        err = _from_blocks(
            (x2d - local),
            QuantMeta(tuple(x.shape), jnp.float32, length, block))
    return out, err


def quantized_psum_scatter_segments(seg, axis_name,
                                    block_size: int | None = None,
                                    with_error: bool = False,
                                    reduce_scatter=None):
    """Reduce-scatter a pre-segmented ``(n, L)`` fp32 buffer on the int8
    wire, ``n`` == total size of ``axis_name``: per-(segment, block)
    scales are shared via a tiny fp32 ``pmax``, the int8 payload rides
    one ``psum_scatter`` with sum-safe headroom, and rank ``i``
    dequantizes segment ``i`` with its own scale row.  Blocks are laid
    out inside each segment, so shard and block boundaries never
    straddle.  Returns ``(shard, err)`` where ``shard`` is the ``(L,)``
    fp32 sum of segment ``axis_index`` and ``err`` (``with_error`` only)
    is this rank's full ``(n, L)`` fp32 local quantization residual
    ``seg - dequant(quant(seg))`` for error feedback.

    ``reduce_scatter`` swaps the int8 payload's transport: a callable
    taking the ``(n*nb, block)`` int8 values and returning the ``(nb,
    block)`` summed shard of segment ``axis_index`` (the overlap
    engine's ppermute ring rides here).  Everything else — scales,
    headroom, residual layout — is shared, so the EF contract cannot
    drift between the monolithic and overlapped wires."""
    n = _axis_prod(axis_name)
    block = resolve_block_size(block_size)
    length = seg.shape[1]
    pad = (-length) % block
    if pad:
        seg = jnp.concatenate(
            [seg, jnp.zeros((n, pad), jnp.float32)], axis=1)
    nb = seg.shape[1] // block
    x3 = seg.reshape(n, nb, block)
    absmax = jnp.max(jnp.abs(x3), axis=2)            # (n, nb)
    qmax = sum_safe_qmax(n)
    scales = lax.pmax(absmax, axis_name) / qmax       # shared (n, nb)
    q = quantize_values(x3.reshape(n * nb, block),
                        scales.reshape(-1), qmax)     # (n*nb, block) i8
    if reduce_scatter is None:
        qsum = lax.psum_scatter(q, axis_name, scatter_dimension=0,
                                tiled=True)           # (nb, block) i8
    else:
        qsum = reduce_scatter(q)
    my_scales = lax.dynamic_index_in_dim(
        scales, lax.axis_index(axis_name), axis=0, keepdims=False)
    out = dequantize_values(qsum, my_scales).reshape(-1)
    if pad:
        out = out[:-pad]
    err = None
    if with_error:
        local = dequantize_values(q, scales.reshape(-1))
        err = (x3.reshape(n, -1) - local.reshape(n, -1))[:, :length]
    return out, err




# ---------------------------------------------------------------------------
# int4: two signed nibbles per wire byte (halves pairing)
# ---------------------------------------------------------------------------

_QMAX4 = 7  # symmetric int4 nibble: values in [-7, 7] (-8 unused)


def sum_safe_qmax4(n: int) -> int:
    """Largest per-rank nibble magnitude such that an n-rank int4 sum
    cannot overflow a nibble: n * (7 // n) <= 7.  Past 7 ranks there is
    no headroom left — refuse loudly (hierarchical mode keeps the
    quantized axis small), never wrap."""
    n = max(int(n), 1)
    qmax = _QMAX4 // n
    if qmax < 1:
        raise ValueError(
            f"int4 quantized reduction over {n} ranks cannot be made "
            f"sum-safe (7 // {n} == 0); reduce the quantized axis — "
            "e.g. HOROVOD_HIERARCHICAL_ALLREDUCE=1 so only the small "
            "cross-slice axis rides int4 — or use int8.")
    return qmax


def _check_int4_block(block: int) -> int:
    if block % 2:
        raise ValueError(
            f"int4 packing needs an even HOROVOD_QUANT_BLOCK_SIZE, "
            f"got {block} (two nibbles share each wire byte).")
    return block


def _quantize_pack4_jnp(x2d, scales, qmax: int):
    """Quantize + pack: halves pairing — element ``i`` (low nibble)
    pairs with element ``i + block/2`` (high nibble), keeping both
    halves contiguous and lane-aligned for the TPU kernels."""
    q = jnp.clip(jnp.round(x2d * _inv_scales(scales)[:, None]),
                 -qmax, qmax).astype(jnp.int32)
    half = q.shape[1] // 2
    return (q[:, half:] * 16 + q[:, :half]).astype(jnp.int8)


def _unpack4_i32(p2d_i32):
    """Packed (possibly partial-sum) bytes back to the (.., block) int
    grid.  Valid whenever every nibble sum stayed in [-7, 7] — the
    sum-safe headroom guarantee — since ``16*hi + lo`` with ``lo`` in
    [-7, 7] recovers ``lo = mod(s + 8, 16) - 8`` exactly."""
    lo = jnp.mod(p2d_i32 + 8, 16) - 8
    hi = (p2d_i32 - lo) // 16
    return jnp.concatenate([lo, hi], axis=1)


def _unpack_dequantize4_jnp(p2d, scales):
    q = _unpack4_i32(p2d.astype(jnp.int32))
    return q.astype(jnp.float32) * scales[:, None]


def _pack4_kernel(x_ref, s_ref, p_ref, *, qmax: int, half: int):
    """Fused quantize + nibble-pack for one row tile (no HBM round trip
    between scale, cast and pack) — the int4 sibling of
    :func:`_quant_kernel`."""
    x = x_ref[...]                      # (R, B) f32
    s = s_ref[:, 0]
    inv = jnp.where(s > 0, 1.0 / jnp.where(s > 0, s, 1.0), 0.0)
    q = jnp.clip(jnp.round(x * inv[:, None]), -qmax, qmax)
    q = q.astype(jnp.int32)
    p_ref[...] = (q[:, half:] * 16 + q[:, :half]).astype(jnp.int8)


def _unpack4_kernel(p_ref, s_ref, x_ref, *, half: int):
    p = p_ref[...].astype(jnp.int32)    # (R, half) packed partial sums
    lo = jnp.mod(p + 8, 16) - 8
    hi = (p - lo) // 16
    s = s_ref[:, 0]
    x_ref[:, :half] = lo.astype(jnp.float32) * s[:, None]
    x_ref[:, half:] = hi.astype(jnp.float32) * s[:, None]


def _use_pallas4(block: int) -> bool:
    # the packed payload must itself stay lane-aligned: block % 256
    return _use_pallas(block, 2 * _LANES)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _pack4_pallas_call(x2d, scales, qmax: int, interpret: bool):
    from jax.experimental import pallas as pl

    nb, block = x2d.shape
    half = block // 2
    x2d, pad = _pad_rows(x2d, _ROW_TILE)
    srep, _ = _pad_rows(_replicate_scales(scales), _ROW_TILE)
    rows = x2d.shape[0]
    p = pl.pallas_call(
        functools.partial(_pack4_kernel, qmax=qmax, half=half),
        grid=(rows // _ROW_TILE,),
        in_specs=[
            pl.BlockSpec((_ROW_TILE, block), lambda i: (i, 0)),
            pl.BlockSpec((_ROW_TILE, _LANES), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((_ROW_TILE, half), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, half), jnp.int8),
        interpret=interpret,
    )(x2d, srep)
    return p[:nb] if pad else p


@functools.partial(jax.jit, static_argnums=(2,))
def _unpack4_pallas_call(p2d, scales, interpret: bool):
    from jax.experimental import pallas as pl

    nb, half = p2d.shape
    block = half * 2
    p2d, pad = _pad_rows(p2d, _ROW_TILE)
    srep, _ = _pad_rows(_replicate_scales(scales), _ROW_TILE)
    rows = p2d.shape[0]
    x = pl.pallas_call(
        functools.partial(_unpack4_kernel, half=half),
        grid=(rows // _ROW_TILE,),
        in_specs=[
            pl.BlockSpec((_ROW_TILE, half), lambda i: (i, 0)),
            pl.BlockSpec((_ROW_TILE, _LANES), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((_ROW_TILE, block), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, block), jnp.float32),
        interpret=interpret,
    )(p2d, srep)
    return x[:nb] if pad else x


def quantize_pack4_values(x2d, scales, qmax: int = _QMAX4):
    """Packed int4 wire bytes for blocked fp32 ``x2d`` under given
    per-block scales: ``(nblocks, block // 2)`` int8, half the bytes of
    the int8 wire (Pallas on TPU, jnp elsewhere)."""
    _check_int4_block(x2d.shape[1])
    if _use_pallas4(x2d.shape[1]):
        interpret = pallas_interpret()
        return _pack4_pallas_call(x2d, scales, int(qmax), interpret)
    return _quantize_pack4_jnp(x2d, scales, qmax)


def unpack_dequantize4_values(p2d, scales):
    """fp32 values for packed int4 bytes (or their sum-safe partial
    sums)."""
    if _use_pallas4(p2d.shape[1] * 2):
        interpret = pallas_interpret()
        return _unpack4_pallas_call(p2d, scales, interpret)
    return _unpack_dequantize4_jnp(p2d, scales)


def _inv_scales(scales):
    return jnp.where(scales > 0, 1.0 / jnp.where(scales > 0, scales, 1.0),
                     0.0)


def quantize4_block_scaled(x, block_size: int | None = None,
                           qmax: int = _QMAX4):
    """Standalone int4 round-trip surface (the int8
    :func:`quantize_block_scaled` sibling): ``(packed int8, scales,
    meta)`` with two values per wire byte."""
    block = _check_int4_block(resolve_block_size(block_size))
    x2d, length = _to_blocks(x, block)
    scales = block_absmax(x2d) / qmax
    p = quantize_pack4_values(x2d, scales, qmax)
    meta = QuantMeta(tuple(x.shape), x.dtype, length, block)
    return p, scales, meta


def dequantize4_block_scaled(p2d, scales, meta: QuantMeta):
    return _from_blocks(unpack_dequantize4_values(p2d, scales), meta)


def int4_psum(x, axis_name, block_size: int | None = None):
    """Sum over ``axis_name`` with the packed int4 wire: one fp32
    scale ``pmax`` + one int8 ``psum`` of HALF the int8 payload."""
    out, _ = _int4_psum_impl(x, axis_name, block_size, with_error=False)
    return out


def int4_psum_with_error(x, axis_name, block_size: int | None = None):
    return _int4_psum_impl(x, axis_name, block_size, with_error=True)


def _int4_psum_impl(x, axis_name, block_size, with_error: bool):
    n = _axis_prod(axis_name)
    block = _check_int4_block(resolve_block_size(block_size))
    x2d, length = _to_blocks(x, block)
    meta = QuantMeta(tuple(x.shape), x.dtype, length, block)
    if n == 1:
        err = jnp.zeros(x.shape, jnp.float32) if with_error else None
        return x, err
    qmax = sum_safe_qmax4(n)
    scales = lax.pmax(block_absmax(x2d), axis_name) / qmax
    packed = quantize_pack4_values(x2d, scales, qmax)
    psummed = lax.psum(packed, axis_name)  # i8 wire, half the bytes
    out = _from_blocks(unpack_dequantize4_values(psummed, scales), meta)
    err = None
    if with_error:
        local = unpack_dequantize4_values(packed, scales)
        err = _from_blocks(
            (x2d - local),
            QuantMeta(tuple(x.shape), jnp.float32, length, block))
    return out, err


def int4_psum_scatter_segments(seg, axis_name,
                               block_size: int | None = None,
                               with_error: bool = False,
                               reduce_scatter=None):
    """The int4 sibling of :func:`quantized_psum_scatter_segments`:
    identical scale / headroom / residual contract, with the packed
    payload — ``(n*nb, block//2)`` int8 — riding the
    ``psum_scatter`` (or the overlap engine's ``reduce_scatter``
    ppermute ring; sum-safe headroom bounds nibble partial sums on
    either transport)."""
    n = _axis_prod(axis_name)
    block = _check_int4_block(resolve_block_size(block_size))
    length = seg.shape[1]
    pad = (-length) % block
    if pad:
        seg = jnp.concatenate(
            [seg, jnp.zeros((n, pad), jnp.float32)], axis=1)
    nb = seg.shape[1] // block
    x3 = seg.reshape(n, nb, block)
    absmax = jnp.max(jnp.abs(x3), axis=2)             # (n, nb)
    qmax = sum_safe_qmax4(n)
    scales = lax.pmax(absmax, axis_name) / qmax       # shared (n, nb)
    packed = quantize_pack4_values(x3.reshape(n * nb, block),
                                   scales.reshape(-1), qmax)
    if reduce_scatter is None:
        psummed = lax.psum_scatter(packed, axis_name,
                                   scatter_dimension=0, tiled=True)
    else:
        psummed = reduce_scatter(packed)              # (nb, block//2)
    my_scales = lax.dynamic_index_in_dim(
        scales, lax.axis_index(axis_name), axis=0, keepdims=False)
    out = unpack_dequantize4_values(psummed, my_scales).reshape(-1)
    if pad:
        out = out[:-pad]
    err = None
    if with_error:
        local = unpack_dequantize4_values(packed, scales.reshape(-1))
        err = (x3.reshape(n, -1) - local.reshape(n, -1))[:, :length]
    return out, err


# ---------------------------------------------------------------------------
# top-k sparsification: fixed-size index+value payloads
# ---------------------------------------------------------------------------

DEFAULT_TOPK_RATIO = 0.01


def resolve_topk_ratio(ratio: float | None = None) -> float:
    if ratio is None:
        ratio = float(_config.get("topk_ratio"))
    return min(max(float(ratio), 1e-6), 1.0)


def topk_k(length: int, ratio: float | None = None) -> int:
    """Static payload size: ``max(1, round(ratio * length))`` capped at
    ``length`` — fixed at trace time so XLA shapes never depend on the
    data."""
    r = resolve_topk_ratio(ratio)
    return max(1, min(int(length), int(round(int(length) * r))))


def _topk_select(flat, k: int):
    """This rank's magnitude top-k of a flat fp32 buffer: ``(int32
    indices, fp32 values)``, both shape ``(k,)``."""
    _, idx = lax.top_k(jnp.abs(flat), k)
    return idx.astype(jnp.int32), jnp.take(flat, idx)


def topk_psum(x, axis_name, ratio: float | None = None):
    out, _ = _topk_psum_impl(x, axis_name, ratio, with_error=False)
    return out


def topk_psum_with_error(x, axis_name, ratio: float | None = None):
    return _topk_psum_impl(x, axis_name, ratio, with_error=True)


def _topk_psum_impl(x, axis_name, ratio, with_error: bool):
    n = _axis_prod(axis_name)
    shape, dtype = x.shape, x.dtype
    flat = x.astype(jnp.float32).reshape(-1)
    if n == 1:
        err = jnp.zeros(shape, jnp.float32) if with_error else None
        return x, err
    k = topk_k(flat.shape[0], ratio)
    idx, vals = _topk_select(flat, k)
    # Every rank's sparse contribution, gathered: the k*(index+value)
    # payload IS the wire — the dense buffer is only rebuilt locally.
    all_idx = lax.all_gather(idx, axis_name, axis=0, tiled=False)
    all_vals = lax.all_gather(vals, axis_name, axis=0, tiled=False)
    dense = jnp.zeros_like(flat).at[all_idx.reshape(-1)].add(
        all_vals.reshape(-1))
    out = dense.reshape(shape).astype(dtype)
    err = None
    if with_error:
        # unselected entries accumulate in the EF residual (DGC-style)
        err = flat.at[idx].set(0.0).reshape(shape)
    return out, err


def topk_psum_scatter_segments(seg, axis_name, ratio: float | None = None,
                               with_error: bool = False):
    """Reduce-scatter a pre-segmented ``(n, L)`` fp32 buffer on the
    sparse wire: each rank picks its per-segment-row magnitude top-k
    (``k = max(1, round(ratio * L))``) and one ``all_to_all`` routes row
    ``r``'s ``(index, value)`` pairs to the rank owning segment ``r``,
    which scatter-adds them into its dense ``(L,)`` shard.  Same
    ``(shard, err)`` contract as :func:`quantized_psum_scatter_segments`
    — ``err`` is this rank's full ``(n, L)`` residual (the unselected
    entries) for error feedback."""
    n = _axis_prod(axis_name)
    L = seg.shape[1]
    if n == 1:
        err = (jnp.zeros(seg.shape, jnp.float32) if with_error else None)
        return seg.reshape(-1), err
    k = topk_k(L, ratio)
    _, idx = lax.top_k(jnp.abs(seg), k)               # (n, k) per row
    idx = idx.astype(jnp.int32)
    vals = jnp.take_along_axis(seg, idx, axis=1)
    ridx = lax.all_to_all(idx, axis_name, split_axis=0, concat_axis=0,
                          tiled=True)                 # (n, k) for MY seg
    rvals = lax.all_to_all(vals, axis_name, split_axis=0, concat_axis=0,
                           tiled=True)
    shard = jnp.zeros((L,), jnp.float32).at[ridx.reshape(-1)].add(
        rvals.reshape(-1))
    err = None
    if with_error:
        err = seg.at[jnp.arange(n)[:, None], idx].set(0.0)
    return shard, err


# ---------------------------------------------------------------------------
# Mode dispatch: the single entry point collectives / overlap / ZeRO use
# ---------------------------------------------------------------------------

LOSSY_MODES = ("int8", "int4", "topk")


def norm_mode(quantized) -> str:
    """Normalize the historical ``quantized`` flag (bool) and the mode
    strings onto one spelling: ``False -> "none"``, ``True -> "int8"``
    (the pre-int4 meaning), strings pass through."""
    if quantized is True:
        return "int8"
    if quantized is False or quantized is None:
        return "none"
    return str(quantized)


def lossy_psum(x, axis_name, mode: str, block_size: int | None = None,
               ratio: float | None = None):
    out, _ = _lossy_psum_impl(x, axis_name, mode, block_size, ratio,
                              with_error=False)
    return out


def lossy_psum_with_error(x, axis_name, mode: str,
                          block_size: int | None = None,
                          ratio: float | None = None):
    return _lossy_psum_impl(x, axis_name, mode, block_size, ratio,
                            with_error=True)


def _lossy_psum_impl(x, axis_name, mode, block_size, ratio,
                     with_error: bool):
    mode = norm_mode(mode)
    if mode == "int8":
        return _quantized_psum_impl(x, axis_name, block_size, with_error)
    if mode == "int4":
        return _int4_psum_impl(x, axis_name, block_size, with_error)
    if mode == "topk":
        return _topk_psum_impl(x, axis_name, ratio, with_error)
    raise ValueError(f"unknown lossy wire mode {mode!r}; expected one "
                     f"of {LOSSY_MODES}")


def lossy_psum_scatter_segments(seg, axis_name, mode: str,
                                block_size: int | None = None,
                                with_error: bool = False,
                                reduce_scatter=None,
                                ratio: float | None = None):
    """Mode-dispatched reduce-scatter of a ``(n, L)`` segment stack.
    ``reduce_scatter`` (the overlap engine's ppermute ring) swaps the
    dense payload transport for int8/int4; top-k ignores it — its
    sparse ``all_to_all`` payload already is the byte cut and has no
    dense summable wire to re-route."""
    mode = norm_mode(mode)
    if mode == "int8":
        return quantized_psum_scatter_segments(
            seg, axis_name, block_size, with_error,
            reduce_scatter=reduce_scatter)
    if mode == "int4":
        return int4_psum_scatter_segments(
            seg, axis_name, block_size, with_error,
            reduce_scatter=reduce_scatter)
    if mode == "topk":
        return topk_psum_scatter_segments(seg, axis_name, ratio,
                                          with_error)
    raise ValueError(f"unknown lossy wire mode {mode!r}; expected one "
                     f"of {LOSSY_MODES}")


# ---------------------------------------------------------------------------
# Error feedback state helpers
# ---------------------------------------------------------------------------


def init_error_feedback(params):
    """Zero residual pytree (fp32, one leaf per parameter) — the
    persistent error-feedback state for quantized gradient reduction."""
    return jax.tree_util.tree_map(
        lambda p: jnp.zeros(jnp.shape(p), jnp.float32), params)


def apply_error_feedback(grads, residuals):
    """Re-inject last step's compression error into this step's
    gradients (leafwise ``g + r`` in g's dtype)."""
    return jax.tree_util.tree_map(
        lambda g, r: (g + r.astype(g.dtype)), grads, residuals)
