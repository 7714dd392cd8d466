"""Gradient compression (parity with reference ``horovod/torch/compression.py``
and ``horovod/tensorflow/compression.py``, 74 LoC each).

Same API shape: ``Compression.none`` / ``Compression.fp16``, each a class
with ``compress(tensor) -> (tensor, ctx)`` and ``decompress(tensor, ctx)``.
The TPU build compresses to **bfloat16** by default — the MXU/ICI native
16-bit format with fp32-range exponent (no overflow hazard on gradient
norms), while ``fp16`` keeps the reference's IEEE-half behavior for
drop-in compatibility.
"""

from __future__ import annotations

import jax.numpy as jnp


class Compressor:
    """Interface for compressing and decompressing a given tensor."""

    @staticmethod
    def compress(tensor):
        raise NotImplementedError

    @staticmethod
    def decompress(tensor, ctx):
        raise NotImplementedError


class NoneCompressor(Compressor):
    """Default no-op compression."""

    @staticmethod
    def compress(tensor):
        return tensor, None

    @staticmethod
    def decompress(tensor, ctx):
        return tensor


class _CastCompressor(Compressor):
    wire_dtype: jnp.dtype

    @classmethod
    def compress(cls, tensor):
        dtype = tensor.dtype
        if jnp.issubdtype(dtype, jnp.floating) and dtype != cls.wire_dtype:
            return tensor.astype(cls.wire_dtype), dtype
        return tensor, None

    @classmethod
    def decompress(cls, tensor, ctx):
        if ctx is not None:
            return tensor.astype(ctx)
        return tensor


class FP16Compressor(_CastCompressor):
    """Compress floating-point gradients to IEEE fp16 on the wire."""
    wire_dtype = jnp.float16


class BF16Compressor(_CastCompressor):
    """Compress floating-point gradients to bfloat16 on the wire (TPU
    extension; preferred on ICI)."""
    wire_dtype = jnp.bfloat16


class _LossyCompressor(Compressor):
    """Base for the scale-aware / sparse wire modes: collective call
    sites dispatch on the ``quantized`` marker and run the mode's
    reduction (:mod:`horovod_tpu.ops.quantization`'s ``lossy_psum``
    family) instead of compress → psum → decompress; the ``mode``
    string is what the dispatch, the program cache keys and the round-0
    handshake carry."""

    quantized = True
    mode = "none"


class Int8Compressor(_LossyCompressor):
    """Block-scaled symmetric int8 quantization (EQuARX-style,
    :mod:`horovod_tpu.ops.quantization`).

    Unlike the cast compressors, the int8 wire is **not** a dtype the
    reduction can sum directly — per-block scales must be agreed across
    ranks first.  Collective call sites therefore dispatch on the
    ``quantized`` marker and run the scale-aware reduction
    (``quantized_psum``: pmax of block absmaxes → int8 psum → dequant)
    instead of compress → psum → decompress; under hierarchical
    allreduce only the cross-slice (DCN) hop is quantized.

    ``compress``/``decompress`` remain a faithful standalone round trip
    (local quantize → (payload, scales) → dequantize) for API parity
    and for one-shot wire uses (e.g. checkpoint shipping).  Integer and
    bool tensors pass through uncompressed, like the cast compressors.
    """

    quantized = True
    mode = "int8"

    @staticmethod
    def compress(tensor):
        if not jnp.issubdtype(tensor.dtype, jnp.floating):
            return tensor, None
        from horovod_tpu.ops import quantization as _q

        q, scales, meta = _q.quantize_block_scaled(tensor)
        return (q, scales), meta

    @staticmethod
    def decompress(tensor, ctx):
        if ctx is None:
            return tensor
        from horovod_tpu.ops import quantization as _q

        q, scales = tensor
        return _q.dequantize_block_scaled(q, scales, ctx)


class Int4Compressor(_LossyCompressor):
    """Packed int4 block quantization: two signed nibbles per wire
    byte with sum-safe headroom (``qmax = 7 // n``), HALF the int8
    payload — see :mod:`horovod_tpu.ops.quantization`.  Designed for
    the small, slow cross-slice axis; refuses axes past 7 ranks."""

    mode = "int4"

    @staticmethod
    def compress(tensor):
        if not jnp.issubdtype(tensor.dtype, jnp.floating):
            return tensor, None
        from horovod_tpu.ops import quantization as _q

        p, scales, meta = _q.quantize4_block_scaled(tensor)
        return (p, scales), meta

    @staticmethod
    def decompress(tensor, ctx):
        if ctx is None:
            return tensor
        from horovod_tpu.ops import quantization as _q

        p, scales = tensor
        return _q.dequantize4_block_scaled(p, scales, ctx)


class TopKCompressor(_LossyCompressor):
    """Magnitude top-k sparsification with a fixed-size
    ``k = max(1, round(HOROVOD_TOPK_RATIO * n))`` index+value payload
    (static shapes for XLA); unselected entries accumulate in the
    error-feedback residual.  The standalone compress/decompress pair
    is the local sparsify round trip; the collective wire gathers every
    rank's sparse payload and scatter-adds (see
    :func:`horovod_tpu.ops.quantization.topk_psum`)."""

    mode = "topk"

    @staticmethod
    def compress(tensor):
        if not jnp.issubdtype(tensor.dtype, jnp.floating):
            return tensor, None
        from horovod_tpu.ops import quantization as _q

        flat = tensor.astype(jnp.float32).reshape(-1)
        k = _q.topk_k(flat.shape[0])
        idx, vals = _q._topk_select(flat, k)
        return (idx, vals), (tuple(tensor.shape), tensor.dtype)

    @staticmethod
    def decompress(tensor, ctx):
        if ctx is None:
            return tensor
        import numpy as _np

        idx, vals = tensor
        shape, dtype = ctx
        total = int(_np.prod(shape)) if shape else 1
        dense = jnp.zeros((total,), jnp.float32).at[idx].set(vals)
        return dense.reshape(shape).astype(dtype)


# Aggressiveness ladder (docs/compression.md): byte cut grows to the
# right.  The adaptive tuner walks it per bucket, and the bounded-loss
# guardrail pins a bucket back to int8 (index 3) when its EF residual
# ratio breaches the ceiling.
MODE_LADDER = ("none", "bf16", "fp16", "int8", "int4", "topk")


class Compression:
    """Optional gradient compression algorithm used during allreduce."""

    none = NoneCompressor
    fp16 = FP16Compressor
    bf16 = BF16Compressor
    int8 = Int8Compressor
    int4 = Int4Compressor
    topk = TopKCompressor

    @classmethod
    def lookup(cls, name: str):
        """Compressor for a ``HOROVOD_COMPRESSION`` knob value."""
        try:
            return {"none": cls.none, "": cls.none, "fp16": cls.fp16,
                    "bf16": cls.bf16, "int8": cls.int8,
                    "int4": cls.int4, "topk": cls.topk}[str(name).lower()]
        except KeyError:
            raise ValueError(
                f"Unknown compression mode {name!r}; expected "
                "none|fp16|bf16|int8|int4|topk") from None


def is_quantized(compression) -> bool:
    """True for compressors needing a scale-aware / sparse reduction
    (int8, int4, topk) rather than the compress→psum→decompress
    sandwich."""
    return bool(getattr(compression, "quantized", False))


def wire_mode(compression) -> str:
    """The mode string a compressor's collective wire runs
    (``none|fp16|bf16|int8|int4|topk``)."""
    if compression is None or compression is NoneCompressor:
        return "none"
    if is_quantized(compression):
        return getattr(compression, "mode", "int8")
    wire = getattr(compression, "wire_dtype", None)
    if wire == jnp.float16:
        return "fp16"
    if wire == jnp.bfloat16:
        return "bf16"
    return "none"


def active_compression():
    """The compressor selected by the ``HOROVOD_COMPRESSION`` knob."""
    from horovod_tpu.common import config as _config

    return Compression.lookup(_config.get("compression"))


# ---------------------------------------------------------------------------
# Per-bucket modes (the adaptive compression stack, docs/compression.md)
# ---------------------------------------------------------------------------


def parse_bucket_modes(spec: str) -> list[str]:
    """Parse a ``HOROVOD_BUCKET_COMPRESSION`` value — colon-separated
    mode names, e.g. ``int8:int4:topk`` (colons keep the value safe in
    the autotuner's CSV log).  Every entry is validated against the
    ladder; raises on typos so a bad knob fails fast instead of
    silently riding the dense wire."""
    modes = [m.strip().lower() for m in str(spec).split(":") if m.strip()]
    for m in modes:
        if m not in MODE_LADDER:
            raise ValueError(
                f"HOROVOD_BUCKET_COMPRESSION entry {m!r} is not a wire "
                f"mode; expected one of {'|'.join(MODE_LADDER)}")
    return modes


def bucket_modes(k: int, default: str = "none") -> list[str]:
    """Effective per-bucket wire modes for a K-bucket schedule: the
    ``HOROVOD_BUCKET_COMPRESSION`` knob (autotuner-owned under
    ``HOROVOD_ADAPTIVE_COMPRESSION``, or set by hand) cycled to length
    ``k``; when unset, ``default`` (the uniform mode the caller
    resolved) for every bucket."""
    from horovod_tpu.common import config as _config

    spec = str(_config.get("bucket_compression")).strip()
    if not spec:
        return [default] * max(1, int(k))
    modes = parse_bucket_modes(spec)
    if not modes:
        return [default] * max(1, int(k))
    return [modes[b % len(modes)] for b in range(max(1, int(k)))]


def effective_bucket_modes(default: str | None = None) -> list[str]:
    """The mode vector the eager data plane will actually run for a
    fused floating payload: K entries when the overlap engine is on
    (one per bucket), one entry otherwise.  Shared by the program
    cache keys (``xla_exec``), the trace-time bodies, and the
    autotuner's wire-byte accounting, so the three can never disagree
    about what crosses the wire."""
    from horovod_tpu.common import config as _config
    from horovod_tpu.ops import overlap as _ovl

    if default is None:
        default = str(_config.get("compression")).lower() or "none"
    k = _ovl.configured_chunks() if _ovl.enabled() else 1
    return bucket_modes(k, default=default)


def payload_wire_bytes(n_elems: int, itemsize: int, mode: str, *,
                       block: int, ratio: float, world: int) -> int:
    """Wire bytes a floating payload of ``n_elems`` elements actually
    moves under ``mode``, on the same one-pass convention the dense
    accounting uses (an allreduce counts its logical payload once):

    * casts — 2 bytes/element when that shrinks the payload;
    * int8 — 1 byte/element + one fp32 scale per block;
    * int4 — HALF a byte/element (two nibbles per wire byte) + scales;
    * topk — ``world * k * 8 / 2``: the gather of ``k`` (int32 index,
      fp32 value) pairs from each of ``world`` ranks moves
      ``world*k*8`` bytes per link where the dense one-pass convention
      counts half of the reduce-scatter+allgather round trip, so the
      halved figure keeps the wire/logical ratio equal to the true
      per-link byte ratio.
    """
    n_elems = max(int(n_elems), 0)
    dense = n_elems * itemsize
    mode = str(mode).lower()
    if n_elems == 0 or mode in ("", "none"):
        return dense
    if mode in ("fp16", "bf16"):
        return n_elems * 2 if itemsize > 2 else dense
    block = max(int(block), 1)
    scales = 4 * (n_elems // block + 1)
    if mode == "int8":
        return n_elems + scales
    if mode == "int4":
        return (n_elems + 1) // 2 + scales
    if mode == "topk":
        k = max(1, int(round(n_elems * ratio)))
        return max(1, max(2, int(world)) * k * 8 // 2)
    return dense


def fused_wire_bytes(n_elems: int, itemsize: int, modes, *, block: int,
                     ratio: float, world: int) -> int:
    """Wire bytes of a fused floating payload under a per-bucket mode
    vector: the payload splits into the same contiguous bucket shares
    the overlap chain uses (``n // k`` plus one extra element for the
    first ``n % k`` buckets), each share counted under ITS mode by
    :func:`payload_wire_bytes`.  The single accounting the autotuner's
    scoring and the ``hvd_data_wire_bytes_total`` metric share — so
    they can never disagree about the achieved byte cut."""
    n_elems = max(int(n_elems), 0)
    modes = list(modes) or ["none"]
    k = len(modes)
    total = 0
    for b, m in enumerate(modes):
        share = n_elems // k + (1 if b < n_elems % k else 0)
        total += payload_wire_bytes(share, itemsize, m, block=block,
                                    ratio=ratio, world=world)
    return total
