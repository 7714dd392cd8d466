"""DistributedOptimizer / gradient-aggregation surface.

Parity targets:
  * ``hvd.DistributedOptimizer`` (reference ``horovod/torch/__init__.py:66-221``
    and ``horovod/tensorflow/__init__.py:266-311``): wrap an optimizer so
    gradients are averaged across ranks before the update, with
    ``backward_passes_per_step`` local accumulation.
  * ``hvd.DistributedGradientTape`` (reference
    ``horovod/tensorflow/__init__.py:475-531``): wrap gradient
    computation itself.

JAX mapping: optimizers are optax ``GradientTransformation``s, and
"wrapping backward" is wrapping ``jax.grad``.  Two execution regimes,
chosen automatically:

  * **compiled** — inside `shard_map` with a named mesh axis: gradients
    reduce with `lax.psum` traced into the step (XLA overlaps them with
    backprop compute; the role of the reference's hook-per-gradient
    eager pipeline).
  * **eager** — concrete arrays: gradients fuse into per-dtype flat
    buffers and go through the background runtime's negotiated
    collectives (tensor fusion, reference ``FuseResponses``).
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from horovod_tpu.common import basics as _basics
from horovod_tpu.common import config as _config
from horovod_tpu.common.types import HorovodTpuError
from horovod_tpu.optim import fused_update as _fused
from horovod_tpu.ops import collectives as _coll
from horovod_tpu.ops import eager as _eager
from horovod_tpu.ops import quantization as _quant
from horovod_tpu.ops.collectives import Adasum, Average, Sum
from horovod_tpu.ops.compression import (Compression, active_compression,
                                         is_quantized, wire_mode)
from horovod_tpu.parallel import mesh as _pmesh
from horovod_tpu.runtime import metrics as _metrics

_M_FUSED_BYTES = _metrics.gauge(
    "hvd_fusion_buffer_bytes",
    "Flat fused-gradient buffer size per dtype group on the eager "
    "path.")

# ZeRO residency gauges (docs/metrics.md / docs/zero.md): the N-fold
# memory claim as scrapeable numbers.  Stamped from the static fused
# layout at optimizer-state init, so they are exact byte counts of what
# is resident per chip — params (stage 3 shards vs replicated), the
# gradient reduction's resident form (stage >= 2 shard vs full fused
# buffer), and the wrapped optimizer's state (sharded from stage 1 on).
_M_ZERO_STAGE = _metrics.gauge(
    "hvd_zero_stage",
    "Resolved ZeRO stage of the last-constructed DistributedOptimizer "
    "(0 = replicated update).")
_M_ZERO_PARAM_BYTES = _metrics.gauge(
    "hvd_zero_param_bytes_per_chip",
    "Resident parameter bytes per chip (1/world flat shards under "
    "zero_stage=3, full replicas below).")
_M_ZERO_GRAD_BYTES = _metrics.gauge(
    "hvd_zero_grad_bytes_per_chip",
    "Resident reduced-gradient bytes per chip (the rank-local shard "
    "under zero_stage>=2; the full fused buffer below).")
_M_ZERO_OPT_BYTES = _metrics.gauge(
    "hvd_zero_opt_state_bytes_per_chip",
    "Wrapped optimizer-state bytes per chip (shard-local from "
    "zero_stage>=1 on).")
_M_RESID_RATIO = _metrics.gauge(
    "hvd_compression_residual_ratio",
    "Per-bucket error-feedback residual-to-reduced-gradient norm "
    "ratio, published while HOROVOD_ADAPTIVE_COMPRESSION is on; the "
    "adaptive tuner's bounded-loss guardrail pins a bucket back to "
    "int8 when this exceeds "
    "HOROVOD_COMPRESSION_MAX_RESIDUAL_RATIO (docs/compression.md).")


def _publish_residual_ratios(ratios) -> None:
    """Host side of the in-trace guardrail signal (jax.debug.callback
    target): one gauge series per bucket index."""
    arr = np.asarray(ratios).reshape(-1)
    for b in range(arr.shape[0]):
        v = float(arr[b])
        if np.isfinite(v):
            _M_RESID_RATIO.set(v, bucket=str(b))


def _report_bucket_residual_ratios(err, ref, n, axis_name,
                                   chunks: int = 1) -> None:
    """In-trace guardrail signal for the adaptive compression stack:
    per-bucket ``||EF residual|| / ||reduced gradient||`` published to
    the metrics registry via a host callback.  ``err`` is the
    full-size ``(n*L,)`` fp32 residual in segment layout; ``ref`` is
    either this rank's ``(L,)`` reduced shard (ZeRO paths — bucket
    norms are psum'd to global) or the full ``(n*L,)`` reduced buffer
    (replicated path — already global).  Bucket bounds mirror the
    scatter chain that produced ``err``, so ratios land on the same
    bucket indices the tuner's mode vector cycles over.  Gated on the
    ``HOROVOD_ADAPTIVE_COMPRESSION`` knob — zero cost otherwise."""
    if not _config.get("adaptive_compression"):
        return
    from jax import lax

    from horovod_tpu.ops import overlap as _ovl

    n = max(int(n), 1)
    L = err.shape[0] // n
    if L == 0:
        return
    bounds = _ovl.bucket_bounds(L, max(1, int(chunks)))
    e2d = err.reshape(n, L)
    full_ref = ref.shape[0] == err.shape[0]
    ref = ref.astype(jnp.float32)
    r2d = ref.reshape(n, L) if full_ref else None
    rs, gs = [], []
    for (s, e) in bounds:
        rs.append(jnp.sum(jnp.square(e2d[:, s:e])))
        gs.append(jnp.sum(jnp.square(r2d[:, s:e] if full_ref
                                     else ref[s:e])))
    rvec, gvec = jnp.stack(rs), jnp.stack(gs)
    rvec = lax.psum(rvec, axis_name)  # residuals are per-rank local
    if not full_ref:
        gvec = lax.psum(gvec, axis_name)  # shard slices are 1/n each
    ratios = jnp.sqrt(rvec) / jnp.maximum(jnp.sqrt(gvec), 1e-12)
    jax.debug.callback(_publish_residual_ratios, ratios)


def _maybe_report_residual_ratio(new_res, reduced, axis_name,
                                 overlap=None) -> None:
    """Replicated-path wrapper for :func:`_report_bucket_residual_
    ratios`: rebuilds the fused float-buffer view the grouped lossy
    allreduce ran on (float leaves raveled fp32 in leaf order, padded
    to the axis size) from the per-leaf residual/reduced trees."""
    if not _config.get("adaptive_compression"):
        return
    from horovod_tpu.ops import overlap as _ovl

    res_l = jax.tree_util.tree_leaves(new_res)
    red_l = jax.tree_util.tree_leaves(reduced)
    if not res_l or len(res_l) != len(red_l) or not _in_trace(res_l):
        return
    # Pair leaf-wise and keep the float ones: the residual tree carries
    # zero entries for integer leaves (they bypass the lossy wire), and
    # dropping the PAIR — not just the gradient side — keeps the two
    # fused views aligned for models with mixed-dtype grads.
    pairs = [(jnp.asarray(r).astype(jnp.float32).reshape(-1),
              jnp.asarray(g).astype(jnp.float32).reshape(-1))
             for r, g in zip(res_l, red_l)
             if jnp.issubdtype(jnp.asarray(g).dtype, jnp.floating)]
    if not pairs:
        return
    rl = [r for r, _ in pairs]
    gl = [g for _, g in pairs]
    ferr = rl[0] if len(rl) == 1 else jnp.concatenate(rl)
    fred = gl[0] if len(gl) == 1 else jnp.concatenate(gl)
    n = _coll._axis_total(axis_name)
    pad = (-ferr.shape[0]) % max(n, 1)
    if pad:
        z = jnp.zeros((pad,), jnp.float32)
        ferr = jnp.concatenate([ferr, z])
        fred = jnp.concatenate([fred, z])
    chunks = (_ovl.configured_chunks() if _ovl.enabled(overlap) else 1)
    _report_bucket_residual_ratios(ferr, fred, n, axis_name,
                                   chunks=chunks)


def _in_trace(tree) -> bool:
    return any(isinstance(x, jax.core.Tracer) for x in jax.tree_util.tree_leaves(tree))


def _check_eager_mesh() -> None:
    """The eager/negotiated wire is flat-world (one lead device per
    process over the ``hvd`` axis); with tp/pp/sp extents on the data
    mesh it would average model-sharded values across islands.  Fail
    loudly instead of corrupting params (docs/mesh.md)."""
    if _pmesh.model_parallel_size() > 1:
        raise HorovodTpuError(
            "eager collectives are flat-world and cannot honor a data "
            f"mesh with model-parallel axes ({_pmesh.canonical_spec(_pmesh.active_spec())!r}); "
            "run the gradient reduction in-trace (shard_map over the "
            "data mesh) or drop the tp/pp/sp extents from HOROVOD_MESH")


def _health_wrap(tx, axis_name: str):
    """Training-health plane (docs/health.md): wrap the finished
    DistributedOptimizer transformation with the in-trace stat taps.

    Knob-gated at TRACE time (``HOROVOD_HEALTH``, validated at the
    round-0 handshake), zero-cost when off.  In-trace, the tap computes
    per-dtype-group finite-part grad norm / max-abs / PRE-reduction
    nonfinite count over the incoming gradient leaves — this rank's
    local gradients, before any reduction, on every ZeRO stage and
    overlap setting — packs them into one small per-rank verdict
    vector, allgathers it (the single collective health adds to the
    step) and publishes via host callback, so a nonfinite names its
    culprit rank + dtype group.  Post-update it publishes the
    update-to-weight ratio (local, zero comm).  On the eager regime the
    negotiated allreduce/reducescatter programs carry the tap instead
    (ops/xla_exec), so nothing is double-counted here.

    ``HOROVOD_HEALTH_SKIP_NONFINITE=1`` adds the skip-step contract:
    a step whose verdict carries a nonfinite applies a zero update and
    HOLDS the optimizer state (momenta, EF residuals) — the same
    state-selection machinery the error-feedback path rides — so
    survivors' parameters stay finite.

    Pure observers otherwise: with the skip knob off, enabling stats
    changes no trained parameter bit (the parity matrix in
    tests/test_health.py pins this across stage 0-3 x overlap x
    int8/int4/topk)."""
    from horovod_tpu.runtime import faults as _faults
    from horovod_tpu.runtime import health as _health

    def update(grads, state, params=None, **extra):
        if not _health.enabled():
            return tx.update(grads, state, params, **extra)
        leaves = jax.tree_util.tree_leaves(grads)
        in_tr = _in_trace(leaves)
        bad = idx = None
        if in_tr:
            if _faults.data_rules():
                # Deterministic in-trace poisoning (nan:/inf: rules,
                # testing only — docs/fault-tolerance.md).
                try:
                    ridx = _coll.shard_index(axis_name)
                except Exception:
                    ridx = None
                leaves2, treedef = jax.tree_util.tree_flatten(grads)
                leaves2 = [
                    _faults.traced_poison(l, f"grads.{l.dtype}", ridx)
                    if jnp.issubdtype(jnp.asarray(l).dtype, jnp.floating)
                    else l for l in leaves2]
                grads = jax.tree_util.tree_unflatten(treedef, leaves2)
                leaves = leaves2
            tap = _health.tap_gradients(leaves, axis_name)
            if tap is not None:
                bad, idx = tap
        upd, new_state = tx.update(grads, state, params, **extra)
        try:
            _health.tap_update_ratio(upd, params)
        except Exception:  # a stat must never cost the step
            pass
        if _health.skip_enabled():
            if in_tr and bad is not None:
                upd, new_state = _health.apply_skip_traced(
                    bad, upd, state, new_state, idx=idx)
            elif not in_tr:
                upd, new_state = _health.apply_skip_eager(
                    upd, state, new_state)
        return upd, new_state

    return type(tx)(tx.init, update)


def _resolve_compression(compression):
    """``None`` → the ``HOROVOD_COMPRESSION`` knob's compressor (so the
    launcher/config surface reaches every default-argument call site);
    an explicit compressor always wins."""
    return active_compression() if compression is None else compression


def allreduce_gradients(grads, op: int = Average,
                        axis_name: str | None = None,
                        compression=None, overlap=None):
    """Allreduce a gradient pytree.

    ``axis_name=None`` resolves to the configured data mesh's ``dp``
    axis (docs/mesh.md), else the flat world axis ``"hvd"``.

    In-trace: one grouped psum (XLA fuses into large ICI transfers);
    ``Compression.int8`` routes through the fused quantized reduction,
    and ``overlap`` (default: the ``HOROVOD_OVERLAP`` knob) swaps the
    monolithic collective for the bucketed ppermute ring schedule
    (:mod:`horovod_tpu.ops.overlap`) so communication hides behind
    compute.  Eager: leaves grouped by dtype, each group raveled into
    one flat buffer -> one negotiated fused collective per dtype
    (tensor fusion, reference ``fusion_buffer_manager.h``); the eager
    wire applies the ``HOROVOD_COMPRESSION`` / ``HOROVOD_OVERLAP``
    knobs inside the negotiated program (per-call arguments cannot
    guarantee cross-rank agreement there — the knobs are validated at
    the round-0 handshake).
    """
    compression = _resolve_compression(compression)
    axis_name = _pmesh.resolve_axis(axis_name)
    leaves, treedef = jax.tree_util.tree_flatten(grads)
    if not leaves:
        return grads
    if _in_trace(leaves):
        # the collective and what is packed, cast and divided around it;
        # a bucketed schedule's own scopes nest inside (docs/perf.md)
        with jax.named_scope("hvd_grad_reduce"):
            reduced = _coll.grouped_allreduce(
                leaves, axis_name=axis_name, op=op,
                compression=compression, overlap=overlap)
        return jax.tree_util.tree_unflatten(treedef, reduced)
    _check_eager_mesh()
    # Quantized wire on the eager path is knob-driven inside the
    # negotiated program (xla_exec); the per-leaf compressor must be a
    # pass-through here.
    eager_comp = Compression.none if is_quantized(compression) \
        else compression
    return jax.tree_util.tree_unflatten(
        treedef, _eager_fused_pytree_allreduce(leaves, op, eager_comp))


def allreduce_gradients_with_feedback(grads, residuals, op: int = Average,
                                      axis_name: str | None = None,
                                      overlap=None, compression=None):
    """Lossy (int8/int4/topk) gradient allreduce with error feedback:
    returns ``(reduced, new_residuals)``.  Last step's residuals are
    re-injected before reduction; the new residuals carry this step's
    local compression error (see :mod:`horovod_tpu.ops.quantization`).
    ``compression=None`` resolves from the ``HOROVOD_COMPRESSION``
    knob, defaulting to int8 when the knob names a non-lossy mode (this
    entry point exists for the EF contract).  In-trace only — the eager
    negotiated program does not expose the local compression error, so
    eager calls reduce without feedback and return the residuals
    unchanged."""
    compression = _resolve_compression(compression)
    axis_name = _pmesh.resolve_axis(axis_name)
    if not is_quantized(compression):
        compression = Compression.int8
    leaves, treedef = jax.tree_util.tree_flatten(grads)
    if not leaves:
        return grads, residuals
    if not _in_trace(leaves):
        return (allreduce_gradients(grads, op=op, axis_name=axis_name,
                                    compression=compression),
                residuals)
    with jax.named_scope("hvd_grad_reduce"):
        injected = _quant.apply_error_feedback(grads, residuals)
        ileaves = jax.tree_util.tree_flatten(injected)[0]
        outs, errs = _coll.grouped_quantized_allreduce(
            ileaves, axis_name=axis_name, op=op, with_error=True,
            overlap=overlap, mode=wire_mode(compression))
    return (jax.tree_util.tree_unflatten(treedef, outs),
            jax.tree_util.tree_unflatten(treedef, errs))


def _fused_pytree_collective(leaves, submit_async):
    """Shared eager fusion: group leaves by dtype, ravel each group into
    one flat buffer, run one async collective per group via
    ``submit_async(flat, label) -> handle``, split results back."""
    groups: dict[Any, list[int]] = {}
    leaves = [jnp.asarray(l) for l in leaves]
    for i, leaf in enumerate(leaves):
        groups.setdefault(np.dtype(leaf.dtype), []).append(i)
    out: list[Any] = [None] * len(leaves)
    handles = []
    for dtype, idxs in groups.items():
        flat = (leaves[idxs[0]].reshape(-1) if len(idxs) == 1 else
                jnp.concatenate([leaves[i].reshape(-1) for i in idxs]))
        _M_FUSED_BYTES.set(int(flat.size) * dtype.itemsize,
                           dtype=str(dtype))
        handles.append((idxs, submit_async(flat, f"{dtype}.{len(idxs)}")))
    for idxs, h in handles:
        red = _eager.synchronize(h)
        off = 0
        for i in idxs:
            size = int(np.prod(leaves[i].shape)) if leaves[i].ndim else 1
            out[i] = red[off:off + size].reshape(leaves[i].shape)
            off += size
    return out


def _eager_fused_pytree_allreduce(leaves, op, compression,
                                  scope: str | None = None):
    # Scoped local-SGD reductions ride the name-prefix wire contract
    # (controller.reduction_scope, docs/local-sgd.md): the negotiated
    # names pin every rank's program to the same (local | cross)
    # sub-axis, and the controller never fuses across scopes.
    prefix = "grad_buffer" if scope is None else f"localsgd.{scope}"
    return _fused_pytree_collective(
        leaves,
        lambda flat, label: _eager.allreduce_async(
            flat, op=op, name=f"{prefix}.{label}",
            compression=compression))


class _AccumulationState(NamedTuple):
    counter: jnp.ndarray
    accum: Any
    inner_state: Any


class _FeedbackState(NamedTuple):
    """Optimizer state wrapper carrying the persistent error-feedback
    residual pytree for quantized (int8) gradient reduction."""
    residual: Any
    inner_state: Any


def _resolve_zero_stage(zero_stage, sharded) -> int:
    """Resolve the ZeRO stage for a DistributedOptimizer: an explicit
    ``zero_stage`` wins (and must agree with an explicit ``sharded``);
    the legacy ``sharded`` boolean pins stage 1/0 exactly; otherwise the
    ``HOROVOD_ZERO_STAGE`` knob applies, with ``HOROVOD_SHARDED_OPTIMIZER``
    kept as the stage-1 spelling it always was."""
    if zero_stage is not None:
        stage = int(zero_stage)
        if stage not in (0, 1, 2, 3):
            raise HorovodTpuError(
                f"zero_stage must be 0..3, got {zero_stage!r} "
                "(0 replicated, 1 sharded optimizer state, 2 + sharded "
                "gradients, 3 + sharded parameters; docs/zero.md)")
        if sharded is not None and bool(sharded) != (stage >= 1):
            raise HorovodTpuError(
                f"conflicting DistributedOptimizer arguments: "
                f"sharded={sharded!r} but zero_stage={stage} "
                f"({'implies' if stage >= 1 else 'disables'} sharding); "
                "drop the legacy sharded= argument.")
        return stage
    if sharded is not None:
        return 1 if sharded else 0
    stage = int(_config.get("zero_stage"))
    if stage not in (0, 1, 2, 3):
        raise HorovodTpuError(
            f"HOROVOD_ZERO_STAGE must be 0..3, got {stage!r}")
    if stage == 0 and bool(_config.get("sharded_optimizer")):
        stage = 1
    return stage


def _zero_chunks(chunks=None) -> int:
    """Bucket count of the ZeRO-2/3 pipelines (scatter of gradients as
    they form, prefetch of parameters under the forward)."""
    if chunks is not None:
        return max(1, int(chunks))
    return max(1, int(_config.get("zero_prefetch_chunks")))


def _leaf_nbytes(leaves) -> int:
    return int(sum(
        (int(np.prod(l.shape)) if getattr(l, "ndim", 0) else 1)
        * np.dtype(l.dtype).itemsize for l in leaves))


def _stamp_zero_bytes(stage: int, layout, inner_state) -> None:
    """Per-chip residency gauges from the static layout (trace-safe:
    everything here is a Python int)."""
    try:
        pbytes = gbytes = 0
        for g, key in enumerate(layout.keys):
            item = jnp.dtype(key).itemsize
            total = sum(layout.sizes[g])
            pbytes += (layout.shard[g] if stage >= 3 else total) * item
            gbytes += (layout.shard[g] if stage >= 2
                       else layout.padded[g]) * item
        _M_ZERO_PARAM_BYTES.set(pbytes)
        _M_ZERO_GRAD_BYTES.set(gbytes)
        _M_ZERO_OPT_BYTES.set(
            _leaf_nbytes(jax.tree_util.tree_leaves(inner_state)))
    except Exception:  # pragma: no cover — metrics must never cost a step
        pass


def _stamp_zero_bytes_replicated(params, state) -> None:
    try:
        n = _leaf_nbytes(jax.tree_util.tree_leaves(params))
        _M_ZERO_PARAM_BYTES.set(n)
        _M_ZERO_GRAD_BYTES.set(n)
        _M_ZERO_OPT_BYTES.set(
            _leaf_nbytes(jax.tree_util.tree_leaves(state)))
    except Exception:  # pragma: no cover
        pass


# ---------------------------------------------------------------------------
# ZeRO-1/2 sharded weight update (arXiv:2004.13336 and beyond):
# reduce-scatter the fused gradient buffers, run the wrapped optimizer
# on only the rank-local 1/world_size shard (optimizer state — Adam
# moments etc. — is initialized and carried shard-local), allgather the
# update shards.  Stage 2 keeps the gradients shard-resident too: the
# fused buffers are scattered bucket-by-bucket as they form and no
# full-size fused gradient buffer ever materializes (docs/zero.md).
# ---------------------------------------------------------------------------


class _ShardLayout(NamedTuple):
    """Static fused-buffer layout shared by init and update: per dtype
    group, the member leaf indices and flat sizes, the buffer length
    padded to a multiple of world size, and the per-rank shard length."""
    keys: tuple      # dtype names, insertion (leaf) order
    idxs: tuple      # tuple[int, ...] per group
    sizes: tuple     # tuple[int, ...] per group (flat leaf sizes)
    padded: tuple    # int per group
    shard: tuple     # int per group (padded // world)


@jax.tree_util.register_pytree_node_class
class _ShardedState:
    """Optimizer state for the sharded update.  ``inner_state`` is the
    wrapped optimizer's state over the rank-local shard buffers (the
    ~1/world_size optimizer-state footprint ZeRO-1 exists for);
    ``residual`` is the int8 error-feedback residual over the full
    fused buffers (input-side EF needs the full local quantization
    error — it is one flat fp32 buffer per float group, not a
    leaf-per-parameter tree; ``None`` without quantization); ``layout``
    is the static :class:`_ShardLayout` (pytree aux data)."""

    def __init__(self, inner_state, residual, layout: _ShardLayout):
        self.inner_state = inner_state
        self.residual = residual
        self.layout = layout

    def tree_flatten(self):
        return (self.inner_state, self.residual), self.layout

    @classmethod
    def tree_unflatten(cls, layout, children):
        return cls(children[0], children[1], layout)

    def __repr__(self) -> str:  # keep state dumps readable
        return (f"_ShardedState(inner_state={self.inner_state!r}, "
                f"residual={self.residual!r})")


def _is_sharded_state(x) -> bool:
    return isinstance(x, _ShardedState)


def _contains_sharded_state(tree) -> bool:
    return any(_is_sharded_state(l) for l in
               jax.tree_util.tree_leaves(tree, is_leaf=_is_sharded_state))


def _shard_layout(leaves, n: int) -> _ShardLayout:
    groups: dict = {}
    for i, leaf in enumerate(leaves):
        groups.setdefault(str(jnp.dtype(leaf.dtype)), []).append(i)
    keys, idxs, sizes, padded, shard = [], [], [], [], []
    for key, ii in groups.items():
        sz = tuple(int(np.prod(leaves[i].shape)) if leaves[i].ndim else 1
                   for i in ii)
        total = sum(sz)
        p = total + (-total) % n
        keys.append(key)
        idxs.append(tuple(ii))
        sizes.append(sz)
        padded.append(p)
        shard.append(p // n)
    return _ShardLayout(tuple(keys), tuple(idxs), tuple(sizes),
                        tuple(padded), tuple(shard))


def _fuse_group(leaves, layout: _ShardLayout, g: int):
    """One flat buffer for group ``g``, zero-padded to the layout's
    world-divisible length."""
    flats = [leaves[i].reshape(-1) for i in layout.idxs[g]]
    flat = flats[0] if len(flats) == 1 else jnp.concatenate(flats)
    pad = layout.padded[g] - flat.shape[0]
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    return flat


def _shard_position(axis_name):
    """(shard index, world size, in_trace) for the current regime.

    The axis binding — not leaf tracer-ness — decides the regime:
    inside ``shard_map`` the gradient leaves can be trace-constants
    (closed-over parameters) while the mesh axis is still what shards
    the update, so probe ``lax.axis_index`` first and fall back to the
    process rank only when the axis is unbound (the eager
    one-process-per-chip regime, or state init outside the step)."""
    try:
        return (_coll.shard_index(axis_name),
                _quant._axis_prod(axis_name), True)
    except Exception:
        pass
    _check_eager_mesh()
    st = _basics.state()
    if st.initialized:
        return st.rank, st.size, False
    return 0, 1, False


def _bucketed_scatter_group(leaves, layout, g: int, n: int, axis_name,
                            quantized, with_error: bool,
                            residual, overlap=None, chunks=None,
                            scope: str = "hvd_zero2_rs"):
    """Stage-2 gradient scatter for dtype group ``g``: the fused buffer
    is never concatenated — K bucket pieces (column slices of the
    ``(n, L)`` segment view) are assembled span-wise straight from the
    gradient leaves (:func:`~horovod_tpu.ops.collectives
    .fuse_bucket_piece`), reduce-scattered one by one in a
    barrier-separated chain (so XLA neither re-fuses them into one
    full-size buffer nor hoists every transfer to the front), and only
    the concatenation of the rank-local bucket shards — the 1/n shard —
    is ever a live value.  Error-feedback residual slices ride into the
    pieces via ``inject`` (the lossy EF contract is unchanged; the
    residual itself is optimizer state and stays full-size, as under
    ZeRO-1).  ``quantized`` accepts the historical bool or a wire-mode
    string, and each bucket of the chain may carry its OWN mode
    (``HOROVOD_BUCKET_COMPRESSION`` — the adaptive stack,
    docs/compression.md).  Returns ``(shard, err)`` with the exact
    ``_scatter_flat_buffer`` layout."""
    from jax import lax

    from horovod_tpu.ops import overlap as _ovl
    from horovod_tpu.ops import quantization as _quantz

    L = layout.padded[g] // n
    bounds = _ovl.bucket_bounds(L, _zero_chunks(chunks))
    lossy_any = _quantz.norm_mode(quantized) in _quantz.LOSSY_MODES
    dtype = jnp.float32 if lossy_any else jnp.dtype(layout.keys[g])
    bmodes = _ovl.resolve_bucket_modes(None, len(bounds), quantized,
                                       dtype)
    inject = None
    if residual is not None:
        inject = lambda lo, hi: residual[lo:hi]  # noqa: E731
    # Already bucketed here: one ring (overlap on) OR one monolithic
    # psum_scatter (off) per bucket — never a second level of
    # sub-buckets (mirrors prefetched_gather_flat_shard's gather side).
    ring = _ovl.enabled(overlap)
    shards: list = [None] * len(bounds)
    errs: list = [None] * len(bounds)
    prev = None
    for k, (s, e) in enumerate(bounds):
        piece = _coll.fuse_bucket_piece(
            leaves, layout.idxs[g], layout.sizes[g], layout.padded[g],
            n, s, e, dtype, inject=inject)
        if prev is not None:
            piece, shards[prev] = lax.optimization_barrier(
                (piece, shards[prev]))
        with jax.named_scope(f"{scope}{k}"):
            if ring:
                shards[k], errs[k] = _ovl.scatter_bucket(
                    piece, axis_name, quantized=bmodes[k],
                    with_error=with_error)
            else:
                shards[k], errs[k] = _coll._scatter_flat_buffer(
                    piece, axis_name, quantized=bmodes[k],
                    with_error=with_error, overlap=False)
            shards[k] = shards[k].astype(dtype)
        prev = k
    shard = shards[0] if len(shards) == 1 else jnp.concatenate(shards)
    err = None
    if with_error:
        err = _ovl._concat_columns(
            _ovl._zero_errs(errs, bounds, n), n)
    return shard, err


def _bucketed_eager_scatter(leaves, layout, op: int, chunks=None):
    """Stage-2 scatter on the negotiated eager wire: one reducescatter
    response per bucket piece (assembled span-wise, so the full fused
    buffer never materializes host-side either); bucket count rides the
    round-0 handshake, so every rank submits the same K names."""
    from horovod_tpu.ops import overlap as _ovl

    st = _basics.state()
    n = st.size if st.initialized else 1
    handles = []
    for g, key in enumerate(layout.keys):
        L = layout.padded[g] // n
        bounds = _ovl.bucket_bounds(L, _zero_chunks(chunks))
        hs = []
        for k, (s, e) in enumerate(bounds):
            piece = _coll.fuse_bucket_piece(
                leaves, layout.idxs[g], layout.sizes[g],
                layout.padded[g], n, s, e, jnp.dtype(key))
            hs.append(_eager.reducescatter_async(
                piece, op=op,
                name=f"shard_rs.{key}.{layout.padded[g]}"
                     f".{k}of{len(bounds)}"))
        handles.append(hs)
    return [jnp.concatenate([_eager.synchronize(h) for h in hs])
            if len(hs) > 1 else _eager.synchronize(hs[0])
            for hs in handles]


def _bucketed_eager_gather(upd_shards, layout, chunks=None):
    """Stage-2 gather on the negotiated eager wire: one allgather per
    bucket of the update shard; returns ``(bucket_outs, bounds)`` per
    group for :func:`~horovod_tpu.ops.collectives.leaf_from_buckets`
    reassembly (no full fused update buffer either)."""
    from horovod_tpu.ops import overlap as _ovl

    per_group = []
    for g, key in enumerate(layout.keys):
        bounds = _ovl.bucket_bounds(int(upd_shards[g].shape[0]),
                                    _zero_chunks(chunks))
        hs = [_eager.allgather_async(
            upd_shards[g][s:e],
            name=f"shard_ag.{key}.{layout.padded[g]}"
                 f".{k}of{len(bounds)}")
            for k, (s, e) in enumerate(bounds)]
        per_group.append(([_eager.synchronize(h) for h in hs], bounds))
    return per_group


def _make_sharded_fns(init_fn, update_fn, op: int, axis_name,
                      compression, overlap=None, zero_stage: int = 1,
                      fused_spec=None):
    """(init, update) pair implementing the sharded weight update around
    the wrapped optimizer's ``init_fn``/``update_fn``.  With ``overlap``
    (default: the ``HOROVOD_OVERLAP`` knob) the scatter and gather run
    as bucketed ppermute ring pipelines (``HOROVOD_OVERLAP_CHUNKS``
    buckets, barrier-separated) instead of one monolithic
    psum_scatter/all_gather per dtype group — the shard layout is
    bucket-independent, so state, checkpoints and specs are identical
    either way.

    ``zero_stage=2`` additionally keeps gradients shard-resident: the
    fused buffer is never concatenated — ``HOROVOD_ZERO_PREFETCH_CHUNKS``
    bucket pieces are assembled span-wise straight from the gradient
    leaves and reduce-scattered as they form, and the update shards
    come back bucket-wise with per-leaf reassembly, so no full-size
    fused buffer exists on either side of the update (the shard itself
    and the layout are bit-identical to stage 1)."""
    from jax import lax

    from horovod_tpu.ops import overlap as _ovl

    quantized = is_quantized(compression)
    qmode = wire_mode(compression) if quantized else "none"

    def _float_group(key: str) -> bool:
        return jnp.issubdtype(jnp.dtype(key), jnp.floating)

    def _param_shards(params, layout, idx):
        if params is None:
            return None
        pleaves = jax.tree_util.tree_leaves(params)
        shards = []
        for g in range(len(layout.keys)):
            buf = _fuse_group(pleaves, layout, g)
            shards.append(lax.dynamic_slice_in_dim(
                buf, idx * layout.shard[g], layout.shard[g]))
        return shards

    def init(params):
        leaves = jax.tree_util.tree_leaves(params)
        idx, n, in_tr = _shard_position(axis_name)
        layout = _shard_layout(leaves, n)
        shards = []
        for g in range(len(layout.keys)):
            buf = _fuse_group(leaves, layout, g)
            shards.append(lax.dynamic_slice_in_dim(
                buf, idx * layout.shard[g], layout.shard[g]))
        residual = None
        if quantized and in_tr:
            # Error feedback runs only in-trace (the eager negotiated
            # program does not expose the local quantization error), so
            # eager-initialized state must not carry dead full-model
            # fp32 residual buffers — the 1/N-memory goal this mode
            # exists for.
            residual = [jnp.zeros((layout.padded[g] if _float_group(k)
                                   else 0,), jnp.float32)
                        for g, k in enumerate(layout.keys)]
        inner = init_fn(shards)
        _stamp_zero_bytes(zero_stage, layout, inner)
        return _ShardedState(inner, residual, layout)

    def update(grads, state, params=None, **extra):
        leaves, treedef = jax.tree_util.tree_flatten(grads)
        idx, n, in_tr = _shard_position(axis_name)
        if not in_tr and _in_trace(leaves):
            raise HorovodTpuError(
                "sharded optimizer update traced without the "
                f"{axis_name!r} mesh axis in scope; run the step inside "
                "shard_map over that axis (or call it eagerly).")
        layout = _shard_layout(leaves, n)
        if layout != state.layout:
            raise HorovodTpuError(
                "sharded optimizer state layout does not match the "
                "gradient pytree (did world size or parameter "
                f"dtypes/shapes change?): {state.layout} vs {layout}")
        gshards: list = []
        new_res = list(state.residual) if state.residual is not None \
            else None
        ef = new_res is not None  # EF state exists (in-trace init)
        if in_tr:
            for g, key in enumerate(layout.keys):
                q = quantized and _float_group(key)
                if zero_stage >= 2 and n > 1:
                    # Stage 2: bucket pieces assembled span-wise from
                    # the gradient leaves — the full fused buffer never
                    # materializes; only the 1/n shard is resident.
                    res = state.residual[g] if (q and ef) else None
                    shard, err = _bucketed_scatter_group(
                        leaves, layout, g, n, axis_name,
                        qmode if q else False, q and ef,
                        res, overlap=overlap)
                else:
                    buf = _fuse_group(leaves, layout, g)
                    if q and ef:
                        buf = buf.astype(jnp.float32) + state.residual[g]
                    shard, err = _coll._scatter_flat_buffer(
                        buf, axis_name, quantized=qmode if q else False,
                        with_error=q and ef, overlap=overlap)
                if err is not None:
                    new_res[g] = err
                    if zero_stage >= 2 and n > 1:
                        _rchunks = _zero_chunks()
                    elif _ovl.enabled(overlap):
                        _rchunks = _ovl.configured_chunks()
                    else:
                        _rchunks = 1
                    _report_bucket_residual_ratios(
                        err, shard, n, axis_name, chunks=_rchunks)
                gshards.append(shard)
        elif zero_stage >= 2:
            gshards = _bucketed_eager_scatter(leaves, layout, op)
        else:
            # Negotiated eager wire: one fused reduce-scatter per dtype
            # group; the HOROVOD_COMPRESSION knob applies inside the
            # negotiated program (like the eager allreduce path, the
            # local quantization error is not exposed, so the residual
            # rides along unchanged).
            handles = []
            for g, key in enumerate(layout.keys):
                buf = _fuse_group(leaves, layout, g)
                handles.append(_eager.reducescatter_async(
                    buf, op=op,
                    name=f"shard_rs.{key}.{layout.padded[g]}"))
            gshards = [_eager.synchronize(h)
                       for h in handles]
        # The optimizer tail.  ``gshards`` holds the RAW post-scatter
        # buffers (wire dtype; summed in-trace, op-applied on the
        # negotiated eager wire) — unscale and group-dtype cast belong
        # to the tail so the fused kernel can collapse them into the
        # update (docs/zero.md).  navg: the in-trace scatter returns
        # the SUM, so Average divides by n here; the eager wire
        # already applied the op.
        navg = n if (op == Average and in_tr) else 1
        fused = None
        if fused_spec is not None:
            fused = _fused.fused_update_groups(
                fused_spec, gshards, state.inner_state, navg,
                [jnp.dtype(k) for k in layout.keys])
        if fused is not None:
            upd_shards, inner = fused
        else:
            cast = []
            for s, key in zip(gshards, layout.keys):
                if navg > 1:
                    s = s / navg
                cast.append(s.astype(jnp.dtype(key)))
            upd_shards, inner = update_fn(
                cast, state.inner_state,
                _param_shards(params, layout, idx), **extra)
        out: list = [None] * len(leaves)
        buckets = None
        fulls: list = []
        if zero_stage >= 2:
            # Stage 2 gather side: update shards come back bucket by
            # bucket and leaves reassemble straight from the bucket
            # outputs — the full fused update buffer never exists.
            if in_tr:
                buckets = [_ovl.prefetched_gather_flat_shard(
                    upd_shards[g], axis_name, chunks=_zero_chunks(),
                    overlap=overlap, scope="hvd_zero2_ag")
                    for g in range(len(layout.keys))]
            else:
                buckets = _bucketed_eager_gather(upd_shards, layout)
        elif in_tr:
            for g in range(len(layout.keys)):
                fulls.append(_coll._gather_flat_shard(
                    upd_shards[g], axis_name, overlap=overlap))
        else:
            handles = [_eager.allgather_async(
                upd_shards[g],
                name=f"shard_ag.{layout.keys[g]}.{layout.padded[g]}")
                for g in range(len(layout.keys))]
            fulls = [_eager.synchronize(h) for h in handles]
        for g in range(len(layout.keys)):
            off = 0
            for i, sz in zip(layout.idxs[g], layout.sizes[g]):
                if buckets is not None:
                    outs_g, bounds_g = buckets[g]
                    flat = _coll.leaf_from_buckets(
                        outs_g, bounds_g, n, layout.shard[g], off, sz)
                else:
                    flat = fulls[g][off:off + sz]
                out[i] = flat.reshape(
                    leaves[i].shape).astype(leaves[i].dtype)
                off += sz
        return (jax.tree_util.tree_unflatten(treedef, out),
                _ShardedState(inner, new_res, layout))

    return init, update


# ---------------------------------------------------------------------------
# ZeRO-3: parameters themselves live as 1/world flat shards between
# steps; the forward gathers them bucket-wise with prefetch (the
# overlap engine run in reverse, ops/overlap.prefetched_gather_flat_shard)
# and the backward reduce-scatters gradients straight into shard form
# via the gather's custom VJP — no full fused parameter or gradient
# buffer is ever resident.  See docs/zero.md.
# ---------------------------------------------------------------------------


@jax.tree_util.register_pytree_node_class
class Zero3Params:
    """Stage-3 resident parameter form: per-dtype-group 1/world flat
    shard buffers (this rank's contiguous segment of the padded fused
    buffer — the exact :class:`_ShardLayout` segment the ZeRO-1/2
    optimizer state uses), plus the static metadata needed to rebuild
    the full pytree (layout, treedef, per-leaf shapes).  A registered
    pytree: ``jax.grad`` of a loss over a ``Zero3Params`` returns
    shard-shaped cotangents (via :func:`zero3_full_params`'s custom
    VJP), and ``optax.apply_updates`` applies shard-shaped updates
    directly."""

    def __init__(self, shards, layout: _ShardLayout, treedef, shapes):
        self.shards = list(shards)
        self.layout = layout
        self.treedef = treedef
        self.shapes = tuple(tuple(s) for s in shapes)

    def tree_flatten(self):
        return tuple(self.shards), (self.layout, self.treedef,
                                    self.shapes)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(list(children), *aux)

    def __repr__(self) -> str:
        return (f"Zero3Params(groups={list(self.layout.keys)}, "
                f"shard_elems={list(self.layout.shard)})")


def _is_zero3(x) -> bool:
    return isinstance(x, Zero3Params)


def _contains_zero3(tree) -> bool:
    return any(_is_zero3(l) for l in
               jax.tree_util.tree_leaves(tree, is_leaf=_is_zero3))


def zero3_shard_params(params, axis_name: str | None = None) -> Zero3Params:
    """Slice a full parameter pytree into this rank's stage-3 resident
    form (:class:`Zero3Params`).  In-trace: the bound mesh axis picks
    the segment; eager: the process rank does.  One-time at setup (or
    re-form) — the full pytree exists here anyway; from then on only
    the 1/world shards persist."""
    axis_name = _pmesh.resolve_axis(axis_name)
    leaves, treedef = jax.tree_util.tree_flatten(params)
    if not leaves:
        raise HorovodTpuError("zero3_shard_params: empty parameter tree")
    idx, n, _ = _shard_position(axis_name)
    layout = _shard_layout(leaves, n)
    shapes = tuple(tuple(l.shape) for l in leaves)
    from jax import lax

    shards = []
    for g in range(len(layout.keys)):
        buf = _fuse_group(leaves, layout, g)
        shards.append(lax.dynamic_slice_in_dim(
            buf, idx * layout.shard[g], layout.shard[g]))
    return Zero3Params(shards, layout, treedef, shapes)


def zero3_full_params(zp: Zero3Params, axis_name: str | None = None,
                      compression=None, chunks: int | None = None,
                      overlap: bool | None = None):
    """Materialize the full parameter pytree from stage-3 shards for
    the forward pass — bucket-wise, with prefetch.

    In-trace the gather runs as ``HOROVOD_ZERO_PREFETCH_CHUNKS``
    barrier-chained bucket allgathers (``hvd_zero3_ag<k>`` named
    scopes; the ppermute ring under ``HOROVOD_OVERLAP``), each layer's
    parameters sliced out of its bucket's output so XLA frees bucket
    ``k`` while bucket ``k+1``'s transfer is still in flight — at no
    point does one full-size fused parameter buffer exist.
    Differentiating through it (``jax.grad`` of a loss w.r.t. ``zp``)
    triggers the custom VJP: cotangents are reduce-scattered bucket by
    bucket straight into shard form (under ``compression=int8`` the
    scatter rides the block-scaled wire, without error feedback), which
    is ZeRO-2/3 gradient sharding for free — pass the result straight
    to the stage-3 optimizer's ``update``.  Eager (one process per
    chip): negotiated per-bucket allgathers; gradients are computed
    against the full tree and the optimizer scatters them instead."""
    compression = _resolve_compression(compression)
    axis_name = _pmesh.resolve_axis(axis_name)
    idx, n, in_tr = _shard_position(axis_name)
    if not in_tr or n == 1:
        return _zero3_full_eager(zp, n, chunks)
    return _zero3_full_traced(zp, axis_name, n, compression, chunks,
                              overlap)


def _zero3_unfuse(bucket_sets, lay, shapes):
    """Full leaves from per-group ``(bucket_outs, bounds, n)`` gather
    results — per-leaf slicing, never a full fused buffer."""
    out = [None] * len(shapes)
    for g in range(len(lay.keys)):
        outs_g, bounds_g, n = bucket_sets[g]
        off = 0
        for i, sz in zip(lay.idxs[g], lay.sizes[g]):
            out[i] = _coll.leaf_from_buckets(
                outs_g, bounds_g, n, lay.shard[g], off,
                sz).reshape(shapes[i])
            off += sz
    return out


def _zero3_full_eager(zp: Zero3Params, n: int, chunks=None):
    from horovod_tpu.ops import overlap as _ovl

    lay = zp.layout
    bucket_sets = []
    for g in range(len(lay.keys)):
        bounds = _ovl.bucket_bounds(lay.shard[g], _zero_chunks(chunks))
        if n == 1:
            outs = [zp.shards[g][s:e] for s, e in bounds]
        else:
            handles = [_eager.allgather_async(
                zp.shards[g][s:e],
                name=f"zero3_ag.{lay.keys[g]}.{lay.padded[g]}"
                     f".{k}of{len(bounds)}")
                for k, (s, e) in enumerate(bounds)]
            outs = [_eager.synchronize(h) for h in handles]
        bucket_sets.append((outs, bounds, n))
    return jax.tree_util.tree_unflatten(
        zp.treedef, _zero3_unfuse(bucket_sets, lay, zp.shapes))


def _zero3_full_traced(zp: Zero3Params, axis_name, n: int, compression,
                       chunks, overlap):
    from horovod_tpu.ops import overlap as _ovl

    lay, treedef, shapes = zp.layout, zp.treedef, zp.shapes
    quantized = is_quantized(compression)
    qmode = wire_mode(compression) if quantized else "none"
    kchunks = _zero_chunks(chunks)

    def impl(shards):
        bucket_sets = []
        for g in range(len(lay.keys)):
            outs, bounds = _ovl.prefetched_gather_flat_shard(
                shards[g], axis_name, chunks=kchunks, overlap=overlap)
            bucket_sets.append((outs, bounds, n))
        return jax.tree_util.tree_unflatten(
            treedef, _zero3_unfuse(bucket_sets, lay, shapes))

    @jax.custom_vjp
    def gather(shards):
        return impl(shards)

    def fwd(shards):
        return impl(shards), None

    def bwd(_, ct):
        # The transpose of the bucketed allgather IS the ZeRO-2
        # bucketed reduce-scatter: per-rank cotangents of the full
        # pytree come back as this rank's summed 1/n shard per dtype
        # group, assembled span-wise so no full fused gradient buffer
        # materializes (named scopes hvd_zero3_rs<k>).
        cleaves = [jnp.asarray(c) for c in
                   jax.tree_util.tree_leaves(ct)]
        gshards = []
        for g, key in enumerate(lay.keys):
            q = quantized and jnp.issubdtype(jnp.dtype(key),
                                             jnp.floating)
            shard, _ = _bucketed_scatter_group(
                cleaves, lay, g, n, axis_name, qmode if q else False,
                False, None, overlap=overlap, chunks=kchunks,
                scope="hvd_zero3_rs")
            gshards.append(shard.astype(jnp.dtype(key)))
        return (gshards,)

    gather.defvjp(fwd, bwd)
    return gather(list(zp.shards))


def _make_zero3_fns(init_fn, update_fn, op: int, axis_name, compression,
                    overlap=None, fused_spec=None):
    """(init, update) pair for the stage-3 optimizer: the training
    loop's "params" are the :class:`Zero3Params` shards; updates come
    back shard-shaped (NO allgather of updates — the next forward's
    prefetched gather is the only place full parameters transiently
    exist) and apply directly via ``optax.apply_updates``."""
    quantized = is_quantized(compression)
    qmode = wire_mode(compression) if quantized else "none"

    def init(params):
        if not _is_zero3(params):
            raise HorovodTpuError(
                "zero_stage=3: DistributedOptimizer.init expects the "
                "shard-resident parameter form — call "
                "hvd.zero3_shard_params(params) once at setup and "
                "train on the returned Zero3Params (docs/zero.md).")
        inner = init_fn(list(params.shards))
        _stamp_zero_bytes(3, params.layout, inner)
        return _ShardedState(inner, None, params.layout)

    def update(grads, state, params=None, **extra):
        idx, n, in_tr = _shard_position(axis_name)
        aux_src = params if _is_zero3(params) else (
            grads if _is_zero3(grads) else None)
        if aux_src is None:
            raise HorovodTpuError(
                "zero_stage=3 update needs the Zero3Params metadata: "
                "pass params=<the Zero3Params> (or gradients produced "
                "by differentiating through zero3_full_params).")
        layout = aux_src.layout
        if layout != state.layout:
            raise HorovodTpuError(
                "zero_stage=3 optimizer state layout does not match "
                "the parameter shards (did world size or parameter "
                f"dtypes/shapes change?): {state.layout} vs {layout}")
        if _is_zero3(grads):
            # Shard-resident cotangents from zero3_full_params's VJP:
            # already summed across ranks by the bucketed scatter.
            gshards = list(grads.shards)
        elif in_tr:
            leaves = jax.tree_util.tree_flatten(grads)[0]
            gshards = []
            for g, key in enumerate(layout.keys):
                q = quantized and jnp.issubdtype(jnp.dtype(key),
                                                 jnp.floating)
                shard, _ = _bucketed_scatter_group(
                    leaves, layout, g, n, axis_name,
                    qmode if q else False, False, None,
                    overlap=overlap, scope="hvd_zero3_rs")
                gshards.append(shard)
        else:
            leaves = jax.tree_util.tree_flatten(grads)[0]
            gshards = _bucketed_eager_scatter(leaves, layout, Sum)
        # Optimizer tail on the raw summed shards: fused kernel when a
        # FusedSpec is attached (unscale + cast + moment update + step
        # in one launch per group), the unfused divide/cast/optax
        # chain otherwise — bit-exact either way (docs/zero.md).
        navg = n if op == Average else 1
        fused = None
        if fused_spec is not None:
            fused = _fused.fused_update_groups(
                fused_spec, gshards, state.inner_state, navg,
                [jnp.dtype(k) for k in layout.keys])
        if fused is not None:
            upd_shards, inner = fused
        else:
            if navg > 1:
                gshards = [s / navg for s in gshards]
            gshards = [s.astype(jnp.dtype(key))
                       for s, key in zip(gshards, layout.keys)]
            pshards = list(params.shards) if _is_zero3(params) else None
            upd_shards, inner = update_fn(gshards, state.inner_state,
                                          pshards, **extra)
        upd = Zero3Params(
            [u.astype(jnp.dtype(key))
             for u, key in zip(upd_shards, layout.keys)],
            aux_src.layout, aux_src.treedef, aux_src.shapes)
        return upd, _ShardedState(inner, None, layout)

    return init, update


def zero3_params_specs(zp: Zero3Params, axis_name: str = "hvd"):
    """``PartitionSpec`` tree for threading stage-3 shards through
    ``jit``/``shard_map``: every shard buffer is ``P(axis_name)`` (the
    global view is the full fused buffer, rank ``r`` holding segment
    ``r``)."""
    from jax.sharding import PartitionSpec as P

    return Zero3Params([P(axis_name)] * len(zp.shards), zp.layout,
                       zp.treedef, zp.shapes)


def zero3_params_to_global(zp: Zero3Params, mesh=None,
                           axis_name: str = "hvd"):
    """Assemble this process's stage-3 shards into global arrays over
    the world mesh (the :func:`sharded_state_to_global` analog for
    parameters).  No-op at size 1."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    st = _basics.state()
    if not st.initialized or st.size == 1:
        return zp
    mesh = mesh if mesh is not None else st.mesh
    shards = []
    for leaf in zp.shards:
        leaf = jnp.asarray(leaf)
        local = jax.device_put(leaf, st.lead_device)
        shards.append(jax.make_array_from_single_device_arrays(
            (st.size * leaf.shape[0],),
            NamedSharding(mesh, P(axis_name)), [local]))
    return Zero3Params(shards, zp.layout, zp.treedef, zp.shapes)


class _HostZero3Params:
    """Host-side commit snapshot of a :class:`Zero3Params`: the FULL
    parameter pytree as numpy (world-size-independent, so an elastic
    re-form re-shards it for any new world).  A plain opaque class —
    not a pytree — so blind ``tree_map`` passes over a commit snapshot
    leave it intact.  Picklable; rides the elastic resync broadcast."""

    def __init__(self, tree):
        self.tree = tree


def _is_host_zero3(x) -> bool:
    return isinstance(x, _HostZero3Params)


def zero3_params_to_host(zp: Zero3Params, gather=None):
    """Allgather stage-3 shards into the full parameter pytree on host
    (elastic commit points; collective at world > 1 — every rank must
    call it).  ``gather`` overrides the eager allgather (tests)."""
    st = _basics.state()

    def default_gather(leaf):
        if st.initialized and st.size > 1:
            return _eager.allgather(jnp.asarray(leaf).reshape(-1))
        return jnp.asarray(leaf)

    gather = default_gather if gather is None else gather
    lay = zp.layout
    leaves = [None] * len(zp.shapes)
    for g in range(len(lay.keys)):
        full = np.asarray(gather(zp.shards[g]))
        off = 0
        for i, sz in zip(lay.idxs[g], lay.sizes[g]):
            leaves[i] = full[off:off + sz].reshape(zp.shapes[i])
            off += sz
    return _HostZero3Params(
        jax.tree_util.tree_unflatten(zp.treedef, leaves))


def _default_shard_world() -> int:
    """Default shard count for host re-shard helpers: the data mesh's
    dp extent when one is configured (ZeRO shards are dp-scoped,
    docs/mesh.md), else the world size."""
    if not _basics.state().initialized:
        return 1
    return _basics.data_parallel_size()


def zero3_params_from_host(host: _HostZero3Params,
                           world: int | None = None,
                           rank: int | None = None) -> Zero3Params:
    """Re-shard a :func:`zero3_params_to_host` snapshot for the CURRENT
    world size — the stage-3 half of an elastic re-form (rank ``r`` of
    the new world takes segment ``r`` of the re-padded fused buffers).
    ``world`` defaults to the dp extent when a data mesh is configured
    (shards are dp-scoped), else the world size."""
    st = _basics.state()
    n = world if world is not None else _default_shard_world()
    r = rank if rank is not None else (st.rank if st.initialized else 0)
    tree = jax.tree_util.tree_map(jnp.asarray, host.tree)
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    layout = _shard_layout(leaves, n)
    shapes = tuple(tuple(l.shape) for l in leaves)
    shards = []
    for g in range(len(layout.keys)):
        buf = _fuse_group(leaves, layout, g)
        shards.append(buf[r * layout.shard[g]:(r + 1) * layout.shard[g]])
    return Zero3Params(shards, layout, treedef, shapes)


def params_to_host(tree, gather=None):
    """Host snapshot of a parameter tree for elastic commits: plain
    leaves become numpy; :class:`Zero3Params` subtrees allgather into
    their world-independent full form (collective at world > 1)."""
    def one(node):
        if _is_zero3(node):
            return zero3_params_to_host(node, gather)
        return jax.tree_util.tree_map(np.asarray, node)

    return jax.tree_util.tree_map(one, tree, is_leaf=_is_zero3)


def params_from_host(tree, world: int | None = None,
                     rank: int | None = None):
    """Rebuild device parameters from a :func:`params_to_host`
    snapshot, re-sharding stage-3 subtrees for the current world."""
    def one(node):
        if _is_host_zero3(node):
            return zero3_params_from_host(node, world, rank)
        return jax.tree_util.tree_map(jnp.asarray, node)

    return jax.tree_util.tree_map(one, tree, is_leaf=_is_host_zero3)


def sharded_state_specs(opt_state, axis_name: str = "hvd"):
    """``PartitionSpec`` pytree for threading a sharded optimizer state
    through ``jit``/``shard_map`` over the world mesh: shard-buffer
    leaves map to ``P(axis_name)`` (the global view is the full fused
    buffer, rank ``r`` holding segment ``r``); step counters and other
    scalars are replicated ``P()``.  Error-feedback residuals are
    per-rank values — not shards of one global array — and cannot ride
    a spec: thread int8+EF states inside a single shard_map program
    instead (see docs/zero.md)."""
    from jax.sharding import PartitionSpec as P

    def one(node):
        if _is_sharded_state(node):
            if node.residual is not None and \
                    jax.tree_util.tree_leaves(node.residual):
                raise HorovodTpuError(
                    "sharded_state_specs cannot express the int8 "
                    "error-feedback residual (per-rank state, not a "
                    "sharding of one global array); keep the state "
                    "inside one shard_map program for int8+EF.")
            shard_lens = set(node.layout.shard)
            inner = jax.tree_util.tree_map(
                lambda l: (P(axis_name)
                           if getattr(l, "ndim", 0) == 1
                           and l.shape[0] in shard_lens else P()),
                node.inner_state)
            return _ShardedState(inner, None, node.layout)
        return jax.tree_util.tree_map(lambda _: P(), node)

    return jax.tree_util.tree_map(one, opt_state,
                                  is_leaf=_is_sharded_state)


def sharded_state_to_global(opt_state, mesh=None, axis_name: str = "hvd"):
    """Assemble this process's shard-buffer leaves into global arrays
    over the world mesh (rank ``r`` holds segment ``r``) so a sharded
    optimizer state can cross a jit boundary at world size > 1 with the
    specs from :func:`sharded_state_specs`.  No-op at size 1."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    st = _basics.state()
    if not st.initialized or st.size == 1:
        return opt_state
    mesh = mesh if mesh is not None else st.mesh

    def one(node):
        if not _is_sharded_state(node):
            return node
        shard_lens = set(node.layout.shard)

        def g(leaf):
            leaf = jnp.asarray(leaf)
            if leaf.ndim == 1 and leaf.shape[0] in shard_lens:
                local = jax.device_put(leaf, st.lead_device)
                return jax.make_array_from_single_device_arrays(
                    (st.size * leaf.shape[0],),
                    NamedSharding(mesh, P(axis_name)), [local])
            return leaf

        return _ShardedState(jax.tree_util.tree_map(g, node.inner_state),
                             node.residual, node.layout)

    return jax.tree_util.tree_map(one, opt_state,
                                  is_leaf=_is_sharded_state)


class _HostShardedState:
    """Host-side commit snapshot of a :class:`_ShardedState`: the inner
    state with every shard-buffer leaf allgathered into its full fused
    (global) form, plus the layout it was sharded under.  A plain class
    (not a pytree/NamedTuple) on purpose — blind ``tree_map`` passes
    over a commit snapshot must treat it as one opaque leaf.  Picklable,
    so it rides the elastic resync broadcast."""

    def __init__(self, inner, layout: _ShardLayout, had_residual: bool):
        self.inner = inner
        self.layout = layout
        self.had_residual = had_residual


def _is_host_sharded(x) -> bool:
    return isinstance(x, _HostShardedState)


def sharded_state_to_host(opt_state, gather=None):
    """Host snapshot of an optimizer state for elastic commit points
    (docs/elastic.md).  Plain leaves become numpy; ZeRO-1
    :class:`_ShardedState` subtrees have their shard-buffer leaves
    **allgathered** back into the full fused buffers, so a later
    :func:`sharded_state_from_host` can re-shard them to a *different*
    world size (the commit survives rank death).  Collective when the
    state is sharded and the world is >1 — every rank must call it.
    ``gather`` overrides the eager allgather (tests / offline tools)."""
    st = _basics.state()

    def default_gather(leaf):
        if st.initialized and st.size > 1:
            return _eager.allgather(jnp.asarray(leaf).reshape(-1))
        return jnp.asarray(leaf)

    gather = default_gather if gather is None else gather

    def one(node):
        if _is_sharded_state(node):
            shard_lens = {s for s in node.layout.shard if s > 0}

            def g(leaf):
                leaf = jnp.asarray(leaf)
                if leaf.ndim == 1 and leaf.shape[0] in shard_lens:
                    return np.asarray(gather(leaf))
                return np.asarray(leaf)

            inner = jax.tree_util.tree_map(g, node.inner_state)
            return _HostShardedState(inner, node.layout,
                                     node.residual is not None)
        return jax.tree_util.tree_map(np.asarray, node)

    return jax.tree_util.tree_map(one, opt_state,
                                  is_leaf=_is_sharded_state)


def sharded_state_from_host(host_state, world: int | None = None,
                            rank: int | None = None):
    """Rebuild a device optimizer state from a
    :func:`sharded_state_to_host` snapshot, re-slicing ZeRO-1 subtrees
    for the CURRENT world size: commit-point global buffers are
    re-padded to the new world-divisible length and this rank takes its
    dense segment.  Error-feedback residuals restart at zero — the
    compression error accumulated before the commit point is already
    folded into the committed parameters, and a stale residual sized
    for the old world would be layout garbage anyway.  ``world``
    defaults to the dp extent when a data mesh is configured (shards
    are dp-scoped, docs/mesh.md), else the world size."""
    st = _basics.state()
    n = world if world is not None else _default_shard_world()
    r = rank if rank is not None else (st.rank if st.initialized else 0)

    def one(node):
        if _is_host_sharded(node):
            old = node.layout
            totals = tuple(sum(sz) for sz in old.sizes)
            padded = tuple(t + (-t) % n for t in totals)
            new = _ShardLayout(old.keys, old.idxs, old.sizes, padded,
                               tuple(p // n for p in padded))
            gathered_lens = {p for p in old.padded if p > 0}

            def g(leaf):
                a = np.asarray(leaf)
                if a.ndim == 1 and a.shape[0] in gathered_lens:
                    # Which group produced this buffer: padded length
                    # first; on a collision (two dtype groups padding to
                    # the same length) equal totals make the choice
                    # irrelevant (identical trim/re-pad/slice), else the
                    # leaf dtype picks the group (groups are keyed by
                    # dtype, and optax moments keep the param dtype).
                    # A collision with UNEQUAL totals and no dtype match
                    # is genuinely ambiguous — trimming with the wrong
                    # total would silently drop real state, so refuse.
                    cands = [i for i in range(len(old.keys))
                             if old.padded[i] == a.shape[0]]
                    gi = cands[0]
                    if len(cands) > 1 and \
                            len({totals[i] for i in cands}) > 1:
                        m = [i for i in cands
                             if np.dtype(old.keys[i]) == a.dtype]
                        if len(m) == 1:
                            gi = m[0]
                        else:
                            raise HorovodTpuError(
                                "cannot re-shard optimizer state: a "
                                f"{a.dtype} buffer of length "
                                f"{a.shape[0]} matches several dtype "
                                f"groups ({[old.keys[i] for i in cands]}"
                                ") with different true sizes "
                                f"({[totals[i] for i in cands]}); "
                                "restoring with the wrong size would "
                                "corrupt state. Restart at the recorded "
                                "world size instead.")
                    buf = a[:totals[gi]]
                    pad = new.padded[gi] - totals[gi]
                    if pad:
                        buf = np.concatenate(
                            [buf, np.zeros((pad,), a.dtype)])
                    return jnp.asarray(
                        buf[r * new.shard[gi]:(r + 1) * new.shard[gi]])
                return jnp.asarray(a)

            inner = jax.tree_util.tree_map(g, node.inner)
            residual = None
            if node.had_residual:
                residual = [
                    jnp.zeros((new.padded[g]
                               if jnp.issubdtype(jnp.dtype(k),
                                                 jnp.floating) else 0,),
                              jnp.float32)
                    for g, k in enumerate(new.keys)]
            return _ShardedState(inner, residual, new)
        return jax.tree_util.tree_map(jnp.asarray, node)

    return jax.tree_util.tree_map(one, host_state,
                                  is_leaf=_is_host_sharded)


def DistributedOptimizer(optimizer, named_parameters=None,
                         compression=None,
                         backward_passes_per_step: int = 1,
                         op: int = Average, axis_name: str | None = None,
                         sharded: bool | None = None,
                         overlap: bool | None = None,
                         zero_stage: int | None = None):
    """Wrap an optax optimizer with cross-rank gradient aggregation.

    ``axis_name=None`` (default) resolves to the configured data mesh's
    ``dp`` axis (``HOROVOD_MESH`` / ``hvd.init(mesh=...)``, see
    docs/mesh.md) — the reduction, the ZeRO shard layouts, the health
    verdict allgather and the error-feedback residuals all scope to the
    dp replicas only, leaving tp/pp/sp-sharded params untouched — else
    to the flat world axis ``"hvd"``.

    Keeps the reference's keyword surface
    (``horovod/torch/__init__.py:395-449``); ``named_parameters`` is
    accepted and ignored (pytrees carry structure).  With
    ``backward_passes_per_step > 1`` gradients accumulate locally and
    communicate only every N steps (reference grad-accumulation,
    ``torch/__init__.py:127-162``); intermediate steps return zero
    updates.

    ``compression=None`` (default) resolves from the
    ``HOROVOD_COMPRESSION`` knob.  With ``Compression.int8`` and
    ``backward_passes_per_step == 1`` the optimizer state additionally
    carries a persistent error-feedback residual pytree: each step's
    quantization error is re-injected into the next step's gradients,
    so compression error averages out over training instead of being
    lost (EQuARX/1-bit-Adam-style EF; state is a
    :class:`_FeedbackState` wrapping the inner optax state).

    ``sharded=None`` (default) resolves from the
    ``HOROVOD_SHARDED_OPTIMIZER`` knob; ``True`` enables the ZeRO-1
    sharded weight update (arXiv:2004.13336): gradients are fused into
    per-dtype flat buffers and **reduce-scattered** instead of
    allreduced, the wrapped optimizer runs on only the rank-local
    ``1/world_size`` shard — its state (Adam moments, …) is initialized
    and carried shard-local, cutting optimizer-state memory
    ~``world_size``-fold — and the updated parameter shards are
    **allgathered** back into the full update pytree.  Composes with
    compression (under int8 + hierarchical only the cross-slice hop is
    quantized) and with ``backward_passes_per_step``; incompatible with
    ``op=Adasum`` (the projection needs the full reduction).  See
    ``docs/zero.md``.

    ``zero_stage=None`` (default) resolves from the
    ``HOROVOD_ZERO_STAGE`` knob (with ``sharded=True`` kept as the
    stage-1 spelling).  Stage 1 is the sharded weight update above;
    **stage 2** additionally keeps gradients shard-resident — the fused
    buffers are reduce-scattered bucket-by-bucket
    (``HOROVOD_ZERO_PREFETCH_CHUNKS`` pieces assembled span-wise from
    the gradient leaves) so no full-size fused gradient buffer ever
    materializes; **stage 3** additionally shards the parameters
    themselves: train on :func:`zero3_shard_params`' ``Zero3Params``,
    materialize the forward's view with :func:`zero3_full_params`
    (bucket-wise prefetched allgather), and this optimizer's ``update``
    returns shard-shaped updates that apply directly — parameters,
    gradients and optimizer state all live as 1/world shards between
    steps.  Stage 3 does not compose with
    ``backward_passes_per_step > 1`` (accumulate full-gradient trees
    outside the optimizer instead).  See ``docs/zero.md``.

    ``overlap=None`` (default) resolves from the ``HOROVOD_OVERLAP``
    knob; ``True`` replaces the single end-of-step fused collective
    with the bucketed ppermute ring schedule of
    :mod:`horovod_tpu.ops.overlap` (``HOROVOD_OVERLAP_CHUNKS``
    buckets, barrier-separated so XLA's latency-hiding scheduler can
    float bucket ``i+1``'s transfer under bucket ``i``'s compute).
    Composes with ``sharded`` (bucket-wise scatter -> shard update ->
    gather pipeline; state layout unchanged), with int8 (per-bucket
    quantization, EF residuals bucket-aligned) and with hierarchical
    allreduce (only the cross-slice hop rides the ring); ignored for
    ``op=Adasum``.  On the eager path the knob governs (it rides the
    round-0 handshake); a per-call argument applies in-trace only.
    See ``docs/overlap.md``.
    """
    del named_parameters
    try:
        init_fn, update_fn = optimizer.init, optimizer.update
    except AttributeError as exc:
        raise TypeError(
            "DistributedOptimizer expects an optax GradientTransformation "
            f"(got {type(optimizer)!r})") from exc

    compression = _resolve_compression(compression)
    axis_name = _pmesh.resolve_axis(axis_name)
    stage = _resolve_zero_stage(zero_stage, sharded)
    sharded = stage >= 1
    k = int(backward_passes_per_step)
    # Pallas-fused optimizer tail (HOROVOD_FUSED_UPDATE=1, docs/
    # zero.md): non-None only when the knob is on AND the wrapped
    # optimizer carries a FusedSpec (hvd.fused_update.sgd/adam) —
    # otherwise one warning and the unfused optax chain runs, so the
    # knob can never change results, only fuse them.
    fspec = _fused.resolve_spec(optimizer)
    if fspec is not None and stage == 0:
        # Replicated tail: substitute the fused per-leaf kernel for the
        # wrapped update BEFORE the EF / accumulation wrappers below,
        # so every stage-0 regime (plain, int8+EF, k>1) composes with
        # it.  Falls back leaf-for-leaf when the state layout is not
        # the recognized optax shape (fail-open).
        _base_update = update_fn

        def update_fn(grads, state, params=None, **extra):  # noqa: F811
            res = _fused.fused_update_tree(fspec, grads, state)
            if res is None:
                return _base_update(grads, state, params, **extra)
            return res

    # The inner optimizer under its own name in the compiled step
    # (docs/perf.md), on every path below: each calls it through this.
    _inner_update = update_fn

    def update_fn(grads, state, params=None, **extra):  # noqa: F811
        with jax.named_scope("hvd_optimizer"):
            return _inner_update(grads, state, params, **extra)

    # Observability (docs/metrics.md): record the resolved schedule so
    # hvd.metrics() shows what the optimizer actually runs with (the
    # env knobs record only the request).
    _ovl = (bool(_config.get("overlap")) if overlap is None
            else bool(overlap))
    _metrics.gauge(
        "hvd_overlap_chunks",
        "Bucket count of the overlap ring schedule (0 = overlap "
        "off).").set(
            int(_config.get("overlap_chunks")) if _ovl else 0)
    _metrics.gauge(
        "hvd_sharded_optimizer",
        "1 when the ZeRO-1 sharded weight update is active.").set(
            1 if sharded else 0)
    _M_ZERO_STAGE.set(stage)

    def reduce_grads(grads):
        return allreduce_gradients(grads, op=op, axis_name=axis_name,
                                   compression=compression,
                                   overlap=overlap)

    if sharded:
        if op == Adasum:
            raise HorovodTpuError(
                "zero_stage>=1 (sharded=True) does not compose with "
                "op=Adasum: the projection's dot/norm math needs the "
                "full reduction, not a scatter. Use op=Average/Sum "
                "with the sharded optimizer.")
        import optax

        if stage >= 3:
            if k != 1:
                raise HorovodTpuError(
                    "zero_stage=3 does not compose with "
                    "backward_passes_per_step > 1: the accumulation "
                    "wrapper holds full-gradient trees, exactly the "
                    "residency stage 3 eliminates. Accumulate "
                    "full-gradient pytrees outside the optimizer and "
                    "feed the mean to update() instead.")
            core_init, core_update = _make_zero3_fns(
                init_fn, update_fn, op, axis_name, compression,
                overlap=overlap, fused_spec=fspec)
            return _health_wrap(
                optax.GradientTransformation(core_init, core_update),
                axis_name)
        core_init, core_update = _make_sharded_fns(
            init_fn, update_fn, op, axis_name, compression,
            overlap=overlap, zero_stage=stage, fused_spec=fspec)
        if k == 1:
            return _health_wrap(
                optax.GradientTransformation(core_init, core_update),
                axis_name)
        # k > 1: the accumulation wrapper below drives the sharded core
        # (which reduces internally), so the pre-reduce hook is a no-op.
        init_fn, update_fn = core_init, core_update

        def reduce_grads(grads):  # noqa: F811 — accumulation path hook
            return grads

    if not sharded and k == 1 and is_quantized(compression) \
            and op != Adasum:
        import optax

        def init_ef(params):
            st = _FeedbackState(_quant.init_error_feedback(params),
                                init_fn(params))
            _stamp_zero_bytes_replicated(params, st.inner_state)
            return st

        def update_ef(grads, state, params=None, **extra):
            reduced, new_res = allreduce_gradients_with_feedback(
                grads, state.residual, op=op, axis_name=axis_name,
                overlap=overlap, compression=compression)
            _maybe_report_residual_ratio(new_res, reduced, axis_name,
                                         overlap=overlap)
            upd, inner = update_fn(reduced, state.inner_state, params,
                                   **extra)
            return upd, _FeedbackState(new_res, inner)

        return _health_wrap(
            optax.GradientTransformation(init_ef, update_ef), axis_name)

    if k == 1:
        def init1(params):
            st = init_fn(params)
            _stamp_zero_bytes_replicated(params, st)
            return st

        def update1(grads, state, params=None, **extra):
            return update_fn(reduce_grads(grads), state, params, **extra)

        import optax

        return _health_wrap(
            optax.GradientTransformationExtraArgs(init1, update1)
            if hasattr(optax, "GradientTransformationExtraArgs")
            else optax.GradientTransformation(init1, update1), axis_name)

    import optax

    def init_k(params):
        accum = jax.tree_util.tree_map(jnp.zeros_like, params)
        inner = init_fn(params)
        if not sharded:  # sharded core already stamped shard-local sizes
            _stamp_zero_bytes_replicated(params, inner)
        return _AccumulationState(jnp.zeros((), jnp.int32), accum, inner)

    def update_k(grads, state, params=None, **extra):
        counter = state.counter + 1
        accum = jax.tree_util.tree_map(lambda a, g: a + g, state.accum, grads)
        sync = counter >= k

        if _in_trace(grads):
            def do_sync(acc, inner):
                mean = jax.tree_util.tree_map(lambda a: a / k, acc)
                upd, new_inner = update_fn(reduce_grads(mean), inner,
                                           params, **extra)
                zeros = jax.tree_util.tree_map(jnp.zeros_like, acc)
                return upd, zeros, new_inner

            def no_sync(acc, inner):
                zeros = jax.tree_util.tree_map(jnp.zeros_like, acc)
                return zeros, acc, inner

            upd, accum2, inner2 = jax.lax.cond(
                sync, do_sync, no_sync, accum, state.inner_state)
            new_counter = jnp.where(sync, 0, counter)
            return upd, _AccumulationState(new_counter, accum2, inner2)

        if bool(sync):
            mean = jax.tree_util.tree_map(lambda a: a / k, accum)
            upd, inner2 = update_fn(reduce_grads(mean), state.inner_state,
                                    params, **extra)
            zeros = jax.tree_util.tree_map(jnp.zeros_like, accum)
            return upd, _AccumulationState(jnp.zeros((), jnp.int32),
                                           zeros, inner2)
        zeros = jax.tree_util.tree_map(jnp.zeros_like, grads)
        return zeros, _AccumulationState(counter, accum, state.inner_state)

    return _health_wrap(
        optax.GradientTransformation(init_k, update_k), axis_name)


class DistributedGradientTape:
    """JAX analog of the reference's TF ``DistributedGradientTape``
    (``tensorflow/__init__.py:475-531``): wraps a loss function so its
    gradients come back allreduced."""

    def __init__(self, loss_fn, compression=None,
                 op: int = Average, axis_name: str | None = None,
                 has_aux: bool = False):
        self._loss_fn = loss_fn
        self._compression = _resolve_compression(compression)
        self._op = op
        self._axis_name = _pmesh.resolve_axis(axis_name)
        self._has_aux = has_aux

    def gradient(self, *args, argnums=0, **kwargs):
        g = jax.grad(self._loss_fn, argnums=argnums,
                     has_aux=self._has_aux)(*args, **kwargs)
        if self._has_aux:
            grads, aux = g
            return allreduce_gradients(grads, self._op, self._axis_name,
                                       self._compression), aux
        return allreduce_gradients(g, self._op, self._axis_name,
                                   self._compression)


def grad(loss_fn, argnums=0, op: int = Average,
         axis_name: str | None = None,
         compression=None, has_aux: bool = False):
    """``jax.grad`` with cross-rank averaging — functional spelling of
    DistributedGradientTape."""
    compression = _resolve_compression(compression)
    axis_name = _pmesh.resolve_axis(axis_name)

    gfn = jax.grad(loss_fn, argnums=argnums, has_aux=has_aux)

    def wrapped(*args, **kwargs):
        g = gfn(*args, **kwargs)
        if has_aux:
            g, aux = g
            return allreduce_gradients(g, op, axis_name, compression), aux
        return allreduce_gradients(g, op, axis_name, compression)

    return wrapped


# ---------------------------------------------------------------------------
# Parameter / object broadcast (reference torch/__init__.py:451-647)
# ---------------------------------------------------------------------------


def broadcast_parameters(params, root_rank: int = 0):
    """Broadcast a parameter pytree from ``root_rank`` to all ranks and
    return the synchronized pytree (functional; the reference mutates
    ``state_dict`` in place, ``torch/__init__.py:451-481``).  Tensors are
    fused per dtype into single transfers.

    Refuses stage-3 shard-resident parameters (:class:`Zero3Params`):
    each rank's shard is a *different* segment of the fused buffers, so
    broadcasting rank 0's would corrupt every other rank — and a silent
    full-gather here would defeat the residency contract.  Resync
    stage-3 params through the elastic commit/restore path
    (:func:`params_to_host` / :func:`params_from_host`, or
    ``checkpoint.save/restore(..., all_ranks=True)``)."""
    _refuse_zero3(params, "broadcast_parameters")
    leaves, treedef = jax.tree_util.tree_flatten(params)
    if not leaves:
        return params
    out = _fused_pytree_collective(
        leaves,
        lambda flat, label: _eager.broadcast_async(
            flat, root_rank, name=f"bcast_buffer.{label}"))
    return jax.tree_util.tree_unflatten(treedef, out)


def broadcast_optimizer_state(opt_state, root_rank: int = 0):
    """Broadcast optimizer state (reference ``torch/__init__.py:483-604``;
    trivial here because optax state is already a pytree of arrays).

    Shard-local (ZeRO-1) subtrees pass through unchanged: each rank's
    shard is authoritative — broadcasting rank 0's moments would
    silently overwrite every other rank's shard with the wrong
    segment.  Everything around them (accumulation buffers, schedules,
    a params tree resynced in the same call) still broadcasts.
    Restore shard-local state with ``checkpoint.save/restore(...,
    all_ranks=True)`` instead (see docs/zero.md)."""
    return broadcast_skipping_shards(opt_state, root_rank)


def _refuse_zero3(tree, what: str) -> None:
    if _contains_zero3(tree):
        raise HorovodTpuError(
            f"{what} called on zero_stage=3 shard-resident parameters "
            "(Zero3Params): every rank holds a DIFFERENT 1/world "
            "segment, so a broadcast would corrupt all but the root "
            "and a full-gather would silently defeat the residency "
            "contract. Outside an elastic re-form, move stage-3 state "
            "with the commit/restore path instead: params_to_host / "
            "params_from_host (hvd.elastic commits do this for you) "
            "or checkpoint.save/restore(..., all_ranks=True). See "
            "docs/zero.md.")


def broadcast_skipping_shards(tree, root_rank: int = 0):
    """Broadcast every leaf of ``tree`` from ``root_rank`` EXCEPT those
    inside shard-local (:class:`_ShardedState`) subtrees, which are
    per-rank by construction.  Returns ``tree`` itself when there is
    nothing to broadcast.  Stage-3 :class:`Zero3Params` anywhere in the
    tree is refused loudly (see :func:`broadcast_parameters`)."""
    _refuse_zero3(tree, "broadcast_skipping_shards")
    leaves, treedef = jax.tree_util.tree_flatten(
        tree, is_leaf=_is_sharded_state)
    plain = [i for i, l in enumerate(leaves)
             if not _is_sharded_state(l)]
    if not plain:
        return tree
    synced = broadcast_parameters([leaves[i] for i in plain],
                                  root_rank=root_rank)
    for i, v in zip(plain, synced):
        leaves[i] = v
    return jax.tree_util.tree_unflatten(treedef, leaves)


# TF-parity alias (reference ``BroadcastGlobalVariablesHook`` semantics).
def broadcast_global_variables(variables, root_rank: int = 0):
    return broadcast_parameters(variables, root_rank)


def broadcast_object(obj, root_rank: int = 0, name: str | None = None):
    """Broadcast an arbitrary picklable object
    (reference ``torch/__init__.py:607-647``: cloudpickle → size bcast →
    payload bcast)."""
    import io
    import pickle

    try:
        import cloudpickle as pickler  # type: ignore
    except ImportError:
        pickler = pickle
    name = name or "broadcast_object"
    if _basics.rank() == root_rank:
        buf = io.BytesIO()
        pickler.dump(obj, buf)
        payload = np.frombuffer(buf.getvalue(), dtype=np.uint8)
        length = np.array([payload.size], dtype=np.int32)
    else:
        payload = None
        length = np.zeros((1,), dtype=np.int32)
    length = np.asarray(_eager.broadcast(jnp.asarray(length), root_rank,
                                         name=f"{name}.len"))
    n = int(length[0])
    if payload is None:
        payload = np.zeros((n,), dtype=np.uint8)
    wire = _eager.broadcast(jnp.asarray(payload), root_rank,
                            name=f"{name}.payload")
    data = np.asarray(wire).tobytes()
    return pickle.loads(data)
