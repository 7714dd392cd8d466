"""XLA execution engine for the eager collective path.

Role of the reference's op layer (``horovod/common/ops/*_operations.cc``):
given tensors that the controller negotiated as globally ready, run the
actual collective.  Here a "collective backend" is a cached, jitted
`shard_map` program over the world mesh: per-process local tensors are
assembled into a global array sharded on the ``hvd`` axis, the program
concatenates the fused set into one flat buffer (the role of
``MemcpyInFusionBuffer``, ``gpu_operations.cc:94-99`` — done by XLA
fusion instead of a staged memcpy), applies one ``psum``/Adasum/
broadcast, and splits results back.

Programs are cached by fused-signature; the controller's fusion buckets
stabilize after warmup, bounding recompilation.
"""

from __future__ import annotations


import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P
from jax import shard_map

from horovod_tpu.common import basics as _basics
from horovod_tpu.common import config as _config
from horovod_tpu.common import logging as _log
from horovod_tpu.common.types import HorovodTpuError
from horovod_tpu.ops import adasum as _adasum
from horovod_tpu.runtime import aot_cache as _aot

# Reduce-op codes shared with collectives.py (import cycle avoidance).
_AVERAGE, _SUM, _ADASUM = 1, 2, 3

_program_cache: dict = {}
_warned_noncontig = False


def clear_cache() -> None:
    _program_cache.clear()


def _hier_admissibility():
    """Knob-independent 2-level admissibility for this job's layout:
    ``(local, warn)`` — the local group size when a (cross, local)
    split exists, else ``(0, reason-to-warn-or-None)``.

    Mirrors the reference's homogeneity gating for
    ``NCCLHierarchicalAllreduce`` (``nccl_operations.cc:161+``): the
    decomposition applies only when every host runs the same number of
    ranks and ranks are host-contiguous, so row ``r`` of the world mesh
    sits at ``(r // local, r % local)`` of the 2-level mesh.
    ``HOROVOD_HIERARCHICAL_LOCAL_SIZE`` overrides the detected local
    group size (test hook).  Shared with the autotuner
    (`hier_possible`) so it never tunes a dimension this gate would
    ignore."""
    st = _basics.state()
    if st.size <= 1:
        return 0, None
    forced = _config.get("hierarchical_local_size")
    local = forced if forced else st.local_size
    if local <= 1 or st.size % local:
        if forced:
            return 0, (
                f"HOROVOD_HIERARCHICAL_LOCAL_SIZE={forced} does not give "
                f"a 2-level split of world size {st.size}; using flat "
                "collectives")
        return 0, None
    if not forced:
        if st.local_size * st.cross_size != st.size or \
                st.rank != st.cross_rank * st.local_size + st.local_rank:
            return 0, ("hierarchical collectives requested but ranks are "
                       "not host-contiguous/homogeneous; falling back to "
                       "flat")
    return local, None


def hier_possible() -> bool:
    """True when the hierarchical on/off knobs can change behavior for
    this job's layout (the autotuner freezes them out otherwise)."""
    try:
        return _hier_admissibility()[0] > 1
    except Exception:
        return False


def _hier_topology(knob: str):
    """Two-level (cross, local) shape for the eager data plane, or None
    (knob off, or the layout fails `_hier_admissibility`)."""
    global _warned_noncontig
    if not _config.get(knob):
        return None
    local, warn = _hier_admissibility()
    if not local:
        if warn and not _warned_noncontig:
            _warned_noncontig = True
            _log.warning(warn, rank=_basics.state().rank)
        return None
    st = _basics.state()
    return (st.size // local, local)


def _hier_mesh(hier):
    """(cross, local) mesh over the same world lead devices."""
    st = _basics.state()
    from jax.sharding import Mesh

    key = ("hmesh", hier, st.epoch)
    mesh = _program_cache.get(key)
    if mesh is None:
        devices = st.mesh.devices.reshape(hier)
        mesh = Mesh(devices, ("cross", "local"))
        _program_cache[key] = mesh
    return mesh


def _to_global(x):
    """Wrap this process's local tensor as row ``rank`` of a global
    ``(size, *shape)`` array sharded over the ``hvd`` axis."""
    st = _basics.state()
    x = jnp.asarray(x)
    local = jax.device_put(x, st.lead_device)
    return jax.make_array_from_single_device_arrays(
        (st.size,) + x.shape,
        NamedSharding(st.mesh, P("hvd")),
        [local.reshape((1,) + x.shape)])


def _local(out):
    """Extract this process's addressable result."""
    return out.addressable_data(0)


def _sizes(shapes):
    return [int(np.prod(s)) if len(s) else 1 for s in shapes]


def overlap_cfg():
    """Chunk count when the overlap engine is on, else ``None`` — part
    of every allreduce/reducescatter program cache key, so toggling
    ``HOROVOD_OVERLAP`` (or the autotuner retuning
    ``HOROVOD_OVERLAP_CHUNKS``) rebuilds the negotiated programs.  Like
    the compression knob, overlap is validated to agree across ranks at
    the round-0 handshake — each rank builds its own collective
    program, and a divergence would deadlock in mismatched
    collectives."""
    from horovod_tpu.ops import overlap as _ovl

    return _ovl.configured_chunks() if _ovl.enabled() else None


def zero_cfg():
    """``(stage, bucket_chunks)`` when ``HOROVOD_ZERO_STAGE >= 2``,
    else ``None`` — part of the reducescatter/allgather program cache
    keys.  From stage 2 on the optimizer submits K bucket-piece
    collectives per fused group, so a retune of
    ``HOROVOD_ZERO_PREFETCH_CHUNKS`` (an autotuner dimension) or a
    stage flip between elastic generations must never replay a program
    negotiated under the other cfg.  Validated to agree across ranks at
    the round-0 handshake, like the compression and overlap knobs."""
    stage = int(_config.get("zero_stage"))
    if stage < 2:
        return None
    return (stage, max(1, int(_config.get("zero_prefetch_chunks"))))


def health_cfg():
    """``(1, skip)`` when the training-health plane is on, else
    ``None`` — part of the allreduce/reducescatter program cache keys:
    the stat tap adds a small verdict allgather to those programs, so
    toggling ``HOROVOD_HEALTH`` (or ``HOROVOD_HEALTH_SKIP_NONFINITE``,
    which selects the skip-step trajectory) must never replay a
    program negotiated under the other cfg.  Both knobs are validated
    to agree across ranks at the round-0 handshake (docs/health.md)."""
    if not _config.get("health"):
        return None
    return (1, 1 if _config.get("health_skip_nonfinite") else 0)


def mesh_cfg():
    """The configured data-mesh spec (``HOROVOD_MESH``, canonical
    string) or ``None`` — part of the allreduce/reducescatter program
    cache keys.  The negotiated eager wire itself stays flat-world, but
    a mesh flip between elastic generations changes the dp-scoped shard
    counts the optimizer feeds these programs, so an executable
    negotiated under the other cfg must never replay.  Validated to
    agree across ranks at the round-0 handshake (docs/mesh.md)."""
    from horovod_tpu.parallel import mesh as _pmesh

    spec = str(_config.get("mesh") or "").strip()
    if not spec:
        return None
    return _pmesh.canonical_spec(_pmesh.parse_mesh_spec(spec))


def control_cfg():
    """The hierarchical control plane's fanout when it is active for
    this world (``world > HOROVOD_CONTROL_FANOUT >= 2``), else
    ``None`` — part of the allreduce/reducescatter program cache keys.
    The data-plane programs themselves are identical under flat and
    hierarchical negotiation (byte-identical ResponseLists by
    construction), but a fanout flip between elastic generations
    changes which epoch-scoped control keys pace the executables'
    launches, so a program negotiated under the other cfg must never
    replay against stale pacing state.  Validated to agree across
    ranks at the round-0 handshake (docs/control-plane.md)."""
    from horovod_tpu.common import basics as _basics
    from horovod_tpu.runtime import controller as _controller

    try:
        world = int(_basics.state().size)
    except Exception:
        return None
    fanout = max(int(_config.get("control_fanout")), 0)
    if _controller.control_topology(world, fanout) is None:
        return None
    return fanout


def local_sgd_cfg():
    """``(H, outer_lr_micro, outer_momentum_micro, mode)`` when the
    local-SGD/DiLoCo regime is active (``HOROVOD_LOCAL_SGD_H >= 2``,
    docs/local-sgd.md), else ``None`` — part of the
    allreduce/reducescatter program cache keys.  H decides which
    collective programs the regime submits (ICI-only inner steps,
    DCN-only pseudo-gradient syncs) and the mode picks the outer
    hop's wire, so a retune of any of these between elastic
    generations must never replay a program negotiated under the
    other cfg.  All four knobs are validated to agree across ranks at
    the round-0 handshake."""
    h = max(int(_config.get("local_sgd_h") or 0), 0)
    if h <= 1:
        return None
    mode = str(_config.get("local_sgd_compression")
               or _config.get("compression")).strip().lower() or "none"
    return (h,
            int(round(float(_config.get("outer_lr")) * 1e6)),
            int(round(float(_config.get("outer_momentum")) * 1e6)),
            mode)


def local_sgd_topology():
    """Two-level ``(cross, local)`` shape the eager local-SGD regime
    scopes its reductions to, or ``None`` when this job's layout has
    no 2-level split (every rank is its own slice — the local group
    degenerates to 1 and inner reductions are the identity).  Knob-
    independent on purpose: the regime implies the topology, so it
    must not require ``HOROVOD_HIERARCHICAL_ALLREDUCE`` to also be
    on."""
    local, _warn = _hier_admissibility()
    if local <= 1:
        return None
    st = _basics.state()
    return (st.size // local, local)


def _pseudo_wire_compression(dtype, ls) -> tuple:
    """``(mode, quant_block, topk_ratio_micro)`` for the cross-slice
    pseudo-gradient hop (``HOROVOD_LOCAL_SGD_COMPRESSION``, falling
    back to ``HOROVOD_COMPRESSION``) — cache-key material like
    :func:`_wire_compression`, but single-mode: the outer sync is one
    fused buffer per dtype, never the bucketed adaptive vector."""
    from horovod_tpu.ops.compression import Compression

    mode = ls[3] if ls is not None else "none"
    Compression.lookup(mode)  # fail fast on typo'd knob values
    if not jnp.issubdtype(dtype, jnp.floating):
        return ("none", 0, 0)
    if mode in ("fp16", "bf16"):
        wire = jnp.float16 if mode == "fp16" else jnp.bfloat16
        if np.dtype(dtype).itemsize <= np.dtype(wire).itemsize:
            mode = "none"
    qblock = (int(_config.get("quant_block_size"))
              if mode in ("int8", "int4") else 0)
    ratio = (int(round(float(_config.get("topk_ratio")) * 1e6))
             if mode == "topk" else 0)
    return (mode, qblock, ratio)


def _health_tap(flat, axes, dtype) -> None:
    """Pre-reduction stat tap inside a negotiated program body: local
    finite-part norm/max-abs/nonfinite count of this rank's block,
    verdict allgathered over the program's own axis and published via
    host callback — culprit attribution over the real wire
    (docs/health.md).  Build-time gated on :func:`health_cfg` (part of
    the cache key), so health-off programs carry zero tap ops."""
    import jax.numpy as jnp

    if not jnp.issubdtype(dtype, jnp.floating):
        return
    from horovod_tpu.runtime import health as _health

    _health.tap_block(flat, axes, str(jnp.dtype(dtype)))


_LOSSY = ("int8", "int4", "topk")


def _eager_guard_signal(modes) -> bool:
    """Whether an eager lossy program should compute and publish its
    per-bucket loss ratio for the adaptive tuner's bounded-loss
    guardrail: the negotiated wire reduces WITHOUT error feedback (the
    residual never leaves the program — docs/compression.md), so under
    ``HOROVOD_ADAPTIVE_COMPRESSION`` the dropped mass is a real loss,
    and without this signal the guardrail would run blind on eager
    frontends and never pin an over-aggressive bucket back to int8."""
    return (bool(_config.get("adaptive_compression"))
            and any(m in _LOSSY for m in modes))


def _publish_eager_loss(err, red, n, axis_name, chunks: int) -> None:
    """Publish the eager program's per-bucket residual-to-gradient
    ratio (``hvd_compression_residual_ratio``) — the same series the
    optimizer's EF paths feed, except here the residual was DROPPED,
    not deferred, which is exactly why the guardrail must see it.  The
    hierarchical eager path reports nothing (its cross-hop residual is
    internal); prefer in-trace EF or an explicit mode vector there."""
    if err is None:
        return
    from horovod_tpu.optim.distributed import \
        _report_bucket_residual_ratios

    ferr = err.astype(jnp.float32).reshape(-1)
    fred = red.astype(jnp.float32).reshape(-1)
    pad = (-ferr.shape[0]) % max(int(n), 1)
    if pad:
        z = jnp.zeros((pad,), jnp.float32)
        ferr = jnp.concatenate([ferr, z])
        fred = jnp.concatenate([fred, z])
    _report_bucket_residual_ratios(ferr, fred, n, axis_name,
                                   chunks=max(1, int(chunks)))


def _wire_compression(dtype) -> tuple:
    """(mode_vector, quant_block, topk_ratio_micro) the negotiated data
    plane applies to this payload dtype under ``HOROVOD_COMPRESSION`` /
    ``HOROVOD_BUCKET_COMPRESSION`` — part of the program cache key, so
    toggling either knob (or the adaptive autotuner retuning the
    per-bucket vector) rebuilds programs.  ``mode_vector`` has one
    entry per overlap bucket when the overlap engine is on (each bucket
    may carry its own mode — the adaptive compression stack,
    docs/compression.md), one entry otherwise.  The knobs are validated
    to agree across ranks at the controller's round-0 handshake; a
    per-rank divergence would otherwise build different collectives and
    hang the job."""
    from horovod_tpu.ops.compression import (Compression,
                                             effective_bucket_modes)

    base = str(_config.get("compression")).lower()
    Compression.lookup(base)  # fail fast on typo'd knob values
    if not jnp.issubdtype(dtype, jnp.floating):
        return (("none",), 0, 0)
    modes = []
    for m in effective_bucket_modes():
        if m in ("fp16", "bf16"):
            # cast entries only when they actually shrink the payload
            wire = jnp.float16 if m == "fp16" else jnp.bfloat16
            m = m if np.dtype(dtype).itemsize > np.dtype(wire).itemsize \
                else "none"
        modes.append(m)
    if all(m == "none" for m in modes):
        return (("none",), 0, 0)
    qblock = (int(_config.get("quant_block_size"))
              if any(m in ("int8", "int4") for m in modes) else 0)
    ratio = (int(round(float(_config.get("topk_ratio")) * 1e6))
             if "topk" in modes else 0)
    return (tuple(modes), qblock, ratio)


def fused_allreduce(tensors: list, op: int, scope: str | None = None) -> list:
    """One collective for a fused bucket of same-dtype tensors.

    ``scope`` pins the reduction to one sub-axis of the 2-level
    (cross, local) topology for the eager local-SGD regime
    (docs/local-sgd.md): ``"local"`` reduces within the slice only
    (ICI, full precision — the inner step), ``"cross"`` across slices
    only (DCN, pseudo-gradient compression applies).  ``None`` is the
    ordinary world-scoped reduction."""
    st = _basics.state()
    if st.size == 1:
        return [t if isinstance(t, jax.Array) else jnp.asarray(t)
                for t in tensors]
    ls = local_sgd_cfg()
    if scope is not None:
        return _scoped_fused_allreduce(tensors, op, scope, ls)
    shapes = tuple(tuple(t.shape) for t in tensors)
    dtype = np.dtype(tensors[0].dtype)
    hier = _hier_topology("hierarchical_allreduce")
    comp = (("none",), 0, 0) if op == _ADASUM else _wire_compression(dtype)
    ov = None if op == _ADASUM else overlap_cfg()
    hp = None if op == _ADASUM else health_cfg()
    key = ("ar", op, dtype, shapes, st.size, hier, comp, ov, hp,
           mesh_cfg(), control_cfg(), ls)
    fn = _program_cache.get(key)
    args = [_to_global(t) for t in tensors]
    if fn is None:
        # Miss: build + AOT-compile through the persistent executable
        # cache (docs/aot-cache.md) — a warm start loads the serialized
        # executable instead of recompiling; fail-closed, so any cache
        # problem degrades to this compile.
        fn = _aot.compile_or_load(
            key,
            lambda: _build_allreduce(st.mesh, shapes, op, st.size, hier,
                                     comp, ov, hp),
            args)
        _program_cache[key] = fn
    outs = fn(*args)
    if len(tensors) == 1:
        outs = (outs,)
    return [_local(o) for o in outs]


def _scoped_fused_allreduce(tensors: list, op: int, scope: str,
                            ls) -> list:
    """Axis-scoped eager reduction of the local-SGD regime: one
    program over the 2-level (cross, local) mesh that reduces over
    ONLY the requested sub-axis.  Inner-step (``"local"``) programs
    therefore contain zero cross-slice collectives by construction —
    the property the ``local_sgd_inner_rules`` HLO preset proves —
    and pseudo-gradient (``"cross"``) programs carry the lossy wire
    on the DCN hop only."""
    if scope not in ("local", "cross"):
        raise HorovodTpuError(
            f"unknown reduction scope {scope!r}: expected 'local' or "
            "'cross'")
    if op == _ADASUM:
        raise HorovodTpuError(
            "scoped (local-SGD) reductions support Sum/Average only: "
            "the Adasum projection needs the full reduction")
    st = _basics.state()
    topo = local_sgd_topology()
    if topo is None:
        # Every rank is its own slice: the local group is 1, so the
        # inner reduction is the identity and the cross hop IS the
        # world reduction (pure DiLoCo).
        if scope == "local":
            return [t if isinstance(t, jax.Array) else jnp.asarray(t)
                    for t in tensors]
        topo = (st.size, 1)
    shapes = tuple(tuple(t.shape) for t in tensors)
    dtype = np.dtype(tensors[0].dtype)
    comp = (("none", 0, 0) if scope == "local"
            else _pseudo_wire_compression(dtype, ls))
    hp = health_cfg() if scope == "local" else None
    key = ("ars", scope, op, dtype, shapes, st.size, topo, comp, hp,
           mesh_cfg(), control_cfg(), ls)
    fn = _program_cache.get(key)
    args = [_to_global(t) for t in tensors]
    if fn is None:
        fn = _aot.compile_or_load(
            key,
            lambda: _build_scoped_allreduce(shapes, op, topo, scope,
                                            comp, hp),
            args)
        _program_cache[key] = fn
    outs = fn(*args)
    if len(tensors) == 1:
        outs = (outs,)
    return [_local(o)[0] for o in outs]


def _build_scoped_allreduce(shapes, op, topo, scope, comp, hp):
    """Program builder for :func:`_scoped_fused_allreduce`: psum over
    one sub-axis of the (cross, local) mesh.  The result varies over
    the OTHER sub-axis (each slice keeps its own local sum; each
    local position keeps its own cross sum), so outputs carry a
    leading axis sharded over it and callers take their own row."""
    sizes = _sizes(shapes)
    mesh = _hier_mesh(topo)
    axis = "local" if scope == "local" else "cross"
    other = "cross" if scope == "local" else "local"
    nax = topo[1] if scope == "local" else topo[0]
    mode, qblock, _ratio = comp

    def body(*blocks):
        flats = [b[0].reshape(-1) for b in blocks]
        flat = flats[0] if len(flats) == 1 else jnp.concatenate(flats)
        in_dtype = flat.dtype
        if hp:
            _health_tap(flat, axis, in_dtype)
        m = mode
        if m in ("fp16", "bf16"):
            flat = flat.astype(jnp.float16 if m == "fp16"
                               else jnp.bfloat16)
            m = "none"
        if m in _LOSSY:
            from horovod_tpu.ops import quantization as _quant

            red = _quant.lossy_psum(flat, axis, m, qblock or None)
        else:
            red = lax.psum(flat, axis)
        red = red.astype(in_dtype)
        if op == _AVERAGE:
            red = (red / nax).astype(red.dtype)
        outs, off = [], 0
        for s, sz in zip(shapes, sizes):
            outs.append(red[off:off + sz].reshape((1,) + s))
            off += sz
        return tuple(outs) if len(outs) > 1 else outs[0]

    k = len(shapes)
    spec = P(("cross", "local"))
    sm = shard_map(body, mesh=mesh, check_vma=False,
                   in_specs=(spec,) * k,
                   out_specs=P(other) if k == 1 else (P(other),) * k)
    out_sh = NamedSharding(mesh, P(other))
    return jax.jit(sm, out_shardings=out_sh if k == 1 else (out_sh,) * k)


def _build_allreduce(mesh, shapes, op, n, hier=None,
                     comp=(("none",), 0, 0), ov=None, hp=None):
    sizes = _sizes(shapes)
    if hier is not None:
        mesh = _hier_mesh(hier)
        axes = ("cross", "local")
    else:
        axes = "hvd"
    modes, qblock, _ratio = comp
    mode = modes[0]

    def body(*blocks):
        flats = [b[0].reshape(-1) for b in blocks]
        if op == _ADASUM:
            # One ppermute chain per fused bucket: the buffer is fused,
            # the projection math stays per tensor (segment sizes), so
            # per-layer scale invariance survives the fusion.
            flat = flats[0] if len(flats) == 1 else jnp.concatenate(flats)
            segments = sizes if len(flats) > 1 else None
            if hier is not None:
                red = _adasum.adasum_hierarchical(flat, "local", "cross",
                                                  segments=segments)
            else:
                red = _adasum.adasum(flat, axes, segments=segments)
            outs, off = [], 0
            for s, sz in zip(shapes, sizes):
                outs.append(red[off:off + sz].reshape(s))
                off += sz
            return tuple(outs) if len(outs) > 1 else outs[0]
        flat = flats[0] if len(flats) == 1 else jnp.concatenate(flats)
        in_dtype = flat.dtype
        if hp:
            # Health tap BEFORE the reduction (docs/health.md): the
            # fused local buffer is exactly this rank's pre-reduction
            # contribution, so the verdict's nonfinite count names the
            # culprit rank + dtype group instead of everyone's NaN.
            _health_tap(flat, axes, in_dtype)
        if ov:
            # Bucketed ppermute ring schedule (docs/overlap.md): K
            # barrier-separated reduce-scatter/allgather buckets the
            # latency-hiding scheduler pipelines; handles the
            # hierarchical decomposition and the per-bucket wire modes
            # (casts sandwich the bucket's transfers, lossy modes
            # compress scale-aware/sparse) internally.
            from horovod_tpu.ops import overlap as _ovl

            red, err = _ovl.overlapped_flat_reduce(
                flat, axes, op=_SUM, quantized="none",
                block_size=qblock or None, chunks=ov,
                modes=list(modes), with_error=_eager_guard_signal(modes))
            _publish_eager_loss(err, red, n, axes, chunks=ov)
            red = red.astype(in_dtype)
        else:
            m = mode
            if m in ("fp16", "bf16"):
                # Cast sandwich composes with the hierarchical split
                # (cast payload on every hop) instead of replacing it.
                flat = flat.astype(jnp.float16 if m == "fp16"
                                   else jnp.bfloat16)
                m = "none"
            if hier is not None:
                from horovod_tpu.ops.collectives import (
                    Compression, Sum, hierarchical_allreduce)

                red = hierarchical_allreduce(
                    flat, local_axis="local", cross_axis="cross", op=Sum,
                    compression=Compression.lookup(m),
                    block_size=qblock or None)
            elif m in _LOSSY:
                from horovod_tpu.ops import quantization as _quant

                if _eager_guard_signal((m,)):
                    red, err = _quant.lossy_psum_with_error(
                        flat, axes, m, qblock or None)
                    _publish_eager_loss(err, red, n, axes, chunks=1)
                else:
                    red = _quant.lossy_psum(flat, axes, m, qblock or None)
            else:
                red = lax.psum(flat, axes)
            red = red.astype(in_dtype)
        if op == _AVERAGE:
            red = (red / n).astype(red.dtype)
        outs, off = [], 0
        for s, sz in zip(shapes, sizes):
            outs.append(red[off:off + sz].reshape(s))
            off += sz
        return tuple(outs) if len(outs) > 1 else outs[0]

    k = len(shapes)
    spec = P(axes) if hier is None else P(("cross", "local"))
    sm = shard_map(body, mesh=mesh, check_vma=False, in_specs=(spec,) * k,
                   out_specs=P() if k == 1 else (P(),) * k)
    out_sh = NamedSharding(mesh, P())
    return jax.jit(sm, out_shardings=out_sh if k == 1 else (out_sh,) * k)


def reducescatter(tensor, op: int):
    """Negotiated eager reduce-scatter along axis 0: every rank gets
    the ``ceil(d0 / size)``-row shard of the cross-rank reduction
    (non-divisible leading dims are zero-padded inside the program —
    the in-trace :func:`horovod_tpu.ops.collectives.reducescatter`
    guard).  The ``HOROVOD_COMPRESSION`` knob applies inside the
    program like the allreduce path: int8 rides the block-scaled wire
    (hierarchical topology splits the scatter so ICI hops stay full
    precision and only the cross-slice hop quantizes)."""
    st = _basics.state()
    tensor = jnp.asarray(tensor)
    if st.size == 1:
        return tensor
    dtype = np.dtype(tensor.dtype)
    hier = _hier_topology("hierarchical_allreduce")
    comp = _wire_compression(dtype)
    ov = overlap_cfg()
    hp = health_cfg()
    key = ("rs", op, dtype, tuple(tensor.shape), st.size, hier, comp, ov,
           zero_cfg(), hp, mesh_cfg(), control_cfg(), local_sgd_cfg())
    fn = _program_cache.get(key)
    arg = _to_global(tensor)
    if fn is None:
        fn = _aot.compile_or_load(
            key,
            lambda: _build_reducescatter(st.mesh, tuple(tensor.shape),
                                         op, hier, comp, ov, hp),
            [arg])
        _program_cache[key] = fn
    return _local(fn(arg))


def _build_reducescatter(mesh, shape, op, hier=None,
                         comp=(("none",), 0, 0), ov=None, hp=None):
    from horovod_tpu.ops.collectives import (Compression,
                                             reducescatter as _rs)

    modes, qblock, _ratio = comp
    # The per-bucket vector (overlap on) is resolved inside the scatter
    # chain at trace time (``overlap.resolve_bucket_modes`` reads the
    # same knob); ``modes`` being part of the cache key is what forces
    # the re-trace when the adaptive tuner changes it.
    compressor = Compression.lookup(modes[0])
    if hier is not None:
        mesh = _hier_mesh(hier)
        axes = ("cross", "local")
        spec = P(("cross", "local"))
    else:
        axes = "hvd"
        spec = P(axes)

    def body(block):
        if hp:
            # Pre-reduction health tap (docs/health.md): the sharded
            # optimizer's gradient scatter is the ZeRO data plane — a
            # poisoned shard names its rank here too.
            _health_tap(block[0].reshape(-1), axes, block[0].dtype)
        return _rs(block[0], axis_name=axes, op=op,
                   compression=compressor, block_size=qblock or None,
                   overlap=bool(ov))

    sm = shard_map(body, mesh=mesh, check_vma=False, in_specs=spec,
                   out_specs=spec)
    return jax.jit(sm, out_shardings=NamedSharding(mesh, spec))


def allgather(tensor, sizes=None):
    """Ragged allgather: concat along axis 0 with per-rank first-dim
    sizes (reference ``MPIAllgather``'s displacement math,
    ``mpi_operations.cc:84+``).  XLA has no ragged all-gather primitive
    (SURVEY §7 hard parts).  ``sizes`` (per-rank first dims) normally
    arrives from the negotiation round that already collected every
    rank's shape — matching the reference, where the Response carries
    tensor sizes so the op needs no extra gather; ``sizes=None`` (direct
    callers outside the negotiated path) falls back to a size-gather
    collective.  Equal sizes ride a tiled ``all_gather``; ragged sizes
    pick between two strategies (``HOROVOD_RAGGED_ALLGATHER``):

    * ``psum`` — each rank embeds its block at its exact displacement
      in a zeros(sum(sizes)) buffer host-side, one ``psum`` produces
      the concatenation (disjoint blocks → sum == concat).  Wire bytes
      scale with ~2*sum(sizes) (reduce-scatter + all-gather halves of
      the psum), independent of the longest rank.
    * ``pad`` — pad to max, gather, trim: bytes ~ max*nranks.  Cheaper
      when sizes are nearly equal (psum pays 2x).

    ``auto`` compares the two byte costs per call.
    """
    st = _basics.state()
    tensor = jnp.asarray(tensor)
    if st.size == 1:
        return tensor
    if tensor.ndim == 0:
        raise HorovodTpuError("allgather requires rank >= 1 tensors")
    d0 = int(tensor.shape[0])
    if sizes is None:
        sizes = [int(v) for v in np.asarray(_gather_sizes(d0))]
    else:
        sizes = [int(v) for v in sizes]
        if len(sizes) != st.size or sizes[st.rank] != d0:
            raise HorovodTpuError(
                f"negotiated allgather sizes {sizes} disagree with local "
                f"first dim {d0} on rank {st.rank}")
    max0 = max(sizes)
    if all(s == max0 for s in sizes):
        gathered = _equal_allgather(tensor)
        return _local(gathered)
    strategy = str(_config.get("ragged_allgather")).lower()
    if strategy == "auto":
        strategy = ("psum" if 2 * sum(sizes) < max0 * st.size else "pad")
    if strategy == "psum":
        return _ragged_psum_allgather(tensor, sizes)
    pad = [(0, max0 - d0)] + [(0, 0)] * (tensor.ndim - 1)
    padded = jnp.pad(tensor, pad)
    gathered = _local(_equal_allgather_blocks(padded))
    parts = [gathered[i * max0: i * max0 + sizes[i]] for i in range(st.size)]
    return jnp.concatenate(parts, axis=0)


def _ragged_psum_allgather(tensor, sizes):
    """Exact-displacement ragged gather: zeros(total) with this rank's
    block written at its offset, one psum.  The program is cached by
    (dtype, total, trailing shape) — the per-rank offsets are host-side
    data prep, so every ragged pattern with the same total reuses it."""
    st = _basics.state()
    cast = None
    if jnp.issubdtype(tensor.dtype, jnp.bool_):  # psum has no bool
        cast = jnp.bool_
        tensor = tensor.astype(jnp.uint8)
    total = int(sum(sizes))
    offset = int(sum(sizes[:st.rank]))
    rest = tuple(tensor.shape[1:])
    buf = jnp.zeros((total,) + rest, tensor.dtype)
    buf = buf.at[offset:offset + tensor.shape[0]].set(tensor)
    key = ("agv", np.dtype(tensor.dtype), (total,) + rest, st.size)
    fn = _program_cache.get(key)
    arg = _to_global(buf)
    if fn is None:
        def build():
            sm = shard_map(lambda b: lax.psum(b[0], "hvd"), mesh=st.mesh,
                           check_vma=False, in_specs=P("hvd"),
                           out_specs=P())
            return jax.jit(sm, out_shardings=NamedSharding(st.mesh, P()))

        fn = _aot.compile_or_load(key, build, [arg])
        _program_cache[key] = fn
    out = _local(fn(arg))
    return out.astype(cast) if cast is not None else out


def _gather_sizes(d0: int):
    st = _basics.state()
    key = ("sizes", st.size)
    fn = _program_cache.get(key)
    arg = _to_global(jnp.asarray([d0], dtype=jnp.int32))
    if fn is None:
        def build():
            sm = shard_map(
                lambda b: lax.all_gather(b[0], "hvd", axis=0, tiled=False),
                mesh=st.mesh, check_vma=False, in_specs=P("hvd"),
                out_specs=P())
            return jax.jit(sm, out_shardings=NamedSharding(st.mesh, P()))

        fn = _aot.compile_or_load(key, build, [arg])
        _program_cache[key] = fn
    return _local(fn(arg)).reshape(-1)


def _equal_allgather(tensor):
    st = _basics.state()
    hier = _hier_topology("hierarchical_allgather")
    key = ("ag", np.dtype(tensor.dtype), tuple(tensor.shape), st.size,
           hier, zero_cfg())
    fn = _program_cache.get(key)
    arg = _to_global(tensor)
    if fn is None:
        def build():
            if hier is not None:
                # Two-level gather (reference MPIHierarchicalAllgather,
                # mpi_operations.h:62): local gather rides ICI, then the
                # cross gather moves each node's block once over DCN.
                mesh = _hier_mesh(hier)
                sm = shard_map(
                    lambda b: lax.all_gather(
                        lax.all_gather(b[0], "local", axis=0, tiled=True),
                        "cross", axis=0, tiled=True),
                    mesh=mesh, check_vma=False,
                    in_specs=P(("cross", "local")), out_specs=P())
                return jax.jit(sm, out_shardings=NamedSharding(mesh, P()))
            sm = shard_map(
                lambda b: lax.all_gather(b[0], "hvd", axis=0, tiled=True),
                mesh=st.mesh, check_vma=False, in_specs=P("hvd"),
                out_specs=P())
            return jax.jit(sm, out_shardings=NamedSharding(st.mesh, P()))

        fn = _aot.compile_or_load(key, build, [arg])
        _program_cache[key] = fn
    return fn(arg)


_equal_allgather_blocks = _equal_allgather  # same program; alias for clarity


def fused_broadcast(tensors: list, root_rank: int) -> list:
    """Fused broadcast of same-dtype tensors from ``root_rank``."""
    st = _basics.state()
    if st.size == 1:
        return [jnp.asarray(t) for t in tensors]
    casts = []
    wires = []
    for t in tensors:
        t = jnp.asarray(t)
        if jnp.issubdtype(t.dtype, jnp.bool_):
            casts.append(jnp.bool_)
            wires.append(t.astype(jnp.uint8))
        else:
            casts.append(None)
            wires.append(t)
    shapes = tuple(tuple(t.shape) for t in wires)
    dtype = np.dtype(wires[0].dtype)
    key = ("bc", root_rank, dtype, shapes, st.size)
    fn = _program_cache.get(key)
    args = [_to_global(t) for t in wires]
    if fn is None:
        fn = _aot.compile_or_load(
            key, lambda: _build_broadcast(st.mesh, shapes, root_rank),
            args)
        _program_cache[key] = fn
    outs = fn(*args)
    if len(wires) == 1:
        outs = (outs,)
    res = []
    for o, c in zip(outs, casts):
        o = _local(o)
        res.append(o.astype(c) if c is not None else o)
    return res


def _build_broadcast(mesh, shapes, root_rank):
    sizes = _sizes(shapes)

    def body(*blocks):
        idx = lax.axis_index("hvd")
        flats = [b[0].reshape(-1) for b in blocks]
        flat = flats[0] if len(flats) == 1 else jnp.concatenate(flats)
        masked = jnp.where(idx == root_rank, flat, jnp.zeros_like(flat))
        red = lax.psum(masked, "hvd")
        outs, off = [], 0
        for s, sz in zip(shapes, sizes):
            outs.append(red[off:off + sz].reshape(s))
            off += sz
        return tuple(outs) if len(outs) > 1 else outs[0]

    k = len(shapes)
    sm = shard_map(body, mesh=mesh, check_vma=False, in_specs=(P("hvd"),) * k,
                   out_specs=P() if k == 1 else (P(),) * k)
    out_sh = NamedSharding(mesh, P())
    return jax.jit(sm, out_shardings=out_sh if k == 1 else (out_sh,) * k)


def alltoall(tensor):
    """Equal-split eager all-to-all along axis 0."""
    st = _basics.state()
    tensor = jnp.asarray(tensor)
    if st.size == 1:
        return tensor
    if tensor.shape[0] % st.size != 0:
        raise HorovodTpuError(
            f"alltoall axis-0 size {tensor.shape[0]} must divide world "
            f"size {st.size}")
    key = ("a2a", np.dtype(tensor.dtype), tuple(tensor.shape), st.size)
    fn = _program_cache.get(key)
    arg = _to_global(tensor)
    if fn is None:
        def build():
            sm = shard_map(
                lambda b: lax.all_to_all(b[0], "hvd", split_axis=0,
                                         concat_axis=0, tiled=True),
                mesh=st.mesh, check_vma=False, in_specs=P("hvd"),
                out_specs=P())
            return jax.jit(sm, out_shardings=NamedSharding(st.mesh, P()))

        fn = _aot.compile_or_load(key, build, [arg])
        _program_cache[key] = fn
    return _local(fn(arg))


def barrier() -> None:
    """Synchronize all processes (used by broadcast_object and the
    launcher teardown)."""
    st = _basics.state()
    if st.size == 1:
        return
    out = fused_allreduce([jnp.zeros((1,), jnp.int32)], _SUM)[0]
    jax.block_until_ready(out)
