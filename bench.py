"""Synthetic ConvNet benchmarks — the reference's headline workloads.

Reference recipe: ``examples/tensorflow2_synthetic_benchmark.py:119-132``
(synthetic ImageNet batches, img/sec per device) over the three models
of ``docs/benchmarks.rst:11-13`` (ResNet, Inception V3, VGG-16).  The
train step is this framework's data-parallel path — a shard_map over
the world ``hvd`` mesh with the DistributedOptimizer's traced psum —
so the measured number is the framework, not a bare model.

Headline metric: ResNet-50 images/sec/chip, scored against an
A100-parity target (the BASELINE.json north star: "matches 8xA100 NCCL
images/sec/chip").  NVIDIA's published NGC number for ResNet-50 v1.5
synthetic training on one A100-SXM4 with AMP+XLA is ~2900 img/s, which
is what an 8xA100 NCCL run achieves per chip at near-linear scaling.
Also reports MFU (XLA-counted flops/step x steps/sec / peak chip
flops), VGG-16 and Inception-V3 throughput, and eager-path dispatch
overhead.

One process opens the backend once and measures.  The run wants a TPU:
when JAX finds none it exits non-zero and prints no metric line.  A CPU
run happens only when asked for (``JAX_PLATFORMS=cpu`` /
``HOROVOD_PLATFORM=cpu``), at test size, and its line says
``"platform": "cpu"`` — a liveness signal, never a device number.  A
model or section that fails fails the run: the line still lands (with
``error``, and whatever was measured before), the exit code is 1.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "images/sec/chip",
   "vs_baseline": N, "extra": {...}}
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

A100_IMG_S_PER_CHIP = 2900.0  # NGC ResNet-50 v1.5 AMP+XLA, 1x A100-SXM4

def _env_bool(name: str, default: str = "0") -> bool:
    """Boolean env knob with the framework's canonical parsing."""
    from horovod_tpu.common.config import _parse_bool

    return _parse_bool(os.environ.get(name, default))


def _gp_span(phase: str):
    """Goodput-ledger span (docs/goodput.md): bench attributes its
    setup/compile wall so the post-run ledger conserves wall-clock.
    Nullcontext when the package can't load — a ledger failure must
    never cost the run."""
    try:
        from horovod_tpu.perf import goodput as _goodput

        return _goodput.span(phase)
    except Exception:
        import contextlib

        return contextlib.nullcontext()


def _stamp_goodput(extra: dict) -> None:
    """Goodput evidence into extras (docs/goodput.md): the ratio the
    perf gate checks, the full phase breakdown, and the named dominant
    bottleneck.  Called on the normal path AND from main()'s finally so
    a run that dies by timeout/abort still keeps its partial wall-clock
    accounting.  Idempotent: section children stamp their own ledgers
    and the parent's merge wins."""
    if "goodput_ratio" in extra:
        return
    try:
        from horovod_tpu.perf import goodput as _goodput

        snap = _goodput.ledger().snapshot()
        if not snap.get("elapsed_s"):
            return
        extra["goodput_ratio"] = snap["goodput_ratio"]
        breakdown = {f"{k}_s": round(v, 3)
                     for k, v in snap["phases"].items()}
        breakdown["unattributed_s"] = round(snap["unattributed_s"], 3)
        breakdown["elapsed_s"] = round(snap["elapsed_s"], 3)
        breakdown["unattributed_ratio"] = snap["unattributed_ratio"]
        extra["goodput"] = breakdown
        dom = _goodput.dominant_bottleneck(snap)
        if dom:
            extra["dominant_bottleneck"] = dom["phase"]
    except Exception:
        pass


def _observe_loss(value: float, step: int | None = None) -> None:
    """Feed the training-health plane the real loss trajectory
    (docs/health.md): the divergence sentinel's and the compression
    guardrail's primary signal.  Advisory — must never cost the run."""
    try:
        from horovod_tpu.runtime import health as _health

        _health.observe_loss(float(value), step=step)
    except Exception:
        pass


def _stamp_autopilot(extra: dict) -> None:
    """Autopilot evidence into extras (docs/autopilot.md): verdict
    counts by outcome, per-rule counts, and applied rollbacks from the
    rank-side engine.  Called from main()'s finally block — a run the
    autopilot rolled back (or one it killed deciding to) must keep the
    intervention record.  Idempotent; no-op when the engine never
    came up."""
    if "autopilot_actions" in extra:
        return
    try:
        from horovod_tpu.runtime import autopilot as _autopilot

        ap = _autopilot._rank_ap
        if ap is None:
            return
        st = ap.stats()
        extra["autopilot_actions"] = int(st["actions_total"])
        extra["autopilot_by_outcome"] = dict(st["by_outcome"])
        extra["autopilot_by_rule"] = dict(st["by_rule"])
        extra["autopilot_rollbacks"] = int(st["rollbacks"])
        if st["dry_run"]:
            extra["autopilot_dry_run"] = True
    except Exception:
        pass


def _stamp_health(extra: dict) -> None:
    """Training-health evidence into extras (docs/health.md): the last
    observed grad norm, how many verdicts carried a nonfinite, and how
    many alerts tripped.  Called on the normal path AND from main()'s
    finally block — a run killed by a divergence it detected must not
    lose the detection.  Idempotent."""
    if "health_alerts" in extra:
        return
    try:
        from horovod_tpu.runtime import health as _health

        snap = _health.monitor().snapshot()
        if snap.get("last_grad_norm") is not None:
            extra["grad_norm_final"] = round(
                float(snap["last_grad_norm"]), 6)
        extra["nonfinite_steps"] = int(snap.get("nonfinite_events", 0))
        extra["health_alerts"] = int(snap.get("alerts_total", 0))
        if snap.get("active_alerts"):
            extra["health_active_alerts"] = list(snap["active_alerts"])
        if snap.get("skipped_steps"):
            extra["health_skipped_steps"] = int(snap["skipped_steps"])
    except Exception:
        pass


def _build_step(model, params, batch_stats, opt, opt_state, mesh,
                steps_per_dispatch: int = 1, opt_state_specs=None,
                zero3: bool = False, data_axes=("hvd",)):
    """One jitted program executing ``steps_per_dispatch`` optimizer
    steps per host dispatch (``lax.scan`` over the step body).  Each
    dispatch pays a host→device round-trip; chaining k steps amortizes
    it k-fold without changing the math (the synthetic batch is reused
    either way,
    matching the reference synthetic bench's fixed data,
    ``tensorflow2_synthetic_benchmark.py:119-132``)."""
    import jax
    import optax
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    has_stats = batch_stats is not None

    def one_step(params, batch_stats, opt_state, images, labels,
                 step_idx):
        # Per-step dropout mask: fold the iteration counter into the
        # key so models with nn.Dropout (VGG-16, Inception V3) get a
        # real RNG and the mask isn't constant-folded out of the
        # timing (apply() without an rngs dict raises InvalidRngError
        # on the first VGG step).
        droprng = jax.random.fold_in(jax.random.PRNGKey(2), step_idx)

        def loss_fn(p):
            if zero3:
                # Stage-3 resident form: the forward's view of the
                # full parameters comes from the bucket-wise prefetched
                # allgather; differentiating through it returns
                # shard-resident gradients (docs/zero.md).
                import horovod_tpu as hvd

                p = hvd.zero3_full_params(p)
            variables = {"params": p}
            if has_stats:
                variables["batch_stats"] = batch_stats
                logits, mut = model.apply(variables, images, train=True,
                                          mutable=["batch_stats"],
                                          rngs={"dropout": droprng})
                new_stats = mut["batch_stats"]
            else:
                logits = model.apply(variables, images, train=True,
                                     rngs={"dropout": droprng})
                new_stats = batch_stats
            onehot = jax.nn.one_hot(labels, logits.shape[-1])
            return (optax.softmax_cross_entropy(logits, onehot).mean(),
                    new_stats)

        (loss, new_stats), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, new_stats, opt_state, loss.reshape(1)

    if steps_per_dispatch <= 1:
        per_device = one_step
    else:
        def per_device(params, batch_stats, opt_state, images, labels,
                       step_idx):
            def body(carry, i):
                p, bs, os_ = carry
                p, bs, os_, loss = one_step(p, bs, os_, images, labels,
                                            step_idx + i)
                return (p, bs, os_), loss

            (params, batch_stats, opt_state), losses = jax.lax.scan(
                body, (params, batch_stats, opt_state),
                jax.numpy.arange(steps_per_dispatch))
            return params, batch_stats, opt_state, losses[-1]

    if zero3:
        import horovod_tpu as hvd

        pspec = hvd.zero3_params_specs(params)
    else:
        pspec = jax.tree_util.tree_map(lambda _: P(), params)
    bspec = jax.tree_util.tree_map(lambda _: P(), batch_stats)
    # ZeRO-1 sharded state threads through with per-leaf specs (shard
    # buffers ride P("hvd"): the global view is the fused buffer, rank r
    # holding segment r); replicated states stay P().  Stage-3 params
    # ride the same layout (zero3_params_specs).
    opt_specs = (opt_state_specs if opt_state_specs is not None
                 else jax.tree_util.tree_map(lambda _: P(), opt_state))
    # Donating params/stats/opt_state lets XLA update weights in place
    # instead of allocating fresh buffers every step (+~2% measured r1).
    # data_axes: the batch dim's mesh axes — ("hvd",) in the flat
    # world, ("cross", "local") under the local-SGD hierarchical mesh.
    dspec = P(tuple(data_axes))
    return jax.jit(shard_map(
        per_device, mesh=mesh, check_vma=False,
        in_specs=(pspec, bspec, opt_specs, dspec, dspec, P()),
        out_specs=(pspec, bspec, opt_specs, P())), donate_argnums=(0, 1, 2))


def _bench_model(hvd, model_ctor, image_size, batch_per_chip,
                 iters_per_round, rounds, want_flops=False):
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = hvd.world_mesh()
    n = hvd.size()
    # bf16 feeds the MXU on TPU; XLA *CPU* emulates bf16 in software
    # (~10x slower than f32), so an asked-for CPU run computes in f32 —
    # it is a liveness signal, not a comparable number.
    on_tpu = jax.devices()[0].platform == "tpu"
    model = model_ctor(num_classes=1000,
                       dtype=jnp.bfloat16 if on_tpu else jnp.float32)
    # dict of rngs: dropout-bearing models need a "dropout" stream at
    # init time too
    init_rngs = {"params": jax.random.PRNGKey(0),
                 "dropout": jax.random.PRNGKey(1)}
    # model.init traces + compiles the init program — attributed as
    # "compile" on the goodput ledger so the bench's wall conserves
    # (docs/goodput.md)
    with _gp_span("compile"):
        variables = model.init(
            init_rngs,
            jnp.zeros((1, image_size, image_size, 3), jnp.float32),
            train=True)
    params = variables["params"]
    batch_stats = variables.get("batch_stats")

    sharded = _env_bool("HOROVOD_SHARDED_OPTIMIZER")
    try:
        zero_stage = int(os.environ.get("HOROVOD_ZERO_STAGE", "0") or 0)
    except ValueError:
        zero_stage = 0
    if zero_stage == 0 and sharded:
        zero_stage = 1
    sharded = zero_stage >= 1
    zero3 = zero_stage >= 3
    opt_extra: dict = {}
    # The APPLIED mode rides the per-model extras (the env-level flag
    # records only the request): opt-state bytes are meaningless
    # without knowing which update produced them.  NB: state is
    # initialized outside the step, so under int8 the sharded bench
    # runs without error feedback (eager-init states carry no
    # residual) — the EF path is covered by tests inside one
    # shard_map program.
    opt_extra["sharded_optimizer_applied"] = sharded
    opt_extra["zero_stage_applied"] = zero_stage
    # Local-SGD regime (docs/local-sgd.md): the benched step runs over
    # a two-level ('cross', 'local') mesh — inner steps reduce over
    # 'local' only, and the host loop fires the compiled outer sync
    # every H-th step.  Stage 0 only here: the bench's ZeRO spec /
    # donation plumbing is scoped to the flat world step, and the
    # ZeRO-composition evidence lives in tests/test_local_sgd.py.
    from horovod_tpu.optim import local_sgd as _lsgd

    ls_h = _lsgd.resolved_h()
    ls_active = ls_h > 1 and zero_stage == 0
    data_axes = ("hvd",)
    if ls_h > 1 and zero_stage:
        opt_extra["local_sgd_skipped"] = (
            f"bench local-SGD step composes with zero_stage=0 only "
            f"(requested stage {zero_stage})")
    if ls_active:
        from horovod_tpu.parallel import mesh as _pmesh

        # Single-process world: span ALL local devices (not just the
        # per-process lead the eager world mesh uses) so a cross axis
        # actually exists — the CPU smoke's liveness value is the
        # two-program H-boundary, not the img/s.
        devs = (list(jax.devices()) if n == 1
                else list(mesh.devices.reshape(-1)))
        n = len(devs)
        # cross=2 "slices" when the world splits evenly; an odd/1-chip
        # world runs the degenerate single-slice form (the outer sync
        # reduces over a size-1 cross axis — the identity).
        local = n // 2 if n % 2 == 0 and n >= 2 else n
        mesh = _pmesh.hierarchical_mesh(devices=devs, local_size=local)
        data_axes = ("cross", "local")
        opt_extra["local_sgd_h"] = ls_h
        opt_extra["local_sgd_slices"] = n // local

    # fused_update.sgd IS optax.sgd (same init/update/state) plus the
    # FusedSpec tag, so HOROVOD_FUSED_UPDATE=1 can fuse the bench's
    # optimizer tail (docs/zero.md); with the knob off it changes
    # nothing.
    if ls_active:
        opt = hvd.LocalSGD(
            hvd.fused_update.sgd(0.1, momentum=0.9),
            op=hvd.Average, axis_name=data_axes, zero_stage=0)
    else:
        opt = hvd.DistributedOptimizer(
            hvd.fused_update.sgd(0.1, momentum=0.9),
            op=hvd.Average, axis_name="hvd", zero_stage=zero_stage)

    from horovod_tpu.optim.distributed import _leaf_nbytes

    def _tree_bytes(tree):
        return _leaf_nbytes(jax.tree_util.tree_leaves(tree))

    # Stage 3: the resident form of the parameters is this process's
    # 1/world flat shards; the step's forward re-materializes the full
    # view bucket-wise (prefetched allgather) and the update writes
    # back only the local shard.
    train_params = hvd.zero3_shard_params(params) if zero3 else params
    opt_state = opt.init(train_params)
    opt_extra["opt_state_bytes_per_chip"] = _tree_bytes(opt_state)
    # The N-fold memory claim as bench numbers (ROADMAP item 2 / the
    # hvd_zero_*_bytes gauges): resident param bytes (shards under
    # stage 3) and the gradient reduction's resident form (shard from
    # stage 2 on; the full fused buffer below).
    opt_extra["param_bytes_per_chip"] = _tree_bytes(train_params)
    from horovod_tpu.optim.distributed import _shard_layout as _lay

    _pl = jax.tree_util.tree_leaves(params)
    _layout = _lay(_pl, n)
    opt_extra["grad_bytes_per_chip"] = int(sum(
        (_layout.shard[g] if zero_stage >= 2 else _layout.padded[g])
        * np.dtype(k).itemsize for g, k in enumerate(_layout.keys)))
    if ls_h > 1:
        try:
            # DCN accounting (docs/benchmarks.md): synchronous DP
            # crosses slices with the gradient payload EVERY step; the
            # local-SGD regime crosses once per H steps with the
            # (possibly compressed) fp32 pseudo-gradient payload —
            # same fused_wire_bytes accounting as the
            # *_wire_compression_ratio stamp, so the two can never
            # disagree about what the DCN hop carries.
            from horovod_tpu.ops import compression as _wcompr

            total_el = int(sum(sum(sz) for sz in _layout.sizes))
            block = int(os.environ.get(
                "HOROVOD_QUANT_BLOCK_SIZE", "256") or 256)
            ratio = float(os.environ.get(
                "HOROVOD_TOPK_RATIO", "0.01") or 0.01)
            outer_mode = (
                os.environ.get("HOROVOD_LOCAL_SGD_COMPRESSION",
                               "").strip()
                or os.environ.get("HOROVOD_COMPRESSION", "").strip()
                or "none")
            outer_wire = _wcompr.fused_wire_bytes(
                total_el, 4, [outer_mode], block=block, ratio=ratio,
                world=max(1, n))
            sync_wire = _wcompr.fused_wire_bytes(
                total_el, 4, _wcompr.effective_bucket_modes(),
                block=block, ratio=ratio, world=max(1, n))
            opt_extra["dcn_bytes_per_step"] = int(
                round(outer_wire / ls_h))
            opt_extra["dcn_bytes_per_step_sync"] = int(sync_wire)
            if outer_wire:
                opt_extra["dcn_bytes_reduction_x"] = round(
                    sync_wire * ls_h / outer_wire, 2)
            opt_extra["dcn_round_reduction_x"] = ls_h
        except Exception:  # a side metric must not cost the run
            pass
    opt_specs = None
    if zero3:
        opt_specs = hvd.sharded_state_specs(opt_state)
        if n > 1:
            opt_state = hvd.sharded_state_to_global(opt_state, mesh)
            train_params = hvd.zero3_params_to_global(train_params, mesh)
    elif sharded:
        opt_specs = hvd.sharded_state_specs(opt_state)
        if n > 1:
            opt_state = hvd.sharded_state_to_global(opt_state, mesh)
    # spd default: 8 on TPU (lax.scan-chained steps amortize the
    # per-dispatch round trip; what that buys on the chip is not
    # measured), 1 elsewhere (a CPU run wants the cheap build).
    spd = max(1, int(os.environ.get("BENCH_STEPS_PER_DISPATCH",
                                    "8" if on_tpu else "1")))
    if ls_active and ls_h % spd:
        # The H-boundary is decided host-side between dispatches
        # (docs/local-sgd.md two-program structure), so the dispatch
        # granularity must divide H.
        spd = 1
    step = _build_step(model, train_params, batch_stats, opt, opt_state,
                       mesh, steps_per_dispatch=spd,
                       opt_state_specs=opt_specs, zero3=zero3,
                       data_axes=data_axes)
    sync_prog = None
    if ls_active:
        from jax import shard_map
        from jax.sharding import PartitionSpec as _P

        # The outer-sync boundary as its own compiled program — the
        # cross/DCN collectives live HERE and only here; the inner
        # step's HLO stays cross-slice silent (docs/local-sgd.md).
        _pspec = jax.tree_util.tree_map(lambda _: _P(), train_params)
        _sspec = jax.tree_util.tree_map(lambda _: _P(), opt_state)
        sync_prog = jax.jit(shard_map(
            opt.outer_sync, mesh=mesh, check_vma=False,
            in_specs=(_pspec, _sspec), out_specs=(_pspec, _sspec)))

    shape = (batch_per_chip * n, image_size, image_size, 3)
    rng_np = np.random.RandomState(0)
    data_sh = NamedSharding(mesh, P(tuple(data_axes)))
    # bf16 feed halves per-step HBM image traffic but measured ~1%
    # slower on v5e (input bandwidth isn't the bottleneck; the extra
    # cast in the stem costs more than the read saves) — default off.
    feed_dtype = (jnp.bfloat16 if _env_bool("BENCH_BF16_FEED")
                  else jnp.float32)
    # Synthetic input generation + host->device transfer is the bench's
    # input pipeline: spanned with hvd.data_wait so it lands on the
    # ledger's input_wait phase (and dogfoods the new instrumentation
    # point, docs/goodput.md).
    with hvd.data_wait("bench_synthetic"):
        images = jax.device_put(
            jnp.asarray(rng_np.rand(*shape), feed_dtype), data_sh)
        labels = jax.device_put(
            jnp.asarray(rng_np.randint(0, 1000, shape[0]), jnp.int32),
            data_sh)

    flops_per_step = None
    if want_flops:
        # the cost analysis pays a full lower + XLA compile —
        # "compile" wall on the goodput ledger
        with _gp_span("compile"):
            step_idx = jnp.zeros((), jnp.int32)
            # HloCostAnalysis counts a While (lax.scan) body ONCE,
            # not trip-count times, so costing the spd-chained
            # program and dividing by spd would understate flops
            # ~spd-fold.  Cost an spd=1 build of the identical step
            # instead (extra compile, but only for the flops-bearing
            # model).
            cost_step = step if spd == 1 else _build_step(
                model, train_params, batch_stats, opt, opt_state,
                mesh, steps_per_dispatch=1,
                opt_state_specs=opt_specs, zero3=zero3,
                data_axes=data_axes)
            cost = cost_step.lower(train_params, batch_stats,
                                   opt_state, images, labels,
                                   step_idx
                                   ).compile().cost_analysis()
        flops_per_step = float(cost.get("flops", 0.0)) or None
    prev_analysis = None
    try:
        # MFU hint for the sampled-capture observatory: flops per
        # trace_step SPAN (one dispatch = spd chained steps), so the
        # background analyzer can stamp hvd_mfu (docs/perf.md).  Always
        # set — None clears a previous model's hint, or a later model's
        # MFU would be computed from the wrong flops.  The snapshot of
        # the last analysis keeps the device-truth stamp below from
        # attributing a previous model's capture to this one.
        from horovod_tpu.perf import capture as _pcap

        _pcap.set_step_flops(
            flops_per_step * spd if flops_per_step else None)
        prev_analysis = _pcap.last_analysis()
    except Exception:
        pass

    # warmup / compile.  A host transfer of the loss is the completion
    # barrier.  The wall time of
    # this block is the model's cold-path cost (dominated by the first
    # step's trace+XLA compile) — stamped as <model>_compile_seconds so
    # the perf gate can fail a cold-path regression (docs/aot-cache.md).
    step_no = 0
    t_compile = time.perf_counter()
    with _gp_span("compile"):  # goodput: warmup wall IS compile wall
        for _ in range(3):
            train_params, batch_stats, opt_state, loss = step(
                train_params, batch_stats, opt_state, images, labels,
                jnp.int32(step_no))
            step_no += spd
        if sync_prog is not None:
            # the outer-sync boundary program compiles in the warmup
            # wall too, so the first timed H-boundary pays no compile
            train_params, opt_state = sync_prog(train_params, opt_state)
        float(np.asarray(loss)[0])
    opt_extra["compile_seconds"] = round(
        time.perf_counter() - t_compile, 3)
    # Stamped AFTER the first (compiling) step, from the gauge rather
    # than the env knob: a trace-time fallback (unrecognized state,
    # non-float group) clears it, so the artifact records what actually
    # ran, not what was requested.
    opt_extra["fused_update_applied"] = hvd.fused_update.active()

    rates = []
    for _ in range(rounds):
        t0 = time.perf_counter()
        for _ in range(iters_per_round):
            # trace_step feeds the hvd_step_time_seconds histogram (and
            # the jax-profiler step annotation) that bench extras and
            # the /metrics endpoints report; per-dispatch wall here,
            # the host-transfer barrier lands in the last span.
            with hvd.trace_step(step=step_no):
                train_params, batch_stats, opt_state, loss = step(
                    train_params, batch_stats, opt_state, images, labels,
                    jnp.int32(step_no))
            step_no += spd
            if sync_prog is not None:
                # H-boundary: the sync wall stays INSIDE the timed
                # round (maybe_outer_sync blocks and ledgers it as
                # comm_exposed) — the regime's img/s is honest about
                # what the DCN hop costs.
                train_params, opt_state = opt.maybe_outer_sync(
                    step_no, train_params, opt_state, sync_fn=sync_prog)
        loss_val = float(np.asarray(loss)[0])  # completion barrier
        dt = time.perf_counter() - t0
        # health bookkeeping AFTER the clock stops: a sentinel trip's
        # flight record/log must not jitter the gated rate
        _observe_loss(loss_val, step=step_no)
        rates.append(shape[0] * iters_per_round * spd / dt)

    # NB: already observed by the last timed round above — observing
    # the same value again here would double-weight the sentinel's
    # EWMA/warmup/streak bookkeeping for one real measurement.
    final_loss = float(np.asarray(loss)[0])
    per_chip = float(np.mean(rates)) / n
    mfu = None
    if flops_per_step and on_tpu:
        # a utilization is a device metric: a CPU run has none, and an
        # unknown device_kind raises in the one peak table
        from horovod_tpu.perf.attribution import peak_flops_per_chip

        peak = peak_flops_per_chip(jax.devices()[0].device_kind)
        step_rate = per_chip * n / shape[0]  # steps/sec
        mfu = flops_per_step * step_rate / (peak * n)

    if _env_bool("HOROVOD_OVERLAP") or _env_bool("BENCH_COMM_EXPOSED"):
        # Comm-exposed seconds: the overlap engine's target metric.
        # Time an identical step with a PLAIN (no cross-rank reduction)
        # optimizer; the per-step difference is the communication time
        # the schedule failed to hide behind compute.  ~0 at world
        # size 1 (liveness signal only there).
        try:
            import optax as _optax

            plain = _optax.sgd(0.1, momentum=0.9)
            pstate = plain.init(params)
            pstep = _build_step(model, params, batch_stats, plain,
                                pstate, mesh, steps_per_dispatch=spd,
                                data_axes=data_axes)
            pp, pbs, pos = params, batch_stats, pstate
            for _ in range(2):
                pp, pbs, pos, pl = pstep(pp, pbs, pos, images, labels,
                                         jnp.int32(0))
            float(np.asarray(pl)[0])
            t0 = time.perf_counter()
            for _ in range(iters_per_round):
                pp, pbs, pos, pl = pstep(pp, pbs, pos, images, labels,
                                         jnp.int32(0))
            float(np.asarray(pl)[0])
            local_rate = (shape[0] * iters_per_round * spd
                          / (time.perf_counter() - t0))
            dist_step_s = shape[0] / (per_chip * n)
            local_step_s = shape[0] / local_rate
            opt_extra["comm_exposed_s_per_step"] = round(
                max(0.0, dist_step_s - local_step_s), 6)
            opt_extra["compute_only_img_s_per_chip"] = round(
                local_rate / n, 2)
            # The subtraction is a host-side estimate with known bias
            # (two separate runs; allocator/dispatch state differs —
            # docs/benchmarks.md); the capture cross-check below stamps
            # the device-measured value next to it when available.
            opt_extra["comm_exposed_method"] = "subtraction"
        except Exception as exc:  # a side metric must not cost the run
            opt_extra["comm_exposed_error"] = repr(exc)[:200]

    try:
        _stamp_device_truth(opt_extra, spd, prev_analysis)
    except Exception as exc:  # a side metric must not cost the run
        opt_extra["device_truth_error"] = repr(exc)[:200]
    return per_chip, mfu, spd, final_loss, opt_extra


def _stamp_device_truth(opt_extra: dict, spd: int,
                        prev_analysis: dict | None = None) -> None:
    """Cross-check satellite (docs/perf.md): when the sampled-capture
    observatory ran during the timed loop
    (``HOROVOD_PROFILE_EVERY_N_STEPS``), stamp the device-measured
    comm/compute attribution next to the host-side subtraction and warn
    when the two disagree >2x — the subtraction's bias (separate runs,
    different allocator/dispatch state, host wall clock) is exactly
    what the device numbers exist to catch."""
    from horovod_tpu.common import config as _bconfig

    try:
        every = int(_bconfig.get("profile_every_n") or 0)
    except (TypeError, ValueError):
        every = 0
    if every <= 0:
        return
    from horovod_tpu.perf import capture as _pcap

    # Analyses run off-thread and a real capture takes tens of seconds
    # to parse (hundreds of thousands of op events); join them so the
    # stamped extras are deterministic, not a race with process exit.
    _pcap.drain(90.0)
    dev = _pcap.last_analysis()
    if not dev or dev is prev_analysis or not dev.get("totals"):
        # no capture landed DURING THIS MODEL'S loop — an earlier
        # model's analysis must not be stamped as this model's truth
        return
    tot = dev["totals"]
    # NB: the capture spans one trace_step dispatch = spd chained
    # optimizer steps; per-optimizer-step numbers divide by spd.
    for src, dst in (
            ("comm_exposed_s_per_step", "device_comm_exposed_s_per_step"),
            ("comm_hidden_s_per_step", "device_comm_hidden_s_per_step"),
            ("comm_s_per_step", "device_comm_s_per_step"),
            ("compute_s_per_step", "device_compute_s_per_step")):
        if tot.get(src) is not None:
            opt_extra[dst] = round(tot[src] / max(1, spd), 6)
    if tot.get("mfu") is not None:
        opt_extra["device_mfu"] = tot["mfu"]
    if tot.get("overlap_eff") is not None:
        opt_extra["device_overlap_eff"] = tot["overlap_eff"]
    opt_extra["device_profile_step"] = dev.get("captured_step")
    sub = opt_extra.get("comm_exposed_s_per_step")
    devv = opt_extra.get("device_comm_exposed_s_per_step")
    if sub is None or devv is None:
        return
    opt_extra["comm_exposed_method"] = "subtraction+device"
    lo, hi = min(sub, devv), max(sub, devv)
    # Disagreement check only when at least one side is measurably
    # nonzero — at world size 1 both are noise around zero.
    if hi > 1e-4 and (lo <= 0 or hi / max(lo, 1e-9) > 2.0):
        opt_extra["comm_exposed_disagreement"] = round(
            hi / max(lo, 1e-9), 2)
        print(f"[bench] WARNING: comm-exposed estimates disagree >2x: "
              f"subtraction {sub:.6f}s vs device {devv:.6f}s per step "
              f"— trust the device number (docs/benchmarks.md)",
              file=sys.stderr)


def _bench_transformer(long: bool = False) -> dict:
    """Flagship transformer LM tokens/sec on one chip (evidence for the
    long-context path; the ConvNets above are the reference's headline,
    this is ours).  GPT-2-small-ish config at seq 1024; ``long=True``
    runs seq 8192 where the auto heuristic switches to the streaming
    Pallas attention kernel (fp32 score block would be ~6.4 GB)."""
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from horovod_tpu.models.transformer import (TransformerConfig,
                                                init_params,
                                                make_train_step,
                                                shard_params)
    from horovod_tpu.parallel.mesh import make_mesh

    # tiny must not shadow the long-context config: with a leftover
    # BENCH_TRANSFORMER_TINY the long metric would silently record
    # seq-32 toy numbers under the transformer_lm_long_* keys
    if os.environ.get("BENCH_TRANSFORMER_TINY", "") and not long:  # CPU smoke
        cfg = TransformerConfig(vocab=256, d_model=64, n_heads=4,
                                head_dim=16, n_layers=2, d_ff=128,
                                max_seq=64)
        batch, seq = 2, 32
    elif long:
        cfg = TransformerConfig(
            vocab=32768, d_model=768, n_heads=12, head_dim=64,
            n_layers=12, d_ff=3072, max_seq=8192)
        batch, seq = 1, 8192
    else:
        seq = int(os.environ.get("BENCH_TRANSFORMER_SEQ", "1024"))
        cfg = TransformerConfig(
            vocab=32768, d_model=768, n_heads=12, head_dim=64,
            n_layers=12, d_ff=3072, max_seq=seq,
            attn_impl=os.environ.get("BENCH_TRANSFORMER_ATTN") or None)
        # measured best on v5e: b16 = 101k tokens/s (b8 95k, b32 OOM)
        batch = int(os.environ.get("BENCH_TRANSFORMER_BATCH", "16"))
    mesh = make_mesh(dp=1, pp=1, tp=1, sp=1, devices=jax.devices()[:1])
    opt = optax.adamw(3e-4)
    spd = max(1, int(os.environ.get("BENCH_STEPS_PER_DISPATCH", "1")))
    rng = np.random.RandomState(1)
    sh = NamedSharding(mesh, P("dp", "sp"))
    tokens = jax.device_put(jnp.asarray(
        rng.randint(0, cfg.vocab, (batch, seq)), jnp.int32), sh)
    targets = jax.device_put(jnp.asarray(
        rng.randint(0, cfg.vocab, (batch, seq)), jnp.int32), sh)

    def measure(mcfg, rounds=3):
        params = shard_params(
            init_params(np.random.RandomState(0), mcfg, ep=1), mcfg, mesh)
        opt_state = opt.init(params)
        step = make_train_step(mcfg, mesh, opt, steps_per_dispatch=spd)
        for _ in range(3):  # warmup/compile
            params, opt_state, loss = step(params, opt_state, tokens,
                                           targets)
        float(np.asarray(loss))
        rates = []
        for _ in range(rounds):
            t0 = time.perf_counter()
            for _ in range(10):
                params, opt_state, loss = step(params, opt_state, tokens,
                                               targets)
            float(np.asarray(loss))
            rates.append(batch * seq * 10 * spd
                         / (time.perf_counter() - t0))
        return round(float(np.mean(rates)), 0)

    label = (f"d{cfg.d_model} L{cfg.n_layers} h{cfg.n_heads} "
             f"seq{seq} b{batch} adamw")
    key = "transformer_lm_long" if long else "transformer_lm"
    out = {f"{key}_tokens_per_sec": measure(cfg), f"{key}_config": label}

    # On TPU with no impl forced, also measure the attention impl the
    # auto-pick did NOT choose — every driver bench run then lands one
    # (seq, batch) point of the pallas-vs-XLA crossover table
    # (docs/benchmarks.md) for free.
    import dataclasses

    if (not long and jax.devices()[0].platform == "tpu"
            and not os.environ.get("BENCH_TRANSFORMER_ATTN", "")
            and not os.environ.get("BENCH_TRANSFORMER_TINY", "")
            and not _env_bool("BENCH_ATTN_SINGLE")):
        # the library's own pick, so labels can't drift; an explicit
        # impl="pallas" on an untileable seq raises in ring_attention
        from horovod_tpu.parallel.ring_attention import auto_impl

        picked = auto_impl(batch, cfg.n_heads, seq)
        other = "pallas" if picked == "xla" else "xla"
        alt = measure(dataclasses.replace(cfg, attn_impl=other), rounds=2)
        out[f"{key}_attn_{picked}_tokens_per_sec"] = \
            out[f"{key}_tokens_per_sec"]
        out[f"{key}_attn_{other}_tokens_per_sec"] = alt
    return out


def _bench_eager(hvd) -> dict:
    """Eager (negotiated) allreduce dispatch latency vs the compiled
    psum program floor.  At world size 1 this
    measures pure framework overhead (queue + controller + dispatch) —
    the cost the fusion/cache machinery exists to amortize."""
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    # Compiled floor: a real traced-psum program over the world mesh
    # (at size 1 the eager engine's fused_allreduce short-circuits, so
    # build the program explicitly rather than through the engine).
    mesh = hvd.world_mesh()
    psum_prog = jax.jit(shard_map(
        lambda x: jax.lax.psum(x, "hvd"), mesh=mesh, check_vma=False,
        in_specs=P(), out_specs=P()))

    out = {}
    for label, nbytes in (("1kb", 1024), ("1mb", 1 << 20),
                          ("64mb", 64 << 20)):
        x = jnp.ones((nbytes // 4,), jnp.float32)
        jax.block_until_ready(x)
        reps = 20 if nbytes <= (1 << 20) else 5
        hvd.allreduce(x, op=hvd.Sum, name=f"warm.{label}")
        t0 = time.perf_counter()
        for i in range(reps):
            r = hvd.allreduce(x, op=hvd.Sum, name=f"bench.{label}.{i}")
        jax.block_until_ready(r)
        out[f"eager_ms_{label}"] = round(
            (time.perf_counter() - t0) / reps * 1e3, 3)
        jax.block_until_ready(psum_prog(x))
        t0 = time.perf_counter()
        for _ in range(reps):
            r = psum_prog(x)
        jax.block_until_ready(r)
        out[f"compiled_ms_{label}"] = round(
            (time.perf_counter() - t0) / reps * 1e3, 3)
    for label in ("1kb", "1mb", "64mb"):
        c = out[f"compiled_ms_{label}"]
        if c:
            out[f"eager_overhead_x_{label}"] = round(
                out[f"eager_ms_{label}"] / c, 2)

    # Eager allgather: the second-hottest negotiated op.
    # Warm repeats ride the all-kinds response-cache fast path and the
    # negotiation-carried sizes (no size-gather collective), so this
    # latency is the direct evidence for both optimizations.
    x = jnp.ones((256, 1024), jnp.float32)  # 1 MB
    jax.block_until_ready(x)
    hvd.allgather(x, name="warm.ag")
    reps = 20
    t0 = time.perf_counter()
    for _ in range(reps):
        r = hvd.allgather(x, name="bench.ag")
    jax.block_until_ready(r)
    out["eager_allgather_ms_1mb"] = round(
        (time.perf_counter() - t0) / reps * 1e3, 3)
    return out


def _checkpoint_partial(result: dict) -> None:
    """Persist what has been measured so far; survives even a SIGKILL
    later in the run.  Best-effort — never allowed to raise."""
    try:
        with open("bench_partial.json", "w") as f:
            json.dump(result, f)
    except Exception:
        pass


def _parse_args(argv=None):
    """CLI surface for the compression sweep (`--compression int8` vs
    the default): flags export the HOROVOD_* env so every spawned rank
    inherits the mode."""
    import argparse

    p = argparse.ArgumentParser(
        description="horovod_tpu synthetic benchmarks")
    p.add_argument("--compression", default=None,
                   choices=["none", "fp16", "bf16", "int8", "int4",
                            "topk"],
                   help="gradient wire compression for the benched "
                        "train steps (HOROVOD_COMPRESSION) — the mode "
                        "ladder of docs/compression.md")
    p.add_argument("--quant-block-size", type=int, default=None,
                   help="int8/int4 quantization block size "
                        "(HOROVOD_QUANT_BLOCK_SIZE)")
    p.add_argument("--topk-ratio", type=float, default=None,
                   help="top-k sparsification density for "
                        "--compression topk (HOROVOD_TOPK_RATIO, "
                        "default 0.01 = top 1%%)")
    p.add_argument("--adaptive-compression", action="store_true",
                   default=None,
                   help="let the autotuner pick the wire mode per "
                        "overlap bucket from measured comm-exposed "
                        "seconds (HOROVOD_ADAPTIVE_COMPRESSION; "
                        "needs --autotune-style knobs on — see "
                        "docs/compression.md); chosen per-bucket "
                        "modes land in extras")
    p.add_argument("--bucket-compression", default=None,
                   help="explicit per-overlap-bucket wire modes, "
                        "colon-separated "
                        "(HOROVOD_BUCKET_COMPRESSION, e.g. "
                        "'int8:int4:topk')")
    p.add_argument("--sharded-optimizer", action="store_true",
                   default=None,
                   help="ZeRO-1 sharded weight update for the benched "
                        "train steps: reduce-scatter grads, shard-local "
                        "optimizer state, allgather updates "
                        "(HOROVOD_SHARDED_OPTIMIZER)")
    p.add_argument("--zero-stage", type=int, default=None,
                   choices=[0, 1, 2, 3],
                   help="ZeRO stage for the benched train steps "
                        "(HOROVOD_ZERO_STAGE): 1 shard optimizer "
                        "state, 2 + shard-resident gradients, 3 + "
                        "shard-resident parameters with bucket-wise "
                        "prefetched allgather under the forward — see "
                        "docs/zero.md")
    p.add_argument("--zero-prefetch-chunks", type=int, default=None,
                   help="ZeRO-2/3 bucket count "
                        "(HOROVOD_ZERO_PREFETCH_CHUNKS)")
    p.add_argument("--overlap", action="store_true", default=None,
                   help="overlapped chunked gradient communication for "
                        "the benched train steps: bucketed ppermute "
                        "ring schedule instead of one monolithic "
                        "collective (HOROVOD_OVERLAP); also measures "
                        "per-step comm-exposed seconds — see "
                        "docs/overlap.md")
    p.add_argument("--overlap-chunks", type=int, default=None,
                   help="overlap bucket count K "
                        "(HOROVOD_OVERLAP_CHUNKS)")
    p.add_argument("--fused-update", action="store_true", default=None,
                   help="Pallas-fused optimizer tail for the benched "
                        "train steps (HOROVOD_FUSED_UPDATE): unscale + "
                        "momentum update + step in one kernel per flat "
                        "buffer, bit-exact vs the unfused chain — see "
                        "docs/zero.md")
    p.add_argument("--aot-cache-dir", default=None,
                   help="persistent AOT executable cache for the "
                        "run's negotiated programs "
                        "(HOROVOD_AOT_CACHE_DIR); a warm re-run "
                        "stamps aot_cache_hits > 0 — see "
                        "docs/aot-cache.md")
    p.add_argument("--fault-spec", default=None,
                   help="deterministic control-plane fault injection "
                        "for the benched steps (HOROVOD_FAULT_SPEC, "
                        "e.g. 'delay:q/*:50ms') — measures degradation "
                        "under injected faults; see "
                        "docs/fault-tolerance.md")
    p.add_argument("--elastic", action="store_true", default=None,
                   help="elastic survivor-continue mode for the benched "
                        "run (HOROVOD_ELASTIC): re-form count and "
                        "latency land in extras; see docs/elastic.md")
    p.add_argument("--min-ranks", type=int, default=None,
                   help="elastic mode: smallest world size the run may "
                        "shrink to (HOROVOD_MIN_RANKS)")
    p.add_argument("--compare", default=None, metavar="BASELINE_JSON",
                   help="perf-regression gate (docs/perf.md): after the "
                        "run, gate the result against a baseline built "
                        "with `python -m horovod_tpu.perf baseline`; a "
                        "regression beyond the noise-aware threshold "
                        "exits 3 (BENCH_COMPARE_INJECT=metric=factor is "
                        "the CI hook proving the gate trips)")
    p.add_argument("--health-gate", action="store_true",
                   help="exit 4 when any hvd_health_alert fired during "
                        "the run (nonfinite gradients, loss/grad-norm "
                        "divergence sentinels — docs/health.md); pair "
                        "with HOROVOD_HEALTH=1")
    p.add_argument("--autopilot", action="store_true", default=None,
                   help="closed-loop autopilot for the benched run "
                        "(HOROVOD_AUTOPILOT): rank-side rules evaluate "
                        "at elastic commits, and action/rollback counts "
                        "land in extras; see docs/autopilot.md")
    p.add_argument("--compare-nsigma", type=float, default=3.0,
                   help="sigma multiplier for the --compare gate "
                        "threshold: max(nsigma*sigma, rel_floor*mean)")
    p.add_argument("--profile-every-n-steps", type=int, default=None,
                   help="sampled device captures: capture every N-th "
                        "timed step with the jax profiler and stamp "
                        "device-truth comm/compute/MFU into extras "
                        "(HOROVOD_PROFILE_EVERY_N_STEPS)")
    p.add_argument("--profile-dir", default=None,
                   help="rotating capture directory for "
                        "--profile-every-n-steps (HOROVOD_PROFILE_DIR)")
    p.add_argument("--mesh", default=None, metavar="SPEC",
                   help="named data-mesh axis sizes, e.g. 'dp:4,tp:2' "
                        "(HOROVOD_MESH, docs/mesh.md); the gradient "
                        "stack reduces over the dp axis only")
    p.add_argument("--local-sgd-h", type=int, default=None, metavar="H",
                   help="local-SGD/DiLoCo outer-sync period for the "
                        "benched train steps (HOROVOD_LOCAL_SGD_H): "
                        "inner steps reduce over the local/ICI axis "
                        "only, every H-th step exchanges "
                        "pseudo-gradients across slices over DCN — "
                        "H <= 1 keeps synchronous training; see "
                        "docs/local-sgd.md")
    p.add_argument("--outer-lr", type=float, default=None,
                   help="outer Nesterov learning rate of the local-SGD "
                        "sync (HOROVOD_OUTER_LR, default 0.7)")
    p.add_argument("--outer-momentum", type=float, default=None,
                   help="outer Nesterov momentum of the local-SGD "
                        "sync (HOROVOD_OUTER_MOMENTUM, default 0.9)")
    p.add_argument("--sim-ranks", type=int, default=None, metavar="N",
                   help="also run the deterministic control-plane "
                        "fleet simulator at N ranks "
                        "(docs/control-plane.md) and stamp per-round "
                        "latency percentiles + root KV messages/round "
                        "into the extras")
    # unknown flags pass through untouched: the driver may append its
    # own arguments, and a bench that dies on argparse records nothing
    args, _ = p.parse_known_args(argv)
    return args


def main() -> None:
    t_start = time.time()
    args = _parse_args()
    if args.compression is not None:
        os.environ["HOROVOD_COMPRESSION"] = args.compression
    if args.quant_block_size is not None:
        os.environ["HOROVOD_QUANT_BLOCK_SIZE"] = str(args.quant_block_size)
    if args.topk_ratio is not None:
        os.environ["HOROVOD_TOPK_RATIO"] = str(args.topk_ratio)
    if args.adaptive_compression:
        os.environ["HOROVOD_ADAPTIVE_COMPRESSION"] = "1"
    if args.bucket_compression is not None:
        os.environ["HOROVOD_BUCKET_COMPRESSION"] = args.bucket_compression
    if args.sharded_optimizer:
        os.environ["HOROVOD_SHARDED_OPTIMIZER"] = "1"
    if args.zero_stage is not None:
        os.environ["HOROVOD_ZERO_STAGE"] = str(args.zero_stage)
    if args.zero_prefetch_chunks is not None:
        os.environ["HOROVOD_ZERO_PREFETCH_CHUNKS"] = \
            str(args.zero_prefetch_chunks)
    if args.overlap:
        os.environ["HOROVOD_OVERLAP"] = "1"
    if args.overlap_chunks is not None:
        os.environ["HOROVOD_OVERLAP_CHUNKS"] = str(args.overlap_chunks)
    if args.fused_update:
        os.environ["HOROVOD_FUSED_UPDATE"] = "1"
    if args.aot_cache_dir is not None:
        os.environ["HOROVOD_AOT_CACHE_DIR"] = args.aot_cache_dir
    if args.fault_spec is not None:
        os.environ["HOROVOD_FAULT_SPEC"] = args.fault_spec
    if args.elastic:
        os.environ["HOROVOD_ELASTIC"] = "1"
    if args.autopilot:
        os.environ["HOROVOD_AUTOPILOT"] = "1"
    if args.min_ranks is not None:
        os.environ["HOROVOD_MIN_RANKS"] = str(args.min_ranks)
    if args.profile_every_n_steps is not None:
        os.environ["HOROVOD_PROFILE_EVERY_N_STEPS"] = \
            str(args.profile_every_n_steps)
    if args.profile_dir is not None:
        os.environ["HOROVOD_PROFILE_DIR"] = args.profile_dir
    if args.mesh is not None:
        os.environ["HOROVOD_MESH"] = args.mesh
    if args.local_sgd_h is not None:
        os.environ["HOROVOD_LOCAL_SGD_H"] = str(args.local_sgd_h)
    if args.outer_lr is not None:
        os.environ["HOROVOD_OUTER_LR"] = str(args.outer_lr)
    if args.outer_momentum is not None:
        os.environ["HOROVOD_OUTER_MOMENTUM"] = str(args.outer_momentum)
    result: dict = {
        "metric": "resnet50_synthetic_images_per_sec_per_chip",
        "value": None, "unit": "images/sec/chip", "vs_baseline": None,
        "extra": {},
    }
    extra = result["extra"]
    # Record the active compression mode with the numbers: a quantized
    # run's img/s is not comparable to a full-precision one without it.
    extra["compression"] = os.environ.get("HOROVOD_COMPRESSION", "none") \
        or "none"
    if extra["compression"] in ("int8", "int4"):
        extra["quant_block_size"] = int(
            os.environ.get("HOROVOD_QUANT_BLOCK_SIZE", "256") or 256)
    if extra["compression"] == "topk":
        extra["topk_ratio"] = float(
            os.environ.get("HOROVOD_TOPK_RATIO", "0.01") or 0.01)
    # Adaptive per-bucket modes (docs/compression.md): record the
    # request; the CHOSEN vector is stamped after the run (the tuner
    # owns HOROVOD_BUCKET_COMPRESSION at runtime).
    extra["adaptive_compression"] = os.environ.get(
        "HOROVOD_ADAPTIVE_COMPRESSION", "").strip().lower() in (
        "1", "true", "yes", "on")
    if (os.environ.get("HOROVOD_BUCKET_COMPRESSION", "") or "").strip():
        extra["bucket_compression"] = \
            os.environ["HOROVOD_BUCKET_COMPRESSION"].strip()
    # Applied optimizer mode rides the extras like compression does: a
    # sharded run's opt-state bytes are not comparable without it.
    extra["sharded_optimizer"] = os.environ.get(
        "HOROVOD_SHARDED_OPTIMIZER", "").strip().lower() in (
        "1", "true", "yes", "on")
    # ZeRO stage: the same comparability rule — a stage-2/3 run's
    # param/grad/opt-state bytes are the headline, and its img/s runs a
    # different program than the replicated step's.
    try:
        extra["zero_stage"] = int(
            os.environ.get("HOROVOD_ZERO_STAGE", "0") or 0)
    except ValueError:  # a typo'd knob must not cost the result line
        extra["zero_stage"] = None
    if extra["zero_stage"] and extra["zero_stage"] >= 2:
        try:
            extra["zero_prefetch_chunks"] = int(
                os.environ.get("HOROVOD_ZERO_PREFETCH_CHUNKS", "4") or 4)
        except ValueError:
            extra["zero_prefetch_chunks"] = None
    # Mesh axes ride the extras like the zero stage does: a dp:4,tp:2
    # run's per-chip img/s reduces over 4-way dp islands, a different
    # program (and batch math) than the flat world's — never compare
    # across mesh shapes.
    _mesh_spec = (os.environ.get("HOROVOD_MESH", "") or "").strip()
    if _mesh_spec:
        try:
            extra["mesh"] = {
                k.strip(): int(v)
                for k, _, v in (part.partition(":")
                                for part in _mesh_spec.split(","))
                if k.strip()}
        except ValueError:  # a typo'd knob must not cost the result line
            extra["mesh"] = _mesh_spec
    # Overlap mode rides the extras the same way: a number measured
    # with the bucketed ring schedule is a different program than the
    # monolithic collective's, and the chunk count is the knob that
    # trades interleave granularity for collective latency.
    extra["overlap"] = os.environ.get(
        "HOROVOD_OVERLAP", "").strip().lower() in (
        "1", "true", "yes", "on")
    if extra["overlap"]:
        try:
            extra["overlap_chunks"] = int(
                os.environ.get("HOROVOD_OVERLAP_CHUNKS", "4") or 4)
        except ValueError:  # a typo'd knob must not cost the result line
            extra["overlap_chunks"] = None
    # Local-SGD runs are a different TRAINING REGIME, not just a
    # different program: H inner steps pass between cross-slice syncs,
    # so img/s and final_loss are never comparable to synchronous DP
    # without the whole outer-loop config riding the artifact.
    try:
        _ls_h = int(os.environ.get("HOROVOD_LOCAL_SGD_H", "0") or 0)
    except ValueError:  # a typo'd knob must not cost the result line
        _ls_h = 0
    if _ls_h > 1:
        extra["local_sgd_h"] = _ls_h
        for key, env, dflt in (
                ("outer_lr", "HOROVOD_OUTER_LR", 0.7),
                ("outer_momentum", "HOROVOD_OUTER_MOMENTUM", 0.9)):
            try:
                extra[key] = float(os.environ.get(env) or dflt)
            except ValueError:
                extra[key] = None
        extra["local_sgd_compression"] = (
            os.environ.get("HOROVOD_LOCAL_SGD_COMPRESSION", "").strip()
            or os.environ.get("HOROVOD_COMPRESSION", "").strip()
            or "none")
    # A fault-injected run's numbers measure degradation, not capacity:
    # stamp the active spec so they are never compared against clean runs.
    if os.environ.get("HOROVOD_FAULT_SPEC", "").strip():
        extra["fault_spec"] = os.environ["HOROVOD_FAULT_SPEC"].strip()
    # Elastic runs stamp the mode up front; re-form count/latency land
    # at the end of _run (after any re-forms actually happened).
    if os.environ.get("HOROVOD_ELASTIC", "").strip().lower() in (
            "1", "true", "yes", "on"):
        extra["elastic"] = True
        try:
            extra["min_ranks"] = int(
                os.environ.get("HOROVOD_MIN_RANKS", "1") or 1)
        except ValueError:  # a typo'd knob must not cost the result line
            extra["min_ranks"] = None
    # Autopilot runs stamp the mode up front; action/rollback counts
    # land in the finally block (after any interventions happened).
    if os.environ.get("HOROVOD_AUTOPILOT", "").strip().lower() in (
            "1", "true", "yes", "on"):
        extra["autopilot"] = True
    exit_code = 0
    # An outer `timeout` kills with SIGTERM, which skips finally blocks
    # by default — convert it so whatever was measured still prints.
    import signal

    def _on_term(signum, frame):
        raise SystemExit(f"terminated by signal {signum}")

    signal.signal(signal.SIGTERM, _on_term)
    hvd = _open_backend(result)   # exits, line unprinted, without a chip
    try:
        exit_code = _run(hvd, result, extra)
        if args.sim_ranks:
            _stamp_simfleet(extra, args.sim_ranks)
        if args.compare:
            exit_code = _apply_compare(args, result, extra, exit_code)
        if args.health_gate:
            exit_code = _apply_health_gate(extra, exit_code)
    except BaseException as exc:  # even KeyboardInterrupt lands a line
        # whatever failed fails the run — a transformer or kernel
        # failure after a measured ResNet-50 included
        result["error"] = repr(exc)[:300]
        exit_code = 1
        if args.compare:
            # The gate must not be skippable by a late crash: gate
            # whatever was measured (metrics the baseline names but the
            # partial run lacks fail the comparison).
            try:
                exit_code = _apply_compare(args, result, extra,
                                           exit_code)
            except Exception:
                exit_code = exit_code or 3
        if args.health_gate:
            # Same contract: a crash must not skip the health gate —
            # whatever alerts fired before the death still gate.
            try:
                exit_code = _apply_health_gate(extra, exit_code)
            except Exception:
                exit_code = exit_code or 4
    finally:
        extra["bench_seconds"] = round(time.time() - t_start, 1)
        # A run ending by timeout/abort still keeps its partial
        # wall-clock accounting (docs/goodput.md) and its health
        # verdict (docs/health.md): the normal path stamped already
        # (both are idempotent), the crash path stamps here.
        _stamp_goodput(extra)
        _stamp_health(extra)
        _stamp_autopilot(extra)
        _checkpoint_partial(result)
        print(json.dumps(result), flush=True)
    sys.exit(exit_code)


def _stamp_simfleet(extra: dict, n_ranks: int) -> None:
    """Control-plane scaling stamp (docs/control-plane.md): the
    deterministic fleet simulator's per-round latency percentiles and
    root KV messages/round at ``--sim-ranks`` scale ride the extras,
    so a control-plane scaling regression lands in the same
    ``--compare`` gate as data-plane perf."""
    try:
        from horovod_tpu.common import config as _config
        from horovod_tpu.runtime import simfleet

        fanout = max(int(_config.get("control_fanout")), 0)
        trace = simfleet.run_trace(world=n_ranks, fanout=fanout,
                                   rounds=6, seed=0)
        lat = sorted(t["latency_ms"] for t in trace)

        def pct(p: float) -> float:
            return round(lat[min(len(lat) - 1,
                                 int(p / 100.0 * len(lat)))], 3)

        extra["sim_ranks"] = n_ranks
        extra["sim_control_fanout"] = fanout
        extra["sim_root_msgs_per_round"] = trace[-1]["root_ops"]
        extra["sim_round_latency_ms_p50"] = pct(50)
        extra["sim_round_latency_ms_p90"] = pct(90)
        extra["sim_round_latency_ms_p99"] = pct(99)
    except Exception as exc:  # the sim must never cost the result line
        extra["sim_error"] = repr(exc)[:200]


def _apply_health_gate(extra: dict, exit_code: int) -> int:
    """The training-health gate (docs/health.md): a run during which
    any hvd_health_alert fired — nonfinite gradients, loss/grad-norm
    divergence — exits 4 so CI fails the build on a convergence
    regression, not just on byte counts and step times."""
    _stamp_health(extra)
    alerts = int(extra.get("health_alerts") or 0)
    if alerts > 0:
        print(f"[bench] HEALTH GATE: {alerts} health alert(s) fired "
              f"({extra.get('health_active_alerts', [])}) — failing "
              "the run", file=sys.stderr)
        return exit_code or 4
    return exit_code


def _apply_compare(args, result: dict, extra: dict,
                   exit_code: int) -> int:
    """Perf-regression gate (docs/perf.md): compare this run's result
    against a ``python -m horovod_tpu.perf baseline`` file.  Noise
    aware — a metric fails only beyond ``max(nsigma*sigma,
    rel_floor*mean)`` in its bad direction.  Exit 3 on regression, and
    on a broken gate (missing/corrupt baseline): CI misconfiguration
    must fail the build, not silently skip the gate."""
    from horovod_tpu.perf import compare as _cmp

    try:
        baseline = _cmp.load_json(args.compare)
        inject = _cmp.parse_inject(
            os.environ.get("BENCH_COMPARE_INJECT", ""))
        cmp = _cmp.compare_result(result, baseline,
                                  nsigma=args.compare_nsigma,
                                  inject=inject)
    except Exception as exc:
        extra["perf_compare_error"] = repr(exc)[:300]
        print(f"[bench] perf gate broken (baseline {args.compare}): "
              f"{exc!r}", file=sys.stderr)
        return 3
    print(_cmp.format_compare(cmp, args.compare), file=sys.stderr)
    extra["perf_compare"] = {
        "baseline": args.compare, "ok": cmp["ok"],
        "failures": cmp["failures"], "checked": len(cmp["checks"])}
    if cmp.get("injected"):
        extra["perf_compare"]["injected"] = cmp["injected"]
    if not cmp["ok"] and exit_code == 0:
        return 3
    return exit_code


def _metrics_summary(snap: dict) -> dict:
    """Compress an ``hvd.metrics()`` snapshot into the handful of
    numbers a BENCH artifact should carry (docs/metrics.md): the
    step-time histogram, retry/staleness/abort counts, and the
    wire-vs-logical byte totals."""
    m = snap.get("metrics", {})
    out: dict = {}

    def total(name: str) -> float:
        series = m.get(name, {}).get("series") or []
        return round(sum(s.get("value", 0) for s in series), 6)

    hist = m.get("hvd_step_time_seconds", {}).get("series") or []
    if hist and hist[0].get("count"):
        h = hist[0]
        out["step_time_count"] = h["count"]
        out["step_time_sum_s"] = round(h.get("sum", 0.0), 6)
        out["step_time_mean_s"] = round(h["sum"] / h["count"], 6)
        out["step_time_buckets"] = h.get("buckets")
    for key, name in (
            ("wire_retries", "hvd_wire_retries_total"),
            ("wire_timeouts", "hvd_wire_timeouts_total"),
            ("coordinated_aborts", "hvd_coordinated_aborts_total"),
            ("data_wire_bytes", "hvd_data_wire_bytes_total"),
            ("data_logical_bytes", "hvd_data_logical_bytes_total"),
            ("comm_dispatch_s_total", "hvd_comm_dispatch_seconds_total"),
            ("blocked_s_total", "hvd_handle_wait_seconds_total"),
            # cold-path speed (docs/aot-cache.md): program-compile wall
            # seconds and the AOT executable cache's hit/miss counters
            ("compile_s", "hvd_compile_seconds_total"),
            ("aot_cache_hits", "hvd_aot_cache_hits_total"),
            ("aot_cache_misses", "hvd_aot_cache_misses_total"),
            ("aot_cache_evictions", "hvd_aot_cache_evictions_total")):
        v = total(name)
        if v:
            out[key] = v
    # ICI-vs-DCN wire split (docs/local-sgd.md): the axis label on
    # hvd_data_wire_bytes_total separates intra-slice bytes from
    # cross-slice bytes — under local-SGD the headline is the cross
    # share collapsing ~H-fold (unlabelled world-scope series carry
    # no axis key and stay out of the split).
    for s in (m.get("hvd_data_wire_bytes_total", {}).get("series")
              or []):
        ax = (s.get("labels") or {}).get("axis")
        if ax:
            k2 = f"data_wire_bytes_{ax}"
            out[k2] = round(out.get(k2, 0) + s.get("value", 0), 6)
    # Achieved byte cut of the active wire modes (docs/compression.md):
    # wire/logical over the run's data-plane responses — the honest
    # compression-ratio number (int4 packed bytes and topk index+value
    # payloads counted as such), gateable via --compare.
    if out.get("data_logical_bytes"):
        out["wire_compression_ratio"] = round(
            out.get("data_wire_bytes", out["data_logical_bytes"])
            / out["data_logical_bytes"], 6)
    resid = (m.get("hvd_compression_residual_ratio", {}).get("series")
             or [])
    if resid:
        out["compression_residual_ratio_max"] = round(
            max(s.get("value", 0) for s in resid), 6)
    for s in (m.get("hvd_step_phase_seconds_total", {}).get("series")
              or []):
        out[f"step_{s['labels'].get('phase')}_s_total"] = round(
            s.get("value", 0), 6)
    stale = (m.get("hvd_heartbeat_staleness_seconds", {}).get("series")
             or [])
    if stale:
        out["heartbeat_staleness_max_s"] = round(
            max(s.get("value", 0) for s in stale), 3)
    # Device-truth gauges from the sampled-capture observatory
    # (docs/perf.md): the xplane-measured split of the last sampled
    # step, so device evidence rides the artifact like the host-side
    # step histogram does.
    for key, name in (
            ("device_compute_s", "hvd_device_compute_seconds"),
            ("device_comm_s", "hvd_device_comm_seconds"),
            ("device_comm_hidden_s", "hvd_device_comm_hidden_seconds"),
            ("device_comm_exposed_s", "hvd_device_comm_exposed_seconds"),
            ("mfu", "hvd_mfu")):
        series = m.get(name, {}).get("series") or []
        if series:
            out[key] = round(series[0].get("value", 0), 6)
    caps = total("hvd_profile_captures_total")
    if caps:
        out["profile_captures"] = caps
        fails = total("hvd_profile_capture_failures_total")
        if fails:
            out["profile_capture_failures"] = fails
    return out


def _open_backend(result: dict):
    """The one place this process opens a backend.  The run wants a
    TPU: when JAX's default backend is anything else and the CPU was
    not asked for (``JAX_PLATFORMS=cpu`` / ``HOROVOD_PLATFORM=cpu``)
    the process exits 2 here, before any metric line can be printed.
    The device rides the result line itself, so a CPU run says
    ``"platform": "cpu"`` on the line that carries its numbers."""
    import jax

    import horovod_tpu as hvd
    from horovod_tpu.common.platform import cpu_asked_for

    t_init = time.perf_counter()
    hvd.init()
    result["extra"]["init_seconds"] = round(
        time.perf_counter() - t_init, 3)
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not cpu_asked_for():
        print(f"[bench] no TPU found: JAX's default backend is "
              f"{dev.platform!r}.  Nothing was measured; a CPU run has "
              "to be asked for with JAX_PLATFORMS=cpu.", file=sys.stderr)
        sys.exit(2)
    result.update(platform=dev.platform, device_kind=dev.device_kind,
                  device_count=len(jax.devices()))
    return hvd


def _run(hvd, result: dict, extra: dict) -> int:
    if os.environ.get("BENCH_SIGTERM_TEST_SLEEP", ""):  # test hook
        time.sleep(int(os.environ["BENCH_SIGTERM_TEST_SLEEP"]))

    from horovod_tpu.models.inception import InceptionV3
    from horovod_tpu.models.resnet import ResNet50
    from horovod_tpu.models.vgg import VGG16

    on_tpu = result["platform"] == "tpu"
    extra["platform"] = result["platform"]
    extra["device_kind"] = result["device_kind"]

    if on_tpu:
        rn_batch = int(os.environ.get("BENCH_BATCH_PER_CHIP", "256"))
        vgg_batch = int(os.environ.get("BENCH_VGG_BATCH", "128"))
        inc_batch = int(os.environ.get("BENCH_INCEPTION_BATCH", "128"))
        specs = {
            "resnet50": (ResNet50, 224, rn_batch, 10, 3),
            "vgg16": (VGG16, 224, vgg_batch, 10, 2),
            "inception3": (InceptionV3, 299, inc_batch, 10, 2),
        }
        default_models = ",".join(specs)
    else:  # asked-for CPU run: tiny but real (vgg exercises dropout)
        # 96px: the CPU number is a liveness signal, not a measurement
        # (see docs/benchmarks.md), and 224px is mostly compile time.
        # resnet runs 8 timed steps (~7 s), not 2: the perf gate's
        # goodput_ratio needs a compute share large enough that ±30%
        # compile-wall jitter on the 1-core image can't swing the
        # ratio past its band (docs/goodput.md).
        specs = {
            "resnet50": (ResNet50, 96, 4, 8, 1),
            "vgg16": (VGG16, 32, 2, 2, 1),
            "inception3": (InceptionV3, 299, 1, 1, 1),
        }
        default_models = "resnet50"

    wanted = [m.strip() for m in os.environ.get(
        "BENCH_MODELS", default_models).split(",")
        if m.strip() not in ("", "none")]
    unknown = [m for m in wanted if m not in specs]
    if unknown:  # a typo must not read as "measure nothing, exit 0"
        raise ValueError(f"BENCH_MODELS names unknown model(s) {unknown}; "
                         f"known: {sorted(specs)} (or 'none')")
    force_fail = set(
        m.strip() for m in os.environ.get("BENCH_FORCE_FAIL", "").split(",")
        if m.strip())

    # Dispatch-latency microbench runs FIRST, on a fresh backend:
    # after the model benches, leftover allocator/dispatch state
    # inflates the compiled-psum floor it is compared against.
    skip_side = _env_bool("BENCH_SKIP_SIDE")
    if (on_tpu and not skip_side) or os.environ.get("BENCH_EAGER", ""):
        extra.update(_bench_eager(hvd))
        _checkpoint_partial(result)

    for mname in wanted:
        ctor, img, batch, iters, rounds = specs[mname]
        if mname in force_fail:   # test hook: a failing model
            raise RuntimeError(
                f"BENCH_FORCE_FAIL: simulated {mname} failure")
        per_chip, mfu, used_spd, final_loss, opt_extra = _bench_model(
            hvd, ctor, img, batch, iters, rounds,
            want_flops=(mname == "resnet50"))
        if mname == "resnet50":
            result["value"] = round(per_chip, 2)
            if on_tpu:  # a CPU rate against a chip target says nothing
                result["vs_baseline"] = round(
                    per_chip / A100_IMG_S_PER_CHIP, 4)
            extra["resnet50_spd"] = used_spd
            if mfu is not None:
                extra["resnet50_mfu"] = round(mfu, 4)
        else:
            extra[f"{mname}_img_s_per_chip"] = round(per_chip, 2)
        # training-health signal next to the throughput: a compression
        # mode that wrecks optimization shows up as a NaN/divergent
        # loss here, not just in accuracy-off-a-cliff a week later
        extra[f"{mname}_final_loss"] = round(final_loss, 4)
        for k_, v_ in opt_extra.items():
            extra[f"{mname}_{k_}"] = v_
        try:
            # Analytic achieved-compression ratio of this model's
            # gradient payload under the active wire modes — the same
            # payload_wire_bytes accounting the autotuner and the
            # hvd_data_wire_bytes_total metric use, so a regression in
            # int4/topk byte counting trips the --compare gate even on
            # a world-1 CPU run (where no negotiated wire exists to
            # measure).  1.0 under mode none, deterministic.
            from horovod_tpu.ops import compression as _compr

            gb = int(opt_extra.get("grad_bytes_per_chip") or 0)
            if gb > 0:
                n_el = gb // 4
                wire = _compr.fused_wire_bytes(
                    n_el, 4, _compr.effective_bucket_modes(),
                    block=int(os.environ.get(
                        "HOROVOD_QUANT_BLOCK_SIZE", "256") or 256),
                    ratio=float(os.environ.get(
                        "HOROVOD_TOPK_RATIO", "0.01") or 0.01),
                    world=max(1, hvd.size()))
                extra[f"{mname}_wire_compression_ratio"] = round(
                    wire / (n_el * 4), 6)
        except Exception:
            pass
        _checkpoint_partial(result)

    if (on_tpu and not skip_side) or os.environ.get("BENCH_TRANSFORMER", ""):
        extra.update(_bench_transformer())
        _checkpoint_partial(result)
    if ((on_tpu and not skip_side)
            or os.environ.get("BENCH_TRANSFORMER_LONG", "")):
        # long-context: pallas streaming path
        extra.update(_bench_transformer(long=True))
        _checkpoint_partial(result)

    if extra.get("elastic"):
        # Re-form observability next to the throughput: a run that
        # shrank mid-bench is not comparable to a full-size one, and
        # the re-form latency is the headline number of the elastic
        # subsystem itself (docs/elastic.md).
        try:
            from horovod_tpu import elastic as _elastic

            es = _elastic.stats()
            extra["elastic_generation"] = es["generation"]
            extra["elastic_reforms"] = es["reforms"]
            if es.get("preempt_drains"):
                # Graceful drains the run absorbed: a bench that shed
                # announced hosts mid-run kept training, but its
                # numbers carry that context (docs/fault-tolerance.md).
                extra["elastic_preempt_drains"] = es["preempt_drains"]
            if es["last_reform_s"] is not None:
                extra["elastic_last_reform_s"] = es["last_reform_s"]
                extra["elastic_total_reform_s"] = es["total_reform_s"]
        except Exception:
            pass

    try:
        # Fleet-health numbers ride every artifact (docs/metrics.md).
        summary = _metrics_summary(hvd.metrics())
        if summary:
            extra["metrics_summary"] = summary
    except Exception:
        pass
    # Wall-clock attribution (docs/goodput.md): goodput ratio, phase
    # breakdown, dominant bottleneck — the perf gate's goodput_ratio
    # metric comes from here.
    _stamp_goodput(extra)
    # Training-health evidence (docs/health.md): grad_norm_final /
    # nonfinite_steps / health_alerts ride every artifact.
    _stamp_health(extra)
    try:
        # AOT executable cache evidence (docs/aot-cache.md): hit/miss/
        # eviction counts and the cold-vs-warm compile-seconds split of
        # THIS run, so a warm artifact is distinguishable from a cold
        # one at a glance.
        from horovod_tpu.runtime import aot_cache as _aot

        s_ = _aot.stats()
        if _aot.enabled() or s_["misses"]:
            extra["aot_cache_hits"] = s_["hits"]
            extra["aot_cache_misses"] = s_["misses"]
            extra["aot_cache_evictions"] = s_["evictions"]
            extra["compile_s_cold"] = s_["compile_s_cold"]
            extra["compile_s_warm"] = s_["compile_s_warm"]
    except Exception:
        pass
    try:
        # The CHOSEN per-bucket modes: under adaptive compression the
        # tuner rewrites HOROVOD_BUCKET_COMPRESSION at runtime, so the
        # post-run knob value IS the converged vector (empty = every
        # bucket stayed on the uniform HOROVOD_COMPRESSION mode).
        from horovod_tpu.common import config as _bcfg

        chosen = str(_bcfg.get("bucket_compression")).strip()
        if chosen or extra.get("adaptive_compression"):
            extra["chosen_bucket_compression"] = chosen
    except Exception:
        pass

    return 0


if __name__ == "__main__":
    main()
