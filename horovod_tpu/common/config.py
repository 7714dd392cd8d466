"""Config / knob system.

The reference exposes every runtime knob through three equivalent
surfaces that all converge on ``HOROVOD_*`` env vars (SURVEY §5.6):
env vars read by the C++ core (reference ``common.h:61-88``,
``operations.cc:403-500``), ``horovodrun`` CLI flags mapped via
``config_parser.set_env_from_args`` (reference
``run/common/util/config_parser.py:141-190``), and a YAML config file
with CLI-override precedence.  This module is the single registry those
three surfaces share in the TPU build.
"""

from __future__ import annotations

import json
import os
from dataclasses import dataclass
from typing import Any, Callable

# ---------------------------------------------------------------------------
# Knob registry
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class Knob:
    env: str            # HOROVOD_* env var (reference-compatible name)
    default: Any
    parse: Callable[[str], Any]
    cli: str | None = None       # horovodrun-style CLI flag
    config_key: str | None = None  # dotted key in the config file
    help: str = ""


def _parse_bool(s: str) -> bool:
    return s.strip().lower() in ("1", "true", "yes", "on")


_KNOBS: dict[str, Knob] = {}


def _register(name: str, knob: Knob) -> None:
    _KNOBS[name] = knob


# Names follow the reference env vars (common.h:61-88) so existing Horovod
# deployment configs carry over unchanged.
_register("fusion_threshold", Knob(
    "HOROVOD_FUSION_THRESHOLD", 64 * 1024 * 1024, int,
    cli="--fusion-threshold-mb", config_key="tensor_fusion.threshold",
    help="Eager-path fusion buffer threshold in bytes (default 64MB, "
         "reference operations.cc:419).  Must agree on every rank "
         "(validated at the round-0 handshake: fusion decides the "
         "fused buffer shapes every rank must build identically)."))
_register("cycle_time_ms", Knob(
    "HOROVOD_CYCLE_TIME", 5.0, float,
    cli="--cycle-time-ms", config_key="tensor_fusion.cycle_time",
    help="Background-loop cycle time in ms (default 5, reference "
         "operations.cc:427)."))
_register("cache_capacity", Knob(
    "HOROVOD_CACHE_CAPACITY", 1024, int,
    cli="--cache-capacity", config_key="cache.capacity",
    help="Response-cache capacity; 0 disables (reference "
         "response_cache.h:44).  Must agree on every rank (validated "
         "at the round-0 handshake: the cache fast path decides which "
         "rounds skip negotiation, so a divergence desynchronizes the "
         "control plane)."))
_register("ragged_allgather", Knob(
    "HOROVOD_RAGGED_ALLGATHER", "auto", str,
    cli="--ragged-allgather", config_key="ragged_allgather",
    help="Ragged-allgather strategy: auto (bandwidth heuristic), "
         "psum (scatter into exact offsets + one psum, bytes ~ "
         "2*sum(sizes)), pad (pad to max + trim, bytes ~ max*nranks). "
         " Must agree on every rank (validated at the round-0 "
         "handshake: the strategy picks which collective program a "
         "ragged gather runs)."))
_register("hierarchical_allreduce", Knob(
    "HOROVOD_HIERARCHICAL_ALLREDUCE", False, _parse_bool,
    cli="--hierarchical-allreduce", config_key="hierarchical.allreduce",
    help="Two-level (intra-slice ICI + cross-slice DCN) allreduce.  "
         "Must agree on every rank (validated at the round-0 "
         "handshake: a rank running the two-level program while "
         "another runs the flat one deadlocks in mismatched "
         "collectives)."))
_register("hierarchical_allgather", Knob(
    "HOROVOD_HIERARCHICAL_ALLGATHER", False, _parse_bool,
    cli="--hierarchical-allgather", config_key="hierarchical.allgather",
    help="Two-level allgather.  Must agree on every rank (validated "
         "at the round-0 handshake, like hierarchical allreduce)."))
_register("hierarchical_local_size", Knob(
    "HOROVOD_HIERARCHICAL_LOCAL_SIZE", 0, int,
    cli="--hierarchical-local-size", config_key="hierarchical.local_size",
    help="Override the detected local group size for hierarchical "
         "collectives (0 = use launcher/hostname topology).  Must "
         "agree on every rank when a hierarchical mode is on "
         "(validated at the round-0 handshake: it reshapes the "
         "ICI/DCN axis split every rank's program is built from)."))
_register("local_sgd_h", Knob(
    "HOROVOD_LOCAL_SGD_H", 0, int,
    cli="--local-sgd-h", config_key="local_sgd.h",
    help="Outer-sync period H of the local-SGD/DiLoCo training regime "
         "(docs/local-sgd.md): 0/1 = off (every step fully "
         "synchronous); H >= 2 makes inner steps reduce over the "
         "local/ICI axis only and exchanges pseudo-gradients across "
         "slices (DCN) every H-th step.  Must agree on every rank "
         "(validated at the round-0 handshake: a rank running inner "
         "ICI-only programs while another reduces across slices "
         "deadlocks in mismatched collectives)."))
_register("outer_lr", Knob(
    "HOROVOD_OUTER_LR", 0.7, float,
    cli="--outer-lr", config_key="local_sgd.outer_lr",
    help="Outer-optimizer learning rate applied to the cross-slice "
         "pseudo-gradient at each local-SGD outer sync (DiLoCo's "
         "published sweet spot is ~0.7 with Nesterov momentum).  Must "
         "agree on every rank when local-SGD is active (validated at "
         "the round-0 handshake: it selects the parameter trajectory "
         "every slice must walk identically after a sync)."))
_register("outer_momentum", Knob(
    "HOROVOD_OUTER_MOMENTUM", 0.9, float,
    cli="--outer-momentum", config_key="local_sgd.outer_momentum",
    help="Nesterov momentum of the local-SGD outer optimizer "
         "(docs/local-sgd.md).  Must agree on every rank when "
         "local-SGD is active (validated at the round-0 handshake, "
         "like the outer learning rate)."))
_register("local_sgd_compression", Knob(
    "HOROVOD_LOCAL_SGD_COMPRESSION", "", str,
    cli="--local-sgd-compression", config_key="local_sgd.compression",
    help="Wire compression for the cross-slice pseudo-gradient hop of "
         "the local-SGD outer sync: none | fp16 | bf16 | int8 | int4 "
         "| topk (empty = inherit HOROVOD_COMPRESSION).  Only the DCN "
         "hop is compressed — inner ICI reductions stay full "
         "precision.  Must agree on every rank when local-SGD is "
         "active (validated at the round-0 handshake: the mode picks "
         "which collective program the outer sync runs)."))
_register("mesh", Knob(
    "HOROVOD_MESH", "", str,
    cli="--mesh", config_key="mesh.axes",
    help="Named data-mesh axis sizes as 'axis:size' pairs, e.g. "
         "'dp:4,tp:2' (axes dp/pp/tp/sp; empty = flat world).  When "
         "set, every gradient collective, the optimizer, and the ZeRO "
         "shard layouts reduce/scatter over the dp axis only, so "
         "params sharded over tp/pp/sp islands are never averaged "
         "across them; see docs/mesh.md.  Must agree on every rank "
         "(validated at the round-0 handshake: a rank reducing over a "
         "different axis split runs a different collective program and "
         "deadlocks or corrupts tp-sharded params)."))
_register("compression", Knob(
    "HOROVOD_COMPRESSION", "none", str,
    cli="--compression", config_key="compression.mode",
    help="Gradient wire compression for allreduce: none | fp16 | bf16 "
         "(dtype casts, reference Compression API) | int8 "
         "(EQuARX-style block-scaled quantization with shared per-block "
         "scales; under hierarchical allreduce only the cross-slice DCN "
         "hop is quantized).  Applies as the DistributedOptimizer "
         "default and to the negotiated eager data plane; must agree "
         "on every rank (validated at the round-0 handshake)."))
_register("quant_block_size", Knob(
    "HOROVOD_QUANT_BLOCK_SIZE", 256, int,
    cli="--quant-block-size", config_key="compression.quant_block_size",
    help="Elements per int8 quantization block (one fp32 scale each; "
         "default 256).  Multiples of 128 keep the Pallas "
         "quantize/dequantize kernels lane-aligned on TPU.  Must "
         "agree on every rank when a block-quantized mode is active "
         "(validated at the round-0 handshake: block size sets the "
         "scale-sidecar shapes on the wire)."))
_register("sharded_optimizer", Knob(
    "HOROVOD_SHARDED_OPTIMIZER", False, _parse_bool,
    cli="--sharded-optimizer", config_key="optimizer.sharded",
    help="ZeRO-1 sharded weight update: DistributedOptimizer "
         "reduce-scatters gradients, runs the optimizer step on the "
         "rank-local 1/world_size shard (optimizer state memory drops "
         "~world_size-fold), and allgathers the updated parameter "
         "shards.  Must agree on every rank (validated at the round-0 "
         "handshake): one rank reduce-scattering while another "
         "allreduces would deadlock.  See docs/zero.md."))
_register("zero_stage", Knob(
    "HOROVOD_ZERO_STAGE", 0, int,
    cli="--zero-stage", config_key="optimizer.zero_stage",
    help="ZeRO sharding stage for DistributedOptimizer (0-3, default "
         "0).  0: replicated update.  1: weight-update sharding "
         "(optimizer state lives as rank-local 1/world shards; same as "
         "HOROVOD_SHARDED_OPTIMIZER=1).  2: additionally keeps "
         "gradients shard-resident — the fused gradient buffers are "
         "reduce-scattered bucket-by-bucket and no full-size fused "
         "buffer ever materializes.  3: additionally shards the "
         "parameters themselves (1/world flat shards between steps, "
         "bucket-wise allgather prefetched under the forward pass; "
         "see hvd.zero3_shard_params / hvd.zero3_full_params).  Must "
         "agree on every rank (validated at the round-0 handshake).  "
         "See docs/zero.md."))
_register("zero_prefetch_chunks", Knob(
    "HOROVOD_ZERO_PREFETCH_CHUNKS", 4, int,
    cli="--zero-prefetch-chunks", config_key="optimizer.zero_prefetch_chunks",
    help="Bucket count for the ZeRO-2/3 bucket pipelines (default 4; "
         "autotuned under HOROVOD_AUTOTUNE when zero_stage >= 3, "
         "bounds 1..32): stage-2 gradients reduce-scatter in this many "
         "barrier-separated buckets, and the stage-3 forward gathers "
         "parameters bucket-wise so bucket k+1's allgather rides under "
         "bucket k's layer math.  Must agree on every rank when any "
         "optimizer runs stage >= 2 (bucket shapes are part of the "
         "negotiated wire).  The round-0 handshake validates it when "
         "HOROVOD_ZERO_STAGE >= 2; a job that selects the stage only "
         "via the zero_stage= optimizer argument should set the env "
         "knob too — like a per-call overlap=True, argument-driven "
         "modes are outside the handshake's view (a divergence "
         "surfaces as a wire timeout naming the mismatched bucket "
         "tensors, not a fail-fast)."))
_register("overlap", Knob(
    "HOROVOD_OVERLAP", False, _parse_bool,
    cli="--overlap", config_key="overlap.enabled",
    help="Overlapped chunked gradient communication: fused gradient "
         "buffers split into HOROVOD_OVERLAP_CHUNKS buckets riding a "
         "software-pipelined ppermute ring reduce-scatter/allgather "
         "schedule instead of one monolithic end-of-step collective, "
         "with lax.optimization_barrier between buckets so XLA's "
         "latency-hiding scheduler can float bucket i+1's transfer "
         "under bucket i's compute.  Applies to the in-trace "
         "DistributedOptimizer path and the negotiated eager data "
         "plane; must agree on every rank (validated at the round-0 "
         "handshake: one rank ring-permuting while another psums would "
         "deadlock).  See docs/overlap.md."))
_register("overlap_chunks", Knob(
    "HOROVOD_OVERLAP_CHUNKS", 4, int,
    cli="--overlap-chunks", config_key="overlap.chunks",
    help="Bucket count K for the overlap schedule (default 4; "
         "autotuned under HOROVOD_AUTOTUNE, bounds 1..32).  More "
         "chunks interleave compute and communication more finely but "
         "pay more per-collective latency; interacts with "
         "HOROVOD_FUSION_THRESHOLD on the eager path (bucket bytes ~= "
         "fused buffer bytes / K).  Must agree on every rank."))
_register("quant_pallas", Knob(
    "HOROVOD_QUANT_PALLAS", "auto", str,
    cli="--quant-pallas", config_key="compression.quant_pallas",
    help="Pallas kernel selection for the quantize/dequantize codecs "
         "AND the fused optimizer tail (HOROVOD_FUSED_UPDATE): auto "
         "(Pallas on TPU, jnp elsewhere), 1 (force Pallas; interpret "
         "mode off-TPU — test hook), 0 (force the jnp path)."))
_register("topk_ratio", Knob(
    "HOROVOD_TOPK_RATIO", 0.01, float,
    cli="--topk-ratio", config_key="compression.topk_ratio",
    help="Top-k sparsification density: each payload (or overlap "
         "bucket) transmits max(1, round(ratio * n_elems)) "
         "(index, value) pairs, the rest accumulating in the "
         "error-feedback residual (default 0.01 = top 1%%).  Must "
         "agree on every rank when the topk mode is active (payload "
         "shapes are part of the negotiated wire; validated at the "
         "round-0 handshake)."))
_register("bucket_compression", Knob(
    "HOROVOD_BUCKET_COMPRESSION", "", str,
    cli="--bucket-compression", config_key="compression.bucket_modes",
    help="Per-overlap-bucket wire modes, colon-separated (e.g. "
         "'int8:int4:topk', cycled over the K buckets); empty (default) "
         "means every bucket rides HOROVOD_COMPRESSION.  Normally "
         "owned by the adaptive autotuner "
         "(HOROVOD_ADAPTIVE_COMPRESSION); settable by hand for "
         "experiments.  Must agree on every rank (validated at the "
         "round-0 handshake).  See docs/compression.md."))
_register("adaptive_compression", Knob(
    "HOROVOD_ADAPTIVE_COMPRESSION", False, _parse_bool,
    cli="--adaptive-compression", config_key="compression.adaptive",
    help="Let the GP autotuner (HOROVOD_AUTOTUNE) choose the wire "
         "compression mode per overlap bucket from measured "
         "comm-exposed seconds (device truth when a sampled capture "
         "is live, the step-span subtraction otherwise), walking the "
         "none->bf16->fp16->int8->int4->topk ladder under the "
         "bounded-loss guardrail "
         "(HOROVOD_COMPRESSION_MAX_RESIDUAL_RATIO).  Must agree on "
         "every rank (validated at the round-0 handshake: a rank "
         "without it would never apply the tuner's mode broadcasts "
         "and drift into mismatched programs at the next retrace).  "
         "See docs/compression.md and docs/autotune.md."))
_register("compression_guard_ratio", Knob(
    "HOROVOD_COMPRESSION_MAX_RESIDUAL_RATIO", 0.5, float,
    cli="--compression-max-residual-ratio",
    config_key="compression.max_residual_ratio",
    help="Bounded-loss guardrail for adaptive compression: when a "
         "bucket's reported error-feedback residual-to-gradient norm "
         "ratio exceeds this ceiling, the tuner pins that bucket back "
         "to int8 instead of int4/topk (0 disables the aggressive "
         "modes entirely for reported buckets)."))
_register("timeline", Knob(
    "HOROVOD_TIMELINE", "", str,
    cli="--timeline-filename", config_key="profiling.timeline_filename",
    help="Chrome-trace timeline output path (rank 0 writes; reference "
         "operations.cc:403-411)."))
_register("timeline_mark_cycles", Knob(
    "HOROVOD_TIMELINE_MARK_CYCLES", False, _parse_bool,
    cli="--timeline-mark-cycles", config_key="profiling.timeline_mark_cycles",
    help="Emit background-cycle markers into the timeline."))
_register("jax_profiler", Knob(
    "HOROVOD_TIMELINE_JAX_PROFILER", "", str,
    cli="--jax-profiler-dir", config_key="profiling.jax_profiler_dir",
    help="Directory for device-side jax.profiler capture (xplane, "
         "TensorBoard profile plugin); every rank writes rank<k>/. "
         "The TPU analog of the reference's CUDA-event op timings."))
_register("profile_every_n", Knob(
    "HOROVOD_PROFILE_EVERY_N_STEPS", 0, int,
    cli="--profile-every-n-steps", config_key="profiling.every_n_steps",
    help="Sampled continuous device capture (docs/perf.md): every N-th "
         "hvd.trace_step() span is captured with the jax profiler into "
         "a rotating per-rank directory (HOROVOD_PROFILE_DIR), "
         "analyzed in the background by the stdlib xplane reader, and "
         "published as hvd_device_*/hvd_mfu gauges on the metrics "
         "plane.  0 (default) disables.  Mutually exclusive with the "
         "whole-run HOROVOD_TIMELINE_JAX_PROFILER capture, which owns "
         "the profiler when set."))
_register("profile_dir", Knob(
    "HOROVOD_PROFILE_DIR", "", str,
    cli="--profile-dir", config_key="profiling.profile_dir",
    help="Root directory for sampled step captures "
         "(HOROVOD_PROFILE_EVERY_N_STEPS); each rank writes "
         "rank<k>/step<n>/ with the raw xplane capture plus its "
         "analysis.json.  Empty (default) means ./hvd_profile.  "
         "Inspect with `python -m horovod_tpu.perf report <dir>`."))
_register("profile_keep", Knob(
    "HOROVOD_PROFILE_KEEP", 4, int,
    cli="--profile-keep", config_key="profiling.keep",
    help="How many sampled step captures each rank keeps "
         "(oldest rotated out), bounding disk use on long runs."))
_register("flight_dir", Knob(
    "HOROVOD_FLIGHT_DIR", "", str,
    cli="--flight-dir", config_key="flight.dir",
    help="Directory for flight-recorder dumps (docs/flight-recorder.md)."
         "  Every rank keeps a crash-surviving in-memory ring of runtime"
         " events (rounds, wire, collectives, heartbeats, stalls,"
         " elastic generations) and atomically dumps it here as JSONL on"
         " a coordinated abort, RanksDownError, SIGTERM/SIGABRT, an"
         " elastic re-form, or hvd.dump_flight_recorder().  Merge and"
         " analyze with `python -m horovod_tpu.trace merge <dir>`."
         "  Empty (default) disables dumping; the in-memory ring still"
         " records."))
_register("flight_events", Knob(
    "HOROVOD_FLIGHT_EVENTS", 4096, int,
    cli="--flight-events", config_key="flight.events",
    help="Flight-recorder ring capacity in events (default 4096; 0"
         " disables recording).  Memory stays bounded at this many"
         " entries regardless of run length — old events are"
         " overwritten in place."))
_register("goodput_dir", Knob(
    "HOROVOD_GOODPUT_DIR", "", str,
    cli="--goodput-dir", config_key="goodput.dir",
    help="Directory for per-rank goodput ledger dumps "
         "(goodput-r<k>-g<g>.json, written on shutdown and on every "
         "abort/fatal-signal flight dump).  Empty (default) falls back "
         "to HOROVOD_FLIGHT_DIR so wall-clock attribution lands next "
         "to the postmortem rings; with neither set, dumps are off "
         "(the in-memory ledger and its gauges still run).  Report "
         "with `python -m horovod_tpu.perf goodput <dir>`.  See "
         "docs/goodput.md."))
_register("goodput_slo", Knob(
    "HOROVOD_GOODPUT_SLO", 0.0, float,
    cli="--goodput-slo", config_key="goodput.slo",
    help="Fleet goodput SLO in (0, 1]: when the sliding-window fleet "
         "goodput (useful compute seconds / world x wall-clock) falls "
         "below it, the launcher aggregate raises "
         "hvd_goodput_alert{reason=<dominant phase>}=1 with the "
         "error-budget burn rate beside it.  0 (default) disarms the "
         "alert; the goodput gauges publish either way.  See "
         "docs/goodput.md."))
_register("goodput_window", Knob(
    "HOROVOD_GOODPUT_WINDOW_SECONDS", 300.0, float,
    cli="--goodput-window-seconds", config_key="goodput.window",
    help="Sliding window for the fleet goodput / dominant-bottleneck / "
         "SLO-burn computation on the launcher aggregate (default "
         "300 s).  Shorter windows react faster but alert on transient "
         "dips; pair with the SLO like a burn-rate alert policy.  See "
         "docs/goodput.md."))
_register("goodput_unattributed_max", Knob(
    "HOROVOD_GOODPUT_UNATTRIBUTED_MAX", 0.10, float,
    cli="--goodput-unattributed-max", config_key="goodput.unattributed_max",
    help="Honest-accounting ceiling: when the goodput ledger's "
         "unattributed share of wall-clock exceeds this ratio "
         "(default 0.10), the rank logs one warning — an "
         "uninstrumented phase is eating the run and the ledger's "
         "other numbers understate it.  0 disables the warning; the "
         "hvd_goodput_unattributed_ratio gauge publishes regardless.  "
         "See docs/goodput.md."))
_register("data_wait_min", Knob(
    "HOROVOD_DATA_WAIT_MIN_SECONDS", 0.0, float,
    cli="--data-wait-min-seconds", config_key="goodput.data_wait_min",
    help="Noise floor for hvd.data_wait() / hvd.wrap_data_loader "
         "spans: waits shorter than this many seconds are not "
         "recorded (they stay attributed to compute).  Default 0 "
         "records every span; raise it when a fast in-memory iterator "
         "makes the per-next() timing overhead itself the signal.  "
         "See docs/goodput.md."))
_register("health", Knob(
    "HOROVOD_HEALTH", False, _parse_bool,
    cli="--health", config_key="health.enabled",
    help="Training-health plane (docs/health.md): in-trace numerics "
         "stat taps in DistributedOptimizer (all ZeRO stages, overlap "
         "on/off) and the negotiated allreduce/reducescatter programs "
         "— per-dtype-group grad norm, max-abs and PRE-reduction "
         "nonfinite count published as hvd_grad_norm / "
         "hvd_nonfinite_total{group,rank} with culprit-rank "
         "attribution, plus the post-update update-to-weight ratio "
         "and the EWMA divergence sentinels.  Near-zero cost: stats "
         "ride the existing programs; the only new communication is "
         "one small packed per-rank verdict vector allgathered per "
         "step.  Must agree on every rank (validated at the round-0 "
         "handshake: the tap adds a small allgather to the negotiated "
         "programs — a rank without it would build a mismatched "
         "collective schedule and deadlock)."))
_register("health_skip_nonfinite", Knob(
    "HOROVOD_HEALTH_SKIP_NONFINITE", False, _parse_bool,
    cli="--health-skip-nonfinite", config_key="health.skip_nonfinite",
    help="Skip-step contract (docs/health.md): when the health "
         "verdict reports a nonfinite gradient on ANY rank, the "
         "optimizer suppresses the step — update zeroed, optimizer "
         "state (momenta, error-feedback residuals) held — so "
         "survivors' parameters stay finite while hvd_nonfinite_total "
         "names the culprit.  Requires HOROVOD_HEALTH=1.  Must agree "
         "on every rank (validated at the round-0 handshake: a rank "
         "skipping while another applies would fork the replicated "
         "parameter trajectory)."))
_register("health_ewma_alpha", Knob(
    "HOROVOD_HEALTH_EWMA_ALPHA", 0.1, float,
    cli="--health-ewma-alpha", config_key="health.ewma_alpha",
    help="EWMA smoothing factor for the divergence sentinels' "
         "loss/grad-norm baselines (default 0.1; the baseline absorbs "
         "only healthy samples so it cannot chase a divergence).  See "
         "docs/health.md."))
_register("health_sentinel_ratio", Knob(
    "HOROVOD_HEALTH_SENTINEL_RATIO", 4.0, float,
    cli="--health-sentinel-ratio", config_key="health.sentinel_ratio",
    help="Divergence sentinel threshold: a loss/grad-norm sample "
         "breaches when it exceeds this multiple of its EWMA baseline "
         "(default 4.0; 0 disables ratio breaches — nonfinite values "
         "still alert immediately).  See docs/health.md."))
_register("health_trip_steps", Knob(
    "HOROVOD_HEALTH_TRIP_STEPS", 3, int,
    cli="--health-trip-steps", config_key="health.trip_steps",
    help="Sentinel hysteresis, trip side: consecutive breaching "
         "samples before hvd_health_alert raises (default 3 — one "
         "noisy batch must not page anyone).  See docs/health.md."))
_register("health_clear_steps", Knob(
    "HOROVOD_HEALTH_CLEAR_STEPS", 20, int,
    cli="--health-clear-steps", config_key="health.clear_steps",
    help="Sentinel hysteresis, clear side: consecutive healthy "
         "samples before an active alert clears (default 20 — an "
         "alert must not flap across the breach boundary).  See "
         "docs/health.md."))
_register("health_dir", Knob(
    "HOROVOD_HEALTH_DIR", "", str,
    cli="--health-dir", config_key="health.dir",
    help="Directory for per-rank health snapshot dumps "
         "(health-r<k>-g<g>.json, written on shutdown and on every "
         "abort/flight dump).  Empty (default) falls back to "
         "HOROVOD_FLIGHT_DIR; with neither set, dumps are off (the "
         "in-memory monitor and its gauges still run).  Report with "
         "`python -m horovod_tpu.perf health <dir>`.  See "
         "docs/health.md."))
_register("metrics_port", Knob(
    "HOROVOD_METRICS_PORT", 0, int,
    cli="--metrics-port", config_key="metrics.port",
    help="Prometheus-text metrics endpoint base port; 0 (default) "
         "disables.  Each rank serves /metrics on base + rank; under "
         "hvdrun the launcher serves the fleet-wide aggregate on the "
         "given port and exports base + 1 to ranks so nothing collides "
         "on a shared host.  See docs/metrics.md."))
_register("metrics_publish_interval", Knob(
    "HOROVOD_METRICS_PUBLISH_INTERVAL", 5.0, float,
    cli="--metrics-publish-interval",
    config_key="metrics.publish_interval",
    help="Seconds between each rank's metric-snapshot publishes into "
         "the rendezvous KV (hvd<epoch>/metrics/<rank>, merged by the "
         "launcher's aggregate /metrics endpoint); 0 disables "
         "publishing.  See docs/metrics.md."))
_register("stall_check_disable", Knob(
    "HOROVOD_STALL_CHECK_DISABLE", False, _parse_bool,
    cli="--no-stall-check", config_key="stall_check.disable",
    help="Disable the stall inspector."))
_register("stall_warning_time", Knob(
    "HOROVOD_STALL_CHECK_TIME_SECONDS", 60.0, float,
    cli="--stall-timeout-seconds", config_key="stall_check.warning_time_seconds",
    help="Seconds before warning about ranks missing a collective "
         "(reference stall_inspector.h:74)."))
_register("stall_shutdown_time", Knob(
    "HOROVOD_STALL_SHUTDOWN_TIME_SECONDS", 0.0, float,
    cli="--stall-shutdown-timeout-seconds",
    config_key="stall_check.shutdown_time_seconds",
    help="Seconds before a stall escalates to shutdown; 0 disables "
         "(reference stall_inspector.h:78)."))
_register("wire_timeout", Knob(
    "HOROVOD_WIRE_TIMEOUT_SECONDS", 600.0, float,
    cli="--wire-timeout-seconds", config_key="fault_tolerance.wire_timeout",
    help="Deadline for one control-plane KV wait (a rank's request "
         "list, the coordinator's response).  Decoupled from "
         "HOROVOD_STALL_SHUTDOWN_TIME_SECONDS, which used to double "
         "as the wire timeout.  See docs/fault-tolerance.md."))
_register("heartbeat_interval", Knob(
    "HOROVOD_HEARTBEAT_INTERVAL", 2.0, float,
    cli="--heartbeat-interval", config_key="fault_tolerance.heartbeat_interval",
    help="Seconds between control-plane heartbeat publishes "
         "(hb/<epoch>/<rank> keys); 0 disables liveness tracking and "
         "coordinated abort.  Must agree on every rank (validated at "
         "the round-0 handshake: a rank with liveness off would be "
         "declared dead by peers expecting beats).  See "
         "docs/fault-tolerance.md."))
_register("control_fanout", Knob(
    "HOROVOD_CONTROL_FANOUT", 8, int,
    cli="--control-fanout", config_key="control_plane.fanout",
    help="Hierarchical control plane (docs/control-plane.md): worlds "
         "larger than this negotiate through per-slice sub-"
         "coordinators (one merged message per slice per round reaches "
         "rank 0) instead of the flat rank-0 star; 0 forces flat mode "
         "at any size.  Must agree on every rank (validated at the "
         "round-0 handshake: a rank negotiating flat against "
         "hierarchical peers would wait on keys nobody writes)."))
_register("fault_spec", Knob(
    "HOROVOD_FAULT_SPEC", "", str,
    cli="--fault-spec", config_key="fault_tolerance.fault_spec",
    help="Deterministic fault injection on the control-plane wire "
         "(testing only): comma-separated delay:<glob>:<dur>, "
         "drop:<glob>[:<n>], die:rank<k>[:round<n>], "
         "preempt:rank<k>[:round<n>][:grace<s>] (graceful advance "
         "notice instead of die's hard exit), "
         "slow:<rank>:<delay> (chronic straggler), "
         "nan:<nameglob>[:round<n>], inf:<nameglob>[:round<n>] "
         "specs.  See docs/fault-tolerance.md."))
_register("kv_retries", Knob(
    "HOROVOD_KV_RETRIES", 3, int,
    cli="--kv-retries", config_key="fault_tolerance.kv_retries",
    help="Bounded retries (exponential backoff + jitter, reconnect "
         "between attempts) for native KV-store wire failures."))
_register("elastic", Knob(
    "HOROVOD_ELASTIC", False, _parse_bool,
    cli="--elastic", config_key="fault_tolerance.elastic",
    help="Elastic mode: survivors of a dead rank re-form the job at the "
         "new world size in-process (hvd.elastic.run) instead of the "
         "whole job restarting; the launcher keeps the rendezvous "
         "server alive across re-forms, blacklists hosts whose ranks "
         "died, and respawns replacements that rejoin at the next "
         "commit boundary.  Must agree on every rank (validated at "
         "the round-0 handshake: an elastic survivor re-forming "
         "against a non-elastic peer would hang the rendezvous).  See "
         "docs/elastic.md."))
_register("min_ranks", Knob(
    "HOROVOD_MIN_RANKS", 1, int,
    cli="--min-ranks", config_key="fault_tolerance.min_ranks",
    help="Elastic mode: smallest world size the job may shrink to; a "
         "re-form that would leave fewer survivors fails the job "
         "(falling back to --restart-attempts when set)."))
_register("blacklist_cooldown", Knob(
    "HOROVOD_BLACKLIST_COOLDOWN_SECONDS", 120.0, float,
    cli="--blacklist-cooldown-seconds",
    config_key="fault_tolerance.blacklist_cooldown",
    help="Elastic mode: how long the launcher refuses to respawn ranks "
         "on a host after one of its ranks died.  After the cooldown "
         "the host is admissible again and the job grows back toward "
         "its original size."))
_register("elastic_settle", Knob(
    "HOROVOD_ELASTIC_SETTLE_SECONDS", 10.0, float,
    cli="--elastic-settle-seconds",
    config_key="fault_tolerance.elastic_settle",
    help="Elastic mode: how long the re-form leader waits for every "
         "expected survivor to announce presence before declaring "
         "stragglers dead and publishing the new-generation roster.  "
         "Survivors hit the failure at different points of the same "
         "training step, so this bounds that skew."))
_register("elastic_join_timeout", Knob(
    "HOROVOD_ELASTIC_JOIN_TIMEOUT_SECONDS", 3600.0, float,
    cli="--elastic-join-timeout-seconds",
    config_key="fault_tolerance.elastic_join_timeout",
    help="Elastic mode: how long a replacement process waits in the "
         "admission waiting room for a survivors' commit boundary to "
         "admit it.  Must exceed the training loop's commit cadence; "
         "on timeout the joiner retracts its registration (so a later "
         "grow re-form never admits a ghost) and exits."))
_register("restart_attempts", Knob(
    "HOROVOD_RESTART_ATTEMPTS", 0, int,
    cli="--restart-attempts", config_key="fault_tolerance.restart_attempts",
    help="hvdrun: relaunch the whole job up to N times after a failed "
         "attempt, resuming from the latest complete checkpoint when "
         "--checkpoint-dir is set (HOROVOD_RESUME_STEP is exported to "
         "the restarted ranks)."))
_register("checkpoint_dir", Knob(
    "HOROVOD_CHECKPOINT_DIR", "", str,
    cli="--checkpoint-dir", config_key="fault_tolerance.checkpoint_dir",
    help="Checkpoint store the launcher consults on restart "
         "(checkpoint.latest_complete: only snapshots with an atomic "
         "DONE marker count; torn snapshots are refused)."))
_register("checkpoint_keep", Knob(
    "HOROVOD_CHECKPOINT_KEEP", 0, int,
    cli="--checkpoint-keep", config_key="fault_tolerance.checkpoint_keep",
    help="Last-K checkpoint retention ring: after each durable save, "
         "complete older snapshots beyond the newest K are pruned "
         "(0 = keep everything, the pre-ring behavior).  K >= 2 is "
         "what makes auto-rollback useful — the newest snapshot may "
         "carry a poisoned health verdict, the ring must still hold a "
         "healthy ancestor.  See docs/autopilot.md."))
_register("checkpoint_verify", Knob(
    "HOROVOD_CHECKPOINT_VERIFY", True, _parse_bool,
    cli="--checkpoint-verify",
    config_key="fault_tolerance.checkpoint_verify",
    help="Integrity verification on restore/discovery: every save "
         "stamps a MANIFEST.json (per-file SHA-256 + sizes) inside "
         "the atomic rename, and restore()/latest_complete()/"
         "latest_healthy() verify against it — a bit-rotted snapshot "
         "is quarantined (step_<N>.corrupt, loud log, flight event) "
         "and the next complete one is used instead.  Pre-manifest "
         "snapshots warn and pass.  0 restores unverified bytes.  "
         "See docs/checkpoint.md."))
_register("checkpoint_replicas", Knob(
    "HOROVOD_CHECKPOINT_REPLICAS", 2, int,
    cli="--checkpoint-replicas",
    config_key="fault_tolerance.checkpoint_replicas",
    help="Total copies of each all_ranks ZeRO shard dir per snapshot "
         "(default 2 = owner + one ring-buddy replica under "
         "step_<N>/rep_<owner>_<holder>/), so one host loss never "
         "takes the only copy of shard-local state; restore prefers "
         "the local copy and falls back to any verified replica.  "
         "0/1 disables replication.  Must agree on every rank "
         "(validated at the round-0 handshake: replication is a "
         "broadcast round per owner inside all_ranks save, so a rank "
         "skipping it while peers replicate deadlocks the save).  "
         "See docs/checkpoint.md."))
_register("preempt_grace", Knob(
    "HOROVOD_PREEMPT_GRACE_SECONDS", 30.0, float,
    cli="--preempt-grace-seconds",
    config_key="fault_tolerance.preempt_grace",
    help="Graceful-preemption plane (docs/fault-tolerance.md): the "
         "advance-notice window a drain must finish inside.  A "
         "noticed rank (SIGTERM/SIGUSR1, hvdrun --preempt, a "
         "preempt: fault rule, or the pluggable metadata source) "
         "publishes el/preempt/<rank>; the fleet takes one emergency "
         "commit at the next agreed step boundary, the noticed rank "
         "exits cleanly, and survivors re-form proactively — no "
         "heartbeat-timeout stall, no blacklist.  <= 0 disables the "
         "plane (SIGTERM means death again)."))
_register("autopilot", Knob(
    "HOROVOD_AUTOPILOT", False, _parse_bool,
    cli="--autopilot", config_key="autopilot.enabled",
    help="Closed-loop supervisor (docs/autopilot.md): the launcher "
         "aggregate loop and the rank-side elastic driver act on the "
         "observability planes — preemptive host blacklist on "
         "sustained straggling, elastic shrink/grow on goodput SLO "
         "burn, auto-rollback to the newest healthy commit on a "
         "divergence sentinel trip, and comm-knob retune from "
         "measured exposed communication.  Every action lands on the "
         "flight ring with its evidence tuple."))
_register("autopilot_dry_run", Knob(
    "HOROVOD_AUTOPILOT_DRY_RUN", False, _parse_bool,
    cli="--autopilot-dry-run", config_key="autopilot.dry_run",
    help="Autopilot shadow mode: every rule still evaluates, paces "
         "its cooldowns, and records would-have-acted verdicts on the "
         "flight ring, but NO actuator fires — the audit trail for "
         "building trust before enabling closed-loop actions.  See "
         "docs/autopilot.md."))
_register("autopilot_cooldown", Knob(
    "HOROVOD_AUTOPILOT_COOLDOWN_SECONDS", 60.0, float,
    cli="--autopilot-cooldown-seconds", config_key="autopilot.cooldown",
    help="Per-rule refractory period: after a rule fires (or dry-run "
         "fires), it cannot fire again for this long — the flap guard "
         "between hysteresis (entry) and the global rate limit "
         "(fleet-wide ceiling).  See docs/autopilot.md."))
_register("autopilot_rate_limit", Knob(
    "HOROVOD_AUTOPILOT_RATE_LIMIT", 4, int,
    cli="--autopilot-rate-limit", config_key="autopilot.rate_limit",
    help="Global action ceiling: at most this many autopilot actions "
         "(all rules combined) per HOROVOD_AUTOPILOT_RATE_WINDOW_"
         "SECONDS; excess verdicts are recorded as suppressed.  See "
         "docs/autopilot.md."))
_register("autopilot_rate_window", Knob(
    "HOROVOD_AUTOPILOT_RATE_WINDOW_SECONDS", 600.0, float,
    cli="--autopilot-rate-window-seconds",
    config_key="autopilot.rate_window",
    help="Sliding window over which HOROVOD_AUTOPILOT_RATE_LIMIT "
         "counts actions.  See docs/autopilot.md."))
_register("autopilot_trip_ticks", Knob(
    "HOROVOD_AUTOPILOT_TRIP_TICKS", 3, int,
    cli="--autopilot-trip-ticks", config_key="autopilot.trip_ticks",
    help="Hysteresis: consecutive evaluation ticks a condition must "
         "hold (same candidate for the straggler rule) before the "
         "rule fires — one noisy sample must not shrink a fleet.  See "
         "docs/autopilot.md."))
_register("autopilot_straggler_factor", Knob(
    "HOROVOD_AUTOPILOT_STRAGGLER_FACTOR", 4.0, float,
    cli="--autopilot-straggler-factor",
    config_key="autopilot.straggler_factor",
    help="Preemptive-blacklist breach multiple: a rank is a chronic "
         "straggler when its coordinator-clock lateness exceeds this "
         "multiple of the fleet median (or supplied baseline), "
         "sustained for HOROVOD_AUTOPILOT_TRIP_TICKS.  See "
         "docs/autopilot.md."))
_register("autopilot_straggler_floor", Knob(
    "HOROVOD_AUTOPILOT_STRAGGLER_FLOOR", 0.05, float,
    cli="--autopilot-straggler-floor",
    config_key="autopilot.straggler_floor",
    help="Absolute lateness floor (seconds) below which the straggler "
         "rule never fires regardless of the relative factor — "
         "microsecond jitter on an idle fleet is not a straggler.  See "
         "docs/autopilot.md."))
_register("autopilot_burn_threshold", Knob(
    "HOROVOD_AUTOPILOT_BURN_THRESHOLD", 2.0, float,
    cli="--autopilot-burn-threshold",
    config_key="autopilot.burn_threshold",
    help="SLO-burn elastic trigger: the shrink rule arms when the "
         "fleet goodput alert is firing AND its burn_rate (lost "
         "goodput over SLO headroom) sustains at or above this "
         "value for HOROVOD_AUTOPILOT_TRIP_TICKS.  Requires "
         "HOROVOD_GOODPUT_SLO.  See docs/autopilot.md."))
_register("autopilot_comm_fraction", Knob(
    "HOROVOD_AUTOPILOT_COMM_FRACTION", 0.25, float,
    cli="--autopilot-comm-fraction",
    config_key="autopilot.comm_fraction",
    help="Retune trigger: when measured exposed-communication time "
         "exceeds this fraction of exposed+compute, sustained for "
         "HOROVOD_AUTOPILOT_TRIP_TICKS, the autopilot proposes a "
         "comm-knob retune through the autotuner's knob ownership "
         "(parameter_manager.apply_params).  See docs/autopilot.md."))
_register("autotune", Knob(
    "HOROVOD_AUTOTUNE", False, _parse_bool,
    cli="--autotune", config_key="autotune.enabled",
    help="Bayesian autotuning of fusion/cycle knobs (reference "
         "parameter_manager.h:42)."))
_register("autotune_log", Knob(
    "HOROVOD_AUTOTUNE_LOG", "", str,
    cli="--autotune-log-file", config_key="autotune.log_file",
    help="CSV log of autotune samples."))
_register("autotune_warmup_samples", Knob(
    "HOROVOD_AUTOTUNE_WARMUP_SAMPLES", 3, int,
    cli="--autotune-warmup-samples", config_key="autotune.warmup_samples",
    help="Discarded warmup windows before scoring."))
_register("autotune_steps_per_sample", Knob(
    "HOROVOD_AUTOTUNE_STEPS_PER_SAMPLE", 10, int,
    cli="--autotune-steps-per-sample", config_key="autotune.steps_per_sample",
    help="Background cycles per autotune scoring window."))
_register("autotune_bayes_opt_max_samples", Knob(
    "HOROVOD_AUTOTUNE_BAYES_OPT_MAX_SAMPLES", 20, int,
    cli="--autotune-bayes-opt-max-samples", config_key="autotune.bayes_opt_max_samples",
    help="Max Bayesian-optimization samples before pinning best."))
_register("autotune_gaussian_process_noise", Knob(
    "HOROVOD_AUTOTUNE_GAUSSIAN_PROCESS_NOISE", 0.8, float,
    cli="--autotune-gaussian-process-noise", config_key="autotune.gaussian_process_noise",
    help="GP observation-noise prior."))
_register("log_level", Knob(
    "HOROVOD_LOG_LEVEL", "warning", str,
    cli="--log-level", config_key="logging.level",
    help="trace/debug/info/warning/error/fatal."))
_register("log_hide_time", Knob(
    "HOROVOD_LOG_HIDE_TIME", False, _parse_bool,
    cli="--log-hide-timestamp", config_key="logging.hide_timestamp",
    help="Hide timestamps in log lines."))

# TPU-build-specific knobs.
_register("platform", Knob(
    "HOROVOD_PLATFORM", "", str,
    cli="--platform", config_key="tpu.platform",
    help="Force JAX platform (cpu for tests, tpu in production)."))
_register("coordinator_addr", Knob(
    "HOROVOD_COORDINATOR_ADDR", "", str, help="jax.distributed coordinator address host:port."))
_register("rendezvous_addr", Knob(
    "HOROVOD_GLOO_RENDEZVOUS_ADDR", "", str,
    help="KV-store rendezvous server address (reference env name kept "
         "for drop-in compatibility, gloo_run.py:152)."))
_register("rendezvous_port", Knob(
    "HOROVOD_GLOO_RENDEZVOUS_PORT", 0, int, help="KV-store rendezvous port."))
_register("heartbeat_timeout", Knob(
    "HOROVOD_HEARTBEAT_TIMEOUT_SECONDS", 20.0, float,
    cli="--heartbeat-timeout-seconds",
    config_key="fault_tolerance.heartbeat_timeout",
    help="How fast a crashed peer is detected: a rank whose "
         "control-plane heartbeat goes stale for this long triggers a "
         "coordinated abort (RanksDownError on every survivor).  Also "
         "passed to jax.distributed's own heartbeat machinery at "
         "init().  Must agree on every rank (validated at the round-0 "
         "handshake, like the heartbeat interval).  See "
         "docs/fault-tolerance.md."))
_register("shutdown_timeout", Knob(
    "HOROVOD_SHUTDOWN_TIMEOUT_SECONDS", 10, int,
    help="Max seconds a terminating process waits at the distributed "
         "shutdown barrier (jax default of 300s stalls crashed jobs)."))
_register("aot_cache_dir", Knob(
    "HOROVOD_AOT_CACHE_DIR", "", str,
    cli="--aot-cache-dir", config_key="aot_cache.dir",
    help="Persistent AOT executable cache for the negotiated data "
         "plane (docs/aot-cache.md): compiled collective programs are "
         "serialized here keyed by (round-0 cfg vector, topology, "
         "jax/jaxlib/libtpu versions, program signature), so a restart "
         "or elastic re-form loads executables in seconds instead of "
         "recompiling every program from scratch.  Fail-closed: any "
         "deserialize error, version skew or key mismatch evicts the "
         "entry and recompiles — a stale program can never run.  Empty "
         "(default) disables.  Inspect/prune with `python -m "
         "horovod_tpu.runtime.aot_cache list|prune`."))
_register("aot_cache_mode", Knob(
    "HOROVOD_AOT_CACHE_MODE", "auto", str,
    cli="--aot-cache-mode", config_key="aot_cache.mode",
    help="AOT cache serialization format: auto (default: 'exec'), "
         "exec (serialized compiled executable — warm loads skip XLA "
         "entirely), export (serialized lowered StableHLO via "
         "jax.export — the escape hatch when executable serialization "
         "misbehaves on a platform; warm loads still pay the XLA "
         "compile and only skip Python tracing), off (disable even "
         "when HOROVOD_AOT_CACHE_DIR is set).  Both formats key on "
         "the exact jax/jaxlib/libtpu versions — a version bump "
         "always recompiles."))
_register("fused_update", Knob(
    "HOROVOD_FUSED_UPDATE", False, _parse_bool,
    cli="--fused-update", config_key="optimizer.fused_update",
    help="Pallas-fused optimizer tail (docs/zero.md): collapse the "
         "post-reduction update chain — unscale, dtype cast, momentum/"
         "Adam moment update, bias correction, step — into one fused "
         "kernel per flat per-dtype buffer instead of a chain of small "
         "HBM-round-tripping XLA ops.  Applies across ZeRO stages 0-3 "
         "when the wrapped optimizer is fusable (built by "
         "hvd.fused_update.sgd/adam — bit-exact vs the unfused optax "
         "chain); silently falls back with one warning otherwise.  "
         "Local-only knob (the update runs after the wire), so it "
         "needs no cross-rank handshake."))
# (HOROVOD_EAGER_PAD_POW2 was registered here through PR 11 but never
# had a reader — the eager path pads fused buffers to world-size
# multiples, not powers of two.  analysis.knob_lint's KNOB-DEAD rule
# now flags registered knobs nothing reads; the dead entry is gone.)


def get(name: str) -> Any:
    """Read a knob: env var wins, else default."""
    k = _KNOBS[name]
    raw = os.environ.get(k.env)
    if raw is None or raw == "":
        return k.default
    try:
        return k.parse(raw)
    except (ValueError, TypeError):
        return k.default


def is_set(name: str) -> bool:
    """True when the knob's env var is explicitly set to a non-blank
    value — the registry-sanctioned way to distinguish an operator's
    explicit choice from the default (raw ``os.environ`` probes
    outside this module are flagged by ``analysis.knob_lint``).
    Whitespace-only counts as unset: ``get()`` would fall back to the
    default for it, and an "explicit" flag that resolves to the
    default is exactly the false positive callers use this to
    avoid."""
    k = _KNOBS[name]
    return bool(os.environ.get(k.env, "").strip())


def set_knob(name: str, value: Any) -> None:
    """Set a knob by exporting its env var (the single source of truth,
    like the reference where all surfaces converge on env)."""
    k = _KNOBS[name]
    if isinstance(value, bool):
        os.environ[k.env] = "1" if value else "0"
    else:
        os.environ[k.env] = str(value)


def knobs() -> dict[str, Knob]:
    return dict(_KNOBS)


# ---------------------------------------------------------------------------
# Config file -> env (reference config_parser.py:38-130)
# ---------------------------------------------------------------------------


def _flatten(d: dict, prefix: str = "") -> dict[str, Any]:
    out: dict[str, Any] = {}
    for key, val in d.items():
        dotted = f"{prefix}.{key}" if prefix else key
        if isinstance(val, dict):
            out.update(_flatten(val, dotted))
        else:
            out[dotted] = val
    return out


def load_config_file(path: str, override: bool = False) -> dict[str, Any]:
    """Load a YAML/JSON config file and export matching knobs to env.

    CLI flags take precedence over the file (reference
    ``runner.py:274-277``): the launcher loads the file first, then
    applies CLI flags on top.  Returns the applied mapping.
    """
    with open(path) as f:
        text = f.read()
    try:
        data = json.loads(text)
    except json.JSONDecodeError:
        try:
            import yaml  # type: ignore

            data = yaml.safe_load(text)
        except ImportError as exc:
            raise RuntimeError(
                "config file is not JSON and PyYAML is unavailable") from exc
    flat = _flatten(data or {})
    applied = {}
    by_key = {k.config_key: (name, k) for name, k in _KNOBS.items() if k.config_key}
    for dotted, value in flat.items():
        if dotted in by_key:
            name, knob = by_key[dotted]
            if not override and os.environ.get(knob.env):
                continue
            set_knob(name, value)
            applied[name] = value
    return applied


def set_env_from_args(args, env: dict | None = None) -> dict:
    """Map parsed launcher CLI args onto HOROVOD_* env (reference
    ``config_parser.py:141-190``)."""
    env = env if env is not None else os.environ  # type: ignore[assignment]
    for name, knob in _KNOBS.items():
        if knob.cli is None:
            continue
        attr = knob.cli.lstrip("-").replace("-", "_")
        if hasattr(args, attr):
            val = getattr(args, attr)
            if val is None:
                continue
            if name == "fusion_threshold":
                val = int(val) * 1024 * 1024  # CLI flag is in MB
            if isinstance(val, bool):
                # explicit False (--no-flag) must override a truthy default
                env[knob.env] = "1" if val else "0"
            else:
                env[knob.env] = str(val)
    return env
