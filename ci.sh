#!/bin/sh
# CI entry point — one command reproducing the full verification a
# fresh checkout needs (the reference ships a Buildkite matrix,
# .buildkite/gen-pipeline.sh; this is the single-environment TPU-stack
# equivalent: CPU-backend suite + virtual-mesh dryruns + codec parity).
#
#   ./ci.sh          # everything (~15 min warm compile cache /
#                    # ~25 min cold on the 1-core image)
#   ./ci.sh quick    # smoke subset (~2 min): wire parity, collectives,
#                    # launcher, 8-device dryrun
#
# Exit code 0 = green. Individual stages echo PASS/FAIL as they finish.
set -eu
cd "$(dirname "$0")"

export HOROVOD_PLATFORM=cpu
export JAX_PLATFORMS=cpu
# Persistent XLA compile cache: dryrun/entry stages and every spawned
# rank share compiled programs with the suite.  One resolution for the
# whole repo (common/platform.ensure_compile_cache): the variable when
# set, else <checkout>/.jax_cache.
JAX_COMPILATION_CACHE_DIR=$(python -c "from horovod_tpu.common.platform import ensure_compile_cache; print(ensure_compile_cache())")
export JAX_COMPILATION_CACHE_DIR
export JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS=${JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS:-0.5}

fail=0
stage() {
    name=$1; shift
    echo "=== [$name] $*"
    if "$@"; then echo "=== [$name] PASS"; else
        echo "=== [$name] FAIL"; fail=1; fi
}

# Native codecs must build and agree byte-for-byte with the Python spec
# before anything that rides the wire runs.
stage wire-parity python -m pytest tests/test_wire.py tests/test_kv_auth.py -q

# Invariant lint suite (docs/analysis.md): knob drift (raw env reads,
# handshake/cache-key/CLI/doc cross-references) and the concurrency
# audit (lock-order cycles, signal-unsafe locks, blocking calls under
# hot-path locks) run on EVERY build — both are AST-level and finish
# in seconds.  Exit is non-zero on any finding not carried by a
# justified entry in analysis_allowlist.json.
stage analysis python -m horovod_tpu.analysis knobs concurrency
# ...and the suite must be able to FAIL a build: each checked-in
# violation fixture — a ZeRO-2 full-buffer program, an
# unregistered-knob tree, a lock-order-cycle tree — must drive exit 1.
stage analysis-trips python -c "
import subprocess, sys
checks = [
    (['hlo', '--hlo-file', 'tests/data/analysis/bad_zero2.hlo'],
     'synthetic ZeRO-2 full-buffer program'),
    (['hlo', '--hlo-file', 'tests/data/analysis/bad_mesh_world.hlo'],
     'world-spanning mesh-placement program'),
    (['hlo', '--hlo-file', 'tests/data/analysis/bad_localsgd_inner.hlo'],
     'cross-slice-collective local-SGD inner program'),
    (['knobs', '--package-dir', 'tests/data/analysis/bad_knobs'],
     'unregistered-knob fixture'),
    (['concurrency', '--package-dir', 'tests/data/analysis/bad_locks'],
     'lock-order-cycle fixture'),
]
for args, what in checks:
    r = subprocess.run(
        [sys.executable, '-m', 'horovod_tpu.analysis', *args,
         '--no-allowlist'], stdout=subprocess.DEVNULL)
    assert r.returncode == 1, \
        f'expected exit 1 on the {what}, got {r.returncode}'
    print(f'analysis fails correctly on the {what}')
"

# Deterministic fleet simulator (docs/control-plane.md): real
# KVControllers at simulated pod scale — 256-rank negotiation, an
# 8-death re-form storm through the real plan_reform, and a
# mid-negotiation coordinated abort.  Each scenario is replayed twice
# and must be byte-identical (~30 s total on the 1-core image).
stage simfleet python -c "
from horovod_tpu.runtime import simfleet
a = simfleet.run_trace(world=256, fanout=16, rounds=3, seed=0)
b = simfleet.run_trace(world=256, fanout=16, rounds=3, seed=0)
assert a == b, 'nondeterministic 256-rank trace'
print('256-rank negotiation: %d root msgs/round, deterministic'
      % a[-1]['root_ops'])
s1 = simfleet.reform_storm(world=256, fanout=16, kill=8)
s2 = simfleet.reform_storm(world=256, fanout=16, kill=8)
assert s1['new_world'] == 248, s1
assert s1['roster_digest'] == s2['roster_digest'], 'storm roster drift'
assert s1['post'] == s2['post'], 'post-reform trace drift'
print('reform storm: 8 deaths -> dense roster of %d, digest %s'
      % (s1['new_world'], s1['roster_digest']))
ab = simfleet.coordinated_abort(world=32, fanout=8, victim=5)
assert ab['died'] == [5], ab
assert ab['survivors_aborted'] == ab['survivors_total'] == 31, ab
print('coordinated abort: all %d survivors observed it'
      % ab['survivors_aborted'])
"
# ...and the scaling claim is gated, not just documented: at
# world=1024 the hierarchical control plane must keep per-round root
# messages at least 8x below the flat star.
stage simfleet-scaling python -c "
from horovod_tpu.runtime import simfleet
out = simfleet.measure_scaling(world=1024, fanout=32, rounds=3)
assert out['ratio'] >= 8.0, out
print('world=1024 root msgs/round: flat %d vs hier %d (%.1fx >= 8x)'
      % (out['flat_root_ops_per_round'],
         out['hier_root_ops_per_round'], out['ratio']))
"

# Closed-loop autopilot (docs/autopilot.md) on the simulated fleet:
# the 256-rank chronic-straggler scenario must blacklist preemptively
# (zero deaths), replay byte-for-byte, and keep dry-run mode
# side-effect free; the rollback drill must resume bit-exact against
# a never-poisoned reference through the real sentinel + ring.
stage autopilot python -c "
import json
from horovod_tpu.runtime import simfleet
a = simfleet.straggler_drill(world=256, fanout=16)
b = simfleet.straggler_drill(world=256, fanout=16)
assert json.dumps(a, sort_keys=True) == json.dumps(b, sort_keys=True), \
    'straggler drill replay drift'
assert a['deaths'] == [] and a['world_after'] == 255, a
dry = simfleet.straggler_drill(world=256, fanout=16, dry_run=True)
assert dry['blacklisted'] == [] and dry['world_after'] == 256, dry
assert any(x['outcome'] == 'dry_run' for x in dry['actions']), dry
print('256-rank straggler: blacklisted %s preemptively (0 deaths), '
      'deterministic; dry-run shadow left the fleet intact'
      % a['blacklisted'])
burn = simfleet.slo_burn_drill()
assert burn == simfleet.slo_burn_drill(), 'burn drill replay drift'
assert burn['shed'] == [burn['victim']] and \
    ['grow', None] in burn['events'], burn
print('SLO burn: shed rank %d at burn>=threshold, grew back on '
      'recovery' % burn['victim'])
rb = simfleet.rollback_drill()
assert rb == simfleet.rollback_drill(), 'rollback drill replay drift'
assert rb['rollbacks'] == 1 and rb['bit_exact'], rb
print('nan -> sentinel -> rollback: ring %s, resumed bit-exact '
      '(digest %s)' % (rb['ring_steps'], rb['final_digest']))
"

# Graceful-preemption storm (docs/fault-tolerance.md) on the simulated
# fleet: 8 ranks scattered across 256 receive advance notices — none
# may die and none may be blacklisted (an announced departure is not a
# fault), the ungated preempt_drain rule must land once per notice even
# under a punitive cooldown/rate-limit, and the whole drill must replay
# byte-for-byte under the fixed seed.
stage preempt-storm python -c "
import json
from horovod_tpu.runtime import simfleet
a = simfleet.preempt_storm(world=256, fanout=16, kill=8)
b = simfleet.preempt_storm(world=256, fanout=16, kill=8)
assert json.dumps(a, sort_keys=True) == json.dumps(b, sort_keys=True), \
    'preempt storm replay drift'
assert a['deaths'] == [] and a['blacklisted'] == [], a
assert a['drained'] == a['victims'], a
assert a['world_after'] == 256 - len(a['victims']), a
assert all(x['outcome'] == 'applied' for x in a['actions']), a
print('256-rank preemption storm: drained %d announced ranks '
      '(0 deaths, 0 blacklists), deterministic (roster %s)'
      % (len(a['drained']), a['roster_digest']))
"

if [ "${1:-}" = "quick" ]; then
    stage collectives python -m pytest tests/test_collectives.py -q
    # int8 quantized-allreduce subsystem: pure-CPU smoke (round trip,
    # scale-aware psum, hierarchical ICI-fp32/DCN-int8 split, error
    # feedback) so the wire format is exercised without TPU access.
    stage quantization python -m pytest tests/test_quantization.py -q
    # ZeRO-1 sharded-optimizer smoke: in-trace sharded-vs-replicated
    # parity, 1/N state sharding and the reduce-scatter/all-gather HLO
    # proof on the virtual 8-device mesh (2-proc spawns stay in the
    # full suite).
    stage sharded-optimizer python -m pytest tests/test_sharded_optimizer.py \
        -q -m "not multiprocess"
    # ZeRO-2/3 sharding contract: stage-0/1/2/3 parity (bit-exact on
    # dyadic data), the HLO residency proofs (stage 2: no full-size
    # fused gradient buffer; stage 3: >= K bucket all-gathers and
    # 1/N-resident params), prefetched-gather round trip, broadcast
    # refusal on shard-resident params (2-proc wire + handshake tests
    # stay in the full suite).
    stage zero23 python -m pytest tests/test_zero23.py \
        -q -m "not multiprocess"
    # Mesh-native data plane: spec parsing / factor_devices, the
    # dp-axis-vs-flat-world bit-exact parity grid (ZeRO 0-3 x overlap
    # x int8), the HLO dp-subgroup placement proof and the round-0
    # mesh-signature cfg (the 2-proc mismatch test stays in the full
    # suite).
    stage mesh python -m pytest tests/test_mesh.py \
        -q -m "not multiprocess"
    # Local-SGD / DiLoCo outer loop (docs/local-sgd.md): H=1 bit-exact
    # parity with the plain DistributedOptimizer, the DiLoCo outer-step
    # math vs a NumPy reference, ZeRO composition, and the HLO proof
    # that the compiled INNER program carries zero cross-slice
    # collectives while the outer program must carry one (the 2-proc
    # handshake-mismatch tests stay in the full suite).
    stage localsgd python -m pytest tests/test_local_sgd.py \
        -q -m "not multiprocess and not slow"
    # ...and the H-fold DCN-round claim is gated at simulated pod
    # scale: 256 ranks, 16 slices, H=4 — per-step outer sync vs the
    # H-step regime must show >= H-fold fewer cross-slice rounds, and
    # the scenario must replay byte-identical.
    stage localsgd-scaling python -c "
import json
from horovod_tpu.runtime import simfleet
a = simfleet.local_sgd_scaling(world=256, fanout=16, h=4, windows=2,
                               seed=0)
b = simfleet.local_sgd_scaling(world=256, fanout=16, h=4, windows=2,
                               seed=0)
assert json.dumps(a, sort_keys=True) == json.dumps(b, sort_keys=True), \
    'local-SGD scaling scenario replay drift'
assert a['cross_round_ratio'] >= 4.0, a
print('world=256 h=4: %d cross rounds/window sync-every-step vs %d '
      'local-SGD (%.1fx >= 4x), deterministic'
      % (a['sync_cross_rounds'], a['localsgd_cross_rounds'],
         a['cross_round_ratio']))
"
    # Overlap engine: ring-vs-monolithic parity (bit-exact fp32),
    # HLO-shape proof (>= K collective-permutes, zero all-reduce),
    # ZeRO-1/int8/hierarchical composition (2-proc wire + handshake
    # tests stay in the full suite).
    stage overlap python -m pytest tests/test_overlap.py \
        -q -m "not multiprocess"
    # Fault-tolerance harness: deterministic delay/drop/die injection,
    # heartbeat-sweep coordinated abort, KV retry/backoff, torn-
    # checkpoint refusal — keeps the HOROVOD_FAULT_SPEC machinery
    # itself exercised (the 2-proc SIGKILL abort test runs in the full
    # suite).
    stage fault-tolerance python -m pytest tests/test_fault_tolerance.py \
        -q -m "not multiprocess"
    # Metrics plane: registry semantics (stdlib-only import enforced by
    # its own test), Prometheus rendering/escaping, KV publish +
    # generation-bump aggregation, endpoint knob, hot-path cost bound
    # (the 2-proc fault-injected scrape stays in the full suite).
    stage metrics python -m pytest tests/test_metrics.py \
        -q -m "not multiprocess"
    # End-to-end scrape smoke: real registry -> real HTTP endpoint.
    stage metrics-scrape python -c "
from urllib.request import urlopen
from horovod_tpu.runtime import metrics as M
M.counter('ci_scrape_total').inc(2)
srv = M.MetricsHTTPServer(M.registry().render, 0, host='127.0.0.1')
text = urlopen('http://127.0.0.1:%d/metrics' % srv.port,
               timeout=10).read().decode()
srv.close()
assert 'ci_scrape_total 2' in text, text[:500]
print('scrape ok:', len(text), 'bytes')
"
    # Flight recorder (docs/flight-recorder.md): ring semantics + the
    # no-syscall hot-path bound, clock-offset math, analyzer units,
    # AND the 2-proc SIGKILL postmortem (survivor dumps on the
    # coordinated abort; merge produces one Perfetto trace; the death
    # report names the dead rank and its last round).
    stage flight python -m pytest tests/test_flight.py -q \
        --deselect tests/test_flight.py::test_straggler_attribution_2proc \
        --deselect tests/test_flight.py::test_straggler_attribution_3proc_blames_only_the_straggler
    # Merged-trace schema validation: the merge output must LOAD as
    # JSON and every trace event must carry ts/pid/tid/ph (the
    # Perfetto/chrome://tracing contract).
    stage flight-schema python -c "
import json, tempfile, os
from horovod_tpu.runtime import flight
from horovod_tpu.trace.merge import merge
d = tempfile.mkdtemp()
r = flight.FlightRecorder(32)
r.record('round', ph='B', round=0, n_req=1)
r.record('arrive', peer=0, round=0)
r.record('round', ph='E', round=0, path='slow', n_resp=1)
r.dump(os.path.join(d, 'flight-r0-g1-p1.jsonl'),
       {'rank': 0, 'size': 1, 'generation': 1})
out, dumps, offsets = merge(d)
trace = json.load(open(out))
assert trace['traceEvents'], 'empty merged trace'
for ev in trace['traceEvents']:
    missing = {'ts', 'pid', 'tid', 'ph'} - set(ev)
    assert not missing, (missing, ev)
print('trace schema ok:', len(trace['traceEvents']), 'events')
"
    # Goodput ledger (docs/goodput.md): state-machine units (phase
    # exclusivity, wall-clock conservation, unattributed bound), the
    # data_wait/input-starvation hook, fleet merge + dominant-
    # bottleneck naming + SLO burn alerts, snapshot-age gauges, and
    # the CLI (the 2-proc straggler attribution runs in the full
    # suite).
    stage goodput python -m pytest tests/test_goodput.py \
        -q -m "not multiprocess and not slow"
    # Device-truth perf observatory (docs/perf.md): stdlib xplane
    # wire-format parser units (varint edges, nested scopes, truncated
    # files degrade to partial results), a real CPU jax.profiler
    # capture -> attribution round trip, the sampled-capture hook with
    # rotation + gauges, and the profiler-bridge elastic lifecycle.
    stage perf python -m pytest tests/test_perf.py -q -m "not slow"
    # Training-health plane (docs/health.md): sentinel hysteresis
    # units, the nan:/inf: fault grammar, in-trace culprit attribution
    # + skip-step + parity/HLO proofs, AND the 2-proc culprit test —
    # both ranks' metrics and the merged flight trace must name the
    # poisoned rank + dtype group over the real negotiated wire.
    stage health python -m pytest tests/test_health.py -q -m "not slow"
    # Adaptive compression stack (docs/compression.md): codec +
    # mode-vector + guardrail units, plus one 2-proc negotiated-wire
    # parity test per new mode (int4 packed, topk sparse).
    stage adaptive-compression python -m pytest \
        tests/test_adaptive_compression.py -q -m "not slow"
    # Persistent AOT executable cache (docs/aot-cache.md): fail-closed
    # hygiene units (corrupt/truncated/version-skewed/wrong-key entries
    # evict + recompile), the key schema, the CLI, AND the 2-proc
    # cold->warm proof (second start: zero cold builds, > 2x less
    # program-materialization wall time).
    stage aot-cache python -m pytest tests/test_aot_cache.py \
        -q -m "not slow"
    # Pallas-fused optimizer tail (docs/zero.md): fp32 parity matrix
    # (fused bit-exact vs the unfused optax chain across ZeRO stages
    # 0-3 x SGD/momentum/Adam), jnp-fallback == Pallas-interpret bit
    # identity, and the fail-open contract (bf16 + int8-EF grid cells
    # run in the full suite).
    stage fused-update python -m pytest tests/test_fused_update.py \
        -q -m "not slow"
    # Elastic re-form: unit protocol tests PLUS the 2-proc SIGKILL
    # survivor-continue test (fault-injected die -> re-form at world
    # size 1 -> final-params parity with an uninterrupted run) — the
    # one scenario that proves the whole generation machinery.
    stage elastic python -m pytest tests/test_elastic.py \
        -q -m "not slow_elastic"
    # Graceful preemption: notice/drain protocol units PLUS the 2-proc
    # SIGTERM drain (notice -> emergency commit -> clean exit 0 ->
    # proactive re-form, bit-exact survivor parity under a 30 s
    # heartbeat timeout it never waited for) and the corrupt-shard
    # ring-buddy replica restore.
    stage preempt python -m pytest tests/test_preemption.py -q
    stage launcher python -m pytest tests/test_launcher.py -q
else
    # Full path additionally lints the CPU-lowered negotiated program
    # set (ZeRO-2/3 residency, overlap schedule, hierarchical lossy
    # placement — with embedded positive controls proving the rules
    # still fire).
    stage analysis-hlo python -m horovod_tpu.analysis hlo
    # Full suite (includes the 2-proc integration tests the reference
    # runs as `horovodrun -np 2 pytest`, gen-pipeline.sh:210).
    stage suite python -m pytest tests/ -q
fi

# Multi-chip sharding must compile + execute on a virtual device mesh
# (the driver's dryrun contract: dp/tp/sp/ep plus a pp>=2 GPipe config;
# the driver also runs 4/16/32 — 8 here keeps CI under half an hour).
stage dryrun-8 python __graft_entry__.py dryrun 8

# Single-chip entry point compiles and runs (CPU here).
stage entry python __graft_entry__.py

exit $fail
