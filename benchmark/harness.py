"""One cell measured in the process that holds the chip.

``measure`` sets the trainer up through the program's normal entry
points, checks it against the plain reference, warms up, measures a
window of about ``seconds`` seconds and, in a traced run, profiles six
more steps and reduces the trace.  It returns a record; ``result_line``
turns one or, for a launched world, several records into the one line
the contract asks for.

Timing is the pattern of ``bench.py``: steps dispatched back to back and
blocked on the loss, here every ``chunk_steps`` steps.  Nothing compiles
inside the window: every compile request is counted and one inside the
window makes the run incorrect.
"""

from __future__ import annotations

import glob
import hashlib
import json
import math
import os
import shutil
import statistics
import time

from benchmark import manifest, reduce

TRACED_STEPS = 6
# the loop's host annotations, outermost first (reduce.label_gaps lets
# the later, inner one win a tie)
SPANS = ("bench_step", "dispatch", "block")


class NoChip(RuntimeError):
    """JAX's backend is not a TPU, or holds fewer chips than the cell
    needs: nothing is measured and no result is printed."""


def say(**facts) -> None:
    """An earlier line of the run: worth reading, not part of the result."""
    print(json.dumps(facts), flush=True)


class CompileCounter:
    """Counts compile requests and persistent-cache hits through
    ``jax.monitoring`` (the listener pattern of ``chip_smoke.py``)."""

    def __init__(self):
        from jax import monitoring

        self.requests = self.hits = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event: str, _seconds: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.requests += 1

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


def measure(cell: manifest.Cell, seed: int, seconds: float, trace: bool,
            t0: float, allow_cpu: bool = False, agree=None) -> dict:
    """Measure ``cell`` in this process.  ``t0`` is ``time.time()`` at
    the start of the run's first process.  ``agree(n)`` makes one number
    of chunks the world's (a launched world's ranks must run the same
    steps); ``allow_cpu`` exists for the tests, which pass a toy cell."""
    import jax
    import numpy as np

    # every program goes to the persistent cache, also the ones that
    # compile in under a second, so a second run compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compiles = CompileCounter()

    import horovod_tpu as hvd

    def since() -> float:
        """Seconds since the run's first process started."""
        return time.time() - t0

    hvd.init()
    init_s = since()
    device = jax.local_devices()[0]
    if device.platform != "tpu" and not allow_cpu:
        raise NoChip(f"no TPU found: JAX's backend is {device.platform!r} "
                     f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r})")
    if len(jax.devices()) < cell.chips:
        raise NoChip(f"the cell needs {cell.chips} chips, JAX sees "
                     f"{len(jax.devices())}")
    peaks = manifest.load_peaks(cell, device.device_kind)
    job, config = cell.job, cell.config
    family = manifest.load_family(cell)

    say(at=since(), did="hvd.init", device=device.device_kind)
    trainer = family.Trainer(config, job, seed, hvd)
    say(at=since(), did="weights and batches on the device")
    reference = trainer.check_reference()
    say(at=since(), did="reference check", **reference)
    probe = (trainer.first_update_probe()
             if hasattr(trainer, "first_update_probe") else None)

    t_compile = time.perf_counter()
    trainer.compile()
    compile_s = time.perf_counter() - t_compile
    has_kernel = reduce.MOSAIC_TARGET in trainer.compiled_text()
    say(at=since(), did="lower().compile()", compile_s=compile_s,
        mosaic_custom_call=has_kernel)

    losses = [trainer.run_step(0)]
    jax.block_until_ready(losses[0])
    if probe is not None:
        probe = trainer.observe_first_update(probe)

    # warm-up: step 0 above, then one chunk, timed: the window is a whole
    # number of chunks fixed before it starts, so that every rank of a
    # world runs the same steps
    chunk_steps = job["chunk_steps"]
    step = 1
    t_chunk = time.perf_counter()
    for _ in range(chunk_steps):
        losses.append(trainer.run_step(step))
        step += 1
    jax.block_until_ready(losses[-1])
    chunk_s = time.perf_counter() - t_chunk
    chunks = max(1, math.ceil(seconds / chunk_s))
    if agree is not None:
        chunks = agree(chunks)
    warm_losses = len(losses)

    requests_before = compiles.requests
    chunk_walls = []
    setup_s = since()
    t_window = time.perf_counter()
    for _ in range(chunks):
        t_chunk = time.perf_counter()
        for _ in range(chunk_steps):
            losses.append(trainer.run_step(step))
            step += 1
        jax.block_until_ready(losses[-1])
        chunk_walls.append(time.perf_counter() - t_chunk)
    window_s = time.perf_counter() - t_window
    compiles_in_window = compiles.requests - requests_before

    losses = [float(np.asarray(v).reshape(-1)[0]) for v in losses]
    window_losses = losses[warm_losses:]
    steps = len(window_losses)
    per_chip = steps * trainer.samples_per_step / window_s / cell.chips
    flops = family.model_flops_per_sample(config, job)
    memory_peak = _memory_peak_bytes(device)
    digest = hashlib.sha256()
    for leaf in jax.tree_util.tree_leaves(trainer.params()):
        digest.update(np.asarray(leaf.addressable_data(0)).tobytes())

    record = {
        "workload": cell.name, "seed": seed, "rank": hvd.rank(),
        "attempted": steps,
        "failed": sum(1 for v in window_losses if not math.isfinite(v)),
        "reference": reference, "first_update": probe,
        "loss_step0": losses[0], "loss_last": window_losses[-1],
        "compiles_in_window": compiles_in_window,
        "params_sha256": digest.hexdigest(),
        "end_to_end": {
            config["sample"]["throughput_metric"]:
                per_chip * trainer.units_per_sample,
            "mfu": flops * per_chip / peaks["bf16_flops_per_s"],
            "setup_s": setup_s,
        },
        "counters": {
            "init_s": init_s, "compile_s": compile_s,
            "cache_misses": compiles.requests - compiles.hits,
            "compile_requests": compiles.requests,
            "chunk_steps": chunk_steps, "chunk_walls": chunk_walls,
            "window_s": window_s, "mosaic_custom_call": has_kernel,
            "memory_peak_bytes": memory_peak,
            "model_flops_per_sample": flops, "peaks": peaks,
            "kernel_costs": (family.kernel_costs(config, job)
                             if hasattr(family, "kernel_costs") else {}),
        },
        "device": {"platform": device.platform, "kind": device.device_kind,
                   "count": len(jax.devices()),
                   "memory_peak_bytes": memory_peak},
    }
    say(at=since(), did="window", steps=steps, window_s=window_s,
        memory_stats=device.memory_stats(),
        step_ms_p50=statistics.median(chunk_walls) / chunk_steps * 1e3,
        loss_step0=losses[0], loss_last=window_losses[-1],
        **record["end_to_end"])

    if trace:
        traced = _trace_steps(cell, trainer, step, profile=hvd.rank() == 0)
        if traced is not None:
            record["device"]["busy_s"] = traced.mean(
                lambda ops: reduce.total(reduce.busy(ops))) * 1e-9
            record["device"]["window_s"] = traced.mean(
                lambda ops: reduce.total([reduce.window(ops)])) * 1e-9
            record["breakdown"] = _breakdown(traced)
            record["per_layer"] = _read_layers(
                cell, traced, record["counters"], from_trace=True)
    return record


def _memory_peak_bytes(device) -> int:
    """The most the chip held, read after the window.  The TPU runtime
    counts a loaded program's scratch memory under ``bytes_reserved``
    and not under ``bytes_in_use`` (ResNet-50's step: 9.1 GB reserved,
    0.8 GB in use; PR 22), so the peak of training is the reserved peak
    plus the live buffers, unless set-up's buffers peaked higher.  A
    backend that reports nothing (the CPU of the tests) gives 0."""
    stats = device.memory_stats() or {}
    return int(max(stats.get("peak_bytes_in_use", 0),
                   stats.get("peak_bytes_reserved", 0)
                   + stats.get("bytes_in_use", 0)))


def _trace_steps(cell: manifest.Cell, trainer, step: int, profile: bool):
    """``TRACED_STEPS`` more steps, dispatched as the window dispatches
    them, each under a ``StepTraceAnnotation`` written from here (the
    program has no span of its own yet).  Every rank of a world runs
    them; the one that profiles (the first: one chip's trace, its own)
    gets the reduced trace back, the others ``None``."""
    import contextlib

    import jax
    from jax import profiler

    trace_dir = os.path.join(cell.out_dir, "trace")
    with contextlib.ExitStack() as stack:
        if profile:
            shutil.rmtree(trace_dir, ignore_errors=True)
            options = profiler.ProfileOptions()
            options.python_tracer_level = 0    # the loop's spans are enough
            stack.enter_context(profiler.trace(trace_dir,
                                               profiler_options=options))
        for k in range(TRACED_STEPS):
            with profiler.StepTraceAnnotation(SPANS[0], step_num=k):
                with profiler.TraceAnnotation(SPANS[1]):
                    loss = trainer.run_step(step + k)
        with profiler.TraceAnnotation(SPANS[2]):
            jax.block_until_ready(loss)
    if not profile:
        return None
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise RuntimeError(f"expected one trace under {trace_dir}, "
                           f"found {found}")
    return reduce.read_trace(found[0], TRACED_STEPS, SPANS)


def _breakdown(traced: reduce.Trace) -> dict:
    """Of the first chip: the ten operations with most time, and the
    five longest idle gaps with what the host was doing in each."""
    ops = next(iter(traced.chips.values()))
    return {"device_ops": reduce.seconds_by_signature(ops)[:10],
            "idle_gaps": reduce.label_gaps(reduce.idle_gaps(ops)[:5],
                                           traced.spans)}


def _read_layers(cell: manifest.Cell, traced, counters: dict,
                 from_trace: bool) -> dict:
    """The per-layer metrics of the cell that come from the device trace
    (``from_trace``) or from clocks and counters, each by its reader; a
    reader that finds nothing returns ``None`` and is left out."""
    values = {}
    for metric in cell.per_layer:
        if (metric["source"] == "device_trace") != from_trace:
            continue
        value = manifest.load_layer_reader(cell, metric["name"])(
            traced, counters, cell)
        if value is not None:
            values[metric["name"]] = {"value": float(value),
                                      "unit": metric["unit"]}
    return values


def _worst_counters(records: list) -> dict:
    """One set of counters for a world: of a number the largest any rank
    counted (the last to be ready, the most cache misses), of anything
    else rank 0's."""
    merged = dict(records[0]["counters"])
    for key, value in merged.items():
        if isinstance(value, (int, float)) and not isinstance(value, bool):
            merged[key] = max(r["counters"][key] for r in records)
    return merged


def result_line(cell: manifest.Cell, records: list, trace: bool,
                world_ok: bool = True) -> dict:
    """The contract's object from the records of every rank (one for an
    inline cell).  ``world_ok`` false — a rank died or left with another
    code than 0 — fails every step."""
    first = records[0]
    if not world_ok:
        return {"correct": False, "attempted": first["attempted"],
                "failed": first["attempted"], "metrics": {},
                "device": first["device"]}
    family = manifest.load_family(cell)
    checks = {
        "reference": all(r["reference"]["ok"] for r in records),
        "losses_finite": all(r["failed"] == 0 for r in records),
        "loss_fell": all(r["loss_last"] < r["loss_step0"] for r in records),
        "no_compile_in_window":
            all(r["compiles_in_window"] == 0 for r in records),
        "params_identical":
            len({r["params_sha256"] for r in records}) == 1,
    }
    if first["first_update"] is not None:
        update = family.check_first_update(
            [r["first_update"] for r in records])
        say(check="first_update", **update)
        checks["first_update"] = update["ok"]
    say(checks=checks)

    def slowest(name: str) -> float:
        """Throughput is the slowest rank's, set-up the last one's."""
        values = [r["end_to_end"][name] for r in records]
        better = next(m["better"] for m in cell.end_to_end
                      if m["name"] == name)
        return min(values) if better == "higher" else max(values)

    if trace:
        metrics = {**_read_layers(cell, None, _worst_counters(records),
                                  from_trace=False), **first["per_layer"]}
    else:
        metrics = {m["name"]: {"value": slowest(m["name"]),
                               "unit": m["unit"]} for m in cell.end_to_end}
    attempted = first["attempted"]
    device = dict(first["device"], memory_peak_bytes=max(
        r["device"]["memory_peak_bytes"] for r in records))
    line = {"correct": all(checks.values()), "attempted": attempted,
            "failed": max(r["failed"] for r in records),
            "metrics": metrics, "device": device}
    if trace:
        line["breakdown"] = first["breakdown"]
    return line
