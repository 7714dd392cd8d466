"""Sampled continuous capture: device truth from a live training job.

``HOROVOD_PROFILE_EVERY_N_STEPS=N`` makes ``hvd.trace_step`` capture
one full step every N into a rotating per-rank directory
(``HOROVOD_PROFILE_DIR/rank<k>/step<nnnnnnnn>/``, newest
``HOROVOD_PROFILE_KEEP`` kept), analyze it on a background thread via
the stdlib xplane reader, and feed the result into the PR 6 metrics
registry:

* ``hvd_device_compute_seconds`` — merged device compute per step;
* ``hvd_device_comm_seconds`` / ``hvd_device_comm_hidden_seconds`` /
  ``hvd_device_comm_exposed_seconds`` — device collective time and how
  much of it the overlap/ZeRO schedules actually hid under math;
* ``hvd_device_comm_kind_seconds{kind=...}`` — per-collective split;
* ``hvd_mfu`` — when a flops-per-step hint is registered
  (:func:`set_step_flops`), on an
  accelerator (the spec table's peak; a CPU run reports none).

The gauges ride the KV snapshot publisher to the launcher's fleet
``/metrics`` merge and land on flight-recorder dumps, so device truth
is live fleet-wide, not a post-hoc notebook exercise.

Design constraints:

* the module imports stdlib-only (jax lazily inside the hooks) — the
  metrics plane pulls this in from ``trace_step``;
* every hook is advisory: a capture/analysis failure increments a
  counter and never takes a training step down;
* analysis runs off-thread; :func:`drain` joins outstanding analyzers
  (so that a reader of the gauges sees deterministic results).
"""

from __future__ import annotations

import json
import os
import shutil
import threading
import time

from horovod_tpu.common import config as _config
from horovod_tpu.common import logging as _log

_lock = threading.Lock()
_state = {
    "count": 0,            # trace_step spans seen
    "active": None,        # in-flight capture dict
    "threads": [],         # outstanding analyzer threads
    "last": None,          # last analysis result dict
    "flops": None,         # flops per trace_step span (hint)
    "warned": False,
    "wire0": 0.0,          # wire-byte counter at capture start
}


def _metrics():
    from horovod_tpu.runtime import metrics as _m

    return _m


def set_step_flops(flops: float | None) -> None:
    """Register the XLA ``cost_analysis`` flops executed per
    ``trace_step`` span (i.e. per dispatch — multiply by
    steps-per-dispatch when the span chains several optimizer steps).
    Enables the ``hvd_mfu`` gauge and the report's MFU column."""
    with _lock:
        _state["flops"] = float(flops) if flops else None


def last_analysis() -> dict | None:
    """Most recent completed capture analysis (or None)."""
    with _lock:
        return _state["last"]


def reset() -> None:  # test hook
    with _lock:
        _state.update(count=0, active=None, threads=[], last=None,
                      flops=None, warned=False, wire0=0.0)


def _profile_root() -> str:
    return str(_config.get("profile_dir") or "hvd_profile")


def _rank() -> int:
    try:
        from horovod_tpu.common import basics as _basics

        st = _basics.state()
        return st.rank if st.initialized else 0
    except Exception:
        return 0


def _bridge_active() -> bool:
    """True when the whole-run JaxProfilerBridge capture owns the
    profiler — jax allows one trace at a time, so sampling must yield."""
    try:
        from horovod_tpu.common import basics as _basics

        prof = _basics.state().profiler
        return bool(prof is not None and getattr(prof, "_active", True))
    except Exception:
        return False


def maybe_start(step: int | None) -> dict | None:
    """Called by ``trace_step`` on span entry (BEFORE the step
    annotation opens, so the annotation lands inside the capture).
    Returns a capture token to pass to :func:`stop_and_analyze`, or
    None when this span is not sampled.  Never raises."""
    try:
        every = int(_config.get("profile_every_n") or 0)
    except (TypeError, ValueError):
        every = 0
    if every <= 0:
        return None
    with _lock:
        count = _state["count"]
        _state["count"] = count + 1
        if _state["active"] is not None:
            return None  # a prior span's capture never stopped; bail
        # skip span 0: the first traced span usually pays the jit
        # compile and would dominate every rotating window
        if count == 0 or count % every != 0:
            return None
        # Backpressure: a real capture takes tens of seconds to parse;
        # when steps outpace the analyzer, piling up a thread (each
        # holding the full xplane bytes) per sample would burn host
        # memory/GIL against training AND let _rotate delete capture
        # dirs whose queued analysis never ran.  Skip sampling until
        # the in-flight analysis finishes — the next due span picks up.
        _state["threads"] = [x for x in _state["threads"]
                             if x.is_alive()]
        backlog = bool(_state["threads"])
    if backlog:
        try:
            _metrics().counter(
                "hvd_profile_skips_total",
                "Sampled spans skipped because the previous capture's "
                "analysis was still in flight (analyzer backpressure)."
            ).inc()
        except Exception:
            pass
        return None
    if _bridge_active():
        with _lock:
            if not _state["warned"]:
                _state["warned"] = True
                _log.warning(
                    "HOROVOD_PROFILE_EVERY_N_STEPS is set but the "
                    "whole-run jax profiler capture "
                    "(HOROVOD_TIMELINE_JAX_PROFILER) owns the profiler; "
                    "sampled captures are disabled for this run")
        return None
    step_id = int(step) if step is not None else count
    out_dir = os.path.join(_profile_root(), f"rank{_rank()}",
                           f"step{step_id:08d}")
    try:
        os.makedirs(out_dir, exist_ok=True)
        import jax

        jax.profiler.start_trace(out_dir)
    except Exception as exc:
        try:
            _metrics().counter(
                "hvd_profile_capture_failures_total",
                "Sampled-capture start/stop/analyze failures.").inc()
        except Exception:
            pass
        with _lock:
            if not _state["warned"]:
                _state["warned"] = True
                _log.warning(f"sampled profiler capture unavailable: "
                             f"{exc!r}")
        return None
    token = {"dir": out_dir, "step": step_id, "t0": time.time()}
    with _lock:
        _state["active"] = token
        try:
            _state["wire0"] = _metrics().counter(
                "hvd_data_wire_bytes_total").total()
        except Exception:
            _state["wire0"] = 0.0
    return token


def _sync_devices() -> None:
    """Drain in-flight device work before ``stop_trace``: dispatch is
    async (TPU especially), so without a fence the sampled step's
    device execution would still be running when the trace stops — the
    capture would hold the host-side dispatch but little of the device
    work it exists to measure.  A trivial computation placed on each
    local device is the fence: XLA runs per-device programs in dispatch
    order, so it completes only after everything queued before it."""
    import jax
    import jax.numpy as jnp

    for dev in jax.local_devices():
        jax.block_until_ready(jax.device_put(jnp.zeros(()), dev) + 1)


def stop_and_analyze(token: dict) -> None:
    """Called by ``trace_step`` on span exit for a sampled span: stop
    the trace and analyze it on a background thread.  Never raises."""
    try:
        import jax

        try:
            # fence cost lands only on sampled spans (1/N), which are
            # already perturbed by the capture itself (docs/perf.md)
            _sync_devices()
        except Exception:
            pass  # advisory: stop_trace still lands whatever executed
        jax.profiler.stop_trace()
    except Exception:
        try:
            _metrics().counter(
                "hvd_profile_capture_failures_total",
                "Sampled-capture start/stop/analyze failures.").inc()
        except Exception:
            pass
        with _lock:
            _state["active"] = None
        return
    with _lock:
        _state["active"] = None
        flops = _state["flops"]
        wire0 = _state["wire0"]
        try:
            wire_bytes = max(
                0.0,
                _metrics().counter("hvd_data_wire_bytes_total").total()
                - wire0)
        except Exception:
            wire_bytes = 0.0
    t = threading.Thread(
        target=_analyze, args=(token, flops, wire_bytes),
        name="hvd-perf-analyze", daemon=True)
    with _lock:
        _state["threads"] = [x for x in _state["threads"]
                             if x.is_alive()] + [t]
    t.start()


def drain(timeout_s: float = 30.0) -> None:
    """Join outstanding analyzer threads (bounded): call it before
    reading :func:`last_analysis` or the gauges."""
    deadline = time.monotonic() + timeout_s
    with _lock:
        threads = list(_state["threads"])
    for t in threads:
        t.join(max(0.0, deadline - time.monotonic()))


def _peak_flops() -> float | None:
    """The MFU denominator: the chip's spec-sheet peak.  A CPU run has
    no utilization to report; an accelerator the table does not know
    raises there."""
    import jax

    from horovod_tpu.perf import attribution as _attr

    dev = jax.devices()[0]
    if dev.platform == "cpu":
        return None
    return _attr.peak_flops_per_chip(dev.device_kind)


def _analyze(token: dict, flops, wire_bytes) -> None:
    try:
        result = analyze_capture(token["dir"], flops_per_step=flops,
                                 wire_bytes=wire_bytes)
        if result is None:
            raise RuntimeError("no xplane.pb landed in the capture dir")
        result["rank"] = _rank()
        result["capture_dir"] = token["dir"]
        result["captured_step"] = token["step"]
        with open(os.path.join(token["dir"], "analysis.json"), "w") as f:
            json.dump(result, f)
        _publish(result)
        with _lock:
            _state["last"] = result
        from horovod_tpu.runtime import flight as _flight

        tot = result.get("totals", {})
        _flight.record("device_truth", step=token["step"],
                       compute_s=tot.get("compute_s"),
                       comm_exposed_s=tot.get("comm_exposed_s"),
                       mfu=tot.get("mfu"))
    except Exception as exc:
        try:
            _metrics().counter(
                "hvd_profile_capture_failures_total",
                "Sampled-capture start/stop/analyze failures.").inc()
            _log.debug(f"sampled-capture analysis failed: {exc!r}")
        except Exception:
            pass
    finally:
        try:
            _rotate(os.path.dirname(token["dir"]))
        except Exception:
            pass


def analyze_capture(capture_dir: str, flops_per_step=None,
                    wire_bytes=None) -> dict | None:
    """Parse + attribute the newest xplane.pb under ``capture_dir``.
    Returns the attribution dict (with ``xplane_path``) or None when no
    capture file exists."""
    from horovod_tpu.perf import attribution as _attr
    from horovod_tpu.perf import xplane as _xp

    path = _newest_xplane(capture_dir)
    if path is None:
        return None
    space = _xp.read_xspace(path, want_stats=_xp.ANALYSIS_STATS)
    peak = _peak_flops()
    result = _attr.attribute(space, flops_per_step=flops_per_step,
                             peak_flops=peak, wire_bytes=wire_bytes)
    result["xplane_path"] = path
    if peak:
        result["peak_flops_per_chip"] = peak
    return result


def _newest_xplane(root: str) -> str | None:
    newest, newest_m = None, -1.0
    for dirpath, _dirnames, filenames in os.walk(root):
        for fn in filenames:
            if fn.endswith(".xplane.pb"):
                p = os.path.join(dirpath, fn)
                try:
                    m = os.path.getmtime(p)
                except OSError:
                    continue
                if m > newest_m:
                    newest, newest_m = p, m
    return newest


def _publish(result: dict) -> None:
    """Device-truth gauges into the metrics registry (KV-published to
    the launcher fleet merge by the PR 6 publisher)."""
    m = _metrics()
    tot = result.get("totals") or {}
    step_pairs = (
        ("hvd_device_compute_seconds",
         "Device compute seconds in the last sampled step (xplane "
         "truth).", "compute_s_per_step"),
        ("hvd_device_comm_seconds",
         "Device collective seconds in the last sampled step.",
         "comm_s_per_step"),
        ("hvd_device_comm_hidden_seconds",
         "Device collective seconds overlapped under compute in the "
         "last sampled step.", "comm_hidden_s_per_step"),
        ("hvd_device_comm_exposed_seconds",
         "Device collective seconds NOT hidden under compute in the "
         "last sampled step — the overlap schedules' true residual.",
         "comm_exposed_s_per_step"),
    )
    for name, help_, key in step_pairs:
        if key in tot:
            m.gauge(name, help_).set(tot[key])
    if tot.get("mfu") is not None:
        m.gauge("hvd_mfu",
                "Model flops utilization of the last sampled step "
                "(cost_analysis flops / peak chip flops).").set(
            tot["mfu"])
    kinds: dict = {}
    for s in result.get("steps") or []:
        for k, v in (s.get("comm_by_kind") or {}).items():
            kinds[k] = kinds.get(k, 0.0) + v
    n = max(1, len(result.get("steps") or []))
    # The gauge reflects ONE capture: kinds absent from it (schedule
    # change, re-form) must not linger as phantom series in the fleet
    # merge — atomic swap, so a concurrent snapshot never sees the
    # partially-populated window between a reset and the re-sets.
    m.gauge(
        "hvd_device_comm_kind_seconds",
        "Per-collective device seconds per step in the last "
        "sampled capture.").replace(
        [({"kind": k}, round(v / n, 6)) for k, v in kinds.items()])
    m.counter("hvd_profile_captures_total",
              "Sampled step captures analyzed.").inc()
    m.gauge("hvd_profile_last_step",
            "Step index of the last sampled capture.").set(
        result.get("captured_step", -1))


def _rotate(rank_dir: str) -> None:
    """Keep the newest HOROVOD_PROFILE_KEEP step dirs per rank."""
    try:
        keep = max(1, int(_config.get("profile_keep")))
    except (TypeError, ValueError):
        keep = 4
    try:
        entries = sorted(
            e for e in os.listdir(rank_dir) if e.startswith("step"))
    except OSError:
        return
    for stale in entries[:-keep]:
        shutil.rmtree(os.path.join(rank_dir, stale), ignore_errors=True)
