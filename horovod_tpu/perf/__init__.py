"""Device-truth performance observatory (docs/perf.md).

The write half of the observability stack already exists: the
``JaxProfilerBridge`` records xplane captures, the overlap/ZeRO
schedules label their buckets with ``hvd_overlap_rs/math/ag<k>`` /
``hvd_zero2_rs<k>`` / ``hvd_zero3_ag<k>`` named scopes, and
``hvd.trace_step`` stamps every step with a
``jax.profiler.StepTraceAnnotation``.  This package is the read half:

* :mod:`horovod_tpu.perf.xplane` — a stdlib-only protobuf wire-format
  reader for the profiler's XSpace dumps (no TF/tensorboard import,
  same dependency discipline as ``runtime/metrics.py``);
* :mod:`horovod_tpu.perf.attribution` — maps device events onto the
  framework's scopes: per-step device comm hidden under math vs
  exposed, per-collective device seconds, compute seconds, MFU;
* :mod:`horovod_tpu.perf.capture` — sampled continuous capture
  (``HOROVOD_PROFILE_EVERY_N_STEPS``) feeding the
  ``hvd_device_*`` / ``hvd_mfu`` gauges of the PR 6 metrics plane;
* :mod:`horovod_tpu.perf.report` —
  ``python -m horovod_tpu.perf report <dir>``;
* :mod:`horovod_tpu.perf.goodput` — the wall-clock ledger: every
  second of a run classified into exclusive phases (init / compile /
  input_wait / compute / comm_exposed / checkpoint / reform /
  unattributed), fleet goodput + dominant-bottleneck naming + SLO
  burn alerts, ``python -m horovod_tpu.perf goodput <dir>``
  (docs/goodput.md).

Importing this package must stay dependency-free (stdlib only; jax is
imported lazily inside the capture hooks) — enforced by a subprocess
test in tests/test_perf.py.
"""

from __future__ import annotations

from horovod_tpu.perf.attribution import attribute, peak_flops_per_chip
from horovod_tpu.perf.capture import (
    drain,
    last_analysis,
    maybe_start,
    set_step_flops,
    stop_and_analyze,
)
from horovod_tpu.perf.goodput import (
    FleetGoodput,
    GoodputLedger,
    fleet_report,
)
from horovod_tpu.perf.report import analyze_dir, format_report
from horovod_tpu.perf.xplane import parse_xspace, read_xspace

__all__ = [
    "FleetGoodput",
    "GoodputLedger",
    "analyze_dir",
    "attribute",
    "drain",
    "fleet_report",
    "format_report",
    "last_analysis",
    "maybe_start",
    "parse_xspace",
    "peak_flops_per_chip",
    "read_xspace",
    "set_step_flops",
    "stop_and_analyze",
]
