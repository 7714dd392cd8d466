"""Platform selection and XLA client bootstrap.

The reference selects its transport stack at runtime from env vars
(``HOROVOD_CONTROLLER``/``HOROVOD_CPU_OPERATIONS``, see
reference ``horovod/common/utils/env_parser.cc:41-109``).  On TPU the
"transport" is the XLA runtime itself, so the analogous choice is which
PJRT platform backs the process (``tpu`` in production, ``cpu`` with a
forced device count for tests) and whether cross-process CPU collectives
are enabled (gloo — the same library the reference uses for its CPU data
plane, ``horovod/common/ops/gloo_operations.cc``).

This must run BEFORE any JAX backend is initialized.
"""

from __future__ import annotations

import os
import threading

_configured = False

# XLA-side half of the overlap engine (docs/overlap.md): the bucketed
# ppermute schedule only hides communication when the TPU compiler may
# (a) run collective-permutes asynchronously and (b) re-order compute
# under the in-flight transfers (the latency-hiding scheduler).  Both
# are libtpu flags and must be in the environment before PJRT init.
_OVERLAP_LIBTPU_FLAGS = (
    "--xla_tpu_enable_latency_hiding_scheduler=true",
    "--xla_tpu_enable_async_collective_permute=true",
)


def _enable_overlap_xla_flags() -> None:
    """Append the overlap engine's libtpu flags to LIBTPU_INIT_ARGS,
    never overriding a flag the operator already pinned."""
    existing = os.environ.get("LIBTPU_INIT_ARGS", "")
    added = [f for f in _OVERLAP_LIBTPU_FLAGS
             if f.split("=", 1)[0] not in existing]
    if added:
        os.environ["LIBTPU_INIT_ARGS"] = " ".join(
            filter(None, [existing] + added))


# The checkout (or install prefix) that holds this package: the default
# compile cache lives inside it, so every process started from the same
# tree resolves the same path without being told.
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


# The version of the names the compiled programs carry: the ``hvd_*``
# scopes and kernel names in every instruction's ``op_name``
# (docs/perf.md).  They are metadata, which JAX keeps out of the cache
# key, so the cache serves an executable compiled from a source with
# other names, or none, and a profile of it shows those
# (``jax_compilation_cache_include_metadata_in_key`` would key on every
# file path and line number as well).  Raise it with a change to a
# scope's or a kernel's name.
NAMES_VERSION = "hvd-names-2"


# ---------------------------------------------------------------------------
# A record for every program compiled (docs/flight-recorder.md)
# ---------------------------------------------------------------------------

# JAX times the three phases of a compile and says so through
# ``jax.monitoring``, each with the program's ``fun_name``
# (jax/_src/dispatch.py, pxla.py), and its persistent cache says what it
# did in between (jax/_src/compiler.py).  They arrive in order on the
# compiling thread:
#   trace* -> lower -> backend[requests_use_cache, hits | misses] -> done
_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND = "/jax/core/compile/backend_compile_duration"
_CACHE_ASKED = "/jax/compilation_cache/compile_requests_use_cache"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
_CACHE_SAVED = "/jax/compilation_cache/compile_time_saved_sec"


class _Compiling(threading.local):
    """The program this thread is compiling, as far as JAX has said."""

    def __init__(self):
        # fun_name -> (start, end) of its longest trace since the last
        # program.  By name, not in a row: a step's lowering traces
        # hundreds of small ``jit``s after the step's own trace has ended
        self.traces: dict = {}
        self.seconds = 0.0           # of the programs recorded so far
        self.reset()

    def reset(self) -> None:
        self.traces.clear()
        self.lower = None            # (fun_name, start, end)
        self.forget_cache()

    def forget_cache(self) -> None:
        self.asked = self.hit = False
        self.retrieval_s = self.saved_s = None


_compiling = _Compiling()
_listening = False


def _on_compile_span(event: str, start: float, end: float,
                     fun_name: str = "", **_) -> None:
    """One of the three phases ended (``start`` and ``end`` on
    ``time.time()``, the ring's ``wall`` clock).  The last one writes
    the program's ``hvd_compile`` record."""
    state = _compiling
    if event == _TRACE:
        if state.lower is not None:   # lowered and never compiled: dropped
            state.reset()
        known = state.traces.get(fun_name)
        if known is None or end - start >= known[1] - known[0]:
            if len(state.traces) >= 4096:      # traces no compile follows
                state.traces.clear()
            state.traces[fun_name] = (start, end)
    elif event == _LOWER:
        state.lower = (fun_name, start, end)
        state.forget_cache()          # of a compile that raised
    elif event == _BACKEND:
        try:
            _record_program(state, fun_name, start, end)
        except Exception:             # a record never fails a compile
            pass
        state.reset()


def _on_cache_event(event: str, **_) -> None:
    if event == _CACHE_ASKED:
        _compiling.asked = True
    elif event == _CACHE_HIT:
        _compiling.hit = True


def _on_cache_seconds(event: str, seconds: float, **_) -> None:
    if event == _CACHE_RETRIEVAL:
        _compiling.retrieval_s = seconds
    elif event == _CACHE_SAVED:
        _compiling.saved_s = seconds


def _record_program(state: _Compiling, fun_name: str, start: float,
                    end: float) -> None:
    """``hvd_compile`` in the flight ring, and the same seconds, once,
    in ``hvd_compile_seconds_total`` (``path="warm"``: the persistent
    cache served the executable)."""
    from horovod_tpu.runtime import flight, metrics

    lowered, lower_start, lower_end = state.lower or (fun_name, start, start)
    # the program's trace is the outermost: the one the lowering names
    # (``jit(step)`` of ``step``), not the ``jit``s traced inside it
    traced = lowered[lowered.find("(") + 1:-1] if lowered.endswith(")") \
        else lowered
    trace_start, trace_end = state.traces.get(traced,
                                              (lower_start, lower_start))
    fields = {
        "fun_name": fun_name,
        "trace_s": trace_end - trace_start,
        "lower_s": lower_end - lower_start,
        "backend_s": end - start,
        # no request reached the cache: JAX built no key for the program
        "cache": ("hit" if state.hit else "miss" if state.asked
                  else "uncached"),
        "start_wall": trace_start,
    }
    if state.hit:
        fields.update(retrieval_s=state.retrieval_s, saved_s=state.saved_s)
    parent = flight.open_span()
    if parent is not None:
        fields["parent"] = parent
    flight.record("hvd_compile", **fields)
    seconds = fields["trace_s"] + fields["lower_s"] + fields["backend_s"]
    state.seconds += seconds
    metrics.counter("hvd_compile_seconds_total").inc(
        seconds, path="warm" if state.hit else "cold")


def compiled_seconds() -> float:
    """The seconds of the programs this thread has compiled so far, as
    counted into ``hvd_compile_seconds_total``: who times a stretch that
    may hold a compile (``runtime/aot_cache``) takes them off, so that
    each second is counted once."""
    return _compiling.seconds


def _listen_to_compiles() -> None:
    """Register the listeners above, once a process.  They run only
    while a program is compiled: a step that compiles nothing pays
    nothing."""
    global _listening
    if _listening:
        return
    _listening = True
    from jax import monitoring

    monitoring.register_event_time_span_listener(_on_compile_span)
    monitoring.register_event_listener(_on_cache_event)
    monitoring.register_event_duration_secs_listener(_on_cache_seconds)


def ensure_compile_cache() -> str:
    """Place JAX's persistent compile cache; returns the directory.

    ``JAX_COMPILATION_CACHE_DIR`` set: nothing to do — JAX reads it at
    import, and no code path sets another.  Unset: ``<checkout>/.jax_cache``
    (a fixed place: a directory that moves between runs never hits),
    exported too so spawned ranks and child processes inherit it.  This
    is the only place that names a compile cache path.  Either way
    ``NAMES_VERSION`` becomes part of every key, and every program
    compiled from here on leaves an ``hvd_compile`` record."""
    from jax._src import cache_key

    cache_key.custom_hook = lambda: NAMES_VERSION
    _listen_to_compiles()
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(_CHECKOUT, ".jax_cache")
        os.environ["JAX_COMPILATION_CACHE_DIR"] = path
        import jax

        # jax read the (unset) variable when it was imported
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def ensure_platform() -> None:
    """Apply HOROVOD_PLATFORM / CPU-collective config before backend init.

    Idempotent.  Called from :func:`horovod_tpu.init` and from test
    conftest.  ``HOROVOD_PLATFORM=cpu`` forces the host platform (used by
    the launcher for CPU-only test jobs, the way the reference CI runs
    ``horovodrun -np 2 pytest`` on localhost,
    reference ``.buildkite/gen-pipeline.sh:210``).
    """
    global _configured
    if _configured:
        return
    _configured = True

    from horovod_tpu.common import config as _config

    ensure_compile_cache()
    if _config.get("overlap"):
        _enable_overlap_xla_flags()

    platform = str(_config.get("platform") or "")
    import jax

    if platform:
        # jax read JAX_PLATFORMS when it was imported; HOROVOD_PLATFORM
        # wins over it for this process.
        jax.config.update("jax_platforms", platform)
    effective = jax.config.jax_platforms or ""
    if platform == "cpu" or effective == "cpu":
        # Cross-process CPU collectives ride gloo, mirroring the
        # reference's gloo CPU data plane.  Only in a multi-process
        # launch: recent jaxlib gloo bindings require the
        # jax.distributed client at backend init, so a single-process
        # run (forced-device-count tests) must stay on the default
        # in-process collectives.
        multiproc = (_config.get("coordinator_addr")
                     or int(os.environ.get("HOROVOD_SIZE", "1") or 1) > 1)
        if multiproc:
            jax.config.update(
                "jax_cpu_collectives_implementation", "gloo")


def cpu_asked_for(env=None) -> bool:
    """Whether this environment asks for the CPU platform outright
    (``HOROVOD_PLATFORM=cpu``, else ``JAX_PLATFORMS=cpu``).  Only then
    may a measuring entry point run without a TPU, and only then does
    the launcher leave its ranks' chip assignment out."""
    env = os.environ if env is None else env
    return (env.get("HOROVOD_PLATFORM") or env.get("JAX_PLATFORMS")
            or "").strip().lower() == "cpu"


def pallas_interpret(requested: bool | None = None) -> bool:
    """``interpret=`` for a ``pallas_call``: kernels compile through
    Mosaic on a TPU backend and run interpreted elsewhere (the CPU test
    mesh).  Asking for the interpreter on a TPU backend raises — a
    kernel that quietly stops being a kernel is measured as one."""
    import jax

    if jax.default_backend() == "tpu":
        if requested:
            raise ValueError(
                "Pallas interpret mode was requested on a TPU backend; "
                "kernels compile through Mosaic there")
        return False
    return True if requested is None else bool(requested)


def platform_name() -> str:
    import jax

    return jax.devices()[0].platform
