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
NAMES_VERSION = "hvd-names-1"


def ensure_compile_cache() -> str:
    """Place JAX's persistent compile cache; returns the directory.

    ``JAX_COMPILATION_CACHE_DIR`` set: nothing to do — JAX reads it at
    import, and no code path sets another.  Unset: ``<checkout>/.jax_cache``
    (a fixed place: a directory that moves between runs never hits),
    exported too so spawned ranks and child processes inherit it.  This
    is the only place that names a compile cache path.  Either way
    ``NAMES_VERSION`` becomes part of every key."""
    from jax._src import cache_key

    cache_key.custom_hook = lambda: NAMES_VERSION
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
