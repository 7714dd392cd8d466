"""Process/topology state and lifecycle: the ``hvd.init()`` surface.

Parity with the reference's ``HorovodBasics`` (``horovod/common/basics.py:22-66``
backed by the C ABI in ``horovod/common/operations.cc:661-799``):
``init/shutdown/size/local_size/rank/local_rank`` plus build/enabled
introspection.  The TPU build keeps the same one-process-per-accelerator
model, but "rank negotiation" is jax.distributed's coordination service
plus launcher-provided env (the reference's gloo launcher exports the
same ``HOROVOD_RANK/SIZE/LOCAL_RANK/...`` names, ``run/gloo_run.py:152-163``),
and the "communicator" is a `jax.sharding.Mesh` whose single ``hvd`` axis
spans one lead device per process.
"""

from __future__ import annotations

import os
import socket
import threading

import numpy as np

from horovod_tpu.common import config as _config
from horovod_tpu.common import logging as _log
from horovod_tpu.common.platform import ensure_platform
from horovod_tpu.common.types import HorovodTpuError
from horovod_tpu.runtime import flight as _flight


class _State:
    """Process-global singleton (reference ``global_state.h:42-122``)."""

    def __init__(self) -> None:
        self.initialized = False
        self.rank = 0
        self.size = 1
        self.local_rank = 0
        self.local_size = 1
        self.cross_rank = 0
        self.cross_size = 1
        self.mesh = None            # world Mesh over per-process lead devices
        self.local_mesh = None      # Mesh over this process's local devices
        self.data_mesh = None       # named (dp,pp,tp,sp) mesh (docs/mesh.md)
        self.data_axes = None       # its axis sizes, e.g. {'dp':4,'tp':2,...}
        self.lead_device = None
        self.joined = False
        self.epoch = 0              # increments per init(); namespaces KV keys
        self.controller = None      # runtime controller (lazy)
        self.background = None      # async op background thread (lazy)
        self.timeline = None
        self.profiler = None        # JaxProfilerBridge (init-time)
        self.metrics_server = None  # per-rank /metrics HTTP endpoint
        self.metrics_publisher = None  # KV snapshot publisher
        self.homogeneous = True     # equal ranks per node (set at init)
        self.lock = threading.Lock()


_state = _State()

# Epoch at which this process first opened each profiler logdir: the
# bridge's generation subdir is epoch-relative-to-first-open, so only
# elastic re-forms over the same dir leave the rank<k> layout.
_PROF_DIR_EPOCH0: dict = {}


def _check_initialized() -> None:
    if not _state.initialized:
        raise HorovodTpuError(
            "Horovod-TPU has not been initialized; use hvd.init().")


def state() -> _State:
    return _state


def init(comm=None, mesh=None) -> None:
    """Initialize the framework.

    ``comm`` is accepted for API compatibility with the reference's
    ``hvd.init(comm=...)`` (``basics.py:33-66``); passing a rank subset is
    not supported on TPU (the ICI mesh is global) and raises.

    ``mesh`` names the data mesh (docs/mesh.md): a spec string
    ('dp:4,tp:2'), an axis dict ({'dp': 4, 'tp': 2}), or a prebuilt
    `jax.sharding.Mesh` whose axis names come from ``parallel.mesh.AXES``.
    Equivalent to exporting ``HOROVOD_MESH`` — the value is canonicalized
    through that knob so the round-0 handshake and the AOT cache key see
    programmatic meshes too.  When set, the gradient stack reduces over
    the ``dp`` axis only.

    Multi-process wiring: if ``HOROVOD_SIZE`` > 1 (exported by the
    launcher), connects to the jax.distributed coordinator at
    ``HOROVOD_COORDINATOR_ADDR`` so every chip joins one XLA runtime.

    Each phase runs under a flight-recorder span, ``hvd_init`` around
    ``hvd_init.distributed`` / ``.backend`` / ``.topology`` /
    ``.meshes`` / ``.planes`` / ``.runtime`` (docs/flight-recorder.md):
    where a slow start went is in every rank's ring.
    """
    if comm not in (None, 0):
        raise HorovodTpuError(
            "init(comm=...) with a rank subset is not supported on TPU; "
            "the device mesh is global.")
    if _state.initialized:   # a repeated call is no bring-up: no span
        return
    with _flight.span("hvd_init"):
        with _state.lock:
            if _state.initialized:
                return
            _init_locked(mesh)
        if _state.size > 1:
            # Spawn the background runtime now, like the reference's
            # InitializeHorovodOnce (operations.cc:604-650) — NOT lazily
            # on first enqueue: every rank must participate in
            # negotiation rounds from the start or the coordinator
            # blocks mid-round on a rank that simply hasn't submitted
            # anything yet, and the stall inspector can never observe
            # the hold-out.
            from horovod_tpu.ops import eager as _eager

            with _flight.span("hvd_init.runtime"):
                _eager._runtime()


def _init_locked(mesh) -> None:
    """``init()`` under ``_state.lock``, not yet initialized."""
    # Goodput ledger (docs/goodput.md): the wall clock starts at the
    # first init() and the bring-up wall lands in the "init" phase; a
    # re-init (elastic re-form) adds its own init span to the same
    # run-long ledger.  Advisory: observability must never fail init.
    import time as _time

    _t_init_gp = _time.monotonic()
    try:
        from horovod_tpu.perf import goodput as _goodput

        _goodput.start()
    except Exception:
        _goodput = None
    ensure_platform()
    import jax

    env_size = int(os.environ.get("HOROVOD_SIZE", "1"))
    env_rank = int(os.environ.get("HOROVOD_RANK", "0"))
    pod_auto = False
    if ("HOROVOD_SIZE" not in os.environ
            and "HOROVOD_RANK" not in os.environ):
        # TPU-pod orchestrator (no launcher): rank/size/coordinator
        # from pod metadata env — the LSF/jsrun-introspection analog
        # (reference run/util/lsf.py).  An explicitly exported
        # HOROVOD_SIZE (even =1, a forced single-process debug run)
        # suppresses auto-detection.
        from horovod_tpu.run import pod as _pod

        info = _pod.detect()
        if info is not None and info.auto:
            # multislice topology: jax's own cluster resolution
            # understands it natively; hand off below.
            pod_auto = True
            _log.info(f"pod metadata ({info.source}): deferring "
                      "topology to jax.distributed auto-detect")
        elif info is not None and info.size > 1:
            env_size, env_rank = info.size, info.rank
            os.environ.setdefault("HOROVOD_COORDINATOR_ADDR",
                                  info.coordinator)
            # export like the launcher would: rank-tagged logging
            # and child tools read these
            os.environ["HOROVOD_RANK"] = str(info.rank)
            os.environ["HOROVOD_SIZE"] = str(info.size)
            _log.info(f"pod metadata ({info.source}): rank="
                      f"{info.rank} size={info.size}", rank=info.rank)
    # NB: must not touch the backend (jax.devices/process_count)
    # before jax.distributed.initialize — probe the distributed
    # client state instead.
    from jax._src import distributed as _jd

    if (env_size > 1 or pod_auto) and _jd.global_state.client is None:
        with _flight.span("hvd_init.distributed"):
            _connect_distributed(env_size, env_rank, pod_auto)

    with _flight.span("hvd_init.backend"):   # the first backend call
        _state.size = jax.process_count()
    if pod_auto:
        _state.rank = jax.process_index()
        os.environ["HOROVOD_RANK"] = str(_state.rank)
        os.environ["HOROVOD_SIZE"] = str(_state.size)
    elif env_size > 1:
        if _state.size != env_size:
            raise HorovodTpuError(
                f"Launcher env size ({env_size}) disagrees with the "
                f"XLA runtime ({_state.size} processes).")
        # The launcher's numbering is the job's.  jax's own process
        # index need not equal it: a TPU backend numbers processes
        # by where their chips sit, whatever process_id
        # jax.distributed was given (docs/launcher.md), so the world
        # mesh is ordered by rank explicitly (_build_meshes).
        _state.rank = env_rank
    else:
        _state.rank = jax.process_index()

    _state.epoch += 1
    with _flight.span("hvd_init.topology"):
        _compute_local_cross_topology()
    with _flight.span("hvd_init.meshes"):
        _build_meshes()
        _apply_mesh_arg(mesh)
        _build_data_mesh()
    _log_idle_devices()
    with _flight.span("hvd_init.planes"):
        _start_planes()
    if _goodput is not None:
        try:
            _goodput.observe("init",
                             _time.monotonic() - _t_init_gp)
        except Exception:
            pass
    _state.initialized = True
    _log.info(
        "horovod_tpu initialized: rank=%d size=%d local_rank=%d "
        "local_size=%d cross_rank=%d cross_size=%d platform=%s"
        % (_state.rank, _state.size, _state.local_rank,
           _state.local_size, _state.cross_rank, _state.cross_size,
           _state.lead_device.platform), rank=_state.rank)


def _connect_distributed(env_size: int, env_rank: int,
                         pod_auto: bool) -> None:
    """Join the jax.distributed coordinator: every chip one XLA
    runtime."""
    import jax

    # Tight failure-detection timeouts: with jax's defaults
    # (heartbeat 100s, shutdown barrier 300s) a crashed peer
    # stalls the job for minutes; the reference's launcher kills
    # the whole job as soon as one rank dies
    # (gloo_run.py:294-304) and these knobs make that prompt.
    # When the control-plane liveness layer is on (its own
    # hb/<epoch>/<rank> heartbeats + coordinated abort,
    # docs/fault-tolerance.md), it must win the race to report
    # a dead peer — jax's service detection QFATALs the
    # survivors with an undiagnosable abort.  Keep the service
    # as a loose backstop (3x) in that case; with liveness
    # disabled it stays the primary detector.
    hb = max(int(_config.get("heartbeat_timeout")), 1)
    if float(_config.get("heartbeat_interval")) > 0:
        hb = max(hb * 3, 30)
    kwargs = {
        "heartbeat_timeout_seconds": hb,
        "shutdown_timeout_seconds": int(
            _config.get("shutdown_timeout")),
    }
    if pod_auto:
        jax.distributed.initialize(**kwargs)
        return
    coord = _config.get("coordinator_addr")
    if not coord:
        raise HorovodTpuError(
            "HOROVOD_SIZE > 1 but HOROVOD_COORDINATOR_ADDR "
            "is not set (the launcher exports it).")
    if _config.get("elastic"):
        # Elastic mode builds the distributed runtime by hand:
        # jax.distributed.initialize's client has no bounded
        # shutdown (a re-form around a dead peer would hang in
        # its 60 s barrier and leave the error-poll thread
        # alive to QFATAL the survivor later).
        _elastic_distributed_init(coord, env_size, env_rank)
    else:
        jax.distributed.initialize(
            coordinator_address=coord,
            num_processes=env_size,
            process_id=env_rank,
            **kwargs)


def _start_planes() -> None:
    """The observability planes of this generation: profiler bridge,
    metrics endpoint and publisher, flight handlers, the AOT cache's
    announcement."""
    # Device-side capture starts here, not in the background
    # runtime: at size 1 that runtime is lazy, and a compiled-only
    # training run would otherwise record nothing.
    prof_dir = _config.get("jax_profiler")
    if prof_dir:
        from horovod_tpu.runtime.timeline import JaxProfilerBridge

        if _state.profiler is not None:
            # A prior generation's bridge still holds the profiler
            # (e.g. a teardown path that never ran): close it so the
            # old capture lands and start_trace can't collide.
            try:
                _state.profiler.close()
            except Exception:
                pass
            _state.profiler = None
        # Generation is relative to the first time THIS process
        # opened THIS logdir — epoch counts every init() in the
        # process, so a plain shutdown()+init() against a fresh dir
        # must still get the documented rank<k> layout; only a
        # re-form over the same dir (where a prior generation's
        # capture lives) moves to gen<g>/rank<k>.
        base = _PROF_DIR_EPOCH0.setdefault(str(prof_dir),
                                           _state.epoch)
        try:
            _state.profiler = JaxProfilerBridge(
                prof_dir, _state.rank,
                generation=_state.epoch - base + 1)
        except Exception as exc:  # capture is advisory, never fatal
            _log.warning(f"jax profiler capture unavailable: {exc!r}")
    # Metrics plane (docs/metrics.md): topology gauges always; the
    # per-rank HTTP endpoint only when HOROVOD_METRICS_PORT is set.
    # An elastic re-form re-enters init() with a new rank/epoch, so
    # the endpoint follows the rank to its new port and the gauges
    # reflect the new generation.
    from horovod_tpu.runtime import metrics as _metrics

    _metrics.gauge(
        "hvd_world_size", "Current world size.").set(_state.size)
    _metrics.gauge(
        "hvd_generation",
        "Communicator generation (KV epoch; bumps on every "
        "elastic re-form).").set(_state.epoch)
    if _state.metrics_server is not None:
        _state.metrics_server.close()
    _state.metrics_server = _metrics.start_rank_endpoint(_state.rank)
    # KV snapshot publisher for the launcher's fleet aggregate —
    # controller-independent so a size-1 elastic survivor (whose
    # LocalController has no transport) still reports its
    # generation/size to the launcher.
    if _state.metrics_publisher is not None:
        _state.metrics_publisher.stop()
    _state.metrics_publisher = _metrics.maybe_start_kv_publisher(
        _state.rank, _state.size, _state.epoch)
    # Flight recorder (docs/flight-recorder.md): lifecycle event +
    # fatal-signal dump handlers (SIGTERM/SIGABRT), so a killed or
    # aborting rank leaves its event ring in HOROVOD_FLIGHT_DIR.
    # Installed here (main thread at first init); an elastic
    # re-init from a worker thread is a no-op.
    _flight.install_signal_handlers()
    _flight.record("init", rank=_state.rank, size=_state.size,
                   generation=_state.epoch)
    # Persistent AOT executable cache (docs/aot-cache.md): nothing
    # to open — entries are keyed per program on demand — but the
    # operator should see where warm starts will come from, and a
    # re-init (elastic re-form) must announce under the NEW
    # topology (the key context includes world size, so the old
    # generation's entries simply stop matching).
    from horovod_tpu.runtime import aot_cache as _aot

    if _aot.enabled():
        _log.info(
            f"aot-cache: {_aot.cache_dir()} (mode={_aot.mode()}) — "
            "negotiated programs will load from cache when keys "
            "match", rank=_state.rank)
        _flight.record("aot", event="enabled", dir=_aot.cache_dir(),
                       mode=_aot.mode())


def _compute_local_cross_topology() -> None:
    """Local/cross ranks: launcher env wins; else derive from hostnames.

    Mirrors the reference where the launcher computes the full
    rank/local/cross allocation up front (``run/gloo_run.py:54-112``) and
    MPI mode derives it from shared-memory communicator splits
    (``mpi_controller.cc:25-81``).
    """
    env = os.environ
    if "HOROVOD_LOCAL_RANK" in env and "HOROVOD_LOCAL_SIZE" in env:
        _state.local_rank = int(env["HOROVOD_LOCAL_RANK"])
        _state.local_size = int(env["HOROVOD_LOCAL_SIZE"])
        _state.cross_rank = int(env.get("HOROVOD_CROSS_RANK", 0))
        _state.cross_size = int(env.get("HOROVOD_CROSS_SIZE", 1))
        # The launcher computed the full allocation, so it knows true
        # homogeneity; a single rank's local_size*cross_size==size test
        # would wrongly say True on e.g. {3,2,1} ranks over 3 nodes.
        flag = env.get("HOROVOD_IS_HOMOGENEOUS")
        _state.homogeneous = (flag == "1" if flag is not None else
                              _state.local_size * _state.cross_size
                              == _state.size)
        return
    if _state.size == 1:
        _state.local_rank = 0
        _state.local_size = 1
        _state.cross_rank = 0
        _state.cross_size = 1
        _state.homogeneous = True
        return
    # Derive from per-process hostnames via the coordination service's
    # key-value store (no collective needed at init time).
    from jax._src import distributed as _jd

    client = _jd.global_state.client
    host = socket.gethostname()
    # epoch-namespaced keys: shutdown()+init() must not collide with a
    # previous generation's keys on the still-live coordination service
    ep = _state.epoch
    client.key_value_set(f"hvd_host/{ep}/{_state.rank}", host)
    client.wait_at_barrier(f"hvd_topology_{ep}", timeout_in_ms=60_000)
    hosts = [client.blocking_key_value_get(f"hvd_host/{ep}/{r}", 60_000)
             for r in range(_state.size)]
    same = [r for r, h in enumerate(hosts) if h == host]
    _state.local_rank = same.index(_state.rank)
    _state.local_size = len(same)
    uniq = sorted(set(hosts), key=hosts.index)
    _state.cross_rank = uniq.index(host)
    _state.cross_size = len(uniq)
    counts = {h: hosts.count(h) for h in uniq}
    _state.homogeneous = len(set(counts.values())) == 1


def _process_of_each_rank() -> list:
    """jax process index of every rank, in rank order.  Ranks publish
    theirs through the coordination service's key-value store (no
    collective exists yet at init time) and read the whole directory
    back after a barrier — one request per rank, not one per peer.
    Keys carry the epoch so a shutdown()+init() on the still-live
    service cannot read a previous generation's."""
    import jax

    if _state.size == 1:
        return [jax.process_index()]
    from jax._src import distributed as _jd

    client = _jd.global_state.client
    prefix = f"hvd_proc/{_state.epoch}/"
    client.key_value_set(f"{prefix}{_state.rank}", str(jax.process_index()))
    client.wait_at_barrier(f"hvd_proc_{_state.epoch}", timeout_in_ms=60_000)
    by_rank = {int(k.rsplit("/", 1)[1]): int(v)
               for k, v in client.key_value_dir_get(prefix)}
    procs = [by_rank.get(r) for r in range(_state.size)]
    if sorted(p for p in procs if p is not None) != list(range(_state.size)):
        raise HorovodTpuError(
            f"ranks 0..{_state.size - 1} report jax process indices "
            f"{procs}; every process must belong to exactly one rank")
    return procs


def _build_meshes() -> None:
    import jax
    from jax.sharding import Mesh

    by_proc: dict = {}
    for d in jax.devices():
        by_proc.setdefault(d.process_index, []).append(d)
    # mesh position r holds rank r's lead device
    leads = []
    with _flight.span("hvd_init.topology"):   # an exchange over the KV
        processes = _process_of_each_rank()
    for r, p in enumerate(processes):
        if p not in by_proc:
            raise HorovodTpuError(f"rank {r} (process {p}) exposes no "
                                  "devices")
        leads.append(by_proc[p][0])
    _state.mesh = Mesh(np.array(leads), ("hvd",))
    local = by_proc[jax.process_index()]
    _state.local_mesh = Mesh(np.array(local), ("local",))
    _state.lead_device = local[0]


def _log_idle_devices() -> None:
    """Say it when this process sees several devices and the world mesh
    takes one of them: on ``hvd.world_mesh()`` the DistributedOptimizer
    then trains on that one and the rest stay idle.  (Forced host
    devices on the CPU test mesh have the same shape but are nobody's
    hardware, hence info there.)"""
    local = _state.local_mesh.devices.size
    if local == 1 or _state.data_mesh is not None:
        return
    say = _log.info if _state.lead_device.platform == "cpu" else _log.warning
    say(f"this process sees {local} local devices and the world mesh "
        f"uses 1 of them ({_state.mesh.devices.size} device(s) for "
        f"{_state.size} process(es)): the others stay idle on "
        "hvd.world_mesh() unless the job runs one process per chip "
        "(hvdrun -np <chips>) or names a data mesh (hvd.init(mesh=...))",
        rank=_state.rank)


def _apply_mesh_arg(mesh) -> None:
    """Canonicalize an ``init(mesh=...)`` argument through the ``mesh``
    knob (docs/mesh.md): the round-0 handshake and the AOT cache key
    read the config registry, so a programmatic mesh must be exactly as
    visible there as an env-configured one."""
    if mesh is None:
        return
    from horovod_tpu.parallel import mesh as _pmesh

    if isinstance(mesh, str):
        axes = _pmesh.parse_mesh_spec(mesh)
    elif isinstance(mesh, dict):
        axes = _pmesh.parse_mesh_spec(
            ",".join(f"{k}:{v}" for k, v in mesh.items()))
    else:
        names = getattr(mesh, "axis_names", None)
        devs = getattr(mesh, "devices", None)
        if names is None or devs is None:
            raise HorovodTpuError(
                "init(mesh=...) wants a spec string ('dp:4,tp:2'), an "
                "axis dict, or a jax.sharding.Mesh; got "
                f"{type(mesh).__name__}")
        shape = dict(zip(names, devs.shape))
        bad = sorted(n for n in shape if n not in _pmesh.AXES)
        if bad:
            raise HorovodTpuError(
                f"init(mesh=...) axis names must come from "
                f"{'/'.join(_pmesh.AXES)}; got {bad}")
        if _pmesh.DATA_AXIS not in shape:
            raise HorovodTpuError(
                "init(mesh=...) mesh has no 'dp' axis; the gradient "
                "stack reduces over dp")
        axes = {a: int(shape.get(a, 1)) for a in _pmesh.AXES}
    canon = _pmesh.canonical_spec(axes)
    knob = str(_config.get("mesh") or "").strip()
    if knob and _pmesh.canonical_spec(_pmesh.parse_mesh_spec(knob)) != canon:
        raise HorovodTpuError(
            f"init(mesh=...) ({canon!r}) disagrees with HOROVOD_MESH "
            f"({knob!r}); set one, not both")
    _config.set_knob("mesh", canon)


def _build_data_mesh() -> None:
    """Build the named data mesh from the ``mesh`` knob, if set.

    The in-process :class:`Mesh` only exists when this process sees
    every device the spec covers (the single-controller shard_map
    regime).  In the one-process-per-chip eager regime the knob still
    scopes shard counts and rides the round-0 handshake, but there is
    no global mesh to build locally — accepted when the spec covers
    exactly the world size.  Anything else is a mis-sized spec and
    raises: silently training on it would shard gradients against the
    wrong replica groups."""
    spec = str(_config.get("mesh") or "").strip()
    if not spec:
        _state.data_mesh = None
        _state.data_axes = None
        return
    from horovod_tpu.parallel import mesh as _pmesh

    axes = _pmesh.parse_mesh_spec(spec)
    n = 1
    for v in axes.values():
        n *= int(v)
    import jax

    if n == len(jax.devices()):
        m = _pmesh.build_data_mesh(axes)
        _state.data_mesh = m
        _state.data_axes = dict(zip(m.axis_names, m.devices.shape))
        _log.info(f"data mesh: {_pmesh.canonical_spec(axes)} over "
                  f"{m.devices.size} devices (axes {_state.data_axes}); "
                  "gradient collectives ride the dp axis",
                  rank=_state.rank)
    elif n == _state.size:
        _state.data_mesh = None
        _state.data_axes = dict(axes)
        _log.info(f"data mesh: {_pmesh.canonical_spec(axes)} spans the "
                  f"{n}-process world (eager regime; no in-process "
                  "global mesh)", rank=_state.rank)
    else:
        raise HorovodTpuError(
            f"HOROVOD_MESH {_pmesh.canonical_spec(axes)!r} covers {n} "
            f"devices but this process sees {len(jax.devices())} and "
            f"the world has {_state.size} ranks; every device must "
            "belong to exactly one mesh coordinate")


def _elastic_distributed_init(coord: str, n: int, rank: int) -> None:
    """Hand-built jax.distributed runtime for elastic worlds.

    Mirrors ``jax.distributed.initialize`` but with a *bounded* client
    shutdown deadline (``HOROVOD_SHUTDOWN_TIMEOUT_SECONDS``) so a
    re-form around a dead peer returns promptly, and jax-layer liveness
    kept a loose 3x backstop behind the control plane's own heartbeats
    (the PR3 rationale: the diagnosable RanksDownError abort must win
    the race against jax's undiagnosable fatal teardown)."""
    from jax._src import distributed as _jd
    from jax._src.lib import _jax

    gs = _jd.global_state
    hb_to = max(max(int(_config.get("heartbeat_timeout")), 1) * 3, 30)
    shutdown_to = max(2, int(_config.get("shutdown_timeout")))
    if rank == 0 and gs.service is None:
        port = coord.rsplit(":", 1)[1]
        gs.service = _jax.get_distributed_runtime_service(
            "[::]:" + port, n, heartbeat_timeout=hb_to,
            shutdown_timeout=shutdown_to)
    gs.client = _jax.get_distributed_runtime_client(
        coord, rank, init_timeout=120, shutdown_timeout=shutdown_to,
        heartbeat_timeout=hb_to, shutdown_on_destruction=False,
        use_compression=True)
    gs.client.connect()
    gs.process_id = rank
    gs.num_processes = n
    gs.coordinator_address = coord


def teardown_distributed(bound_s: float | None = None) -> None:
    """Bounded teardown of the jax.distributed runtime + XLA backends so
    :func:`init` can re-form the world at a different size in the SAME
    process (the elastic re-form path, docs/elastic.md).

    Each shutdown call runs in a daemon thread joined for ``bound_s``
    (default ``HOROVOD_SHUTDOWN_TIMEOUT_SECONDS``): with a dead peer the
    client's shutdown barrier can never complete, and a survivor must
    not ride it out.  Afterwards the distributed global state is
    force-reset and every backend/device cache is cleared — process
    topology getters (``jax.process_count`` et al.) are lru-cached on
    top of the backend cache, so clearing only the backends would leave
    them vouching for the dead world."""
    import jax

    if bound_s is None:
        bound_s = max(2, int(_config.get("shutdown_timeout")))
    if _state.timeline is not None:
        # Elastic teardown path: flush and join the timeline writer
        # before the world is torn down, so a re-forming rank's trace
        # ends on a complete record instead of truncating mid-event
        # (close() is idempotent; shutdown() may already have run).
        try:
            _state.timeline.close()
        except Exception:
            pass
        _state.timeline = None
    if _state.profiler is not None:
        # Stop the device capture BEFORE the world is torn down: the
        # old generation's xplane profile only lands at stop_trace, and
        # the re-init's new bridge (under gen<g+1>/rank<k>) cannot
        # start while this one holds the profiler — leaving it open
        # used to lose the re-formed generation's capture entirely
        # (start_trace raised, the advisory catch swallowed it).
        try:
            _state.profiler.close()
        except Exception:
            pass
        _state.profiler = None
    from jax._src import distributed as _jd

    gs = _jd.global_state

    def _swallow(fn):
        try:
            fn()
        except Exception:
            pass

    for obj in (gs.client, gs.service):
        if obj is not None:
            t = threading.Thread(target=_swallow, args=(obj.shutdown,),
                                 daemon=True)
            t.start()
            t.join(bound_s)
    gs.client = None
    gs.service = None
    gs.process_id = 0
    gs.num_processes = 1
    gs.coordinator_address = None
    gs.preemption_sync_manager = None
    jax.clear_caches()
    from horovod_tpu.ops import xla_exec as _exec

    _exec.clear_cache()
    from jax._src import xla_bridge as _xb

    _xb._clear_backends()
    for fn in (_xb.get_backend, _xb.local_devices, _xb.process_count,
               jax.process_count, jax.process_index, jax.device_count,
               jax.local_device_count, jax.devices, jax.local_devices):
        cc = getattr(fn, "cache_clear", None)
        if cc is not None:
            _swallow(cc)
    _state.mesh = None
    _state.local_mesh = None
    _state.data_mesh = None
    _state.data_axes = None
    _state.lead_device = None


def shutdown() -> None:
    """Tear down background machinery (reference ``horovod_shutdown``,
    ``operations.cc:688``).  A clean exit leaves its flight ring where
    ``HOROVOD_FLIGHT_DIR`` names a directory, as the failure paths do:
    what the start of a run cost is read from it afterwards."""
    if teardown():
        _flight.dump("shutdown")


def teardown() -> bool:
    """``shutdown()`` without the ring's dump, for an elastic re-form:
    it has dumped the generation it leaves already, and cleared the
    ring since.  Returns whether there was anything to tear down."""
    with _state.lock:
        if not _state.initialized:
            return False
        _flight.record("shutdown", rank=_state.rank,
                       generation=_state.epoch)
        # The goodput ledger's final accounting: a clean shutdown dumps
        # the wall-clock attribution next to the flight dumps so the
        # `python -m horovod_tpu.perf goodput <dir>` report covers
        # healthy runs too (abort paths dump via flight.dump_on_failure).
        try:
            from horovod_tpu.perf import goodput as _goodput

            _goodput.dump("shutdown")
        except Exception:
            pass
        # ...and the health monitor's (docs/health.md): a clean
        # shutdown leaves the per-rank health verdict next to the
        # goodput ledger so `python -m horovod_tpu.perf health <dir>`
        # covers healthy runs too.
        try:
            from horovod_tpu.runtime import health as _health

            if _health._monitor is not None:
                _health.dump("shutdown")
        except Exception:
            pass
        if _state.background is not None:
            _state.background.stop()
            _state.background = None
        if _state.timeline is not None:
            _state.timeline.close()
            _state.timeline = None
        if _state.profiler is not None:
            _state.profiler.close()
            _state.profiler = None
        if _state.metrics_server is not None:
            _state.metrics_server.close()
            _state.metrics_server = None
        if _state.metrics_publisher is not None:
            _state.metrics_publisher.stop()
            _state.metrics_publisher = None
        _state.controller = None
        _state.data_mesh = None
        _state.data_axes = None
        _state.initialized = False
        _state.joined = False
    return True


def is_initialized() -> bool:
    return _state.initialized


def rank() -> int:
    _check_initialized()
    return _state.rank


def size() -> int:
    _check_initialized()
    return _state.size


def local_rank() -> int:
    _check_initialized()
    return _state.local_rank


def local_size() -> int:
    _check_initialized()
    return _state.local_size


def cross_rank() -> int:
    _check_initialized()
    return _state.cross_rank


def cross_size() -> int:
    _check_initialized()
    return _state.cross_size


def is_homogeneous() -> bool:
    """True iff every node runs the same number of ranks (reference
    ``basics.py:122-129``; hierarchical collectives and Adasum assume
    it).  Computed from the launcher's full allocation or the gathered
    per-host rank counts — never from one rank's local view."""
    _check_initialized()
    return bool(_state.homogeneous)


def world_mesh():
    """The 1-D ``('hvd',)`` mesh over per-process lead devices that backs
    the eager collective path."""
    _check_initialized()
    return _state.mesh


def local_mesh():
    """Mesh over this process's local devices (for intra-process model
    parallelism)."""
    _check_initialized()
    return _state.local_mesh


def data_mesh():
    """The named (dp,pp,tp,sp) data mesh (docs/mesh.md) when one is
    configured via ``hvd.init(mesh=...)`` / ``HOROVOD_MESH``, else
    ``None`` (flat-world regime).  Under hierarchical mode the dp axis
    appears as the ('dpc','dpl') sub-axis pair."""
    _check_initialized()
    return _state.data_mesh


def data_parallel_size() -> int:
    """Replica count of the gradient reduction: the mesh's dp extent
    when a data mesh is configured, else the world size.  This is the
    shard count ZeRO layouts and checkpoint shard metadata use."""
    from horovod_tpu.parallel import mesh as _pmesh

    dp = _pmesh.data_parallel_size()
    if dp is not None:
        return dp
    return _state.size if _state.initialized else 1


def lead_device():
    _check_initialized()
    return _state.lead_device


# --- build/enabled introspection (reference basics.py:90-150) -------------

def mpi_threads_supported() -> bool:
    """No MPI in the TPU build; collective dispatch is thread-safe."""
    return False


def mpi_built() -> bool:
    return False


def mpi_enabled() -> bool:
    return False


def gloo_built() -> bool:
    """True when cross-process CPU collectives are available (test mode)."""
    return True


def gloo_enabled() -> bool:
    import jax

    return _state.initialized and _state.lead_device.platform == "cpu"


def nccl_built() -> bool:
    return False


def ddl_built() -> bool:
    return False


def ccl_built() -> bool:
    return False


def xla_built() -> bool:
    """TPU-build addition: the data plane is XLA collectives."""
    return True


def ici_enabled() -> bool:
    """True when collectives ride a real TPU interconnect."""
    return _state.initialized and _state.lead_device.platform == "tpu"
