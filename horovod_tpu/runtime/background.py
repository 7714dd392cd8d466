"""Background runtime: per-process coordinator thread + tensor queue.

Parity with the reference's core runtime (``horovod/common/operations.cc``):
framework threads only enqueue (``EnqueueTensorAllreduce``,
``operations.cc:803``) into a mutex-guarded tensor queue
(``tensor_queue.{h,cc}``); a single background thread drives ≤cycle-time
negotiation rounds (``RunLoopOnce``, ``operations.cc:550-600``), executes
the negotiated fused collectives, and completes handles.  Framework
threads never touch the wire — the design rationale documented at
``operations.cc:311-331``.
"""

from __future__ import annotations

import contextlib
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from horovod_tpu.common import basics as _basics
from horovod_tpu.common import config as _config
from horovod_tpu.common import logging as _log
from horovod_tpu.common.types import (DuplicateNameError, RanksDownError,
                                      Status, dtype_code, dtype_from_code)
from horovod_tpu.ops import xla_exec as _exec
from horovod_tpu.runtime import flight as _flight
from horovod_tpu.runtime import metrics as _metrics
from horovod_tpu.runtime.controller import (JOIN_NAME, RANKS_DOWN_PREFIX,
                                            Request, make_controller,
                                            reduction_scope, tensor_nbytes)


def _scope_of(resp) -> str | None:
    """Axis scope of a negotiated allreduce response (docs/local-sgd.md):
    ``"local"``/``"cross"`` for the local-SGD scoped reductions (derived
    from the negotiated tensor names, the wire contract), else None."""
    if resp.kind != "allreduce" or not resp.names:
        return None
    return reduction_scope(resp.names[0])

# Background-loop observability (docs/metrics.md).
_M_NEG_LAT = _metrics.histogram(
    "hvd_negotiation_seconds",
    "Wall time of one negotiation round (request post -> response "
    "list executed locally).")
_M_RESP_SIZE = _metrics.histogram(
    "hvd_response_list_size",
    "Responses per negotiated round (post-fusion launch count).",
    lo=0, hi=12)
_M_FAST_ROUNDS = _metrics.gauge(
    "hvd_negotiation_fast_rounds",
    "Rounds resolved via the cache-bit fast path since init.")
_M_DISPATCH = _metrics.counter(
    "hvd_comm_dispatch_seconds_total",
    "Background-thread seconds executing negotiated collectives.")
_M_WIRE_BYTES = _metrics.counter(
    "hvd_data_wire_bytes_total",
    "Data-plane bytes a negotiated response moves on the wire, after "
    "HOROVOD_COMPRESSION, labeled by collective kind and by axis "
    "(axis=local: ICI-only scoped reductions of the local-SGD inner "
    "step; axis=cross: everything that crosses slices over DCN — "
    "world-scoped collectives and local-SGD pseudo-gradient syncs).")
_M_LOGICAL_BYTES = _metrics.counter(
    "hvd_data_logical_bytes_total",
    "Uncompressed payload bytes of the same responses — "
    "wire/logical is the achieved compression ratio.")


class _Entry:
    __slots__ = ("name", "kind", "op", "root_rank", "tensor", "handle",
                 "postprocess")

    def __init__(self, name, kind, op, root_rank, tensor, handle,
                 postprocess):
        self.name = name
        self.kind = kind
        self.op = op
        self.root_rank = root_rank
        self.tensor = tensor
        self.handle = handle
        self.postprocess = postprocess


class TensorQueue:
    """Mutex-guarded name table + FIFO (reference ``tensor_queue.h:28-64``).
    Duplicate name before completion → error (reference ``common.h:161``)."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._fifo: list[_Entry] = []
        self._table: dict[str, _Entry] = {}

    def add(self, entry: _Entry) -> None:
        with self._lock:
            if entry.name in self._table:
                raise DuplicateNameError(
                    f"Requested to {entry.kind} a tensor with the same name "
                    f"as another tensor that is currently being processed. "
                    f"If you want to request another tensor, pass a "
                    f"different tensor name. Tensor name: {entry.name}")
            self._table[entry.name] = entry
            self._fifo.append(entry)

    def pop_pending(self) -> list[_Entry]:
        with self._lock:
            out, self._fifo = self._fifo, []
            return out

    def drain_all(self) -> list[_Entry]:
        """Remove and return every outstanding entry — both queued and
        already-negotiating (used on shutdown/failure so no handle is
        left hanging)."""
        with self._lock:
            out = list(self._table.values())
            self._table.clear()
            self._fifo = []
            return out

    def finalize(self, name: str) -> "_Entry | None":
        with self._lock:
            return self._table.pop(name, None)

    def outstanding(self) -> int:
        with self._lock:
            return len(self._table)


class BackgroundRuntime:
    def __init__(self, handle_manager) -> None:
        st = _basics.state()
        self.rank = st.rank
        self.world = st.size
        self.hm = handle_manager
        self.queue = TensorQueue()
        self.controller = make_controller(self.rank, self.world, st.epoch)
        self._counters: dict[str, int] = {}
        self._counter_lock = threading.Lock()
        self._stop_requested = threading.Event()
        self._wake = threading.Event()
        self._stopped = threading.Event()
        self._join_requested = threading.Event()
        self._join_done = threading.Event()
        self._join_result = -1
        self._error: str | None = None
        self._error_class: type | None = None
        self._dumped_flight = False
        self.pm = None
        self._pending_tune: dict | None = None
        if self.rank == 0 and _config.get("autotune"):
            from horovod_tpu.runtime.parameter_manager import ParameterManager

            self.pm = ParameterManager(world=self.world)
        self.timeline = None
        tl_path = _config.get("timeline")
        if tl_path and self.rank == 0:
            from horovod_tpu.runtime.timeline import make_timeline

            self.timeline = make_timeline(tl_path)
            st.timeline = self.timeline
        # Created at hvd.init() (basics), shared here for dispatch
        # annotations; None when capture is disabled.
        self.profiler = getattr(st, "profiler", None)
        # Liveness: publish this rank's heartbeat for the duration of
        # the runtime (docs/fault-tolerance.md) — peers' controllers
        # sweep it and coordinate an abort when it goes stale.
        if hasattr(self.controller, "start_heartbeat"):
            self.controller.start_heartbeat()
        self._thread = threading.Thread(
            target=self._run, name="hvd-background", daemon=True)
        self._thread.start()

    # -- framework-thread API ---------------------------------------------

    def autoname(self, kind: str) -> str:
        with self._counter_lock:
            i = self._counters.get(kind, 0)
            self._counters[kind] = i + 1
        return f"{kind}.noname.{i}"

    def enqueue(self, kind, tensor, name, op, handle, postprocess,
                root_rank=-1) -> None:
        if self._stopped.is_set() or self._error:
            self.hm.mark_done(handle, Status.aborted(
                self._error or "Horovod-TPU runtime has been shut down.",
                self._error_class), None)
            return
        if not isinstance(tensor, jax.Array):
            # numpy/list inputs only: re-wrapping a jax.Array pays the
            # full jnp.array promotion machinery (~0.1 ms) per op
            tensor = jnp.asarray(tensor)
        name = name or self.autoname(kind)
        entry = _Entry(name, kind, op, root_rank, tensor, handle,
                       postprocess)
        if self.timeline:
            self.timeline.negotiate_start(name, kind)
        try:
            self.queue.add(entry)
        except DuplicateNameError:
            self.hm.mark_done(handle, Status.aborted("duplicate name"), None)
            raise
        # Close the race with a concurrent stop(): if the loop exited
        # between the check above and queue.add, nothing will ever
        # process this entry — fail it here.
        if self._stopped.is_set():
            if self.queue.finalize(name) is not None:
                self.hm.mark_done(handle, Status.aborted(
                    self._error or
                    "Horovod-TPU runtime has been shut down.",
                    self._error_class), None)
        # Wake the loop: a single op shouldn't pay the full cycle-time
        # sleep in dispatch latency (the cycle still bounds how often
        # negotiation rounds run under sustained load, the reference's
        # batching rationale, operations.cc:550-560).
        self._wake.set()

    def flush(self, timeout: float = 600.0) -> None:
        deadline = time.monotonic() + timeout
        while self.queue.outstanding() and time.monotonic() < deadline:
            time.sleep(0.001)

    def join(self) -> int:
        """Block until every rank joins (reference semantics §5.3)."""
        self._join_done.clear()
        self._join_requested.set()
        self._wake.set()
        self._join_done.wait()
        return self._join_result

    def stop(self) -> None:
        self._stop_requested.set()
        self._wake.set()
        self._thread.join(timeout=30)
        if hasattr(self.controller, "close"):
            self.controller.close()  # heartbeat publisher + transport
        if self.timeline:
            self.timeline.close()
        # profiler closed by basics.shutdown() (it owns the bridge)

    # -- background loop ---------------------------------------------------

    def _run(self) -> None:
        while True:
            # Re-read each cycle: autotune retunes it at runtime
            # (reference ParameterManager owns CycleTimeMs the same way).
            cycle_s = _config.get("cycle_time_ms") / 1000.0
            t0 = time.monotonic()
            if self.timeline and _config.get("timeline_mark_cycles"):
                self.timeline.mark_cycle()
            try:
                stop = self._run_cycle()
            except RanksDownError as exc:
                # Coordinated abort: peers are gone.  Every pending and
                # future handle fails with the diagnosable error (dead
                # ranks, round, staleness) instead of a generic
                # shutdown message or a 600 s hang.  The flight ring
                # dumps BEFORE handles fail: a survivor that catches
                # RanksDownError and os._exit()s immediately must still
                # find its dump on disk.
                _log.error(f"coordinated abort: {exc}", rank=self.rank)
                self._error = str(exc)
                self._error_class = RanksDownError
                # Ring dump first (cheap local file IO), handle failure
                # second, KV metrics flush LAST: the publish retries
                # with backoff against a possibly-dead store, and that
                # wait must not keep training threads blocked in
                # HandleManager.wait past the abort.
                _flight.dump_on_failure("ranks_down", flush_metrics=False)
                self._dumped_flight = True
                self._fail_outstanding()
                _flight.flush_terminal_metrics()
                stop = True
            except Exception as exc:  # never kill the loop silently
                _log.error(f"background loop error: {exc!r}", rank=self.rank)
                self._error = f"Horovod-TPU background failure: {exc!r}"
                _flight.dump_on_failure("background_failure",
                                        flush_metrics=False)
                self._dumped_flight = True
                self._fail_outstanding()
                _flight.flush_terminal_metrics()
                stop = True
            if stop:
                break
            elapsed = time.monotonic() - t0
            if elapsed < cycle_s:
                self._wake.wait(cycle_s - elapsed)
            self._wake.clear()
        self._stopped.set()
        self._fail_outstanding()
        if self._error:
            # A coordinated abort / background failure usually ends the
            # process before anyone calls stop(): flush and join the
            # timeline writer NOW so the dying rank's trace isn't
            # truncated mid-record (close() is idempotent — a later
            # stop()/shutdown() is a no-op), dump the flight-recorder
            # ring (the per-rank postmortem the trace merge tool
            # reads), and push one terminal KV metrics snapshot so the
            # launcher aggregate sees the abort counters instead of
            # the last periodic publish.
            if self.timeline:
                try:
                    self.timeline.close()
                except Exception:
                    pass
            if not self._dumped_flight:
                # The one _error path with no exception: a
                # coordinator-initiated stop (error ResponseList, e.g.
                # the round-0 handshake mismatch) — the except-branch
                # dumps already covered the abort/failure paths.
                _flight.dump_on_failure("coordinated_stop")
        if self._join_requested.is_set():
            self._join_done.set()

    def _run_cycle(self) -> bool:
        pending = self.queue.pop_pending()
        joined = self._join_requested.is_set()
        shutdown = self._stop_requested.is_set()
        have_work = bool(pending) or joined or shutdown
        ctl = self.controller
        if hasattr(ctl, "should_participate"):
            # Outstanding-but-unresolved entries (ours, or — on the
            # coordinator — another rank's half-arrived negotiation)
            # keep rounds running every cycle, like the reference's
            # unconditional ComputeResponseList: that is what lets the
            # stall inspector observe a rank that never shows up.
            waiting = bool(self.queue.outstanding()) or bool(
                getattr(ctl, "coordinator", None)
                and (ctl.coordinator.table.entries
                     or ctl.coordinator.joined))
            if not ctl.should_participate(have_work or waiting):
                return False
            if have_work or waiting:
                ctl.kick()
        elif not have_work and not self.queue.outstanding():
            return False

        requests = [Request(e.name, e.kind, e.op, dtype_code(e.tensor.dtype),
                            tuple(e.tensor.shape), e.root_rank)
                    for e in pending]
        tune, self._pending_tune = self._pending_tune, None
        neg_t0 = time.perf_counter()
        result = ctl.negotiate(requests, joined, shutdown, tune=tune)
        _M_NEG_LAT.observe(time.perf_counter() - neg_t0)
        _M_RESP_SIZE.observe(len(result.responses))
        fast = getattr(ctl, "fast_rounds", None)
        if fast is not None:
            _M_FAST_ROUNDS.set(fast)
        if result.should_stop and self._error is None and not shutdown:
            # A coordinator-initiated stop (e.g. the round-0 cfg
            # handshake mismatch) must surface its reason on EVERY
            # outstanding/late handle, not just the names already
            # negotiated — otherwise racing enqueues die with a generic
            # "runtime has been shut down".
            for resp in result.responses:
                if resp.kind == "error" and resp.error:
                    self._error = resp.error
                    if resp.error.startswith(RANKS_DOWN_PREFIX):
                        self._error_class = RanksDownError
                    break
        for resp in result.responses:
            self._execute(resp)
        if self.pm is not None:
            self._pending_tune = self.pm.tick()
            if self._pending_tune is not None and self.world == 1:
                # No wire to ride: apply directly.  Multi-process ranks
                # (rank 0 included) apply only on payload receipt so env
                # state can never diverge across ranks — a tune produced
                # on the final round is dropped everywhere alike.
                from horovod_tpu.runtime.parameter_manager import apply_params

                apply_params(self._pending_tune)
        if result.all_joined and self._join_requested.is_set():
            # Clear the flag here (not in the waiting thread) so the next
            # cycle doesn't re-mark this rank joined before the user
            # thread wakes.
            self._join_requested.clear()
            self._join_result = result.last_joined
            self._join_done.set()
        return result.should_stop

    def _fail_outstanding(self) -> None:
        msg = self._error or "Horovod-TPU runtime has been shut down."
        for entry in self.queue.drain_all():
            if entry.handle is not None:
                self.hm.mark_done(
                    entry.handle,
                    Status.aborted(msg, self._error_class), None)

    # -- response execution (the data plane) ------------------------------

    def _execute(self, resp) -> None:
        if resp.kind == "join":
            return
        if resp.kind == "error":
            exc_class = (RanksDownError if resp.error
                         and resp.error.startswith(RANKS_DOWN_PREFIX)
                         else None)
            for name in resp.names:
                entry = self.queue.finalize(name)
                if entry is not None:
                    if self.timeline:
                        self.timeline.negotiate_end(name, entry.kind)
                    self.hm.mark_done(
                        entry.handle,
                        Status.precondition(resp.error, exc_class), None)
            return

        entries = []
        dtype = dtype_from_code(resp.dtype_code)
        for name, shape in zip(resp.names, resp.shapes):
            entry = self.queue.finalize(name)
            if entry is None:
                # This rank joined: contribute zeros of the negotiated
                # shape (reference zero-fill,
                # ``tensor_queue.cc GetTensorEntriesFromResponse``).
                if resp.kind == "allgather":
                    shape = (0,) + tuple(shape[1:])
                zero = jnp.zeros(tuple(shape), dtype=dtype)
                entry = _Entry(name, resp.kind, resp.op, resp.root_rank,
                               zero, None, None)
            if self.timeline:
                self.timeline.negotiate_end(name, entry.kind)
            entries.append(entry)

        # Deterministic gradient poisoning (nan:/inf: fault rules,
        # docs/health.md): applied to the local payload BEFORE dispatch
        # so the health tap inside the negotiated program observes the
        # poison pre-reduction and the verdict names this rank.
        from horovod_tpu.runtime import faults as _faults

        rnd = int(getattr(self.controller, "round", 0) or 0)
        if _faults.data_rules():
            entries = _faults.poison_entries(entries, self.rank, rnd)
        if _config.get("health"):
            # Round marker for the eager clear hysteresis: a completed
            # clean round counts once toward HOROVOD_HEALTH_CLEAR_STEPS
            # regardless of how many fused buffers it dispatched.
            from horovod_tpu.runtime import health as _health

            _health.note_wire_round(rnd)

        wire_b = self._wire_nbytes(resp, dtype)
        logical_b = self._logical_nbytes(resp, dtype)
        if self.pm is not None:
            self.pm.record_bytes(wire_b, logical_b)
        # axis=local: ICI-scoped local-SGD inner reductions; axis=cross:
        # anything whose bytes cross slices over DCN (docs/local-sgd.md
        # — the cross series is what the >= H x reduction is counted
        # on).
        scope = _scope_of(resp)
        _M_WIRE_BYTES.inc(wire_b, kind=resp.kind,
                          axis="local" if scope == "local" else "cross")
        _M_LOGICAL_BYTES.inc(logical_b, kind=resp.kind)

        activity = f"XLA_{resp.kind.upper()}"
        if self.timeline:
            for e in entries:
                self.timeline.activity_start(e.name, activity)
            self._mark_overlap_schedule(resp, entries)
        annotate = (self.profiler.annotate(f"hvd_{resp.kind}")
                    if self.profiler else contextlib.nullcontext())
        _flight.record("dispatch", ph="B", collective=resp.kind,
                       n=len(entries), bytes=wire_b,
                       names=[e.name for e in entries[:8]])
        disp_t0 = time.perf_counter()
        try:
            with annotate:
                outs = self._dispatch(resp, entries)
            status = Status.ok()
        except Exception as exc:
            outs = [None] * len(entries)
            status = Status.unknown(
                f"Collective {resp.kind} failed: {exc!r}")
            _log.error(status.reason, rank=self.rank)
        _M_DISPATCH.inc(time.perf_counter() - disp_t0, kind=resp.kind)
        _flight.record("dispatch", ph="E", collective=resp.kind,
                       ok=status.ok_p())
        if self.timeline:
            for e in entries:
                self.timeline.activity_end(e.name, activity)
        for entry, out in zip(entries, outs):
            if entry.handle is None:
                continue
            if status.ok_p() and entry.postprocess is not None:
                out = entry.postprocess(out)
            self.hm.mark_done(entry.handle, status, out)

    def _mark_overlap_schedule(self, resp, entries) -> None:
        """Per-bucket ``overlap/rs|compute|ag`` timeline ticks for a
        fused response riding the overlap engine, so the K-bucket
        schedule is visible in the Chrome trace next to the response's
        negotiation/activity rows.  Ticks record issue order (the
        schedule is one XLA program); device-side bucket durations live
        in the profiler's ``hvd_overlap_*`` named scopes
        (docs/overlap.md)."""
        if resp.kind not in ("allreduce", "reducescatter") or \
                resp.op == _exec._ADASUM or self.world <= 1:
            return
        from horovod_tpu.ops import overlap as _ovl

        if not _ovl.enabled():
            return
        if resp.kind == "reducescatter":
            # The rs wire pads each tensor's LEADING dim to the world
            # size (ops/collectives.grouped_reducescatter), so the
            # per-rank bucket space is the sum of ceil(d0/n) rows per
            # tensor — padding the flat total is only right for
            # allreduce and would mislabel the very schedule these
            # events exist to visualize.
            shard = sum(-(-int(s[0]) // self.world)
                        * (int(np.prod(s[1:])) if len(s) > 1 else 1)
                        for s in resp.shapes)
        else:
            total = sum(int(np.prod(s)) if s else 1 for s in resp.shapes)
            shard = (total + (-total) % self.world) // self.world
        name = entries[0].name
        for b, (s, e) in enumerate(_ovl.bucket_bounds(shard)):
            for phase in ("rs", "compute", "ag"):
                self.timeline.overlap_phase(name, b, phase,
                                            (e - s) * self.world)

    @staticmethod
    def _logical_nbytes(resp, dtype) -> int:
        """Uncompressed payload bytes of a response — the denominator
        of the wire/logical compression ratio in the metrics plane."""
        if resp.kind == "allgather" and resp.first_dims:
            row = (tensor_nbytes(tuple(resp.shapes[0][1:]), dtype)
                   if len(resp.shapes[0]) > 1 else dtype.itemsize)
            return sum(int(d) for d in resp.first_dims) * row
        return sum(tensor_nbytes(s, dtype) for s in resp.shapes)

    def _wire_nbytes(self, resp, dtype) -> int:
        """Bytes this response actually moves on the wire, accounting
        for the compression knobs (``HOROVOD_COMPRESSION`` and the
        per-bucket ``HOROVOD_BUCKET_COMPRESSION`` vector) inside the
        allreduce/reducescatter programs — the autotuner scores
        throughput per wire byte, and the
        ``hvd_data_wire_bytes_total``/``hvd_data_logical_bytes_total``
        ratio is the achieved-compression metric, so int4's packed
        half-bytes and topk's ``k * (index + value)`` payloads must be
        counted as what they are, not as dense element-width payloads.
        Allgather counts the gathered payload (sum of every rank's
        negotiated rows), not one rank's submission: a reduce-scatter
        + allgather round trip (the sharded optimizer's wire pattern)
        then scores the same bytes an allreduce of the full buffer
        would."""
        import numpy as _np

        if resp.kind == "allgather" and resp.first_dims:
            row = (tensor_nbytes(tuple(resp.shapes[0][1:]), dtype)
                   if len(resp.shapes[0]) > 1 else dtype.itemsize)
            return sum(int(d) for d in resp.first_dims) * row
        nbytes = sum(tensor_nbytes(s, dtype) for s in resp.shapes)
        # Adasum programs never compress (xla_exec builds them with
        # comp=none): count their full-precision bytes.  Local-SGD
        # inner reductions (scope=local) are full precision on ICI by
        # contract, so they count dense too.
        scope = _scope_of(resp)
        if resp.kind not in ("allreduce", "reducescatter") \
                or resp.op == _exec._ADASUM or scope == "local" or \
                not jnp.issubdtype(_np.dtype(dtype), jnp.floating):
            return nbytes
        from horovod_tpu.ops import compression as _compression

        itemsize = _np.dtype(dtype).itemsize
        n_elems = nbytes // itemsize
        if scope == "cross":
            # The pseudo-gradient hop rides its own wire mode
            # (HOROVOD_LOCAL_SGD_COMPRESSION, inheriting
            # HOROVOD_COMPRESSION), never the per-bucket vector.
            ls = _exec.local_sgd_cfg()
            modes = [ls[3]] if ls is not None else ["none"]
        else:
            modes = _compression.effective_bucket_modes()
        return _compression.fused_wire_bytes(
            n_elems, itemsize, modes,
            block=max(1, int(_config.get("quant_block_size"))),
            ratio=float(_config.get("topk_ratio")),
            world=max(self.world, 1))

    def _dispatch(self, resp, entries):
        if resp.kind == "allreduce":
            return _exec.fused_allreduce([e.tensor for e in entries],
                                         resp.op, scope=_scope_of(resp))
        if resp.kind == "broadcast":
            return _exec.fused_broadcast([e.tensor for e in entries],
                                         resp.root_rank)
        if resp.kind == "allgather":
            sizes = list(resp.first_dims) or None
            return [_exec.allgather(e.tensor, sizes=sizes)
                    for e in entries]
        if resp.kind == "alltoall":
            return [_exec.alltoall(e.tensor) for e in entries]
        if resp.kind == "reducescatter":
            return [_exec.reducescatter(e.tensor, resp.op)
                    for e in entries]
        raise RuntimeError(f"unknown response kind {resp.kind}")
