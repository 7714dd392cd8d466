"""Horovod Timeline: Chrome-tracing profile of every tensor's lifecycle.

Parity with reference ``horovod/common/timeline.{h,cc}``: per-tensor
rows (one trace "thread" per tensor name), NEGOTIATE_* → QUEUE → op
activity phases, optional cycle markers
(``HOROVOD_TIMELINE_MARK_CYCLES``, ``timeline.h:98``).  Records flow
through a queue to a dedicated writer thread so the background loop
never blocks on file IO (the reference uses a boost lock-free SPSC
queue, ``timeline.h:68-75``).  Rank 0 writes the file
(``operations.cc:403-411``); view in chrome://tracing or Perfetto.
"""

from __future__ import annotations

import json
import queue
import threading
import time


class NativeTimeline:
    """C++ writer (csrc/timeline.cc): record formatting and file IO run
    on a native thread, so the background loop pays only a ctypes call
    per event — the reference's native-writer design exactly."""

    def __init__(self, path: str) -> None:
        import ctypes

        from horovod_tpu.runtime import native_build

        lib = native_build.load_shared("libhvdtl", "timeline.cc")
        lib.hvd_tl_open.restype = ctypes.c_void_p
        lib.hvd_tl_open.argtypes = [ctypes.c_char_p]
        lib.hvd_tl_event.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                     ctypes.c_char_p, ctypes.c_char]
        lib.hvd_tl_marker.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        lib.hvd_tl_close.argtypes = [ctypes.c_void_p]
        self._lib = lib
        self._h = lib.hvd_tl_open(path.encode())
        if not self._h:
            raise OSError(f"timeline: cannot open {path}")

    def negotiate_start(self, name: str, kind: str) -> None:
        self._lib.hvd_tl_event(self._h, name.encode(),
                               f"NEGOTIATE_{kind.upper()}".encode(), b"B")

    def negotiate_end(self, name: str, kind: str) -> None:
        self._lib.hvd_tl_event(self._h, name.encode(),
                               f"NEGOTIATE_{kind.upper()}".encode(), b"E")

    def negotiate_rank_ready(self, name: str, rank: int) -> None:
        """Instant tick on the tensor's row: ``rank``'s request reached
        the coordinator (reference ``timeline.h:85-88`` — the straggler
        diagnostic: who was late for this negotiation)."""
        self._lib.hvd_tl_event(self._h, name.encode(),
                               f"RANK{rank}_READY".encode(), b"i")

    def activity_start(self, name: str, activity: str) -> None:
        self._lib.hvd_tl_event(self._h, name.encode(), activity.encode(),
                               b"B")

    def activity_end(self, name: str, activity: str) -> None:
        self._lib.hvd_tl_event(self._h, name.encode(), activity.encode(),
                               b"E")

    def mark_cycle(self) -> None:
        self._lib.hvd_tl_marker(self._h, b"CYCLE_START")

    def overlap_phase(self, name: str, bucket: int, phase: str,
                      elems: int = 0) -> None:
        """Instant tick on a per-bucket row: bucket ``bucket`` of the
        overlap schedule issued ``phase`` (``rs``/``compute``/``ag``).
        Issue order only — device-side durations ride the jax profiler's
        ``hvd_overlap_*`` named scopes (docs/overlap.md)."""
        del elems  # the native writer has no args payload
        self._lib.hvd_tl_event(
            self._h, f"{name}/bucket{bucket}".encode(),
            f"overlap/{phase}".encode(), b"i")

    def close(self) -> None:
        if self._h:
            self._lib.hvd_tl_close(self._h)
            self._h = None


class JaxProfilerBridge:
    """Device-side tracing via ``jax.profiler`` — the TPU-native analog
    of the reference's CUDA-event activity timing (its GPU op timings
    ride CUDA events drained by finalizer threads,
    ``gpu_operations.h:103-112``; on TPU the runtime's XLA profiler
    already records per-op device timelines, so the framework's job is
    to start/stop capture and label its collectives in the trace).

    Writes a TensorBoard-loadable xplane profile under
    ``<logdir>/rank<k>`` per process; view with TensorBoard's profile
    plugin, Perfetto, or ``python -m horovod_tpu.perf report``
    (docs/perf.md).  Enabled by ``HOROVOD_TIMELINE_JAX_PROFILER``
    (every rank captures: device activity is per-process, unlike the
    host-side Chrome timeline that only rank 0 aggregates).

    Elastic lifecycle: an elastic re-form tears the world down and
    re-enters ``init()`` in the same process — the old bridge is closed
    first (``teardown_distributed``, landing the old generation's
    capture on disk) and the new one opens under
    ``gen<g>/rank<k>`` so re-formed generations never write into a
    prior generation's directory (ranks are renumbered across re-forms:
    the new rank 0 may be a different host than the old rank 0's
    still-valuable capture).
    """

    def __init__(self, logdir: str, rank: int,
                 generation: int = 1) -> None:
        import atexit
        import os

        import jax

        self._jax_profiler = jax.profiler
        sub = (f"rank{rank}" if generation <= 1
               else os.path.join(f"gen{generation}", f"rank{rank}"))
        self._dir = os.path.join(logdir, sub)
        os.makedirs(self._dir, exist_ok=True)
        self._jax_profiler.start_trace(self._dir)
        self._active = True
        # The capture only lands at stop_trace; scripts that exit
        # without hvd.shutdown() must still get their profile.
        atexit.register(self.close)

    def annotate(self, label: str):
        """Context manager labelling framework work (e.g. the fused
        dispatch of one negotiated response) in the device trace."""
        return self._jax_profiler.TraceAnnotation(label)

    def close(self) -> None:
        if self._active:
            self._active = False
            try:
                self._jax_profiler.stop_trace()
            except RuntimeError:
                pass  # no trace running (e.g. double shutdown)


def make_timeline(path: str):
    """Native C++ writer when it builds, Python fallback otherwise."""
    try:
        return NativeTimeline(path)
    except Exception as exc:
        from horovod_tpu.common import logging as _log

        _log.warning("native timeline unavailable (%r); using the "
                     "Python writer" % (exc,))
        return Timeline(path)


class Timeline:
    def __init__(self, path: str) -> None:
        self._path = path
        self._q: queue.Queue = queue.Queue()
        self._tids: dict[str, int] = {}
        self._start = time.monotonic()
        self._file = open(path, "w")
        self._file.write("[\n")
        self._first = True
        self._closed = False
        self._writer = threading.Thread(target=self._write_loop,
                                        name="hvd-timeline", daemon=True)
        self._writer.start()

    # -- record API (called from the background thread) --------------------

    def _us(self) -> int:
        return int((time.monotonic() - self._start) * 1e6)

    def _tid(self, tensor_name: str) -> int:
        tid = self._tids.get(tensor_name)
        if tid is None:
            tid = len(self._tids) + 1
            self._tids[tensor_name] = tid
            self._q.put({"name": "thread_name", "ph": "M", "pid": 0,
                         "tid": tid,
                         "args": {"name": tensor_name}})
        return tid

    def negotiate_start(self, name: str, kind: str) -> None:
        self._q.put({"name": f"NEGOTIATE_{kind.upper()}", "ph": "B",
                     "pid": 0, "tid": self._tid(name), "ts": self._us()})

    def negotiate_end(self, name: str, kind: str) -> None:
        self._q.put({"name": f"NEGOTIATE_{kind.upper()}", "ph": "E",
                     "pid": 0, "tid": self._tid(name), "ts": self._us()})

    def negotiate_rank_ready(self, name: str, rank: int) -> None:
        """Instant tick: ``rank``'s request for ``name`` reached the
        coordinator (reference ``timeline.h:85-88``)."""
        self._q.put({"name": f"RANK{rank}_READY", "ph": "i", "pid": 0,
                     "tid": self._tid(name), "ts": self._us(), "s": "t",
                     "args": {"rank": rank}})

    def activity_start(self, name: str, activity: str) -> None:
        self._q.put({"name": activity, "ph": "B", "pid": 0,
                     "tid": self._tid(name), "ts": self._us()})

    def activity_end(self, name: str, activity: str) -> None:
        self._q.put({"name": activity, "ph": "E", "pid": 0,
                     "tid": self._tid(name), "ts": self._us()})

    def mark_cycle(self) -> None:
        self._q.put({"name": "CYCLE_START", "ph": "i", "pid": 0, "tid": 0,
                     "ts": self._us(), "s": "g"})

    def overlap_phase(self, name: str, bucket: int, phase: str,
                      elems: int = 0) -> None:
        """Per-bucket overlap-schedule tick (``overlap/rs``,
        ``overlap/compute``, ``overlap/ag``) on a ``<name>/bucket<k>``
        row, so the K-bucket pipeline is visible in the Chrome trace.
        These record host-side *issue* order — the whole schedule is
        one XLA program, so per-bucket device durations live in the
        ``hvd_overlap_*`` named scopes of the jax profiler capture
        (``HOROVOD_TIMELINE_JAX_PROFILER``); see docs/overlap.md."""
        self._q.put({"name": f"overlap/{phase}", "ph": "i", "pid": 0,
                     "tid": self._tid(f"{name}/bucket{bucket}"),
                     "ts": self._us(), "s": "t",
                     "args": {"bucket": bucket, "elems": int(elems)}})

    # -- writer ------------------------------------------------------------

    def _write_loop(self) -> None:
        while True:
            item = self._q.get()
            if item is None:
                # Footer written by the owner of the file handle so
                # closing can't race a mid-backlog writer.
                self._file.write("\n]\n")
                self._file.close()
                return
            text = json.dumps(item)
            if self._first:
                self._first = False
                self._file.write(text)
            else:
                self._file.write(",\n" + text)

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        self._q.put(None)
        self._writer.join(timeout=10)
