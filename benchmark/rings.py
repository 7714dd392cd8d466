"""A cell's flight rings, and what they say of its set-up.

Every process of the program keeps a flight ring
(``horovod_tpu/runtime/flight.py``, docs/flight-recorder.md).  Set-up
leaves in it: ``hvd_process`` (when the process started), the span
``hvd_init`` around ``hvd.init()``, and one ``hvd_compile`` record for
every program compiled, with the seconds of its trace, its lowering and
its backend compile and what the persistent cache did.  ``hvdrun`` keeps
a ring of its own with the span ``hvd_launch``.

``of_cell`` gives a cell's rings as a list, one a process: this
process's in an inline cell, the dumps the ranks and the launcher left
under ``<out_dir>/ranks/flight/`` in a launched one (``hvdrun
--output-filename`` implies that directory, and a clean
``hvd.shutdown()`` dumps there).  A reader under ``layers/`` takes the
worst rank's value.  A program that records none of this (the parent of
PR 37) gives no ring, or rings without ``hvd_process``: every reader
then returns ``None``.

``python -m benchmark.rings <out_dir>`` prints, a process, the phases,
every ``hvd_compile`` record ordered by its seconds, and, from
``records.json``, ``setup_s`` less what the records cover.
"""

from __future__ import annotations

import glob
import json
import os
import sys

DUMPS = "flight-*.jsonl"


def load(path: str) -> list:
    """The events of one dump, in order (its first line is the header)."""
    with open(path, encoding="utf-8") as f:
        events = [json.loads(line) for line in f if line.strip()]
    return sorted((e for e in events if "meta" not in e),
                  key=lambda e: e["seq"])


def _dump_paths(out_dir: str) -> list:
    return [path for where in ("ranks/flight", "flight")
            for path in sorted(glob.glob(os.path.join(out_dir, where, DUMPS)))]


def dumps_under(out_dir: str) -> list:
    """Every ring a run left under its output directory: a launched
    world's, and the one ``of_cell`` writes for an inline cell."""
    return [load(path) for path in _dump_paths(out_dir)]


_read: dict = {}     # out_dir -> (what the rings were read from, the rings)


def of_cell(cell) -> list:
    """The cell's rings, one a process, each a list of events.  Seven
    readers ask in a row: the answer is kept while its source stands."""
    out_dir = cell.out_dir
    if cell.job["launcher"] == "hvdrun":
        stamp = [(p, os.stat(p).st_mtime_ns) for p in _dump_paths(out_dir)]
        if _read.get(out_dir, (None,))[0] != stamp:
            _read[out_dir] = (stamp, dumps_under(out_dir))
        return _read[out_dir][1]
    flight = sys.modules.get("horovod_tpu.runtime.flight")
    if flight is None:
        return []
    recorder = flight.recorder()
    if _read.get(out_dir, (None,))[0] != (recorder, recorder.recorded_total()):
        # kept for the view by hand: an inline cell's process leaves
        # through no hvd.shutdown(), so nothing else would write its ring
        flight.dump("benchmark", directory=os.path.join(out_dir, "flight"))
        _read[out_dir] = ((recorder, recorder.recorded_total()),
                          [recorder.snapshot()])
    return _read[out_dir][1]


# ---------------------------------------------------------------------------
# One ring
# ---------------------------------------------------------------------------


def first(ring: list, kind: str, ph: str = "i") -> dict | None:
    return next((e for e in ring if e["kind"] == kind and e["ph"] == ph),
                None)


def is_launcher(ring: list) -> bool:
    return first(ring, "hvd_launch", "B") is not None


def started_wall(ring: list) -> float | None:
    """When the ring's process started (``hvd_process``)."""
    process = first(ring, "hvd_process")
    return process and process.get("started_wall")


def spans(ring: list, prefix: str) -> list:
    """``(kind, seconds, B event)`` of every closed span whose kind
    starts with ``prefix``, in the order they were opened."""
    begun, closed = {}, []
    for event in ring:
        if not event["kind"].startswith(prefix):
            continue
        if event["ph"] == "B":
            begun[event["id"]] = event
        elif event["ph"] == "E" and event["id"] in begun:
            begin = begun.pop(event["id"])
            closed.append((begin["kind"], event["mono"] - begin["mono"],
                           begin))
    return sorted(closed, key=lambda span: span[2]["seq"])


def import_s(ring: list) -> float | None:
    """From the process's start until ``hvd.init()`` was entered: the
    interpreter, the imports of ``jax`` and ``horovod_tpu`` and whatever
    else the program did first."""
    start, init = started_wall(ring), first(ring, "hvd_init", "B")
    if start is None or init is None:
        return None
    return init["wall"] - start


def hvd_init_s(ring: list) -> float | None:
    """The first ``hvd_init`` span, whole."""
    whole = [s for kind, s, _ in spans(ring, "hvd_init")
             if kind == "hvd_init"]
    if started_wall(ring) is None or not whole:
        return None
    return whole[0]


def compiles(ring: list) -> list | None:
    """The ``hvd_compile`` records of a ring that has its ``hvd_init``;
    ``None`` of a program that records none."""
    if started_wall(ring) is None or first(ring, "hvd_init", "B") is None:
        return None
    return [e for e in ring if e["kind"] == "hvd_compile"]


def _compile_seconds(ring: list, fields: tuple, hit=None) -> float | None:
    """``fields`` added up over the ring's ``hvd_compile`` records: all
    of them, or those the persistent cache served (``hit`` true) or did
    not (false)."""
    records = compiles(ring)
    if records is None:
        return None
    return sum(e[f] for e in records for f in fields
               if hit is None or (e["cache"] == "hit") == hit)


def trace_lower_s(ring: list) -> float | None:
    """Tracing and lowering, which no cache saves."""
    return _compile_seconds(ring, ("trace_s", "lower_s"))


def backend_compile_s(ring: list) -> float | None:
    """XLA's own compiles: of the programs the cache did not serve."""
    return _compile_seconds(ring, ("backend_s",), hit=False)


def cache_load_s(ring: list) -> float | None:
    """Reading and loading the executables the cache served."""
    return _compile_seconds(ring, ("backend_s",), hit=True)


def programs_compiled(ring: list) -> int | None:
    records = compiles(ring)
    return None if records is None else len(records)


# ---------------------------------------------------------------------------
# A cell
# ---------------------------------------------------------------------------


def worst(cell, value) -> float | None:
    """``value(ring)`` of the rank it is largest for; ``None`` where
    there is no rank's ring or one of them gives none."""
    values = [value(ring) for ring in of_cell(cell)
              if not is_launcher(ring)]
    if not values or None in values:
        return None
    return max(values)


def launch_s(rings: list) -> float | None:
    """From the launcher's own start (its interpreter and imports
    count) until the last rank's process had started."""
    launchers = [started_wall(r) for r in rings if is_launcher(r)]
    ranks = [started_wall(r) for r in rings if not is_launcher(r)]
    if len(launchers) != 1 or not ranks or None in launchers + ranks:
        return None
    return max(ranks) - launchers[0]


# ---------------------------------------------------------------------------
# By hand
# ---------------------------------------------------------------------------


def describe(rings: list, setup_s: float | None) -> dict:
    """Every process's phases and programs, and what of ``setup_s`` (the
    last rank's) they leave to running the programs: the reference
    check, step 0, the warm-up chunk."""
    starts = [s for s in map(started_wall, rings) if s is not None]
    origin = min(starts) if starts else None
    out = {"launch_s": launch_s(rings), "processes": []}
    for ring in rings:
        programs = sorted(
            compiles(ring) or [],
            key=lambda e: -(e["trace_s"] + e["lower_s"] + e["backend_s"]))
        process = {
            "argv0": (first(ring, "hvd_process") or {}).get("argv0"),
            "launcher": is_launcher(ring),
            "import_s": import_s(ring),
            "hvd_init_s": hvd_init_s(ring),
            "spans": [[kind, seconds] for prefix in ("hvd_import",
                                                     "hvd_launch",
                                                     "hvd_init.")
                      for kind, seconds, _ in spans(ring, prefix)],
            "trace_lower_s": trace_lower_s(ring),
            "backend_compile_s": backend_compile_s(ring),
            "cache_load_s": cache_load_s(ring),
            "programs_compiled": len(programs),
            "programs": [[e["fun_name"], e["trace_s"], e["lower_s"],
                          e["backend_s"], e["cache"]] for e in programs],
        }
        if setup_s is not None and origin is not None:
            process["compiled_after_setup"] = [
                e["fun_name"] for e in programs
                if e["start_wall"] - origin > setup_s]
            if not process["launcher"] and process["import_s"] is not None:
                covered = sum(
                    e["trace_s"] + e["lower_s"] + e["backend_s"]
                    for e in programs
                    if e["start_wall"] - origin <= setup_s)
                process["setup_s_less_records"] = (
                    setup_s - (started_wall(ring) - origin)
                    - process["import_s"] - (process["hvd_init_s"] or 0.0)
                    - covered)
        out["processes"].append(process)
    return out


def main(argv: list) -> int:
    (out_dir,) = argv
    rings = dumps_under(out_dir)
    if not rings:
        print(f"{out_dir}: no flight ring was dumped there",
              file=sys.stderr)
        return 1
    setup_s = None
    records = os.path.join(out_dir, "records.json")
    if os.path.exists(records):
        with open(records, encoding="utf-8") as f:
            setup_s = max(r["end_to_end"]["setup_s"] for r in json.load(f))
    print(json.dumps({"setup_s": setup_s, **describe(rings, setup_s)},
                     indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
