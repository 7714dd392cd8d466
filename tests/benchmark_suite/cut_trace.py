"""How ``data/trace_gpt2-124m.s8192_2steps.textproto.gz`` was made, kept
so that a later benchmark PR can record another:

    python tests/benchmark_suite/cut_trace.py <trace>.xplane.pb <out>.textproto.gz

Cuts a profiler trace of a traced benchmark run to what the reduction
reads — the ``XLA Ops`` and ``Steps`` lines of each device plane for the
first two steps, and the host line with the loop's annotations — moves
time to start at 0, and writes the ``XSpace`` message in text form,
gzipped.  ``benchmark.reduce.load`` reads it back through
``ProfileData.from_text_proto``, the same reader as for a whole trace.
"""

import gzip
import json
import sys

from jax.profiler import ProfileData

KEEP_STEPS = 2
SPANS = ("bench_step", "dispatch", "block")


def kept(profile) -> dict:
    """``{plane name: {line name: [event, ...]}}`` of what stays."""
    planes = {}
    for plane in profile.planes:
        lines = {line.name: list(line.events) for line in plane.lines}
        if not plane.name.startswith("/device:"):
            keep = {name: [e for e in events if e.name in SPANS]
                    for name, events in lines.items()}
        elif lines.get("Steps"):
            steps = sorted(lines["Steps"],
                           key=lambda e: e.start_ns)[:KEEP_STEPS]
            lo = steps[0].start_ns
            hi = steps[-1].start_ns + steps[-1].duration_ns
            keep = {name: [e for e in lines[name] if lo <= e.start_ns < hi]
                    for name in ("Steps", "XLA Ops")}
        else:
            continue
        keep = {name: events for name, events in keep.items() if events}
        if keep:
            planes[plane.name] = keep
    return planes


def cut(profile) -> str:
    planes = kept(profile)
    base = min(e.start_ns for lines in planes.values()
               for events in lines.values() for e in events)
    out = []
    for plane, lines in planes.items():
        ids: dict = {}
        out.append(f"planes {{\n  name: {json.dumps(plane)}")
        for number, (name, events) in enumerate(lines.items()):
            out.append(f"  lines {{\n    id: {number}\n"
                       f"    name: {json.dumps(name)}")
            for e in events:
                key = ids.setdefault(e.name, len(ids) + 1)
                out.append(
                    f"    events {{ metadata_id: {key} offset_ps: "
                    f"{round((e.start_ns - base) * 1000)} "
                    f"duration_ps: {round(e.duration_ns * 1000)} }}")
            out.append("  }")
        for name, key in ids.items():
            out.append(f"  event_metadata {{ key: {key} value {{ id: {key} "
                       f"name: {json.dumps(name)} }} }}")
        out.append("}")
    return "\n".join(out) + "\n"


if __name__ == "__main__":
    source, target = sys.argv[1:]
    with gzip.open(target, "wt", encoding="utf-8") as f:
        f.write(cut(ProfileData.from_file(source)))
