"""``python -m benchmark.run --workload <name> --seed <n> --seconds <s>
--trace <0|1>``: one cell, one run, one last line.

An ``inline`` cell is measured in this process.  An ``hvdrun`` cell is a
world of one process per chip: this process then never starts a JAX
backend (it would hold a chip its ranks need); it starts
``python -m horovod_tpu.run -np <world> -- python -m benchmark.rank ...``,
waits until every rank has left through ``hvd.shutdown()`` with exit 0,
and merges the records the ranks wrote into the one line.
"""

import argparse
import json
import os
import shutil
import signal
import subprocess
import sys
import time

from benchmark import manifest

T0 = time.time()      # set-up counts from here when this is the program
RANK_MODULE = (sys.executable, "-m", "benchmark.rank")
# a first run in a checkout compiles (the contract allows it 1200 s)
WORLD_BOUND_S = 1100


def prepare_out_dir(cell: manifest.Cell) -> None:
    """Empty the cell's output directory and send libtpu's log there
    (its default is ``/tmp/tpu_logs``, outside the checkout)."""
    shutil.rmtree(cell.out_dir, ignore_errors=True)
    os.makedirs(cell.out_dir)
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(cell.out_dir,
                                                      "tpu_logs"))


def _tail(path: str, lines: int = 15) -> str:
    try:
        with open(path, errors="replace") as f:
            return "".join(f.readlines()[-lines:])
    except OSError as exc:
        return f"({exc})\n"


def launch_world(cell: manifest.Cell, seed: int, seconds: float,
                 trace: bool, t0: float, rank_command: tuple) -> tuple:
    """Run the cell's world to its end.  Returns ``(records, ok)``:
    the records of the ranks that wrote one, and whether the launcher and
    with it every rank left with exit 0.  On a failure the ends of each
    rank's stderr and of libtpu's logs go to earlier lines."""
    world = cell.job["world"]
    logs = os.path.join(cell.out_dir, "ranks")
    command = [sys.executable, "-m", "horovod_tpu.run", "-np", str(world),
               "--output-filename", logs, "--", *rank_command,
               "--manifest", cell.manifest_path, "--workload", cell.name,
               "--seed", str(seed), "--seconds", str(seconds),
               "--trace", str(int(trace)), "--t0", repr(t0)]
    proc = subprocess.Popen(command, cwd=manifest.ROOT,
                            start_new_session=True)
    try:
        code = proc.wait(timeout=WORLD_BOUND_S)
    except subprocess.TimeoutExpired:
        code = 124
    finally:
        # the launcher's group holds every rank; none may outlive the run
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        proc.wait()
    records = []
    for rank in range(world):
        path = os.path.join(cell.out_dir, f"rank{rank}.json")
        if os.path.exists(path):
            with open(path, encoding="utf-8") as f:
                records.append(json.load(f))
    ok = code == 0 and len(records) == world
    if not ok:
        print(f"benchmark: the world of {world} left with exit {code} and "
              f"{len(records)} records", flush=True)
        for rank in range(world):
            print(f"--- rank {rank} stderr\n" + _tail(os.path.join(
                logs, f"rank.{rank}", "stderr")), flush=True)
        tpu_logs = os.environ["TPU_LOG_DIR"]
        for name in sorted(os.listdir(tpu_logs)
                           if os.path.isdir(tpu_logs) else []):
            print(f"--- {name}\n" + _tail(os.path.join(tpu_logs, name)),
                  flush=True)
    return records, ok


def run(workload: str, seed: int, seconds: float, trace: bool, t0: float,
        manifest_path: str = manifest.MANIFEST, allow_cpu: bool = False,
        rank_command: tuple = RANK_MODULE) -> int:
    """Measure one cell and print its line; returns the exit code.
    ``t0`` is ``time.time()`` at the start of the run.  ``allow_cpu`` and
    ``rank_command`` exist for the tests: the command line has no way to
    reach a run without a TPU."""
    from benchmark import harness

    cell = manifest.load_cell(workload, manifest_path)
    prepare_out_dir(cell)
    if cell.job["launcher"] == "hvdrun":
        records, ok = launch_world(cell, seed, seconds, trace, t0,
                                   rank_command)
        if not records:
            print("benchmark: no rank wrote a record; nothing was measured",
                  file=sys.stderr)
            return 1
    else:
        try:
            records, ok = [harness.measure(cell, seed, seconds, trace, t0,
                                           allow_cpu=allow_cpu)], True
        except harness.NoChip as exc:
            print(f"benchmark: {exc}", file=sys.stderr)
            return 1
    line = harness.result_line(cell, records, trace, world_ok=ok)
    with open(os.path.join(cell.out_dir, "records.json"), "w",
              encoding="utf-8") as f:
        json.dump(records, f)
    print(json.dumps(line), flush=True)
    return 0 if ok else 1


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = p.parse_args(argv)
    return run(args.workload, args.seed, args.seconds, bool(args.trace), T0)


if __name__ == "__main__":
    sys.exit(main())
