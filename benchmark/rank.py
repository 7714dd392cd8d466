"""One rank of a launched world: ``benchmark.run`` starts
``python -m horovod_tpu.run -np <world> -- python -m benchmark.rank ...``
for a cell whose launcher is ``hvdrun``.  The rank measures the cell on
its own chip, writes its record beside the other ranks' and leaves
through ``hvd.shutdown()``.

The ranks agree on the length of the window through a file: rank 0
writes the number of chunks it wants, the others read it.  (A collective
would do, but the benchmark adds no traffic of its own to the step.)
"""

import argparse
import json
import os
import sys
import time

from benchmark import harness, manifest

AGREE_BOUND_S = 120


def agree_through(path: str, rank: int):
    """``agree(n)`` for ``harness.measure``: rank 0's ``n`` for all."""
    def agree(chunks: int) -> int:
        if rank == 0:
            with open(path + ".tmp", "w", encoding="utf-8") as f:
                f.write(str(chunks))
            os.replace(path + ".tmp", path)
            return chunks
        deadline = time.monotonic() + AGREE_BOUND_S
        while not os.path.exists(path):
            if time.monotonic() > deadline:
                raise TimeoutError(f"rank 0 wrote no {path}")
            time.sleep(0.01)
        with open(path, encoding="utf-8") as f:
            return int(f.read())
    return agree


def main(argv=None, allow_cpu: bool = False) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--manifest", required=True)
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), required=True)
    p.add_argument("--t0", type=float, required=True)
    args = p.parse_args(argv)

    import horovod_tpu as hvd

    cell = manifest.load_cell(args.workload, args.manifest)
    rank = int(os.environ["HOROVOD_RANK"])
    record = harness.measure(
        cell, args.seed, args.seconds, bool(args.trace), args.t0,
        allow_cpu=allow_cpu,
        agree=agree_through(os.path.join(cell.out_dir, "chunks"), rank))
    path = os.path.join(cell.out_dir, f"rank{rank}.json")
    with open(path + ".tmp", "w", encoding="utf-8") as f:
        json.dump(record, f)
    os.replace(path + ".tmp", path)
    hvd.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
