"""A rank for the CPU tests of a launched world: ``benchmark.rank`` with
the CPU allowed.  The benchmark's own command line cannot say so."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import rank  # noqa: E402

if __name__ == "__main__":
    sys.exit(rank.main(allow_cpu=True))
