"""Invariant lint suite (docs/analysis.md).

Every PR since the int8 wire landed has proven its core claims with
one-off regex scans over HLO text, and every review-hardening pass has
re-fixed the same drift classes by hand: a knob that reached the config
registry but not the round-0 handshake or a program cache key, and
lock-order/signal-safety bugs on the abort path.  This package
mechanizes those three invariant families as static-analysis passes:

* :mod:`~horovod_tpu.analysis.hlo_lint` — structural checks over parsed
  HLO instructions (residency, bucketing, lossy placement, overlap
  schedule shape) replacing the per-test regexes;
* :mod:`~horovod_tpu.analysis.knob_lint` — AST cross-referencing of the
  knob registry against raw env reads, the round-0 handshake vector,
  the program/AOT cache keys, the launcher's CLI surface, and the
  docs;
* :mod:`~horovod_tpu.analysis.concurrency_lint` — a lock-acquisition
  graph over ``runtime/``, ``run/`` and ``common/`` reporting
  lock-order cycles, non-reentrant locks reachable from signal
  handlers, and blocking wire calls under hot-path locks.

CLI: ``python -m horovod_tpu.analysis [hlo|knobs|concurrency|all]
[--json]`` — exits non-zero on any finding not covered by a justified
entry in the repo-root ``analysis_allowlist.json``.

The ``knobs`` and ``concurrency`` passes are pure AST work: no module
under lint is imported, only the stdlib-only config registry.  The
``hlo`` pass additionally lowers the program set through jax.  Note
the CLI still needs the ``horovod_tpu`` package importable (package
``__init__`` pulls jax), so a jax-less environment must call the pass
modules' ``run()`` directly rather than ``python -m``.
"""

from __future__ import annotations

import os

from horovod_tpu.analysis.findings import Finding, SEVERITIES

__all__ = ["Finding", "SEVERITIES", "PASSES", "repo_root", "run_pass"]


def repo_root() -> str:
    """The checkout root (parent of the ``horovod_tpu`` package)."""
    pkg = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.dirname(pkg)


def _run_knobs(package_dir: str | None = None) -> list:
    from horovod_tpu.analysis import knob_lint

    return knob_lint.run(package_dir=package_dir)


def _run_concurrency(package_dir: str | None = None) -> list:
    from horovod_tpu.analysis import concurrency_lint

    return concurrency_lint.run(package_dir=package_dir)


def _run_hlo(package_dir: str | None = None) -> list:
    # package_dir is accepted for CLI uniformity but unused: the hlo
    # pass lints lowered programs, not source trees.
    del package_dir
    from horovod_tpu.analysis import programs

    return programs.run()


# Pass registry: name -> (runner, description).  Adding a pass =
# one entry here plus a module exposing run() -> list[Finding]
# (docs/analysis.md "adding a pass").
PASSES = {
    "knobs": (_run_knobs,
              "knob drift: raw env reads, handshake/cache-key/CLI/doc "
              "cross-references"),
    "concurrency": (_run_concurrency,
                    "lock-order cycles, signal-unsafe locks, blocking "
                    "calls under hot-path locks"),
    "hlo": (_run_hlo,
            "residency/placement/schedule invariants of the CPU-lowered "
            "negotiated program set"),
}


def run_pass(name: str, package_dir: str | None = None) -> list:
    runner, _ = PASSES[name]
    return runner(package_dir=package_dir)
