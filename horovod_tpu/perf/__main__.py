"""CLI: ``python -m horovod_tpu.perf {report,goodput,health}``.

``report <dir>``    — device-truth attribution for every capture under
                      a profile directory (``--json`` for machines).
``goodput <path>``  — wall-clock attribution table per rank and
                      fleet-wide from goodput ledger dumps or a live
                      ``/metrics.json`` endpoint (docs/goodput.md).
``health <path>``   — per-rank training-health table (grad norm, loss,
                      nonfinite culprit attribution, sentinel alerts)
                      from health dumps or a live ``/metrics.json``
                      endpoint (docs/health.md).
See docs/perf.md.
"""

from __future__ import annotations

import argparse
import json
import sys


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="python -m horovod_tpu.perf",
        description="Device-truth perf observatory: xplane, goodput "
                    "and health reports (docs/perf.md).")
    sub = p.add_subparsers(dest="cmd", required=True)

    r = sub.add_parser("report", help="analyze captures under a "
                                      "profile dir")
    r.add_argument("dir", help="HOROVOD_PROFILE_DIR / "
                               "HOROVOD_TIMELINE_JAX_PROFILER directory")
    r.add_argument("--json", action="store_true",
                   help="machine-readable output")
    r.add_argument("--flops", type=float, default=None,
                   help="flops per step (enables MFU when the capture "
                        "has no recorded hint)")

    g = sub.add_parser(
        "goodput",
        help="wall-clock attribution per rank + fleet "
             "(docs/goodput.md)")
    g.add_argument("path",
                   help="a directory of goodput-*.json ledger dumps "
                        "(HOROVOD_GOODPUT_DIR / the flight dir), a "
                        "single dump, or a live rank endpoint URL "
                        "(http://host:port — /metrics.json is "
                        "fetched)")
    g.add_argument("--json", action="store_true",
                   help="machine-readable output")
    g.add_argument("--slo", type=float, default=None,
                   help="goodput SLO in (0,1] for the report's verdict "
                        "line (default: HOROVOD_GOODPUT_SLO)")

    h = sub.add_parser(
        "health",
        help="per-rank training-health table (docs/health.md)")
    h.add_argument("path",
                   help="a directory of health-*.json dumps "
                        "(HOROVOD_HEALTH_DIR / the flight dir), a "
                        "single dump, or a live rank endpoint URL "
                        "(http://host:port — /metrics.json is "
                        "fetched)")
    h.add_argument("--json", action="store_true",
                   help="machine-readable output")
    return p


def main(argv=None) -> int:
    from horovod_tpu.perf import report as _report

    args = build_parser().parse_args(argv)
    if args.cmd == "health":
        from horovod_tpu.runtime import health as _health

        try:
            rep = _health.load_report(args.path)
        except Exception as exc:
            print(f"health report failed for {args.path}: {exc!r}",
                  file=sys.stderr)
            return 1
        if args.json:
            print(json.dumps(rep))
        else:
            print(_health.format_report(rep))
        return 0 if rep["ranks"] else 1
    if args.cmd == "goodput":
        from horovod_tpu.perf import goodput as _goodput

        try:
            rep = _goodput.load_report(args.path, slo=args.slo)
        except Exception as exc:
            print(f"goodput report failed for {args.path}: {exc!r}",
                  file=sys.stderr)
            return 1
        if args.json:
            print(json.dumps(rep))
        else:
            print(_goodput.format_report(rep))
        return 0 if rep["ranks"] else 1
    rep = _report.analyze_dir(args.dir, flops_per_step=args.flops)
    if args.json:
        print(json.dumps(rep))
    else:
        print(_report.format_report(rep))
    return 0 if rep["captures"] else 1


if __name__ == "__main__":
    sys.exit(main())
