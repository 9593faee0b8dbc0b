#!/usr/bin/env python3
"""A builder's tool, not the benchmark's command: run one cell as
``chipbench/run.py`` does, with what only a builder needs.

    python3 chipbench/tools/builder.py --workload <cell> --seed <n> \\
        --seconds <s> [--trace 1] [--rehearse] [--control fp8] \\
        [--series FILE] [--set traffic.rate_rps=3 ...]

``--rehearse`` walks the cell at the tiny sizes its files give under
"rehearse" on whatever JAX has and reports counts only (a time, a rate or a
share is null); ``--control`` prints the lower precisions' numbers beside the
check's; ``--series`` writes the per-step (or per-slice) series; ``--set``
overrides a value of the traffic or the configuration for a sweep.
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(argv=None):
    from chipbench import run as bench_run

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--series", metavar="FILE")
    ap.add_argument("--control", help="comma-separated lower precisions")
    ap.add_argument("--set", action="append", default=[], metavar="K=V",
                    help="traffic.<key>=<json> or config.<key>=<json>")
    args = ap.parse_args(argv)
    series = None
    if args.series:
        def series(obj, path=args.series):
            os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
            with open(path, "w") as f:
                json.dump(obj, f)
    opts = bench_run.Options(
        args.seed, args.seconds, args.trace, args.rehearse, series,
        args.control.split(",") if args.control else None,
        overrides={k: json.loads(v) for k, v in
                   (kv.split("=", 1) for kv in args.set)})
    bench_run.emit(bench_run.run_cell(args.workload, opts))
    return 0


if __name__ == "__main__":
    sys.exit(main())
