#!/usr/bin/env python3
"""A builder's tool: say what the host did over each of a cell's longest idle
gaps of the device, by the program's own annotations.

    python3 chipbench/tools/gaps.py --workload <cell> --seed <n> --seconds <s>

Runs the cell as ``run.py --trace 1`` does.  The result line's
``breakdown.idle_gaps`` names each gap by the innermost span that covers most
of it (``chipbench.lib.trace.composition``: the harness's ``chipbench.*`` spans
and the program's ``mxtpu.*`` ones, the phases of the decode scheduler's lap
and of a trainer's step); before it, one line a gap gives the whole
composition, since a gap often begins in the tail of a wait and ends in the
next phase.
"""
import argparse
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
    args = ap.parse_args(argv)
    gaps = []
    result = bench_run.run_cell(args.workload, bench_run.Options(
        args.seed, args.seconds, True,
        series=lambda run: gaps.extend(
            (run["trace"] or {}).get("idle_gap_spans", []))))
    for length, spans in gaps:
        print("idle gap %8.3f ms: %s" % (1e3 * length, ", ".join(
            "%s %.3f" % (n, 1e3 * s) for n, s in
            sorted(spans.items(), key=lambda kv: -kv[1]))), flush=True)
    bench_run.emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
