#!/usr/bin/env python3
"""A builder's tool: name a cell's longest idle gaps of the device by the
program's own annotations.

    python3 chipbench/tools/gaps.py --workload <cell> --seed <n> --seconds <s>

Runs the cell as ``run.py --trace 1`` does, after widening
``chipbench.lib.trace.ANNOTATION_PREFIX`` in this process alone to the
program's ``mxtpu.*`` spans (the phases of the decode scheduler's lap and of a
trainer's step).  The result line's ``breakdown.idle_gaps`` then names each gap
by the innermost span it began in; before it, one line a gap says what the
host did over the whole gap, innermost spans first.  The ledger's ``idle_gaps``
go on reading ``host_between_annotations`` until a ``benchmark`` PR widens the
prefix in ``lib/trace.py`` itself (ROADMAP.md).
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def composition(events, top=10):
    """The ``top`` longest idle gaps of the first device: (seconds, {span:
    seconds of the gap during which it was the innermost open span})."""
    from chipbench.lib import trace

    dev = [e for e in events if trace.DEVICE_PLANE.match(e["plane"])]
    first = min(e["plane"] for e in dev)
    _, busy = trace._union([(e["start"], e["start"] + e["dur"])
                            for e in dev if e["plane"] == first])
    gaps = sorted(((busy[i + 1][0] - busy[i][1], busy[i][1], busy[i + 1][0])
                   for i in range(len(busy) - 1)), reverse=True)[:top]
    host = [(e["start"], e["start"] + e["dur"], e["name"]) for e in events
            if not trace.DEVICE_PLANE.match(e["plane"])]
    out = []
    for length, lo, hi in gaps:
        over = [h for h in host if h[0] < hi and h[1] > lo]
        cuts = sorted({lo, hi} | {t for h in over for t in h[:2]
                                  if lo < t < hi})
        spans = {}
        for a, b in zip(cuts, cuts[1:]):
            open_ = [h for h in over if h[0] <= a and h[1] >= b]
            # innermost: the latest to start and, of those, the first to end
            name = max(open_, key=lambda h: (h[0], -h[1]))[2] if open_ \
                else "host_between_annotations"
            spans[name] = spans.get(name, 0.0) + (b - a)
        out.append((length, spans))
    return out


def main(argv=None):
    from chipbench import run as bench_run
    from chipbench.lib import trace

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    trace.ANNOTATION_PREFIX = ("chipbench.", "mxtpu.")
    reduce, gaps = trace.reduce, []

    def reduce_and_keep(events, chips):
        gaps.extend(composition(events))
        return reduce(events, chips)

    trace.reduce = reduce_and_keep
    result = bench_run.run_cell(
        args.workload, bench_run.Options(args.seed, args.seconds, True))
    for length, spans in gaps:
        print("idle gap %8.3f ms: %s" % (1e3 * length, ", ".join(
            "%s %.3f" % (n, 1e3 * s) for n, s in
            sorted(spans.items(), key=lambda kv: -kv[1]))), flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
