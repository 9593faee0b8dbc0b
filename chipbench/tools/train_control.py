#!/usr/bin/env python3
"""A builder's tool, not part of a run: the training check's control read on
the chip at a configuration's own size.  For each seed the plain reference
follows its three steps in float32 and again in each lower precision, and the
numbers `correct` compares are printed for the lower one against the float32
one.  No program, no window.

    python3 chipbench/tools/train_control.py --config resnet50_v1 \
        --seeds 1,2,3 --precisions fp8,bfloat16
"""
import argparse
import importlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--precisions", default="fp8")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    from chipbench import run as bench_run
    from chipbench.runners import train

    with open(os.path.join(ROOT, "chipbench", "configs",
                           args.config + ".json")) as f:
        config = json.load(f)
    if args.rehearse:
        config = bench_run._merge(config, config.get("rehearse", {}))
    else:
        bench_run.arm_compile_cache()
    ref = importlib.import_module(config["reference"])
    sizes, opt = config["sizes"], config["optimizer"]
    for seed in (int(s) for s in args.seeds.split(",")):
        batches = ref.make_batches(seed, sizes, train.CHECK_STEPS)
        base = ref.follow(seed, sizes, batches, opt, train.CHECK_STEPS,
                          "float32")
        for precision in args.precisions.split(","):
            low = ref.follow(seed, sizes, batches, opt, train.CHECK_STEPS,
                             precision)
            print("seed %d control %s %s" % (seed, precision, json.dumps(
                train.compare(low, base))), flush=True)


if __name__ == "__main__":
    main()
