"""Profiler walkthrough: chrome-trace capture around a training loop.

Reference analogue: example/profiler/profiler_executor.py — set_config →
set_state('run') → train → set_state('stop') → dump; opens in
chrome://tracing / perfetto. Scoped Task/Marker objects annotate phases,
and the aggregate table prints per-op totals (MXDumpAggregateStats
parity).

Run: JAX_PLATFORMS=cpu python examples/profiler/profile_training.py
"""
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import mxnet_tpu as mx
from mxnet_tpu import gluon, profiler
from mxnet_tpu.gluon import nn


def main():
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=10)
    args = ap.parse_args()
    trace_file = os.environ.get("MXTPU_PROFILE_OUT", "/tmp/mxtpu_profile.json")
    profiler.set_config(filename=trace_file, profile_all=True)
    profiler.set_state("run")

    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(128, activation="relu"), nn.Dense(10))
    net.initialize(mx.init.Xavier())
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1})
    lossfn = gluon.loss.SoftmaxCrossEntropyLoss()
    rng = np.random.RandomState(0)

    with profiler.Task("train-steps"):
        for step in range(args.steps):
            profiler.Marker("step-%d" % step).mark()
            x = mx.nd.array(rng.randn(32, 64).astype(np.float32))
            y = mx.nd.array(rng.randint(0, 10, (32,)).astype(np.float32))
            with mx.autograd.record():
                l = lossfn(net(x), y)
            l.backward()
            trainer.step(32)
    mx.nd.waitall()

    profiler.set_state("stop")
    profiler.dump()
    print("chrome trace written to %s (%d bytes) — open in "
          "chrome://tracing" % (trace_file, os.path.getsize(trace_file)))
    print("\nper-op aggregate (reference: MXDumpAggregateStats):")
    print(profiler.dumps(reset=True))


if __name__ == "__main__":
    main()
