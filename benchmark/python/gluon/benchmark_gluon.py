#!/usr/bin/env python
"""Gluon model-zoo throughput microbenchmark (reference:
benchmark/python/gluon/benchmark_gluon.py — per-model fwd / fwd+bwd
imgs/sec across batch sizes).

TPU-native framing: each (model, batch) config times the hybridized
forward and a full compiled train step (fwd + CE + bwd + SGD update via
DistributedTrainer, one donated XLA executable). Prints one JSON line per
config.

Run (CPU smoke): JAX_PLATFORMS=cpu python benchmark/python/gluon/benchmark_gluon.py \
        --models resnet18_v1 --batch-sizes 2 --image-size 64 --iters 3
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), *[".."] * 3))

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from common import timeit  # noqa: E402


def bench_model(name, batch, size, iters, warmup):
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon.model_zoo import vision
    from mxnet_tpu.parallel import DistributedTrainer, make_mesh

    ctx = mx.tpu()
    with ctx:
        net = getattr(vision, name)()
        net.initialize(ctx=ctx)
        rng = np.random.RandomState(0)
        x = mx.nd.array(rng.uniform(-1, 1, (batch, 3, size, size))
                        .astype(np.float32), ctx=ctx)
        y = mx.nd.array(rng.randint(0, 1000, (batch,)).astype(np.float32),
                        ctx=ctx)
        net(x)
    net.hybridize()

    fwd_s = timeit(lambda: net(x), iters, warmup)

    mesh = make_mesh([("dp", 1)], devices=[jax.devices()[0]])
    trainer = DistributedTrainer(
        net, "sgd", {"learning_rate": 0.01, "momentum": 0.9},
        loss=gluon.loss.SoftmaxCrossEntropyLoss(), mesh=mesh)
    train_s = timeit(lambda: trainer.step(x, y), iters, warmup)

    print(json.dumps({
        "model": name, "batch": batch, "image_size": size,
        "fwd_imgs_per_sec": round(batch / fwd_s, 2),
        "train_imgs_per_sec": round(batch / train_s, 2),
        "device": jax.devices()[0].device_kind,
    }), flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--models", default="resnet18_v1",
                    help="comma-separated model_zoo.vision names")
    ap.add_argument("--batch-sizes", default="1,32")
    ap.add_argument("--image-size", type=int, default=224)
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--warmup", type=int, default=2)
    args = ap.parse_args()
    for m in args.models.split(","):
        for b in (int(v) for v in args.batch_sizes.split(",")):
            bench_model(m.strip(), b, args.image_size, args.iters,
                        args.warmup)


if __name__ == "__main__":
    main()
