"""Sharded data-parallel training over a device mesh.

Reference analogue: example/distributed_training-horovod/gluon_mnist.py and
tools/launch.py dist_sync jobs — but TPU-native: instead of per-worker
processes exchanging gradients through a parameter server, ONE compiled XLA
step runs over the whole mesh (`parallel.DistributedTrainer`), gradients
all-reduced by the compiler over ICI. The same script spans dp-only or
dp x tp meshes; on a CPU host it uses 8 virtual devices.

Run: JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
         python examples/distributed/train_dist.py [--tp 2]
Multi-host: python tools/launch.py -n <hosts> -- python ... (the mesh then
spans all hosts' devices via the jax.distributed rendezvous).
"""
import argparse
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel axis size (rest goes to dp)")
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--amp", action="store_true", help="bf16 compute")
    args = ap.parse_args()

    import jax

    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.parallel import DistributedTrainer, make_mesh

    n = len(jax.devices())
    if n % args.tp:
        raise SystemExit("device count %d not divisible by tp=%d"
                         % (n, args.tp))
    axes = [("dp", n // args.tp)] + ([("tp", args.tp)] if args.tp > 1
                                     else [])
    mesh = make_mesh(axes)
    print("mesh:", dict(zip(mesh.axis_names, mesh.devices.shape)),
          "on", jax.devices()[0].platform)

    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(256, activation="relu"),
                nn.Dense(256, activation="relu"),
                nn.Dense(10))
    net.initialize(mx.init.Xavier())
    net(mx.nd.zeros((1, 100)))  # materialize deferred shapes

    trainer = DistributedTrainer(
        net, "sgd", {"learning_rate": 0.1, "momentum": 0.9},
        loss=gluon.loss.SoftmaxCrossEntropyLoss(), mesh=mesh,
        amp_dtype="bfloat16" if args.amp else None)

    rng = np.random.RandomState(0)
    W = rng.randn(100, 10).astype(np.float32)
    for step in range(args.steps):
        x = rng.randn(args.batch, 100).astype(np.float32)
        y = (x @ W).argmax(1).astype(np.float32)
        loss = trainer.step(x, y)
        if step % 10 == 0 or step == args.steps - 1:
            print("step %3d  loss %.4f" % (step, float(loss.asnumpy())))
    final = float(loss.asnumpy())
    import numpy as _np

    assert _np.isfinite(final), "non-finite loss"
    if args.steps >= 30:
        # the convergence bar needs the full default step count
        assert final < 1.5, "did not learn (loss %.3f)" % final
    print("done — global batch %d sharded over %d device(s)"
          % (args.batch, n))


if __name__ == "__main__":
    main()
