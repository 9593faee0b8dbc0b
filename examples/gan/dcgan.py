"""DCGAN: adversarial training end to end.

Reference analogue: example/gan/dcgan.py (deconv generator vs conv
discriminator, alternating updates). Scaled to 16x16 synthetic data so it
runs anywhere; exercises Deconvolution, BatchNorm under dual optimizers,
and detached-generator updates — the graph patterns GANs stress.

Run: JAX_PLATFORMS=cpu python examples/gan/dcgan.py
"""
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon
from mxnet_tpu.gluon import nn

Z = 16


def build_generator():
    net = nn.HybridSequential(prefix="gen_")
    with net.name_scope():
        # z (B, Z, 1, 1) -> (B, 1, 16, 16)
        net.add(nn.Conv2DTranspose(32, 4, strides=1, padding=0,
                                   use_bias=False),
                nn.BatchNorm(), nn.Activation("relu"),
                nn.Conv2DTranspose(16, 4, strides=2, padding=1,
                                   use_bias=False),
                nn.BatchNorm(), nn.Activation("relu"),
                nn.Conv2DTranspose(1, 4, strides=2, padding=1,
                                   use_bias=False),
                nn.Activation("tanh"))
    return net


def build_discriminator():
    net = nn.HybridSequential(prefix="disc_")
    with net.name_scope():
        net.add(nn.Conv2D(16, 4, strides=2, padding=1),
                nn.LeakyReLU(0.2),
                nn.Conv2D(32, 4, strides=2, padding=1),
                nn.BatchNorm(), nn.LeakyReLU(0.2),
                nn.Conv2D(1, 4, strides=1, padding=0))
    return net


def main():
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=40)
    args = ap.parse_args()
    rng = np.random.RandomState(0)
    # "real" data: smooth blobs in [-1, 1]
    yy, xx = np.mgrid[0:16, 0:16].astype(np.float32)

    def real_batch(n):
        cx = rng.uniform(4, 12, (n, 1, 1))
        cy = rng.uniform(4, 12, (n, 1, 1))
        img = np.exp(-((xx - cx) ** 2 + (yy - cy) ** 2) / 8.0)
        return (img * 2 - 1).astype(np.float32)[:, None]

    gen, disc = build_generator(), build_discriminator()
    gen.initialize(mx.init.Normal(0.02))
    disc.initialize(mx.init.Normal(0.02))
    g_tr = gluon.Trainer(gen.collect_params(), "adam",
                         {"learning_rate": 2e-3, "beta1": 0.5})
    d_tr = gluon.Trainer(disc.collect_params(), "adam",
                         {"learning_rate": 2e-3, "beta1": 0.5})
    lossfn = gluon.loss.SigmoidBinaryCrossEntropyLoss()

    B = 16
    for step in range(args.steps):
        real = mx.nd.array(real_batch(B))
        z = mx.nd.array(rng.randn(B, Z, 1, 1).astype(np.float32))
        ones = mx.nd.ones((B,))
        zeros = mx.nd.zeros((B,))

        # discriminator: real -> 1, fake (detached generator) -> 0
        with autograd.record():
            fake = gen(z)
            d_loss = (lossfn(disc(real).reshape((B,)), ones) +
                      lossfn(disc(fake.detach()).reshape((B,)), zeros)).mean()
        d_loss.backward()
        d_tr.step(B)

        # generator: fool the discriminator
        with autograd.record():
            g_loss = lossfn(disc(gen(z)).reshape((B,)), ones).mean()
        g_loss.backward()
        g_tr.step(B)

        if step % 10 == 0 or step == 39:
            print("step %2d  d_loss %.4f  g_loss %.4f"
                  % (step, float(d_loss.asnumpy()),
                     float(g_loss.asnumpy())))

    assert np.isfinite(float(d_loss.asnumpy()))
    assert np.isfinite(float(g_loss.asnumpy()))
    fake_np = fake.asnumpy()
    assert fake_np.shape == (B, 1, 16, 16)
    print("done — generator output range [%.2f, %.2f]"
          % (fake_np.min(), fake_np.max()))


if __name__ == "__main__":
    main()
