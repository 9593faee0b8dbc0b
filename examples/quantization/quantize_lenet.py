"""INT8 post-training quantization, end to end.

Reference analogue: example/quantization/imagenet_gen_qsym.py +
imagenet_inference.py (train fp32 → calibrate on sample batches →
quantize_model → compare fp32 vs int8 accuracy). Scaled to LeNet on
synthetic MNIST-shaped data so it runs anywhere (zero-egress / CPU);
the same flow quantizes any exported symbol on the chip.

Run: JAX_PLATFORMS=cpu python examples/quantization/quantize_lenet.py
"""
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import mxnet_tpu as mx
from mxnet_tpu import gluon
from mxnet_tpu.contrib.quantization import quantize_model
from mxnet_tpu.gluon import nn


def build_lenet():
    net = nn.HybridSequential(prefix="lenet_")
    with net.name_scope():
        net.add(nn.Conv2D(8, kernel_size=3, activation="relu"),
                nn.MaxPool2D(pool_size=2, strides=2),
                nn.Conv2D(16, kernel_size=3, activation="relu"),
                nn.MaxPool2D(pool_size=2, strides=2),
                nn.Flatten(),
                nn.Dense(64, activation="relu"),
                nn.Dense(10))
    return net


def main():
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="1 epoch over 128 samples (CI smoke configs)")
    args = ap.parse_args()
    n, n_epochs = (256, 2) if args.smoke else (512, 3)
    rng = np.random.RandomState(0)
    # synthetic "MNIST": 10 gaussian class prototypes + noise
    protos = rng.uniform(-1, 1, (10, 1, 28, 28)).astype(np.float32)
    X = np.concatenate([protos[i % 10][None] + 0.1 * rng.randn(1, 1, 28, 28)
                        for i in range(n)]).astype(np.float32)
    Y = np.array([i % 10 for i in range(n)], dtype=np.float32)

    net = build_lenet()
    net.initialize(mx.init.Xavier())
    net.hybridize()
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": 2e-3})
    lossfn = gluon.loss.SoftmaxCrossEntropyLoss()
    for epoch in range(n_epochs):
        for i in range(0, n, 64):
            x = mx.nd.array(X[i:i + 64])
            y = mx.nd.array(Y[i:i + 64])
            with mx.autograd.record():
                l = lossfn(net(x), y)
            l.backward()
            trainer.step(64)
        print("epoch %d loss %.4f" % (epoch, float(l.mean().asnumpy())))

    def accuracy(fwd):
        pred = fwd(mx.nd.array(X)).asnumpy().argmax(1)
        return (pred == Y).mean()

    fp32_acc = accuracy(net)

    # export → quantize with entropy (KL) calibration → rebind
    prefix = "/tmp/lenet_q"
    net.export(prefix, epoch=0)
    sym, arg_params, aux_params = mx.model.load_checkpoint(prefix, 0)
    calib = mx.io.NDArrayIter(X[:128], Y[:128], batch_size=64,
                              label_name="softmax_label")
    qsym, qarg, qaux = quantize_model(
        sym, arg_params, aux_params, ctx=mx.cpu(),
        calib_mode="entropy", calib_data=calib, num_calib_examples=128)

    mod = mx.module.Module(qsym, label_names=None, context=mx.cpu())
    mod.bind(data_shapes=[("data", (n, 1, 28, 28))], for_training=False)
    mod.set_params(qarg, qaux, allow_missing=True)

    def q_fwd(x):
        mod.forward(mx.io.DataBatch([x], None), is_train=False)
        return mod.get_outputs()[0]

    int8_acc = accuracy(q_fwd)
    print("fp32 accuracy: %.3f   int8 accuracy: %.3f   drop: %.3f"
          % (fp32_acc, int8_acc, fp32_acc - int8_acc))
    tol = 0.06 if args.smoke else 0.02   # 1-2 epoch accuracies are noisy
    assert int8_acc > fp32_acc - tol, \
        "int8 accuracy dropped >%.0f%%" % (tol * 100)


if __name__ == "__main__":
    main()
