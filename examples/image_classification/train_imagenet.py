#!/usr/bin/env python
"""ImageNet training — the reference's headline script
(example/image-classification/train_imagenet.py + common/fit.py), with the
same argument surface (subset) over the gluon model zoo.

Data: point --data-train/--data-val at RecordIO files (ImageRecordIter,
same .rec format as the reference, packed by tools/im2rec.py); without
them the script runs on synthetic ImageNet-shaped batches so it is
runnable anywhere (zero-egress CI, perf smoke on the chip).

TPU-first knobs beyond the reference: --dtype bfloat16 (bf16 compute +
fp32 master weights via DistributedTrainer) and --layout NHWC
(channels-last zoo build, the MXU-preferred layout).
"""
from __future__ import annotations

import argparse
import logging
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))

import numpy as np


def add_fit_args(parser):
    """reference: common/fit.py:77 add_fit_args (subset)."""
    t = parser.add_argument_group("Training")
    t.add_argument("--network", type=str, default="resnet50_v1",
                   help="model zoo factory name (resnet50_v1, resnet18_v1, "
                        "inception_v3, mobilenet1_0, ...)")
    t.add_argument("--kv-store", type=str, default="device")
    t.add_argument("--num-epochs", type=int, default=1)
    t.add_argument("--lr", type=float, default=0.1)
    t.add_argument("--lr-factor", type=float, default=0.1)
    t.add_argument("--lr-step-epochs", type=str, default="30,60")
    t.add_argument("--optimizer", type=str, default="sgd")
    t.add_argument("--mom", type=float, default=0.9)
    t.add_argument("--wd", type=float, default=1e-4)
    t.add_argument("--batch-size", type=int, default=32)
    t.add_argument("--disp-batches", type=int, default=20)
    t.add_argument("--model-prefix", type=str, default=None)
    t.add_argument("--top-k", type=int, default=0)
    t.add_argument("--dtype", type=str, default="bfloat16",
                   choices=["float32", "bfloat16"])
    t.add_argument("--layout", type=str, default="NCHW",
                   choices=["NCHW", "NHWC"])
    t.add_argument("--num-classes", type=int, default=1000)
    t.add_argument("--image-shape", type=str, default="3,224,224")
    t.add_argument("--data-train", type=str, default=None,
                   help="RecordIO file (tools/im2rec.py); synthetic if unset")
    t.add_argument("--data-val", type=str, default=None)
    t.add_argument("--num-batches", type=int, default=10,
                   help="synthetic-data batches per epoch")
    return parser


def _synthetic_batches(args, shape, rng):
    for _ in range(args.num_batches):
        x = rng.uniform(-1, 1, (args.batch_size,) + shape).astype(np.float32)
        y = rng.randint(0, args.num_classes, (args.batch_size,))
        yield x, y


def main():
    args = add_fit_args(argparse.ArgumentParser(
        description="train imagenet-1k",
        formatter_class=argparse.ArgumentDefaultsHelpFormatter)).parse_args()
    logging.basicConfig(level=logging.INFO)

    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon.model_zoo import vision
    from mxnet_tpu.parallel import DistributedTrainer, make_mesh

    c, h, w = (int(s) for s in args.image_shape.split(","))
    nhwc = args.layout == "NHWC"
    shape = (h, w, c) if nhwc else (c, h, w)

    ctx = mx.tpu() if mx.context.num_gpus() else mx.cpu()
    fac = getattr(vision, args.network)
    with ctx:
        if nhwc:
            with gluon.nn.layout_scope():
                net = fac(classes=args.num_classes)
        else:
            net = fac(classes=args.num_classes)
        net.initialize(mx.init.Xavier(), ctx=ctx)
        net(mx.nd.zeros((args.batch_size,) + shape, ctx=ctx))

    import jax

    # data-parallel over every visible device (the reference script's
    # multi-GPU behavior); batch is sliced across the dp axis
    devices = jax.devices()
    dp = len(devices)
    while args.batch_size % dp:
        dp -= 1  # largest device count dividing the batch
    if dp != len(devices):
        logging.warning("using %d/%d devices (batch %d not divisible)",
                        dp, len(devices), args.batch_size)
    mesh = make_mesh([("dp", dp)], devices=devices[:dp])
    opt_params = {"learning_rate": args.lr, "wd": args.wd}
    if args.optimizer == "sgd":
        opt_params["momentum"] = args.mom
    trainer = DistributedTrainer(
        net, args.optimizer, opt_params,
        loss=gluon.loss.SoftmaxCrossEntropyLoss(), mesh=mesh,
        amp_dtype=None if args.dtype == "float32" else args.dtype)

    lr_steps = [int(e) for e in args.lr_step_epochs.split(",") if e]
    metric = mx.metric.Accuracy()
    if args.top_k:
        metric = mx.metric.CompositeEvalMetric(
            [metric, mx.metric.TopKAccuracy(args.top_k)])

    def _rec_batches(path, shuffle):
        it = mx.io.ImageRecordIter(path_imgrec=path, data_shape=(c, h, w),
                                   batch_size=args.batch_size,
                                   shuffle=shuffle)
        for b in it:
            xb = b.data[0]
            if nhwc:
                # device-side relayout; no host round trip
                xb = mx.nd.transpose(xb, (0, 2, 3, 1))
            yield xb, b.label[0], b.pad or 0

    def _evaluate(epoch):
        trainer.sync_params()  # copy mesh-trained values into the block
        metric.reset()
        for xb, yb, pad in _rec_batches(args.data_val, shuffle=False):
            with mx.autograd.predict_mode():
                out = net(xb.as_in_context(ctx))
            keep = xb.shape[0] - pad  # last batch pads by cycling samples;
            metric.update([yb[:keep].as_in_context(ctx)],  # don't score dups
                          [out[:keep]])
        for name, val in metric.get_name_value():
            logging.info("Epoch[%d] Validation-%s=%f", epoch, name, val)

    rng = np.random.RandomState(0)
    for epoch in range(args.num_epochs):
        if epoch in lr_steps:
            trainer.set_learning_rate(trainer.learning_rate * args.lr_factor)
        if args.data_train:
            batches = _rec_batches(args.data_train, shuffle=True)
        else:
            batches = ((mx.nd.array(x, ctx=ctx), mx.nd.array(y, ctx=ctx), 0)
                       for x, y in _synthetic_batches(args, shape, rng))

        tic = time.time()
        win_tic, win_n = time.time(), 0   # Speedometer-style window: the
        n = 0                             # first-batch compile cost only
        for i, (xb, yb, pad) in enumerate(batches):  # hits first interval
            # the padded tail still trains at the static batch shape
            # (reference behavior); only the sample accounting excludes it
            loss = trainer.step(xb.as_in_context(ctx),
                                yb.astype("float32").as_in_context(ctx))
            n += xb.shape[0] - pad
            win_n += xb.shape[0] - pad
            if (i + 1) % args.disp_batches == 0:
                logging.info("Epoch[%d] Batch [%d]\tSpeed: %.2f samples/sec"
                             "\tloss=%.4f", epoch, i + 1,
                             win_n / (time.time() - win_tic),
                             float(loss.asnumpy()))
                win_tic, win_n = time.time(), 0
        logging.info("Epoch[%d] Train-samples/sec=%f", epoch,
                     n / (time.time() - tic))
        logging.info("Epoch[%d] Time cost=%.3f", epoch, time.time() - tic)
        if args.data_val:
            _evaluate(epoch)

        if args.model_prefix:
            trainer.sync_params()  # export the trained weights, not init
            net.export(args.model_prefix, epoch=epoch)
    print("done: trained %s %s %s on %s" % (
        args.network, args.dtype, args.layout, ctx))


if __name__ == "__main__":
    main()
