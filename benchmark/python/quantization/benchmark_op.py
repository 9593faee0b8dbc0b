#!/usr/bin/env python
"""Quantized-op microbenchmark (reference: benchmark/python/quantization/
benchmark_op.py — quantized_conv vs fp32 conv throughput per shape).

Times per config: fp32 conv, bf16 conv, the bare int8 kernel
(quantized_conv, int8xint8->int32 on the MXU; operands pre-quantized),
and the end-to-end int8 layer path (per-batch activation quantize ->
quantized_conv -> dequantize). One JSON line each with imgs/sec and the
speedups vs fp32 for both int8 accountings.

Run (CPU smoke): JAX_PLATFORMS=cpu python benchmark/python/quantization/benchmark_op.py \
        --configs 2x16x16x16x3 --iters 3
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


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--configs", default="32x64x56x56x64,32x128x28x28x128",
                    help="BxCxHxWxF per config (F = out filters), comma-sep")
    ap.add_argument("--kernel", type=int, default=3)
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--warmup", type=int, default=2)
    args = ap.parse_args()

    import jax

    import mxnet_tpu as mx
    from mxnet_tpu import nd

    dev = jax.devices()[0].device_kind
    rng = np.random.RandomState(0)
    k = args.kernel

    for cfg in args.configs.split(","):
        b, c, h, w, f = (int(v) for v in cfg.split("x"))
        x = mx.nd.array(rng.uniform(-1, 1, (b, c, h, w)).astype(np.float32))
        wt = mx.nd.array(rng.uniform(-1, 1, (f, c, k, k))
                         .astype(np.float32))

        t_fp32 = timeit(lambda: nd.Convolution(
            x, wt, kernel=(k, k), num_filter=f, no_bias=True, pad=(1, 1)),
            args.iters, args.warmup)

        xb, wb = x.astype("bfloat16"), wt.astype("bfloat16")
        t_bf16 = timeit(lambda: nd.Convolution(
            xb, wb, kernel=(k, k), num_filter=f, no_bias=True, pad=(1, 1)),
            args.iters, args.warmup)

        lo, hi = mx.nd.array([-1.0]), mx.nd.array([1.0])
        xq, xmin, xmax = nd.contrib.quantize(x, lo, hi, out_type="int8")
        wq, wmin, wmax = nd.contrib.quantize(wt, lo, hi, out_type="int8")
        zero_bias = mx.nd.zeros((f,), dtype="int8")
        # bare int8 kernel (activations AND weights pre-quantized)
        t_int8 = timeit(lambda: nd.contrib.quantized_conv(
            xq, wq, zero_bias, xmin, xmax, wmin, wmax, kernel=(k, k),
            num_filter=f, no_bias=True, pad=(1, 1))[0],
            args.iters, args.warmup)

        def int8_e2e():
            # what a real inference layer pays per batch: quantize the
            # activations, conv, dequantize the int32 accumulator
            aq, amin, amax = nd.contrib.quantize(x, lo, hi, out_type="int8")
            o, omin, omax = nd.contrib.quantized_conv(
                aq, wq, zero_bias, amin, amax, wmin, wmax, kernel=(k, k),
                num_filter=f, no_bias=True, pad=(1, 1))
            return nd.contrib.dequantize(o, omin, omax)

        t_int8_e2e = timeit(int8_e2e, args.iters, args.warmup)

        print(json.dumps({
            "config": cfg, "kernel": k,
            "fp32_imgs_per_sec": round(b / t_fp32, 1),
            "bf16_imgs_per_sec": round(b / t_bf16, 1),
            "int8_kernel_imgs_per_sec": round(b / t_int8, 1),
            "int8_e2e_imgs_per_sec": round(b / t_int8_e2e, 1),
            "int8_kernel_vs_fp32": round(t_fp32 / t_int8, 2),
            "int8_e2e_vs_fp32": round(t_fp32 / t_int8_e2e, 2),
            "bf16_vs_fp32": round(t_fp32 / t_bf16, 2),
            "device": dev}), flush=True)


if __name__ == "__main__":
    main()
