#!/usr/bin/env python
"""Sparse op microbenchmarks (reference: benchmark/python/sparse/dot.py
and cast_storage.py — csr dot and storage-cast throughput at given
densities).

One JSON line per (op, shape, density) config with GB/s effective
throughput (bytes of the DENSE-equivalent operands over time — the
reference's accounting, so speedups from sparsity show up directly).

Run (CPU smoke): JAX_PLATFORMS=cpu python benchmark/python/sparse/sparse_op.py \
        --rows 1024 --cols 512 --densities 0.05 --iters 3
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


def _rand_csr(rows, cols, density, rng):
    import mxnet_tpu as mx

    dense = rng.uniform(-1, 1, (rows, cols)).astype(np.float32)
    mask = rng.uniform(size=(rows, cols)) < density
    return mx.nd.array(dense * mask).tostype("csr")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=65536)
    ap.add_argument("--cols", type=int, default=1024)
    ap.add_argument("--out-cols", type=int, default=256)
    ap.add_argument("--densities", default="0.01,0.05,0.25")
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--warmup", type=int, default=2)
    args = ap.parse_args()

    import jax

    import mxnet_tpu as mx

    rng = np.random.RandomState(0)
    dev = jax.devices()[0].device_kind
    rhs = mx.nd.array(rng.uniform(-1, 1, (args.cols, args.out_cols))
                      .astype(np.float32))
    for density in (float(d) for d in args.densities.split(",")):
        csr = _rand_csr(args.rows, args.cols, density, rng)
        dense_bytes = 4 * (args.rows * args.cols
                           + args.cols * args.out_cols)

        s = timeit(lambda: mx.nd.sparse.dot(csr, rhs), args.iters,
                   args.warmup)
        print(json.dumps({"op": "csr_dot_dense", "rows": args.rows,
                          "cols": args.cols, "density": density,
                          "ms": round(s * 1e3, 3),
                          "dense_equiv_gb_per_sec":
                              round(dense_bytes / s / 1e9, 2),
                          "device": dev}), flush=True)

        dense_nd = csr.tostype("default")
        s = timeit(lambda: dense_nd.tostype("csr"), args.iters, args.warmup)
        print(json.dumps({"op": "cast_storage_csr", "rows": args.rows,
                          "cols": args.cols, "density": density,
                          "ms": round(s * 1e3, 3),
                          "dense_equiv_gb_per_sec":
                              round(4 * args.rows * args.cols / s / 1e9, 2),
                          "device": dev}), flush=True)


if __name__ == "__main__":
    main()
