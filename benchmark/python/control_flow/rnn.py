#!/usr/bin/env python
"""Control-flow microbenchmark (reference: benchmark/python/control_flow/
rnn.py — foreach vs while_loop vs Python-unrolled RNN throughput).

Times an LSTMCell over a sequence three ways:
  unroll   — Python-loop unroll inside the traced step (XLA sees the
             whole unrolled graph; best for short fixed lengths)
  foreach  — `nd.contrib.foreach`, lowering to `lax.scan` under trace
             (O(1) compile size; the long-sequence mode)
  while_loop — `nd.contrib.while_loop`, lowering to `lax.while_loop`

One JSON line per (mode, seq_len, batch) config.

Run (CPU smoke): JAX_PLATFORMS=cpu python benchmark/python/control_flow/rnn.py \
        --seq-lens 16 --batch-sizes 2 --iters 3
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


HIDDEN = 512


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq-lens", default="64,256")
    ap.add_argument("--batch-sizes", default="16")
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--warmup", type=int, default=2)
    args = ap.parse_args()

    import jax

    import mxnet_tpu as mx
    from mxnet_tpu import gluon, nd

    dev = jax.devices()[0].device_kind
    rng = np.random.RandomState(0)

    for seq_len in (int(v) for v in args.seq_lens.split(",")):
        for batch in (int(v) for v in args.batch_sizes.split(",")):
            cell = gluon.rnn.LSTMCell(HIDDEN, input_size=HIDDEN)
            cell.initialize(mx.init.Xavier())
            seq = mx.nd.array(rng.normal(
                size=(seq_len, batch, HIDDEN)).astype(np.float32))
            begin = cell.begin_state(batch_size=batch)

            def run_unroll():
                out, _ = cell.unroll(seq_len, seq, begin_state=begin,
                                     layout="TNC", merge_outputs=True)
                return out

            def step(data, states):
                out, new_states = cell(data, states)
                return out, new_states

            def run_foreach():
                out, _ = nd.contrib.foreach(step, seq, begin)
                return out

            def run_while():
                def cond(i, *_):
                    return i < seq_len

                def body(i, h, c):
                    out, (nh, nc) = cell(seq[i], [h, c])
                    return [out.sum()], [i + 1, nh, nc]

                outs, _ = nd.contrib.while_loop(
                    cond, body, [mx.nd.array([0]).reshape(()).astype("int32"),
                                 begin[0], begin[1]],
                    max_iterations=seq_len)
                return outs[0]

            for mode, fn in (("unroll", run_unroll),
                             ("foreach", run_foreach),
                             ("while_loop", run_while)):
                try:
                    s = timeit(fn, args.iters, args.warmup)
                    print(json.dumps({
                        "mode": mode, "seq_len": seq_len, "batch": batch,
                        "hidden": HIDDEN, "ms": round(s * 1e3, 2),
                        "steps_per_sec": round(seq_len * batch / s, 1),
                        "device": dev}), flush=True)
                except Exception as e:  # keep other modes running
                    print(json.dumps({"mode": mode, "seq_len": seq_len,
                                      "batch": batch,
                                      "error": str(e)[:200]}), flush=True)


if __name__ == "__main__":
    main()
