"""Sparse linear classification: CSR data + row_sparse weights.

Reference analogue: example/sparse/linear_classification/train.py — a
linear model over high-dimensional sparse features (CSR batches), with
row_sparse weight/grad so the optimizer touches only the rows each batch
hits (lazy update), and kvstore row_sparse_pull fetching just those rows.

Run: JAX_PLATFORMS=cpu python examples/sparse/linear_classification.py
"""
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import mxnet_tpu as mx
from mxnet_tpu import autograd
from mxnet_tpu.ndarray import sparse

DIM, ACTIVE, BATCH = 1000, 12, 32


def synth_batch(rng, w_true):
    """CSR batch: ACTIVE random features per row."""
    data, indices, indptr, ys = [], [], [0], []
    for _ in range(BATCH):
        cols = rng.choice(DIM, ACTIVE, replace=False)
        vals = rng.randn(ACTIVE).astype(np.float32)
        data.extend(vals)
        indices.extend(cols)
        indptr.append(len(data))
        ys.append(1.0 if vals @ w_true[cols] > 0 else 0.0)
    x = sparse.csr_matrix(
        (np.array(data, np.float32), np.array(indices, np.int64),
         np.array(indptr, np.int64)), shape=(BATCH, DIM))
    return x, mx.nd.array(np.array(ys, np.float32))


def main():
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=150)
    args = ap.parse_args()
    rng = np.random.RandomState(0)
    w_true = rng.randn(DIM).astype(np.float32)

    # dense master weight + row_sparse gradients: the optimizer's lazy
    # update touches only the rows each batch hits (reference keeps the
    # weight row_sparse on the PS; here the chip holds it dense in HBM and
    # sparsity lives in the gradient/update path)
    weight = mx.nd.zeros((DIM, 1))
    weight.attach_grad(stype="row_sparse")
    opt = mx.optimizer.create("adagrad", learning_rate=0.5)
    state = opt.create_state(0, weight)

    kv = mx.kv.create("local")
    kv.init(0, weight)

    correct = total = 0
    for step in range(args.steps):
        if step == max(args.steps - 30, args.steps * 4 // 5):
            correct = total = 0  # measure post-convergence accuracy
        x, y = synth_batch(rng, w_true)
        with autograd.record():
            logits = sparse.dot(x, weight).reshape((BATCH,))
            # logistic loss
            loss = mx.nd.log(1 + mx.nd.exp(-(2 * y - 1) * logits)).mean()
        loss.backward()
        assert weight.grad.stype == "row_sparse", weight.grad.stype
        opt.update(0, weight, weight.grad, state)
        kv.push(0, weight)

        pred = (logits.asnumpy() > 0).astype(np.float32)
        correct += (pred == y.asnumpy()).sum()
        total += BATCH
        if step % 30 == 0 or step == 149:
            print("step %3d  loss %.4f  running acc %.3f  nnz rows %d"
                  % (step, float(loss.asnumpy()), correct / total,
                     weight.grad.indices.shape[0]))

    # row_sparse pull of just-seen rows (the reference's demo op)
    rows = mx.nd.array(np.arange(8, dtype=np.float32))
    out = mx.nd.zeros((DIM, 1)).tostype("row_sparse")
    kv.row_sparse_pull(0, out=out, row_ids=rows)
    acc = correct / total
    print("final accuracy %.3f" % acc)
    bar = 0.8 if args.steps >= 150 else 0.6   # smoke runs train less
    assert acc > bar, "sparse linear model failed to learn (acc %.3f)" % acc


if __name__ == "__main__":
    main()
