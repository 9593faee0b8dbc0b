"""Worker body for the dist-trainer regression test: gluon.Trainer with a
dist_sync kvstore and ONE local device must still allreduce gradients
across ranks (reference trainer.py:169 — 'dist' in kvstore.type engages
the kvstore regardless of local device count; the standard
1-GPU-per-worker mode).

Each rank trains linear regression on a different data shard; with grad
sync the ranks stay bit-identical and converge to the true weights. The
parent greps the per-rank weight checksum to prove cross-rank identity."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from mxnet_tpu.parallel import collectives  # noqa: E402

collectives.init_process_group()

import numpy as np  # noqa: E402

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import autograd, gluon  # noqa: E402
from mxnet_tpu.gluon import nn  # noqa: E402


def main():
    kv = mx.kv.create("dist_sync")
    r, n = kv.rank, kv.num_workers

    np.random.seed(42)  # same data-generating process on every rank
    w_true = np.random.normal(size=(8, 1)).astype(np.float32)
    x_all = np.random.normal(size=(128, 8)).astype(np.float32)
    y_all = x_all @ w_true
    xr, yr = x_all[r::n], y_all[r::n]  # per-rank shard

    # deliberately DIFFERENT init per rank: the dist kvstore's init-time
    # broadcast must make rank 0's draw authoritative, or the replicas
    # train permanently diverged (identical grad sums never close an
    # initial offset)
    np.random.seed(1000 + r)
    net = nn.Dense(1, in_units=8, use_bias=False)
    net.initialize(mx.init.Normal(0.5))
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1}, kvstore=kv)
    l2 = gluon.loss.L2Loss()
    for _ in range(60):
        with autograd.record():
            loss = l2(net(mx.nd.array(xr)), mx.nd.array(yr))
        loss.backward()
        trainer.step(len(xr) * n)

    w = net.weight.data().asnumpy()
    err = float(np.abs(w.flatten() - w_true.flatten()).max())
    assert err < 0.05, "rank %d did not converge: err=%s" % (r, err)
    # checksum must be IDENTICAL across ranks (grad sync every step)
    print("DIST_TRAINER_OK rank=%d/%d wsum=%.6f" % (r, n, float(w.sum())),
          flush=True)


if __name__ == "__main__":
    main()
