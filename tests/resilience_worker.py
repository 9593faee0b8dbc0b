"""Worker body for the kill-a-worker resume-equivalence test
(tests/test_resilience.py — the fault-tolerance acceptance path).

Trains a deterministic linear regression with gluon.Trainer over a
dist_sync kvstore, checkpointing through parallel.resilience
.CheckpointManager every MXTPU_TEST_CKPT_EVERY steps and AUTO-RESUMING
from the newest complete checkpoint at startup. The parent test runs it
twice: once uninterrupted, once with MXTPU_FAULT_INJECT killing rank 1
mid-training under `tools/launch.py --max-restarts` — final weight
checksums must match exactly, proving the restart generation resumed from
the atomic checkpoint and replayed the identical update stream."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from mxnet_tpu.parallel import collectives  # noqa: E402

collectives.init_process_group()

import numpy as np  # noqa: E402

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import autograd, gluon  # noqa: E402
from mxnet_tpu.gluon import nn  # noqa: E402
from mxnet_tpu.parallel.resilience import (CheckpointManager,  # noqa: E402
                                           restart_generation)

TOTAL_STEPS = int(os.environ.get("MXTPU_TEST_TOTAL_STEPS", "12"))
CKPT_EVERY = int(os.environ.get("MXTPU_TEST_CKPT_EVERY", "2"))
BATCH = 16
DIM = 8


def batch_for(step, rank, n):
    """Deterministic batch for a given (1-based) step and rank — the SAME
    stream regardless of how many process lives consumed it, so a resumed
    run replays exactly what the uninterrupted run saw."""
    rng = np.random.RandomState(10_000 + step)
    x = rng.normal(size=(BATCH * n, DIM)).astype(np.float32)
    w = np.arange(1, DIM + 1, dtype=np.float32).reshape(DIM, 1) / DIM
    y = x @ w
    return x[rank::n], y[rank::n]


def main():
    kv = mx.kv.create("dist_sync")
    r, n = kv.rank, kv.num_workers

    np.random.seed(77)  # same init draw on every rank
    net = nn.Dense(1, in_units=DIM, use_bias=False)
    net.initialize(mx.init.Normal(0.5))
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.05, "momentum": 0.9},
                            kvstore=kv)
    mgr = CheckpointManager(os.environ["MXTPU_CKPT_DIR"],
                            keep_last=3, save_every=CKPT_EVERY)

    # auto-resume: every rank restores the newest COMPLETE checkpoint
    # (written by rank 0; shared filesystem). load_states also restores the
    # trainer's step cursor, so the loop below continues mid-schedule.
    header = mgr.restore(load_params=net.load_parameters,
                         load_states=trainer.load_states)
    start = trainer.step_count
    if header is not None:
        print("RESILIENCE_RESUMED rank=%d gen=%d from_step=%d"
              % (r, restart_generation(), start), flush=True)

    l2 = gluon.loss.L2Loss()
    for step in range(start + 1, TOTAL_STEPS + 1):
        xb, yb = batch_for(step, r, n)
        with autograd.record():
            loss = l2(net(mx.nd.array(xb)), mx.nd.array(yb))
        loss.backward()
        # the MXTPU_FAULT_INJECT hook fires inside step() at the boundary
        trainer.step(len(xb) * n)
        mgr.maybe_save(trainer.step_count,
                       save_params=net.save_parameters,
                       save_states=trainer.save_states,
                       meta={"kind": "resilience-test"})

    w = net.weight.data().asnumpy()
    print("RESILIENCE_OK rank=%d/%d gen=%d steps=%d wsum=%.6f"
          % (r, n, restart_generation(), trainer.step_count, float(w.sum())),
          flush=True)


if __name__ == "__main__":
    main()
