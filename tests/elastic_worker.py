"""Worker body for the preemption / elastic world-size resume tests
(tests/test_preempt_elastic.py — the ISSUE 17 acceptance path).

Trains a deterministic linear regression with gluon.Trainer over a
dist_sync kvstore, writing PER-RANK SHARDED checkpoints through
parallel.resilience.CheckpointManager.save_sharded_async every
MXTPU_TEST_CKPT_EVERY steps and auto-resuming via restore_sharded at
startup — the fast path when the manifest matches this run's world size,
the elastic path (all shards read, state reassembled) when it does not.
On SIGTERM (MXTPU_FAULT_INJECT preempt action, or a real scheduler) the
in-flight step finishes, a SOLO emergency checkpoint lands inside the
grace window, and the process exits MXTPU_PREEMPT_EXIT_CODE so
tools/launch.py restarts it for free.

Cross-world-size exactness trick: EVERY rank computes the FULL global
batch, so each rank's local gradient is identical and the dist_sync
allreduce-sum divided by (batch × world) is bit-exact for power-of-two
world sizes — a 2-rank trajectory equals a 1-rank trajectory to the last
ulp, which lets the parent test assert exact final-weight matches across
preempt→resume at the same AND at a different world size."""
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from mxnet_tpu.parallel import collectives  # noqa: E402

collectives.init_process_group()

import numpy as np  # noqa: E402

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import autograd, gluon  # noqa: E402
from mxnet_tpu.gluon import nn  # noqa: E402
from mxnet_tpu.parallel import resilience  # noqa: E402
from mxnet_tpu.parallel.resilience import (CheckpointManager,  # noqa: E402
                                           restart_generation)

TOTAL_STEPS = int(os.environ.get("MXTPU_TEST_TOTAL_STEPS", "12"))
CKPT_EVERY = int(os.environ.get("MXTPU_TEST_CKPT_EVERY", "2"))
BATCH = 16
DIM = 8


def batch_for(step):
    """The FULL deterministic global batch for a (1-based) step — the same
    on every rank and at every world size (see module docstring)."""
    rng = np.random.RandomState(10_000 + step)
    x = rng.normal(size=(BATCH, DIM)).astype(np.float32)
    w = np.arange(1, DIM + 1, dtype=np.float32).reshape(DIM, 1) / DIM
    return x, x @ w


def main():
    kv = mx.kv.create("dist_sync")
    r, n = kv.rank, kv.num_workers
    topology = {"world_size": n}

    np.random.seed(77)  # same init draw on every rank
    net = nn.Dense(1, in_units=DIM, use_bias=False)
    net.initialize(mx.init.Normal(0.5))
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.05, "momentum": 0.9},
                            kvstore=kv)
    mgr = CheckpointManager(os.environ["MXTPU_CKPT_DIR"],
                            keep_last=3, save_every=CKPT_EVERY)
    resilience.install_preemption_handler()

    def payload():
        """This rank's shard: replicated params + the trainer-states blob
        (opaque bytes via the public save_states API, so the optimizer
        cursor and momentum ride along)."""
        fd, tmp = tempfile.mkstemp(prefix="trainer-states-")
        os.close(fd)
        try:
            trainer.save_states(tmp)
            with open(tmp, "rb") as f:
                blob = f.read()
        finally:
            os.unlink(tmp)
        return {"params": {k: v.data().asnumpy()
                           for k, v in net.collect_params().items()},
                "states_blob": blob, "step": trainer.step_count}

    def load_shards(payloads, header):
        # params are fully replicated, so ANY shard reassembles the whole
        # model — exactly why a solo emergency checkpoint (1 shard) can
        # elastically resume at any world size
        p = payloads[min(payloads)]
        for k, v in net.collect_params().items():
            v.set_data(mx.nd.array(p["params"][k]))
        fd, tmp = tempfile.mkstemp(prefix="trainer-states-")
        os.close(fd)
        try:
            with open(tmp, "wb") as f:
                f.write(p["states_blob"])
            trainer.load_states(tmp)
        finally:
            os.unlink(tmp)

    header = mgr.restore_sharded(load_shards, rank=r, world_size=n,
                                 topology=topology)
    start = trainer.step_count
    if header is not None:
        elastic = not (header.get("topology") == topology
                       and int(header.get("shards") or 0) == n)
        print("ELASTIC_RESUMED rank=%d/%d gen=%d from_step=%d elastic=%d "
              "shards=%d" % (r, n, restart_generation(), start, int(elastic),
                             int(header.get("shards") or 0)), flush=True)

    def emergency():
        mgr.flush()  # let any in-flight periodic shard publish first
        mgr.save_sharded(trainer.step_count, payload(), rank=0, world_size=1,
                         topology={"world_size": 1}, meta={"preempt": True})

    l2 = gluon.loss.L2Loss()
    for step in range(start + 1, TOTAL_STEPS + 1):
        xb, yb = batch_for(step)
        with autograd.record():
            loss = l2(net(mx.nd.array(xb)), mx.nd.array(yb))
        loss.backward()
        # the MXTPU_FAULT_INJECT hook fires inside step() at the boundary;
        # the preempt action SIGTERMs this very process there
        trainer.step(BATCH * n)
        if step % CKPT_EVERY == 0:
            mgr.save_sharded_async(step, payload(), rank=r, world_size=n,
                                   topology=topology,
                                   meta={"kind": "elastic-test"})
        resilience.maybe_preempt_exit(emergency_save=emergency, rank=r)

    mgr.close()  # drain the async writer so the final manifest publishes
    w = net.weight.data().asnumpy()
    print("ELASTIC_OK rank=%d/%d gen=%d steps=%d wsum=%.8f"
          % (r, n, restart_generation(), trainer.step_count, float(w.sum())),
          flush=True)


if __name__ == "__main__":
    main()
