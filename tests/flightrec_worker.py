"""Worker body for the flight-recorder end-to-end test
(tests/test_telemetry.py::test_flight_recorder_hang_e2e).

Runs a tiny gluon training loop under tools/launch.py. The parent test
arms `MXTPU_FAULT_INJECT=hang@step=5,rank=1` plus a short
`MXTPU_WATCHDOG_TIMEOUT`: rank 1 parks forever at the step-5 boundary (the
deterministic stand-in for a wedged collective), its telemetry watchdog
dumps thread stacks + the event ring to a per-rank file and aborts, and the
launcher's SIGUSR1-then-SIGTERM teardown makes the still-alive rank 0 leave
its own dump behind. No process group is formed — the hang/teardown
machinery is what's under test, and skipping the rendezvous keeps the test
runnable on boxes that can't assemble jax groups.
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import autograd, gluon  # noqa: E402
from mxnet_tpu.gluon import nn  # noqa: E402


def main():
    rank = int(os.environ.get("MXTPU_PROCESS_ID", "0"))
    total = int(os.environ.get("MXTPU_TEST_TOTAL_STEPS", "400"))
    pause = float(os.environ.get("MXTPU_TEST_STEP_SLEEP", "0.05"))

    net = nn.Dense(1, in_units=4, use_bias=False)
    net.initialize(mx.init.Normal(0.1))
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.01})
    l2 = gluon.loss.L2Loss()
    x = mx.nd.array(np.ones((4, 4), dtype=np.float32))
    y = mx.nd.array(np.zeros((4, 1), dtype=np.float32))

    for _ in range(total):
        with autograd.record():
            loss = l2(net(x), y)
        loss.backward()
        # MXTPU_FAULT_INJECT's hang action fires inside step() at the
        # boundary, AFTER the step's watchdog heartbeat — exactly the
        # "step N never completes" shape a real wedge has
        trainer.step(4)
        time.sleep(pause)
    print("FLIGHTREC_WORKER_DONE rank=%d steps=%d"
          % (rank, trainer.step_count), flush=True)


if __name__ == "__main__":
    main()
