#!/usr/bin/env python
"""memory_bench: committed CPU evidence for the memory-observability row
(docs/observability.md §Memory).

Three checks, one JSON row (``bench_capture.sh`` archives it as
``BENCH_<tag>_memory.json``):

  1. **footprint attribution** — load a model through `ModelRepository`
     with the persistent compile cache armed; its per-bucket
     `memory_analysis()` figures and total device footprint must be
     computed (the number ``MXTPU_SERVE_MEMORY_BUDGET`` enforces).
  2. **budget admission** — reload under a budget SMALLER than the
     measured footprint (must be rejected with the typed
     `MemoryBudgetError`, HTTP 507) and under a budget larger (must
     publish), plus the ``warn:`` canary mode (must publish).
  3. **donation verifier** — one `DistributedTrainer` fused step; the
     fill-hook verifier must report the donated param/optimizer buffers
     actually aliased (ROADMAP item 1's invariant as a measured number).

Per-phase peak RSS rides every stage. Exit 0 only when all three checks
hold.

    JAX_PLATFORMS=cpu python tools/memory_bench.py > BENCH_memory.json
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def log(msg):
    sys.stderr.write("[memory_bench] %s\n" % msg)
    sys.stderr.flush()


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--max-batch", type=int, default=8)
    args = p.parse_args(argv)

    workdir = tempfile.mkdtemp(prefix="memory_bench_")
    # armed persistent tier: memory figures come from the AOT fill hook
    # and survive in the MXTPUEXE1 headers
    os.environ["MXTPU_COMPILE_CACHE"] = os.path.join(workdir, "cache")
    os.environ.pop("MXTPU_SERVE_MEMORY_BUDGET", None)

    import numpy as np

    import mxnet_tpu  # noqa: F401  (package init pins platform handling)
    from mxnet_tpu import nd
    from mxnet_tpu.gluon import loss as gloss, nn
    from mxnet_tpu.parallel import DistributedTrainer, make_mesh
    from mxnet_tpu.serving import MemoryBudgetError, ModelRepository
    from mxnet_tpu.telemetry import memory as tm_memory

    from serve_bench import _build_mlp  # noqa: E402

    mem_phases = {"start": tm_memory.read_process_memory()}

    log("building mlp ...")
    prefix, input_shapes = _build_mlp(workdir)

    # -- 1: footprint attribution ------------------------------------------
    repo = ModelRepository()
    model = repo.load("m", prefix, input_shapes=input_shapes,
                      max_batch=args.max_batch)
    footprint = model.memory_bytes
    per_bucket = {str(b): f for b, f in sorted(model.bucket_memory.items())}
    mem_phases["loaded"] = tm_memory.read_process_memory()
    log("footprint %s bytes across buckets %s" % (footprint, model.buckets))
    repo.unload("m", timeout=5)

    # -- 2: budget admission ------------------------------------------------
    rejected = accepted = warn_accepted = False
    reject_status = None
    if footprint:
        os.environ["MXTPU_SERVE_MEMORY_BUDGET"] = str(footprint // 2)
        try:
            repo.load("m", prefix, input_shapes=input_shapes,
                      max_batch=args.max_batch)
        except MemoryBudgetError as e:
            rejected = True
            reject_status = e.status
            log("over-budget load rejected (HTTP %d): %s" % (e.status, e))
        os.environ["MXTPU_SERVE_MEMORY_BUDGET"] = "warn:%d" % (footprint // 2)
        try:
            repo.load("m", prefix, input_shapes=input_shapes,
                      max_batch=args.max_batch)
            warn_accepted = True
            repo.unload("m", timeout=5)
            log("warn-mode over-budget load published (canary posture)")
        except MemoryBudgetError:
            pass
        os.environ["MXTPU_SERVE_MEMORY_BUDGET"] = str(footprint * 4)
        try:
            m2 = repo.load("m", prefix, input_shapes=input_shapes,
                           max_batch=args.max_batch)
            accepted = m2.memory_bytes == footprint
            repo.unload("m", timeout=5)
            log("within-budget load accepted (footprint stable: %s)"
                % accepted)
        except MemoryBudgetError:
            pass
        os.environ.pop("MXTPU_SERVE_MEMORY_BUDGET", None)
    mem_phases["budget_checks"] = tm_memory.read_process_memory()

    # -- 3: donation verifier -----------------------------------------------
    net = nn.HybridSequential()
    net.add(nn.Dense(128, activation="relu"), nn.Dense(10))
    net.initialize()
    net(nd.zeros((8, 64)))
    tr = DistributedTrainer(net, "sgd", {"learning_rate": 0.1},
                            loss=gloss.SoftmaxCrossEntropyLoss(),
                            mesh=make_mesh([("dp", -1)]))
    x = nd.array(np.random.RandomState(0).rand(8, 64).astype("float32"))
    y = nd.array(np.arange(8) % 10)
    tr.step(x, y)
    donation = tm_memory.last_donation_report()
    log("donation report: %s" % (donation,))
    mem_phases["trainer_step"] = tm_memory.read_process_memory()

    try:
        sha = subprocess.run(["git", "rev-parse", "--short", "HEAD"],
                             capture_output=True, text=True,
                             cwd=os.path.dirname(os.path.dirname(
                                 os.path.abspath(__file__))),
                             timeout=10).stdout.strip() or None
    except Exception:
        sha = None
    ok = bool(footprint and rejected and accepted and warn_accepted
              and donation and donation.get("ok"))
    result = {
        "mode": "serve_memory",
        "metric": "serve_memory_budget_mb%d" % args.max_batch,
        "footprint_bytes": footprint,
        "per_bucket_memory": per_bucket,
        "over_budget_rejected": rejected,
        "reject_status": reject_status,
        "warn_mode_accepted": warn_accepted,
        "within_budget_accepted": accepted,
        "donation": donation,
        "memory_phases": mem_phases,
        "executables_by_temp": tm_memory.executables_top(5),
        "ok": ok,
        "backend": "cpu" if os.environ.get("JAX_PLATFORMS") == "cpu"
        else "device",
        "sha": sha,
        "utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    }
    json.dump(result, sys.stdout, indent=1)
    sys.stdout.write("\n")
    return 0 if ok else 4


if __name__ == "__main__":
    sys.exit(main())
