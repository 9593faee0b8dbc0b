"""mxnet_tpu.serving — dynamic-batching inference serving.

The serving layer that turns the single-request predict API
(`mxnet_tpu.predict`, the c_predict_api rebuild) into sustained
high-occupancy inference (docs/serving.md):

  * `DynamicBatcher` — coalesces concurrent requests into padded,
    power-of-two-bucketed batches so every bucket hits one cached XLA
    executable (batcher.py);
  * `ModelRepository` / `ServedModel` — versioned multi-model registry
    over export prefixes and compiled ``.mxc`` artifacts, bucket warmup
    at load, hot load/unload with in-flight draining
    (model_repository.py);
  * `ServingServer` — stdlib `ThreadingHTTPServer` frontend with
    deterministic admission control: 429 on queue overflow, 504 on
    deadline expiry, bounded graceful SIGTERM drain (server.py);
  * `ReplicaPool` + `supervisor` — the resilience layer: N supervised
    replica worker processes per model with heartbeat health checks,
    ejection + respawn (restart generations, exponential backoff,
    process-group teardown), exactly-once batch failover, deterministic
    load shedding (503 + Retry-After scaled to healthy replicas) and
    per-request deadline propagation (replica_pool.py / supervisor.py);
  * `generate` — continuous-batching autoregressive decode with a paged
    KV cache: `GenerateScheduler` (token-level join/leave),
    `KVPageAllocator`, the `TransformerLMEngine` incremental LM runner
    and `ServedLM` (``POST /v1/models/<name>:generate``) — Orca-style
    iteration scheduling + PagedAttention, TPU-native (generate.py);
  * `Autoscaler` — the elastic loop over all of the above: SLO-verdict
    driven in-place replica scale-up (admitted against the memory
    budget, warm via manifest prefetch), idle scale-down with drain,
    and budget-pressure bin-packing in the repository — shrink cold
    pools, evict idle models — instead of flat 507s (autoscaler.py,
    docs/serving.md §Autoscaling).

Launch with ``python tools/serve.py`` (``--replicas N`` for a pool,
``--autoscale`` for the elastic loop); the generate path's rates are
the serving cells of ``chipbench/`` (chipbench/README.md). All knobs are typed
``MXTPU_SERVE_*`` / ``MXTPU_AUTOSCALE_*`` variables in `mxnet_tpu.env`
(docs/env_vars.md).
"""
from __future__ import annotations

from .autoscaler import Autoscaler  # noqa: F401
from .batcher import (  # noqa: F401
    DeadlineExceededError, DrainingError, DynamicBatcher,
    MemoryBudgetError, ModelUnavailableError, OverloadedError,
    QueueFullError, ServeRequest,
    ServingError, bucket_for, pad_batch, power_of_two_buckets,
)
from .generate import (  # noqa: F401
    GenerateScheduler, GenRequest, KVPageAllocator, ServedLM,
    TransformerLMEngine, load_lm, save_lm,
)
from .model_repository import (  # noqa: F401
    ModelRepository, ServedModel, build_runner,
)
from .replica_pool import ReplicaPool  # noqa: F401
from .server import ServingServer  # noqa: F401

__all__ = [
    "Autoscaler",
    "DynamicBatcher", "ServeRequest", "ModelRepository", "ServedModel",
    "ServingServer", "ReplicaPool", "ServingError", "QueueFullError",
    "DeadlineExceededError", "ModelUnavailableError", "DrainingError",
    "OverloadedError", "MemoryBudgetError", "power_of_two_buckets",
    "bucket_for", "pad_batch",
    "build_runner",
    "GenerateScheduler", "GenRequest", "KVPageAllocator", "ServedLM",
    "TransformerLMEngine", "save_lm", "load_lm",
]
