"""`mxnet_tpu.compile` — the unified executable cache.

One registry every executable factory resolves through (the reference's
shared dependency-engine execution layer, PAPER.md layer 1, rebuilt for
XLA): per-op eager jit (`ops.invoke_jax`), autograd backward
(`autograd._bwd_jitted`), symbolic Executor forward/backward, gluon
CachedOp, the sharded trainers' fused steps, and — through the Executor —
the serving layer's per-bucket predictors.

Key = (kind, graph/op fingerprint) x input shapes x dtypes x static
attrs x sharding x donation (key.py); the persistent tier additionally
keys on jax version + XLA backend. Tiers, counters and the fill hook are
documented in registry.py; on-disk artifacts in persist.py; serving
warmup manifests in manifest.py. `python -m mxnet_tpu.compile` lists,
inspects and prunes the persistent tier. docs/compile_cache.md is the
operator-facing writeup.
"""
from __future__ import annotations

from .key import ExecutableKey
from .manifest import (list_manifests, model_manifest_id, prefetch,
                       read_manifest, write_manifest)
from .persist import cache_dir
from .registry import (Registry, begin_touch_log, clear_staged, compiled,
                       end_touch_log, get_or_build, instance_token,
                       invalidate_tag, keys_since, lookup, mark,
                       prefetch_paths, registry, reset, stats)

__all__ = [
    "ExecutableKey", "Registry", "registry", "get_or_build", "lookup",
    "invalidate_tag", "reset", "stats", "compiled", "mark", "keys_since",
    "prefetch_paths", "clear_staged", "instance_token", "cache_dir",
    "begin_touch_log", "end_touch_log",
    "model_manifest_id", "write_manifest", "read_manifest", "prefetch",
    "list_manifests",
]
