"""Shared harness plumbing for benchmark/python scripts: the one timeit
used by every script."""
from __future__ import annotations

import time


def timeit(fn, iters, warmup):
    """Mean seconds per call; warms up, then times `iters` free-running
    calls with one sync at the end (async dispatch pipelines the loop)."""
    import jax

    def _sync(v):
        jax.block_until_ready(getattr(v, "_data", v))

    for _ in range(warmup):
        fn()
    _sync(fn())
    t0 = time.perf_counter()
    out = None
    for _ in range(iters):
        out = fn()
    _sync(out)
    return (time.perf_counter() - t0) / iters
