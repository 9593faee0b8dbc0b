"""Execution engine facade.

The reference's dependency engine (src/engine/threaded_engine.cc, SURVEY §2.1
N1) topologically orders ops by read/write variable dependencies and runs them
on per-device worker threads. On TPU the equivalent machinery is XLA/PJRT's
async dispatch: every jax op/executable launch is enqueued onto the device
stream and Python returns immediately; data dependencies are carried by the
arrays themselves, and transfers/computation overlap automatically. What
remains for us is the *control* surface the reference exposes:

- ``WaitForAll`` / per-array ``wait_to_read`` barriers,
- a sync "naive engine" debug mode (disable per-op jit, run op-by-op),
- bulking hints (`set_bulk_size`) — a no-op, XLA fuses within a jit scope.

Async exceptions: like threaded_engine.cc:418-503, device-side errors (e.g.
NaN-checking, OOM) surface at the next blocking read; jax raises them from
``block_until_ready``/``__array__`` which our NDArray sync points call.
"""
from __future__ import annotations

import contextlib
import threading

_local = threading.local()


def is_naive():
    """True when running in sync, per-op-uncompiled debug mode
    (reference env MXNET_ENGINE_TYPE=NaiveEngine, src/engine/engine.cc:33)."""
    import os

    if getattr(_local, "naive", None) is not None:
        return _local.naive
    return os.environ.get("MXNET_ENGINE_TYPE", "") == "NaiveEngine"


@contextlib.contextmanager
def naive_engine(enable=True):
    """Scoped sync/debug scheduler mode (SURVEY §5.2 item b)."""
    prev = getattr(_local, "naive", None)
    _local.naive = enable
    try:
        yield
    finally:
        _local.naive = prev


def wait_all():
    """Block until all pending device work is done
    (reference: Engine::WaitForAll include/mxnet/engine.h:234)."""
    import jax

    (jax.device_put(0) + 0).block_until_ready()
    jax.effects_barrier()


def set_bulk_size(size):
    """Reference: python/mxnet/engine.py:26 — engine op bulking. XLA fuses
    everything inside a jit scope, so this is an accepted no-op; returns the
    previous value for API parity."""
    prev = getattr(_local, "bulk", 15)
    _local.bulk = size
    return prev


@contextlib.contextmanager
def bulk(size):
    prev = set_bulk_size(size)
    try:
        yield
    finally:
        set_bulk_size(prev)
