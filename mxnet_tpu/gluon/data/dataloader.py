"""DataLoader.

Reference: python/mxnet/gluon/data/dataloader.py:98-120 — multi-worker loader
feeding shared-memory NDArrays. TPU-native equivalent:

- `num_workers>0` runs decode/augment in worker *processes* (the reference's
  design: Python-side JPEG decode + augmentation is GIL-bound, so threads
  cannot scale it), returning batches through POSIX shared memory
  (multiprocessing.shared_memory — the reference's cpu_shared_storage_manager
  role). The parent wraps the segment, uploads to device (device_put copies
  anyway), and unlinks.
- Workers default to the *fork* context (like the reference; spawn and
  forkserver both re-import the user's __main__, breaking unguarded
  scripts). A forked child can never run jax (the inherited PJRT client's
  threadpool does not survive fork), so workers run in HOST_ARRAY_MODE:
  decode/dataset stages return plain numpy and the whole per-sample path
  stays host-pure. At pool creation the dataset is probed once in host mode;
  if its __getitem__ still yields device arrays (e.g. a jax-backed
  transform), the loader logs a warning and falls back to the threaded
  prefetcher instead of deadlocking. `ctx="spawn"` is available for
  datasets that need a fresh interpreter (requires the standard
  `if __name__ == "__main__"` guard).
- `thread_pool=True` keeps the round-1 threaded prefetcher (useful when the
  dataset is already numpy and pickling would dominate).
"""
from __future__ import annotations

import os
import pickle

import numpy as _np

from ... import ndarray as nd
from ...base import MXNetError
from .sampler import BatchSampler, RandomSampler, SequentialSampler

__all__ = ["DataLoader", "default_batchify_fn"]


def default_batchify_fn(data):
    """Stack samples into a batch (reference: dataloader.py default_batchify_fn)."""
    if isinstance(data[0], nd.NDArray):
        return nd.stack(*data, axis=0)
    if isinstance(data[0], tuple):
        data = zip(*data)
        return [default_batchify_fn(i) for i in data]
    data = _np.asarray(data)
    return nd.array(data, dtype=data.dtype if data.dtype != _np.float64 else "float32")


# ---------------------------------------------------------------------------
# worker-process machinery
# ---------------------------------------------------------------------------

def _np_batchify(data):
    """Worker-side batchify: same stacking as default_batchify_fn but
    producing plain numpy (workers never hand jax buffers across the
    process boundary)."""
    first = data[0]
    if isinstance(first, nd.NDArray):
        return _np.stack([d.asnumpy() for d in data])
    if isinstance(first, tuple):
        return tuple(_np_batchify(list(f)) for f in zip(*data))
    if isinstance(first, list):
        return tuple(_np_batchify(list(f)) for f in zip(*data))
    arr = _np.asarray(data)
    if arr.dtype == _np.float64:
        arr = arr.astype(_np.float32)
    return arr


def _to_shm(obj, segments):
    """Replace numpy arrays in a (possibly nested tuple) batch with
    shared-memory descriptors; created segments collect into `segments`."""
    from multiprocessing import shared_memory

    if isinstance(obj, tuple):
        return tuple(_to_shm(o, segments) for o in obj)
    assert isinstance(obj, _np.ndarray)
    if obj.nbytes == 0:
        return ("__nd0__", obj.shape, obj.dtype.str, None)
    shm = shared_memory.SharedMemory(create=True, size=obj.nbytes)
    view = _np.ndarray(obj.shape, dtype=obj.dtype, buffer=shm.buf)
    view[:] = obj
    # ownership transfers to the parent (which unlinks after upload); drop
    # this process's resource_tracker registration or its exit handler
    # double-unlinks and spams warnings
    try:
        from multiprocessing import resource_tracker

        resource_tracker.unregister(shm._name, "shared_memory")
    except Exception:
        pass
    segments.append(shm)
    return ("__nd__", obj.shape, obj.dtype.str, shm.name)


def _from_shm(desc):
    """Parent-side: materialize NDArrays from shm descriptors and release
    the segments."""
    from multiprocessing import shared_memory

    if isinstance(desc, tuple) and len(desc) == 4 and \
            desc[0] in ("__nd__", "__nd0__"):
        tag, shape, dtype, name = desc
        if tag == "__nd0__":
            return nd.array(_np.empty(shape, _np.dtype(dtype)))
        shm = shared_memory.SharedMemory(name=name)
        try:
            view = _np.ndarray(shape, dtype=_np.dtype(dtype), buffer=shm.buf)
            # owned host copy BEFORE unlinking: jax's CPU backend may alias
            # the numpy buffer zero-copy, and unmapping the segment under a
            # live alias segfaults later
            out = nd.array(_np.array(view))
        finally:
            shm.close()
            shm.unlink()
        return out
    return [_from_shm(d) for d in desc]


def _unlink_desc(desc):
    """Release shm segments of an unconsumed batch."""
    from multiprocessing import shared_memory

    if isinstance(desc, tuple) and len(desc) == 4 and \
            desc[0] in ("__nd__", "__nd0__"):
        if desc[3] is not None:
            try:
                shm = shared_memory.SharedMemory(name=desc[3])
                shm.close()
                shm.unlink()
            except Exception:
                pass
        return
    for d in desc:
        _unlink_desc(d)


_WORKER_DATASET = None
_WORKER_BATCHIFY = None


def _worker_initializer(dataset_bytes, batchify_bytes):
    """Runs once in each worker process."""
    import os
    import signal

    from ... import base as _base

    # a forked worker inherits the trainer's SIGTERM-with-grace handler
    # (parallel/resilience.py), which only records the request for the next
    # step boundary; a data worker has no step, so the pool's terminate()
    # would never end it and its join would hang. SIGTERM kills a worker,
    # also one sent before this line: a worker is born with the signal
    # blocked (`_make_pool`), so it waits here and is delivered now.
    signal.signal(signal.SIGTERM, signal.SIG_DFL)
    signal.pthread_sigmask(signal.SIG_UNBLOCK, {signal.SIGTERM})
    os.environ["JAX_PLATFORMS"] = "cpu"  # data workers never own a TPU
    _base.HOST_ARRAY_MODE = True        # decode/dataset stages stay numpy
    global _WORKER_DATASET, _WORKER_BATCHIFY
    _WORKER_DATASET = pickle.loads(dataset_bytes)
    _WORKER_BATCHIFY = pickle.loads(batchify_bytes) if batchify_bytes \
        else None


def _has_nd(x):
    if isinstance(x, nd.NDArray):
        return True
    if isinstance(x, (tuple, list)):
        return any(_has_nd(i) for i in x)
    return False


def _worker_probe():
    """Runs INSIDE a worker: fetch one sample and report host-purity. A
    dataset whose __getitem__ needs jax either returns NDArray leaves
    (reported False) or hangs on the forked runtime (caught by the parent's
    result timeout)."""
    try:
        return not _has_nd(_WORKER_DATASET[0])
    except Exception:
        return False


def _host_safe_probe(dataset, pool_factory, timeout=None):
    """True iff the dataset is picklable and one sample round-trips through
    a real worker process without producing device arrays, hanging, or
    raising. The probe runs in the worker itself (never toggling parent
    state — other threads may be decoding concurrently); a worker that
    deadlocks on the forked jax runtime is caught by the timeout
    (MXTPU_DATALOADER_PROBE_TIMEOUT, default 20s — the legit probe path
    touches no jax and returns in well under a second)."""
    if timeout is None:
        from ... import env as _env

        timeout = _env.get("MXTPU_DATALOADER_PROBE_TIMEOUT")
    try:
        pickle.dumps(dataset)
    except Exception:
        return False, None
    pool = pool_factory()
    try:
        ok = bool(pool.apply_async(_worker_probe).get(timeout=timeout))
    except Exception:
        ok = False
    if not ok:
        try:
            pool.terminate()
        except Exception:
            pass
        pool = None
    return ok, pool


def _worker_fn(indices):
    samples = [_WORKER_DATASET[i] for i in indices]
    if _WORKER_BATCHIFY is not None:
        batch = _WORKER_BATCHIFY(samples)
        # custom fn may return NDArray(s); flatten to numpy for shm
        def to_np(b):
            if isinstance(b, nd.NDArray):
                return b.asnumpy()
            if isinstance(b, (list, tuple)):
                return tuple(to_np(x) for x in b)
            return _np.asarray(b)
        batch = to_np(batch)
    else:
        batch = _np_batchify(samples)
    segments = []
    desc = _to_shm(batch if isinstance(batch, tuple) else (batch,), segments)
    single = not isinstance(batch, tuple)
    for s in segments:
        s.close()  # parent unlinks
    return single, desc


class _MultiWorkerIter:
    """Ordered async iterator over a process pool (reference:
    dataloader.py _MultiWorkerIter — pushes 2*num_workers tasks ahead,
    yields strictly in batch order)."""

    def __init__(self, pool, batch_sampler, prefetch):
        self._pool = pool
        self._batches = iter(batch_sampler)
        self._pending = {}
        self._sent = 0
        self._recv = 0
        self._exhausted = False
        for _ in range(max(1, prefetch)):
            self._push_next()

    def _push_next(self):
        if self._exhausted:
            return
        try:
            batch = next(self._batches)
        except StopIteration:
            self._exhausted = True
            return
        self._pending[self._sent] = self._pool.apply_async(
            _worker_fn, (list(batch),))
        self._sent += 1

    def __iter__(self):
        return self

    def __next__(self):
        if self._recv == self._sent and self._exhausted:
            raise StopIteration
        result = self._pending.pop(self._recv)
        self._recv += 1
        self._push_next()
        # bounded wait: a worker killed mid-task (OOM, native segfault)
        # leaves its AsyncResult forever pending — surface an error instead
        # of hanging the training loop
        from ... import env as _env

        timeout = _env.get("MXTPU_DATALOADER_TIMEOUT")
        try:
            single, desc = result.get(timeout=timeout)
        except Exception as e:
            self.close()
            raise MXNetError(
                "DataLoader worker batch did not arrive within %.0fs "
                "(worker died or is stuck; raise MXTPU_DATALOADER_TIMEOUT "
                "for very slow pipelines): %r" % (timeout, e)) from e
        out = _from_shm(desc)
        return out[0] if single else out

    def close(self):
        """Unlink segments of batches that were produced but never
        consumed — an abandoned iterator (break mid-epoch) must not leak
        /dev/shm (workers deliberately unregister from their
        resource_tracker because ownership passes to the parent)."""
        self._exhausted = True
        for idx in sorted(self._pending):
            result = self._pending.pop(idx)
            try:
                _, desc = result.get(timeout=30)
                _unlink_desc(desc)
            except Exception:
                pass

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


class DataLoader:
    def __init__(self, dataset, batch_size=None, shuffle=False, sampler=None,
                 last_batch=None, batch_sampler=None, batchify_fn=None,
                 num_workers=0, pin_memory=False, prefetch=None,
                 thread_pool=False, ctx=None):
        from ... import env as _env

        self._mp_ctx = ctx or _env.get("MXTPU_DATALOADER_CTX")
        self._dataset = dataset
        if batch_sampler is None:
            if batch_size is None:
                raise MXNetError("batch_size required when batch_sampler is None")
            if sampler is None:
                sampler = RandomSampler(len(dataset)) if shuffle \
                    else SequentialSampler(len(dataset))
            elif shuffle:
                raise MXNetError("shuffle must be False with custom sampler")
            batch_sampler = BatchSampler(sampler, batch_size, last_batch or "keep")
        elif (batch_size is not None or shuffle or sampler is not None
              or last_batch is not None):
            raise MXNetError("batch_size/shuffle/sampler/last_batch incompatible "
                             "with batch_sampler")
        self._batch_sampler = batch_sampler
        self._batchify_fn = batchify_fn or default_batchify_fn
        self._custom_batchify = batchify_fn
        self._num_workers = max(0, num_workers)
        self._thread_pool = thread_pool
        self._prefetch = max(0, prefetch if prefetch is not None
                             else 2 * self._num_workers)
        self._pool = None
        self._host_safe = None

    def __len__(self):
        return len(self._batch_sampler)

    def _load(self, batch_indices):
        return self._batchify_fn([self._dataset[i] for i in batch_indices])

    def _make_pool(self):
        import multiprocessing as mp
        import signal

        ctx = mp.get_context(self._mp_ctx)
        # SIGTERM stays blocked in every worker until its initializer has
        # put the default action back (the pool's own threads, which fork
        # replacements, are made here and keep the mask)
        mask = signal.pthread_sigmask(signal.SIG_BLOCK, {signal.SIGTERM})
        try:
            return ctx.Pool(
                self._num_workers, initializer=_worker_initializer,
                initargs=(pickle.dumps(self._dataset),
                          pickle.dumps(self._custom_batchify)
                          if self._custom_batchify else b""))
        finally:
            signal.pthread_sigmask(signal.SIG_SETMASK, mask)

    def _get_pool(self):
        if self._pool is None:
            import atexit

            self._pool = self._make_pool()
            # terminate at exit while the interpreter is intact — letting
            # the GC find the pool during teardown trips Pool.__del__ noise
            atexit.register(self._pool.terminate)
        return self._pool

    def __del__(self):
        try:
            if self._pool is not None:
                self._pool.terminate()
        except Exception:
            pass  # interpreter teardown: pool internals may already be gone

    def __iter__(self):
        """Instrumented front: yields from the real iterator while feeding
        the telemetry wait-vs-compute split — seconds this consumer spent
        BLOCKED on batch production vs. seconds it held the batch (its own
        step compute) between `next` calls. A starved accelerator shows up
        as wait >> compute."""
        import time as _time

        from ... import telemetry

        tm_wait = telemetry.counter("mxtpu_data_wait_seconds_total",
                                    {"src": "dataloader"})
        tm_compute = telemetry.counter("mxtpu_data_compute_seconds_total",
                                       {"src": "dataloader"})
        tm_batches = telemetry.counter("mxtpu_data_batches_total",
                                       {"src": "dataloader"})
        inner = self._iter_raw()
        while True:
            t0 = _time.perf_counter()
            try:
                batch = next(inner)
            except StopIteration:
                return
            t1 = _time.perf_counter()
            tm_wait.inc(t1 - t0)
            tm_batches.inc()
            yield batch
            tm_compute.inc(_time.perf_counter() - t1)

    def _iter_raw(self):
        if self._num_workers == 0:
            for batch in self._batch_sampler:
                yield self._load(batch)
            return
        if self._thread_pool:
            yield from self._iter_threaded()
            return
        if self._host_safe is None:
            self._host_safe, pool = _host_safe_probe(
                self._dataset, self._make_pool)
            if pool is not None:
                self._pool = pool
                import atexit

                atexit.register(pool.terminate)
            if not self._host_safe:
                import logging

                logging.warning(
                    "DataLoader(num_workers=%d): dataset __getitem__ is not "
                    "host-pure (returns device arrays, is unpicklable, or "
                    "its transform needs jax) — falling back to threaded "
                    "prefetch. Return numpy from __getitem__ to enable "
                    "worker processes.", self._num_workers)
        if not self._host_safe:
            yield from self._iter_threaded()
            return
        yield from _MultiWorkerIter(self._get_pool(), self._batch_sampler,
                                    self._prefetch)

    def _iter_threaded(self):
        # threaded prefetch pipeline on the shared mxnet_tpu.data core
        # (thread_pool=True, and the fallback when worker processes are
        # unviable); bounded put + capture-as-local generation semantics
        # live in data/core.PrefetchBuffer
        from ...data.core import PrefetchBuffer

        batches = iter(self._batch_sampler)

        def produce():
            return self._load(next(batches))

        buf = PrefetchBuffer(produce, depth=self._prefetch or 2,
                             name="mxtpu-dataloader-prefetch",
                             owner="DataLoader", src="dataloader")
        try:
            while True:
                try:
                    yield buf.get()
                except StopIteration:
                    return
        finally:
            # abandoned iterator (break mid-epoch) or natural end: stop +
            # join the producer either way
            buf.close()
