"""Process-worker DataLoader tests (reference:
python/mxnet/gluon/data/dataloader.py:98-120 shared-memory workers).

Correctness only: scaling with workers is not measured (no cell of
chipbench/ loads through worker processes)."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.gluon.data import ArrayDataset, DataLoader


class _NpDataset:
    """Host-pure dataset: numpy in, numpy out (worker-process eligible)."""

    def __init__(self, n=32, shape=(3, 8, 8)):
        self.n = n
        self.shape = shape

    def __len__(self):
        return self.n

    def __getitem__(self, i):
        rng = np.random.RandomState(i)
        return (rng.uniform(size=self.shape).astype(np.float32),
                np.float32(i % 7))


def _expected(i, shape=(3, 8, 8)):
    return np.random.RandomState(i).uniform(size=shape).astype(np.float32)


def test_mp_loader_matches_inline():
    ds = _NpDataset(24)
    ref = [(d.asnumpy(), l.asnumpy())
           for d, l in DataLoader(ds, batch_size=4, num_workers=0)]
    got = [(d.asnumpy(), l.asnumpy())
           for d, l in DataLoader(ds, batch_size=4, num_workers=2)]
    assert len(ref) == len(got) == 6
    for (rd, rl), (gd, gl) in zip(ref, got):
        np.testing.assert_array_equal(rd, gd)
        np.testing.assert_array_equal(rl, gl)


def test_mp_loader_order_and_values():
    dl = DataLoader(_NpDataset(16), batch_size=4, num_workers=2)
    seen = 0
    for d, l in dl:
        for row in range(d.shape[0]):
            np.testing.assert_allclose(d.asnumpy()[row], _expected(seen),
                                       rtol=1e-6)
            assert float(l.asnumpy()[row]) == seen % 7
            seen += 1
    assert seen == 16


def test_mp_loader_multiple_epochs_reuse_pool():
    dl = DataLoader(_NpDataset(12), batch_size=4, num_workers=2)
    for _ in range(3):
        assert sum(1 for _ in dl) == 3
    assert dl._pool is not None  # pool persisted across epochs


def test_mp_loader_shuffle():
    dl = DataLoader(_NpDataset(32), batch_size=8, num_workers=2, shuffle=True)
    labels = np.concatenate([l.asnumpy() for _, l in dl])
    assert labels.shape == (32,)
    # every sample exactly once
    ref = np.sort(np.arange(32) % 7)
    np.testing.assert_array_equal(np.sort(labels), ref)


def test_device_dataset_falls_back_to_threads(monkeypatch):
    """jax-backed items can't cross into forked workers; the loader must
    fall back to threaded prefetch with identical results."""
    # the probe worker deadlocks by design here; don't wait the full
    # default before concluding that
    monkeypatch.setenv("MXTPU_DATALOADER_PROBE_TIMEOUT", "5")
    X = np.arange(24 * 2, dtype=np.float32).reshape(24, 2)
    ds = ArrayDataset(mx.nd.array(X), mx.nd.array(np.arange(24.0)))
    dl = DataLoader(ds, batch_size=6, num_workers=2)
    got = [d.asnumpy() for d, _ in dl]
    assert dl._host_safe is False
    np.testing.assert_array_equal(np.concatenate(got), X)


class _FakeMNIST:
    """Module-level (hence picklable) stand-in with the built-in datasets'
    storage convention: numpy payloads, NDArray wrap outside host mode."""

    def __init__(self):
        self._data = np.zeros((10, 28, 28, 1), np.uint8)
        self._label = np.arange(10, dtype=np.int32)

    def __len__(self):
        return 10

    def __getitem__(self, idx):
        from mxnet_tpu.base import HOST_ARRAY_MODE
        from mxnet_tpu import ndarray as nd

        data = self._data[idx]
        if not HOST_ARRAY_MODE:
            data = nd.array(data, dtype="uint8")
        return data, self._label[idx]


def test_builtin_vision_dataset_is_host_pure():
    """MNIST-style datasets store numpy payloads and must be eligible for
    worker processes (HOST_ARRAY_MODE returns numpy)."""
    dl = DataLoader(_FakeMNIST(), batch_size=5, num_workers=2)
    batches = list(dl)
    assert dl._host_safe is True  # ran in real worker processes
    assert len(batches) == 2
    # and outside host mode the same dataset yields NDArray (API parity)
    item = _FakeMNIST()[0]
    assert isinstance(item[0], mx.nd.NDArray)


def test_mp_loader_empty_and_partial_batches():
    dl = DataLoader(_NpDataset(10), batch_size=4, num_workers=2,
                    last_batch="keep")
    sizes = [d.shape[0] for d, _ in dl]
    assert sizes == [4, 4, 2]


def test_mp_loader_abandoned_iteration_no_shm_leak():
    """break mid-epoch must not leak /dev/shm segments (workers unregister
    from their resource_tracker; the iterator's close() owns cleanup)."""
    import gc
    import glob

    before = set(glob.glob("/dev/shm/psm_*"))
    dl = DataLoader(_NpDataset(32), batch_size=4, num_workers=2)
    it = iter(dl)
    next(it)
    del it
    gc.collect()
    after = set(glob.glob("/dev/shm/psm_*"))
    assert after <= before, "leaked shm segments: %s" % (after - before)


@pytest.mark.parametrize("used", [False, True],
                         ids=["workers-still-starting", "workers-idle"])
def test_mp_loader_pool_ends_under_the_preemption_handler(used):
    """A process that armed the SIGTERM-with-grace handler (a trainer under
    the resilience layer) forks its data workers with that handler in
    place; it only records the request, so the pool's terminate() could
    never end them and the loader's teardown hung. A worker puts the
    default action back, and is born with the signal blocked so that a
    terminate() that comes while it is still starting ends it too."""
    import signal
    import threading

    from mxnet_tpu.parallel import resilience

    before = signal.getsignal(signal.SIGTERM)
    assert resilience.install_preemption_handler()
    dl = DataLoader(_NpDataset(12), batch_size=4, num_workers=2)
    pool = dl._get_pool()
    try:
        if used:
            assert sum(1 for _ in dl) == 3
        ender = threading.Thread(target=pool.terminate, daemon=True)
        ender.start()
        ender.join(60)                   # hung here before the fix
        assert not ender.is_alive(), "pool.terminate() did not return"
        assert all(p.exitcode is not None for p in pool._pool)
    finally:
        for p in pool._pool:             # a failure must not hang the exit
            if p.exitcode is None:
                p.kill()
        if before is not resilience._on_sigterm:
            signal.signal(signal.SIGTERM, before)
            resilience._PREEMPT["installed"] = False
