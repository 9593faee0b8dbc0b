"""mxnet_tpu.data async input pipeline tests (ISSUE 20 acceptance):

  * core: PrefetchBuffer ordering + loud error propagation + bounded-queue
    backpressure + clean join on close; DecodePool source-order delivery
    under parallel decode, error surfaced at its source position, feeder
    read-ahead bounded by depth+workers;
  * sharded streaming: exactly-once rank coverage at world<=files AND
    world>files, deterministic (seed, epoch) shuffle, checkpoint cursor
    resume-equivalence with the decode pool's read-ahead excluded;
  * device prefetch: batches land sharded to batch_spec over the mesh,
    cursor tracks DELIVERED batches only;
  * faults: slow_batch@step=,ms= producer stall fires in the producer
    thread and a correctly-sized prefetcher absorbs it;
  * chaos e2e (subprocess): prefetched fit over StreamDataIter with a
    slow_batch stall is preempted mid-epoch -> rc 83 + an emergency
    checkpoint carrying the data cursor; the resumed run lands EXACTLY on
    the uninterrupted run's weights (mid-epoch batch-cursor equivalence).
"""
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import recordio
from mxnet_tpu.base import MXNetError
from mxnet_tpu.data import (DecodePool, DevicePrefetcher, PrefetchBuffer,
                            ShardedRecordStream, StreamDataIter)
from mxnet_tpu.parallel import resilience

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _no_data_threads():
    return [t.name for t in threading.enumerate()
            if t.is_alive() and t.name.startswith(("mxtpu-data",
                                                   "mxtpu-io",
                                                   "mxtpu-image"))] == []


# --------------------------------------------------------------------------
# core: PrefetchBuffer
# --------------------------------------------------------------------------

def test_prefetch_buffer_order_error_and_join():
    items = iter(range(10))

    def produce():
        v = next(items)
        if v == 7:
            raise ValueError("decode exploded")
        return v

    buf = PrefetchBuffer(produce, depth=2, name="mxtpu-data-t1")
    got = []
    with pytest.raises(ValueError, match="decode exploded"):
        while True:
            got.append(buf.get())
    assert got == list(range(7))  # order preserved up to the error
    with pytest.raises(StopIteration):
        buf.get()  # a dead buffer stays dead, it does not hang
    buf.close()
    assert _no_data_threads()


def test_prefetch_buffer_backpressure():
    produced = []

    def produce():
        produced.append(len(produced))
        return produced[-1]

    buf = PrefetchBuffer(produce, depth=2, name="mxtpu-data-t2")
    assert buf.get() == 0  # starts the worker
    deadline = time.monotonic() + 2.0
    # producer fills the bounded queue and blocks: depth staged + one in
    # the blocked put + one consumed
    while len(produced) < 4 and time.monotonic() < deadline:
        time.sleep(0.01)
    time.sleep(0.15)
    assert len(produced) <= 2 + 2, produced
    buf.close()
    assert _no_data_threads()


# --------------------------------------------------------------------------
# core: DecodePool
# --------------------------------------------------------------------------

def test_decode_pool_source_order_under_parallel_decode():
    src = iter(range(24))

    def decode(v):
        time.sleep(0.001 * (v % 5))  # scramble completion order
        return v * v

    pool = DecodePool(lambda: next(src), decode, workers=4, depth=4)
    got = []
    try:
        while True:
            got.append(pool.get())
    except StopIteration:
        pass
    assert got == [v * v for v in range(24)]
    pool.close()
    assert _no_data_threads()


def test_decode_pool_error_at_source_position_and_backpressure():
    pulled = []

    def source():
        if len(pulled) >= 40:
            raise StopIteration
        pulled.append(len(pulled))
        return pulled[-1]

    def decode(v):
        if v == 5:
            raise RuntimeError("bad record 5")
        return v

    pool = DecodePool(source, decode, workers=2, depth=2)
    got = []
    for _ in range(5):
        got.append(pool.get())
    assert got == [0, 1, 2, 3, 4]
    # feeder read-ahead is slot-bounded: depth + workers + delivered
    assert len(pulled) <= 2 + 2 + 5 + 1, pulled
    with pytest.raises(RuntimeError, match="bad record 5"):
        pool.get()
    pool.close()
    assert _no_data_threads()


# --------------------------------------------------------------------------
# sharded RecordIO streaming
# --------------------------------------------------------------------------

def _make_recs(dirname, counts, feat=6):
    """RecordIO files whose records carry (float32[feat] data, label) made
    deterministically from the global record id."""
    rng = np.random.RandomState(0)
    paths = []
    gid = 0
    os.makedirs(dirname, exist_ok=True)
    for f, n in enumerate(counts):
        idx = os.path.join(dirname, "part%d.idx" % f)
        rec = os.path.join(dirname, "part%d.rec" % f)
        w = recordio.MXIndexedRecordIO(idx, rec, "w")
        for k in range(n):
            data = rng.uniform(-1, 1, (feat,)).astype(np.float32)
            label = float(data.sum() > 0)
            w.write_idx(k, recordio.pack(
                recordio.IRHeader(0, label, gid, 0), data.tobytes()))
            gid += 1
        w.close()
        paths.append(rec)
    return paths


def _decode_sample(raw):
    header, payload = recordio.unpack(raw)
    return np.frombuffer(payload, dtype=np.float32), np.float32(header.label)


def _drain_ids(stream):
    ids = []
    try:
        while True:
            ids.append(recordio.unpack(stream.next_record())[0].id)
    except StopIteration:
        pass
    return ids


@pytest.mark.parametrize("world", [2, 5])
def test_stream_exactly_once_rank_coverage(tmp_path, world):
    """Every record is seen by exactly one rank per epoch — whole-file
    ownership at world<=files, intra-file index striding at world>files."""
    paths = _make_recs(str(tmp_path), [5, 4, 3])
    seen = []
    for r in range(world):
        s = ShardedRecordStream(paths, rank=r, world=world)
        seen.extend(_drain_ids(s))
        s.close()
    assert sorted(seen) == list(range(12))


def test_stream_shuffle_deterministic_per_epoch(tmp_path):
    paths = _make_recs(str(tmp_path), [6, 6])
    a = ShardedRecordStream(paths, shuffle=True, seed=3)
    b = ShardedRecordStream(paths, shuffle=True, seed=3)
    e0a, e0b = _drain_ids(a), _drain_ids(b)
    assert e0a == e0b  # pure function of (seed, epoch)
    assert sorted(e0a) == list(range(12))
    a.advance_epoch()
    b.advance_epoch()
    e1a, e1b = _drain_ids(a), _drain_ids(b)
    assert e1a == e1b and e1a != e0a  # reshuffled, still deterministic
    a.close()
    b.close()


def test_stream_cursor_resume_and_topology_guard(tmp_path):
    paths = _make_recs(str(tmp_path), [7, 5])
    s = ShardedRecordStream(paths, shuffle=True, seed=9)
    s.advance_epoch()  # mid-trajectory: epoch 1
    head = [recordio.unpack(s.next_record())[0].id for _ in range(5)]
    st = s.state()
    tail = _drain_ids(s)
    s.close()
    r = ShardedRecordStream(paths, shuffle=True, seed=9)
    r.set_state(st)
    assert _drain_ids(r) == tail  # exact mid-epoch re-entry
    assert sorted(head + tail) == list(range(12))
    r.close()
    other = ShardedRecordStream(paths, shuffle=True, seed=1)
    with pytest.raises(MXNetError, match="exactly-once"):
        other.set_state(st)  # different seed = different record order
    other.close()


def test_stream_iter_cursor_excludes_decode_readahead(tmp_path):
    """state() counts DELIVERED samples: the decode pool's read-ahead must
    not advance the checkpoint cursor past what the consumer saw."""
    paths = _make_recs(str(tmp_path), [16, 16])

    def it_over(stream):
        return StreamDataIter(stream, batch_size=8,
                              decode_fn=_decode_sample, data_shape=(6,),
                              workers=2)

    it = it_over(ShardedRecordStream(paths))
    first = [it.next() for _ in range(2)]  # pool reads ahead beyond 16
    st = it.state()
    assert st["pos"] == 16
    rest = []
    try:
        while True:
            rest.append(it.next().data[0].asnumpy())
    except StopIteration:
        pass
    it.close()

    fresh = it_over(ShardedRecordStream(paths))
    fresh.set_state(st)
    fresh.reset()  # fit's epoch-top reset: one-shot no-op after set_state
    rest2 = []
    try:
        while True:
            rest2.append(fresh.next().data[0].asnumpy())
    except StopIteration:
        pass
    fresh.close()
    assert len(first) == 2 and len(rest) == len(rest2) == 2
    for x, y in zip(rest, rest2):
        np.testing.assert_array_equal(x, y)
    assert _no_data_threads()


# --------------------------------------------------------------------------
# device prefetch
# --------------------------------------------------------------------------

def test_device_prefetcher_shards_batches_over_mesh():
    import jax

    from mxnet_tpu.parallel import make_mesh
    from mxnet_tpu.parallel.sharding import batch_spec, named_sharding

    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices (conftest forces 8)")
    mesh = make_mesh()
    X = np.arange(16 * 4, dtype=np.float32).reshape(16, 4)
    Y = np.arange(16, dtype=np.float32)
    it = mx.io.NDArrayIter(X, Y, batch_size=8, label_name="softmax_label")
    pf = DevicePrefetcher(it, depth=2, mesh=mesh)
    batches = list(pf)
    pf.close()
    assert len(batches) == 2
    want = named_sharding(mesh, batch_spec(mesh, 2))
    for b in batches:
        data = b.data[0]._data
        assert data.sharding.is_equivalent_to(want, data.ndim)
    # values survive placement
    np.testing.assert_array_equal(batches[0].data[0].asnumpy(), X[:8])
    assert _no_data_threads()


def test_device_prefetcher_cursor_tracks_delivered_only(tmp_path):
    paths = _make_recs(str(tmp_path), [24])
    it = StreamDataIter(ShardedRecordStream(paths), batch_size=8,
                        decode_fn=_decode_sample, data_shape=(6,))
    pf = DevicePrefetcher(it, depth=2)
    next(pf)
    next(pf)  # prefetcher has read AHEAD of these two delivered batches
    st = pf.state()
    assert st["pos"] == 16  # delivered, not read-ahead
    pf.close()
    assert _no_data_threads()


# --------------------------------------------------------------------------
# fault injection: the producer-side slow_batch stall
# --------------------------------------------------------------------------

def test_slow_batch_spec_parses_and_fires(monkeypatch):
    spec = resilience.fault_spec("slow_batch@step=2,ms=40")
    assert spec[0]["action"] == "slow_batch" and spec[0]["ms"] == 40

    monkeypatch.setenv("MXTPU_FAULT_INJECT", "slow_batch@step=2,ms=120")
    monkeypatch.setattr(resilience, "_fault_cache", resilience._UNPARSED)
    t0 = time.perf_counter()
    resilience.maybe_inject_data_stall(1)
    assert time.perf_counter() - t0 < 0.1  # wrong batch: no-op
    t0 = time.perf_counter()
    resilience.maybe_inject_data_stall(2)
    assert time.perf_counter() - t0 >= 0.12


def test_slow_batch_absorbed_by_prefetch(monkeypatch):
    """The stall fires in the PRODUCER thread; a consumer with staged
    batches keeps draining without blocking for the full stall."""
    monkeypatch.setenv("MXTPU_FAULT_INJECT", "slow_batch@step=3,ms=300")
    monkeypatch.setattr(resilience, "_fault_cache", resilience._UNPARSED)
    items = iter(range(6))
    buf = PrefetchBuffer(lambda: next(items), depth=3,
                         name="mxtpu-data-t3")
    assert buf.get() == 0
    time.sleep(0.1)  # let batches 1-2 stage; producer stalls on batch 3
    t0 = time.perf_counter()
    assert buf.get() == 1
    assert buf.get() == 2
    staged_wait = time.perf_counter() - t0
    assert staged_wait < 0.25, staged_wait  # stall absorbed, not serialized
    assert [buf.get() for _ in range(3)] == [3, 4, 5]
    buf.close()
    assert _no_data_threads()


# --------------------------------------------------------------------------
# chaos e2e: prefetched fit + slow_batch + mid-epoch preempt -> exact resume
# --------------------------------------------------------------------------

def _run_stream_fit(ckpt_dir, rec_dir, resume=None):
    """3-epoch MLP fit over a StreamDataIter (2 decode workers); returns
    the final absolute weight sum. Always driven in a subprocess (via
    _STREAM_FIT_BODY): a compiled fit must never run inside the pytest
    process, where a later fork()-based test would inherit its runtime
    state mid-lock and deadlock."""
    import mxnet_tpu.symbol as S

    counts = [32, 32, 32]
    paths = [os.path.join(rec_dir, "part%d.rec" % f)
             for f in range(len(counts))]
    if not os.path.exists(paths[0]):
        _make_recs(rec_dir, counts)

    x = S.Variable("data")
    h = S.FullyConnected(x, num_hidden=8, name="fc1")
    h = S.Activation(h, act_type="relu")
    h = S.FullyConnected(h, num_hidden=2, name="fc2")
    sym = S.SoftmaxOutput(h, name="softmax")

    mx.random.seed(42)
    np.random.seed(42)
    train = StreamDataIter(ShardedRecordStream(paths, shuffle=True, seed=5),
                           batch_size=8, decode_fn=_decode_sample,
                           data_shape=(6,), workers=2)
    mod = mx.mod.Module(sym, context=mx.cpu())
    mod.fit(train, num_epoch=3, optimizer="sgd",
            optimizer_params={"learning_rate": 0.1},
            checkpoint_dir=str(ckpt_dir), resume=resume)
    train.close()
    w = mod.get_params()[0]
    return sum(float(np.abs(v.asnumpy()).sum()) for v in w.values())


_STREAM_FIT_BODY = r"""
import sys
sys.path.insert(0, %(root)r)
from test_data_pipeline import _run_stream_fit
resume = sys.argv[3] if len(sys.argv) > 3 else None
print("FIT_DONE wsum=%%.17g"
      %% _run_stream_fit(sys.argv[1], sys.argv[2], resume=resume),
      flush=True)
"""


def _stream_fit_subprocess(ckpt_dir, rec_dir, resume=None, **extra_env):
    """Run _run_stream_fit in a worker subprocess; returns (rc, stdout+err,
    wsum-or-None). wsum stays a %.17g string so equality is bit-exact."""
    from test_resilience import _worker_env

    argv = [sys.executable, "-c", _STREAM_FIT_BODY % {"root": _ROOT},
            str(ckpt_dir), str(rec_dir)]
    if resume is not None:
        argv.append(resume)
    proc = subprocess.run(
        argv,
        env=_worker_env(
            PYTHONPATH=_ROOT + os.pathsep + os.path.join(_ROOT, "tests"),
            **extra_env),
        capture_output=True, text=True, timeout=300)
    out = proc.stdout + proc.stderr
    wsum = None
    for ln in proc.stdout.splitlines():
        if ln.startswith("FIT_DONE wsum="):
            wsum = ln.split("=", 1)[1].strip()
    return proc.returncode, out, wsum


def test_chaos_preempt_resume_exact_data_cursor(tmp_path):
    """fit with MXTPU_DATA_PREFETCH=1 over a shuffled StreamDataIter,
    slow_batch stalling the producer, preempted at update 5 (mid-epoch-0):
    rc 83, the emergency checkpoint's meta carries the batch cursor, and
    the resumed run re-enters the SAME epoch order at the exact record
    boundary — final weights equal the uninterrupted run's exactly."""
    ckpt, recs = tmp_path / "ck", str(tmp_path / "recs")
    rc, out, _ = _stream_fit_subprocess(
        ckpt, recs,
        MXTPU_FAULT_INJECT="slow_batch@step=3,ms=60;preempt@step=5,grace=30",
        MXTPU_DATA_PREFETCH="1")
    assert rc == 83, out
    assert "FIT_DONE" not in out
    header = json.load(open(ckpt / "ckpt-00000000" / "meta.json"))
    assert header["meta"]["preempt"] is True
    assert header["meta"]["batches_done"] == 5
    cursor = header["meta"]["data_state"]
    assert cursor["epoch"] == 0 and cursor["pos"] == 5 * 8

    rc, out, ref = _stream_fit_subprocess(tmp_path / "ref", recs)
    assert rc == 0 and ref is not None, out
    rc, out, got = _stream_fit_subprocess(ckpt, recs, resume="auto")
    assert rc == 0 and got is not None, out
    assert got == ref, (got, ref)
    assert _no_data_threads()
