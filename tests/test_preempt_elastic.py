"""Preemption-tolerant elastic training tests (ISSUE 17 acceptance):

  * unit: the async checkpoint writer (named daemon thread, backpressure,
    error re-raise, MXTPU_CKPT_ASYNC=0 degrade), the per-rank sharded
    checkpoint format (fast-path vs elastic restore, format guards), the
    preemption handler + exit-code contract, and kill_during_ckpt crash
    consistency for BOTH formats (latest() never regresses, no torn
    manifest);
  * launcher: preemption-rc exits restart for free (--max-restarts budget
    untouched, backoff reset) — no jax needed, fast;
  * module.fit: SIGTERM mid-epoch lands a batch-granular emergency
    checkpoint and the resumed run reproduces the uninterrupted weights
    exactly;
  * in-process mesh: ShardedTrainer elastic reshard FSDP×2 → FSDP×4 with
    exactly ONE honest recompile on the new topology;
  * group e2e (guarded like test_resilience): preempt@step=7,rank=1 under
    tools/launch.py → emergency checkpoint inside the grace window → free
    restart resumes with exact final weights; elastic resume across world
    sizes 2→1 and 1→2 with exact trajectory equality (the worker feeds
    every rank the full replicated batch, making allreduce-mean bit-exact
    across power-of-two world sizes — tests/elastic_worker.py); and the
    zero-compile preempt restart: generation 1 reaches the end of training
    with ZERO jit_compile events on the same topology.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.base import MXNetError
from mxnet_tpu.parallel.resilience import CheckpointManager

from test_resilience import _require_group_support, _worker_env

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_LAUNCH = os.path.join(_ROOT, "tools", "launch.py")
_EWORKER = os.path.join(_ROOT, "tests", "elastic_worker.py")


# --------------------------------------------------------------------------
# unit: async checkpoint writer
# --------------------------------------------------------------------------

def test_async_writer_thread_hygiene_and_flush(tmp_path):
    """save_sharded_async returns promptly; the writer is ONE named daemon
    thread; flush() makes the manifest durable; close() joins the thread
    (nothing for the conftest leaked-thread report to count)."""
    import threading

    mgr = CheckpointManager(str(tmp_path), keep_last=3)
    assert mgr._async_writer is None  # lazily created
    mgr.save_sharded_async(2, {"w": np.arange(4.0)}, rank=0, world_size=1,
                           topology={"world_size": 1})
    w = mgr._async_writer
    assert w is not None
    assert w._thread.name == "mxtpu-ckpt-writer"
    assert w._thread.daemon
    assert mgr.flush(timeout=30)
    assert mgr.latest()[0] == 2
    assert mgr.close()
    assert not w._thread.is_alive()
    assert [t for t in threading.enumerate()
            if t.name == "mxtpu-ckpt-writer" and t.is_alive()] == []


def test_async_writer_error_reraise_and_degrade(tmp_path, monkeypatch):
    # a payload pickle can't serialize -> the WRITER captures the error
    # and the next flush() re-raises it instead of passing silently
    mgr = CheckpointManager(str(tmp_path / "a"), keep_last=3)
    mgr.save_sharded_async(1, {"bad": lambda: None}, rank=0, world_size=1)
    with pytest.raises(Exception):
        mgr.flush(timeout=30)
    mgr.close()

    # MXTPU_CKPT_ASYNC=0 degrades to the synchronous path: no thread
    monkeypatch.setenv("MXTPU_CKPT_ASYNC", "0")
    mgr2 = CheckpointManager(str(tmp_path / "b"), keep_last=3)
    mgr2.save_sharded_async(3, {"w": np.ones(2)}, rank=0, world_size=1)
    assert mgr2._async_writer is None
    assert mgr2.latest()[0] == 3  # durable before the call returned


# --------------------------------------------------------------------------
# unit: sharded checkpoint format
# --------------------------------------------------------------------------

def test_sharded_save_restore_fast_and_elastic(tmp_path):
    d = str(tmp_path)
    topo = {"world_size": 2}
    # sync save, rank 1 stages its shard first, rank 0 publishes
    mgr1 = CheckpointManager(d, keep_last=3)
    assert mgr1.save_sharded(4, {"rank": 1}, rank=1, world_size=2,
                             topology=topo) is None
    mgr0 = CheckpointManager(d, keep_last=3)
    path = mgr0.save_sharded(4, {"rank": 0}, rank=0, world_size=2,
                             topology=topo)
    assert path and mgr0.latest()[0] == 4
    header = mgr0.read_meta(path)
    assert header["format"] == "sharded"
    assert header["shards"] == 2 and header["topology"] == topo

    # fast path: same topology + world size -> each rank sees ONLY its own
    seen = {}

    def fast(payloads, hdr):
        seen.update(payloads)

    hdr = mgr0.restore_sharded(fast, rank=1, world_size=2, topology=topo)
    assert hdr["step"] == 4 and set(seen) == {1}

    # elastic: world size changed -> every shard is handed to the loader
    seen.clear()
    hdr = mgr0.restore_sharded(fast, rank=0, world_size=1,
                               topology={"world_size": 1})
    assert hdr["step"] == 4 and set(seen) == {0, 1}
    assert seen[0] == {"rank": 0} and seen[1] == {"rank": 1}


def test_sharded_and_plain_formats_refuse_each_other(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep_last=3)
    mgr.save_sharded(1, {"w": 1}, rank=0, world_size=1)
    with pytest.raises(MXNetError, match="restore_sharded"):
        mgr.restore(load_params=lambda p: None)
    mgr2 = CheckpointManager(str(tmp_path / "plain"), keep_last=3)
    mgr2.save(1, save_params=lambda p: open(p, "wb").write(b"x"))
    with pytest.raises(MXNetError, match="not sharded"):
        mgr2.restore_sharded(lambda payloads, hdr: None)


# --------------------------------------------------------------------------
# unit: kill_during_ckpt crash consistency (subprocess — the fault kills)
# --------------------------------------------------------------------------

_KILL_CKPT_BODY = r"""
import os, sys
sys.path.insert(0, %(root)r)
from mxnet_tpu.parallel.resilience import CheckpointManager
mgr = CheckpointManager(sys.argv[2], keep_last=4)
if sys.argv[1] == "plain":
    mgr.save(1, save_params=lambda p: open(p, "wb").write(b"v1"))
    mgr.save(2, save_params=lambda p: open(p, "wb").write(b"v2"))
else:
    mgr.save_sharded(1, {"v": 1}, rank=0, world_size=1)
    mgr.save_sharded(2, {"v": 2}, rank=0, world_size=1)
print("UNREACHABLE past step-2 save", flush=True)
"""


@pytest.mark.parametrize("fmt", ["plain", "sharded"])
def test_kill_during_ckpt_crash_consistency(tmp_path, fmt):
    """The mid-save chaos hook dies AFTER staging, BEFORE publish: the
    process exits with the fault code, latest() still answers the
    PREVIOUS step, and a fresh save at the same step publishes fine."""
    d = str(tmp_path / fmt)
    proc = subprocess.run(
        [sys.executable, "-c", _KILL_CKPT_BODY % {"root": _ROOT}, fmt, d],
        env=_worker_env(MXTPU_FAULT_INJECT="kill_during_ckpt@step=2",
                        PYTHONPATH=_ROOT),
        capture_output=True, text=True, timeout=180)
    assert proc.returncode == 42, proc.stdout + proc.stderr
    assert "UNREACHABLE" not in proc.stdout
    mgr = CheckpointManager(d, keep_last=4)
    assert mgr.latest()[0] == 1  # step 2 never became visible
    # no torn manifest: every published step passes verification
    if fmt == "sharded":
        mgr.save_sharded(2, {"v": 2}, rank=0, world_size=1)
        got = {}
        mgr.restore_sharded(lambda p, h: got.update(p))
        assert got == {0: {"v": 2}}
    else:
        mgr.save(2, save_params=lambda p: open(p, "wb").write(b"v2"))
        assert mgr.latest()[0] == 2


# --------------------------------------------------------------------------
# unit: preemption handler + exit-code contract (subprocess — it exits)
# --------------------------------------------------------------------------

_PREEMPT_BODY = r"""
import os, signal, sys
sys.path.insert(0, %(root)r)
from mxnet_tpu.parallel import resilience
assert resilience.install_preemption_handler()
assert not resilience.preemption_requested()
resilience.maybe_preempt_exit()  # no-op until SIGTERM lands
os.kill(os.getpid(), signal.SIGTERM)
assert resilience.preemption_requested()
assert resilience.preempt_grace_s() == 7.5, resilience.preempt_grace_s()
mode = sys.argv[1]
def save_ok():
    open(sys.argv[2], "w").write("saved")
def save_boom():
    raise RuntimeError("disk gone")
resilience.maybe_preempt_exit(
    emergency_save=save_ok if mode == "ok" else save_boom)
print("UNREACHABLE", flush=True)
"""


@pytest.mark.parametrize("mode,rc_delta", [("ok", 0), ("boom", 1)])
def test_preempt_handler_rc_contract(tmp_path, mode, rc_delta):
    """SIGTERM raises a flag; maybe_preempt_exit runs the emergency save
    and exits MXTPU_PREEMPT_EXIT_CODE — or code+1 when the save failed,
    so the launcher correctly charges that restart to the crash budget."""
    marker = str(tmp_path / "saved.txt")
    proc = subprocess.run(
        [sys.executable, "-c", _PREEMPT_BODY % {"root": _ROOT}, mode, marker],
        env=_worker_env(MXTPU_PREEMPT_GRACE_S="7.5",
                        MXTPU_PREEMPT_EXIT_CODE="83", PYTHONPATH=_ROOT),
        capture_output=True, text=True, timeout=180)
    assert proc.returncode == 83 + rc_delta, proc.stdout + proc.stderr
    assert "UNREACHABLE" not in proc.stdout
    assert os.path.exists(marker) == (mode == "ok")


# --------------------------------------------------------------------------
# launcher: preemption restarts are free (no jax — fast)
# --------------------------------------------------------------------------

def _run_launcher(worker_body, tmp_path, max_restarts, backoff="0.1"):
    worker = tmp_path / "w.py"
    worker.write_text(worker_body)
    proc = subprocess.run(
        [sys.executable, _LAUNCH, "-n", "1",
         "--max-restarts", str(max_restarts), "--restart-backoff", backoff,
         "--", sys.executable, str(worker)],
        env=dict(os.environ), capture_output=True, text=True, timeout=120)
    return proc, proc.stdout + proc.stderr


def test_launcher_preempt_free_restart(tmp_path):
    """Two consecutive preemptions with --max-restarts 1 still finish:
    preempt-rc exits never consume the crash budget."""
    body = ("import os, sys\n"
            "g = int(os.environ.get('MXTPU_RESTART_GENERATION', '0'))\n"
            "sys.exit({0: 83, 1: 83}.get(g, 0))\n")
    proc, out = _run_launcher(body, tmp_path, max_restarts=1)
    assert proc.returncode == 0, out
    assert out.count("restart budget untouched: 0/1 used") == 2, out
    assert "spawning generation 2" in out, out


def test_launcher_preempt_resets_backoff_then_crashes_consume(tmp_path):
    """A crash doubles the backoff; a later preemption resets it to the
    initial value; further crashes still consume the budget and the
    exhaustion message is unchanged."""
    body = ("import os, sys\n"
            "g = int(os.environ.get('MXTPU_RESTART_GENERATION', '0'))\n"
            "sys.exit({0: 5, 1: 83, 2: 5, 3: 5}.get(g, 0))\n")
    proc, out = _run_launcher(body, tmp_path, max_restarts=2, backoff="0.2")
    assert proc.returncode == 5, out
    # gen0 crash consumed restart 1 of 2 at the initial 0.2s backoff...
    assert "restarting (1/2) in 0.2s" in out, out
    # ...gen1 preempted: free restart, backoff RESET to 0.2 (a crash ramp
    # would have shown 0.5s here)
    assert "free restart as generation 2 in 0.2s" in out, out
    # gen2+gen3 crashes consume the remaining budget and exhaust it
    assert "restarting (2/2) in 0.2s" in out, out
    assert "2 restart(s) exhausted, giving up" in out, out


def test_launcher_preempt_without_budget_fails_fast(tmp_path):
    """--max-restarts 0 keeps fail-fast semantics even for preemptions
    (nothing to restart with); the preempt rc propagates."""
    body = "import sys; sys.exit(83)\n"
    proc, out = _run_launcher(body, tmp_path, max_restarts=0)
    assert proc.returncode == 83, out
    assert "free restart" not in out


# --------------------------------------------------------------------------
# module.fit: graceful preemption with exact batch-granular resume
# --------------------------------------------------------------------------

_FIT_BODY = r"""
import sys
sys.path.insert(0, %(root)r)
from test_preempt_elastic import _run_fit
print("FIT_DONE wsum=%%.8f" %% _run_fit(sys.argv[1], resume="auto"),
      flush=True)
"""


def _run_fit(ckpt_dir, resume=None):
    """4-epoch MLP fit with deterministic seeds; returns the final
    absolute weight sum. Shared by the in-process reference/resume runs
    and the preempted subprocess."""
    import mxnet_tpu.symbol as S

    x = S.Variable("data")
    h = S.FullyConnected(x, num_hidden=8, name="fc1")
    h = S.Activation(h, act_type="relu")
    h = S.FullyConnected(h, num_hidden=2, name="fc2")
    sym = S.SoftmaxOutput(h, name="softmax")

    rng = np.random.RandomState(0)
    X = rng.uniform(-1, 1, (128, 6)).astype(np.float32)
    Y = (X.sum(axis=1) > 0).astype(np.float32)
    mx.random.seed(42)
    np.random.seed(42)
    train = mx.io.NDArrayIter(X, Y, batch_size=32,
                              label_name="softmax_label")
    mod = mx.mod.Module(sym, context=mx.cpu())
    mod.fit(train, num_epoch=4, optimizer="sgd",
            optimizer_params={"learning_rate": 0.1},
            checkpoint_dir=str(ckpt_dir), resume=resume)
    w = mod.get_params()[0]
    return sum(float(np.abs(v.asnumpy()).sum()) for v in w.values())


def test_fit_preempt_resume_exact(tmp_path):
    """fit() preempted at update 3 (mid-epoch-0) exits rc 83 with an
    emergency checkpoint whose meta carries the batch cursor; the resumed
    fit fast-forwards past the already-applied batches and lands on
    EXACTLY the uninterrupted run's weights."""
    ckpt = tmp_path / "ck"
    proc = subprocess.run(
        [sys.executable, "-c", _FIT_BODY % {"root": _ROOT}, str(ckpt)],
        env=_worker_env(MXTPU_FAULT_INJECT="preempt@step=3,grace=30",
                        PYTHONPATH=_ROOT + os.pathsep
                        + os.path.join(_ROOT, "tests")),
        capture_output=True, text=True, timeout=300)
    assert proc.returncode == 83, proc.stdout + proc.stderr
    assert "FIT_DONE" not in proc.stdout
    header = json.load(open(ckpt / "ckpt-00000000" / "meta.json"))
    assert header["meta"]["preempt"] is True
    assert header["meta"]["batches_done"] == 3
    ref = _run_fit(tmp_path / "ref")
    got = _run_fit(ckpt, resume="auto")
    assert got == ref, (got, ref)


# --------------------------------------------------------------------------
# in-process: elastic reshard on a real FSDP mesh, one honest recompile
# --------------------------------------------------------------------------

def test_sharded_trainer_elastic_reshard_one_recompile(tmp_path, monkeypatch):
    """ShardedTrainer on FSDP×2 checkpoints genuinely partitioned shards;
    restoring onto FSDP×4 reshards N→M and pays EXACTLY ONE recompile on
    the new topology; restoring onto an identical mesh is bit-exact with
    zero recompiles (the in-memory executable registry hits)."""
    import jax

    from mxnet_tpu import gluon
    from mxnet_tpu.gluon import loss as gloss
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.parallel import FSDP, make_mesh
    from mxnet_tpu.telemetry import recorder

    devs = jax.devices()
    if len(devs) < 4:
        pytest.skip("needs 4 virtual devices (conftest forces 8)")

    compiles = []
    real_record = recorder.record_event

    def record(kind, **fields):
        if kind == "jit_compile":
            compiles.append(fields)
        return real_record(kind, **fields)

    monkeypatch.setattr(recorder, "record_event", record)

    def build(mesh):
        np.random.seed(3)
        mx.random.seed(3)
        # fixed prefix: every rebuilt trainer names its params identically
        # (a restarted process would); 2048-elem weight -> fsdp-sharded
        net = nn.Dense(64, in_units=32, prefix="ew_")
        net.initialize()
        x = mx.nd.array(np.random.RandomState(5).randn(8, 32)
                        .astype(np.float32))
        net(x)
        tr = gluon.Trainer(net.collect_params(), "sgd",
                           {"learning_rate": 0.1, "momentum": 0.9},
                           sharded=True, block=net,
                           loss=gloss.L2Loss(), mesh=mesh)
        return net, tr

    def batch(step):
        r = np.random.RandomState(100 + step)
        return (mx.nd.array(r.randn(8, 32).astype(np.float32)),
                mx.nd.array(r.randn(8, 64).astype(np.float32)))

    def weights(tr, net):
        tr.sync_params()
        return {k: v.data().asnumpy()
                for k, v in net.collect_params().items()}

    mesh2 = make_mesh([(FSDP, 2)], devices=devs[:2])
    net_a, tr_a = build(mesh2)
    mgr = CheckpointManager(str(tmp_path), keep_last=3)
    for step in (1, 2, 3):
        tr_a.step_batch(*batch(step))
    tr_a.save_sharded_checkpoint(mgr)
    assert mgr.flush(timeout=60)
    # the checkpoint is genuinely partitioned: >1 distinct piece keys
    got = {}
    hdr = mgr.restore_sharded(lambda p, h: got.update(p))
    assert len(got[0]["params"]["ew_weight"]["pieces"]) == 2

    # same-mesh restore: bit-exact continuation, ZERO new compiles
    for step in (4, 5):
        tr_a.step_batch(*batch(step))
    ref_w = weights(tr_a, net_a)

    net_b, tr_b = build(make_mesh([(FSDP, 2)], devices=devs[:2]))
    tr_b.restore_sharded_checkpoint(mgr)
    assert tr_b.step_count == 3
    compiles.clear()
    for step in (4, 5):
        tr_b.step_batch(*batch(step))
    assert compiles == [], compiles
    same_w = weights(tr_b, net_b)
    for k in ref_w:
        np.testing.assert_array_equal(same_w[k], ref_w[k], err_msg=k)

    # elastic: restore onto FSDP×4 — one honest recompile, then reuse
    net_c, tr_c = build(make_mesh([(FSDP, 4)], devices=devs[:4]))
    tr_c.restore_sharded_checkpoint(mgr)
    assert tr_c.step_count == 3
    compiles.clear()
    tr_c.step_batch(*batch(4))
    assert len(compiles) >= 1, "new topology must honestly recompile"
    n_first = len(compiles)
    tr_c.step_batch(*batch(5))
    assert len(compiles) == n_first, "second step must reuse the executable"
    new_w = weights(tr_c, net_c)
    for k in ref_w:
        np.testing.assert_allclose(new_w[k], ref_w[k], rtol=1e-5,
                                   atol=1e-6, err_msg=k)
    mgr.close()


# --------------------------------------------------------------------------
# group e2e (guarded): preempt -> grace checkpoint -> elastic resume
# --------------------------------------------------------------------------

def _run_group(ckpt_dir, n, total_steps, fault=None, max_restarts=0):
    extra = {"MXTPU_CKPT_DIR": str(ckpt_dir), "PYTHONPATH": _ROOT,
             "MXTPU_TEST_TOTAL_STEPS": str(total_steps),
             "MXTPU_TEARDOWN_GRACE": "3",
             "MXTPU_CKPT_SHARD_TIMEOUT_S": "60"}
    if fault:
        extra["MXTPU_FAULT_INJECT"] = fault
    cmd = [sys.executable, _LAUNCH, "-n", str(n)]
    if max_restarts:
        cmd += ["--max-restarts", str(max_restarts),
                "--restart-backoff", "0.2"]
    cmd += ["--", sys.executable, _EWORKER]
    proc = subprocess.run(cmd, env=_worker_env(**extra),
                          capture_output=True, text=True, timeout=420)
    return proc, proc.stdout + proc.stderr


def _wsums(out):
    import re

    return [(m.group(1), float(m.group(2))) for m in re.finditer(
        r"ELASTIC_OK rank=(\d+/\d+) gen=\d+ steps=\d+ wsum=(-?[\d.]+)", out)]


def test_preempt_elastic_group_e2e(tmp_path):
    """THE acceptance chain (one reference, then three resumed lives):

      ref : 1 rank, 12 uninterrupted steps                  -> wsum_ref
      A   : 2 ranks, rank 1 preempted at step 7; the solo emergency
            checkpoint restarts the group for FREE and generation 1
            elastically resumes (1 shard -> 2 ranks) to step 12 == ref
      B   : 2 ranks to step 6, then 1 rank resumes 2->1 to step 10,
            then 2 ranks resume 1->2 to step 12             == ref

    Every rank trains the full replicated batch, so all of these are
    EXACT weight matches, not tolerances."""
    _require_group_support()

    proc, out = _run_group(tmp_path / "ref", 1, 12)
    assert proc.returncode == 0, out[-4000:]
    ref = dict(_wsums(out))["0/1"]

    # -- A: same-world preemption, free restart, solo-shard elastic resume
    proc, out = _run_group(tmp_path / "a", 2, 12,
                           fault="preempt@step=7,rank=1,grace=30",
                           max_restarts=1)
    assert proc.returncode == 0, out[-4000:]
    assert "group preempted (rc=83)" in out, out[-4000:]
    assert "restart budget untouched: 0/1 used" in out, out[-4000:]
    assert "emergency checkpoint" in out, out[-4000:]
    resumed = [ln for ln in out.splitlines() if "ELASTIC_RESUMED" in ln]
    assert len(resumed) == 2, out[-4000:]
    for ln in resumed:
        assert "from_step=7 elastic=1 shards=1" in ln, ln
    sums = _wsums(out)
    assert sorted(r for r, _ in sums) == ["0/2", "1/2"], out[-4000:]
    assert all(s == ref for _, s in sums), (sums, ref)

    # -- B: world-size-elastic resume, both directions, exact trajectory
    proc, out = _run_group(tmp_path / "b", 2, 6)
    assert proc.returncode == 0, out[-4000:]

    proc, out = _run_group(tmp_path / "b", 1, 10)  # 2 shards -> 1 rank
    assert proc.returncode == 0, out[-4000:]
    assert "ELASTIC_RESUMED rank=0/1 gen=0 from_step=6 elastic=1 shards=2" \
        in out, out[-4000:]

    proc, out = _run_group(tmp_path / "b", 2, 12)  # 1 shard -> 2 ranks
    assert proc.returncode == 0, out[-4000:]
    for r in (0, 1):
        assert ("ELASTIC_RESUMED rank=%d/2 gen=0 from_step=10 elastic=1 "
                "shards=1" % r) in out, out[-4000:]
    sums = _wsums(out)
    assert all(s == ref for _, s in sums), (sums, ref)


_PREEMPT_ZC_WORKER = r"""
import os, sys
gen = os.environ.get("MXTPU_RESTART_GENERATION", "0")
tdir = os.path.join(os.environ["TRB_TDIR"], "gen" + gen)
os.makedirs(tdir, exist_ok=True)
os.environ["MXTPU_TELEMETRY_DIR"] = tdir

import numpy as np
import mxnet_tpu as mx
from mxnet_tpu import gluon
from mxnet_tpu.gluon import nn, loss as gloss
from mxnet_tpu.parallel import resilience
from mxnet_tpu.parallel.resilience import CheckpointManager

np.random.seed(0); mx.random.seed(0)
net = nn.HybridSequential(prefix="pz_")
with net.name_scope():
    net.add(nn.Dense(4, activation="relu", prefix="d1_"))
    net.add(nn.Dense(3, prefix="d2_"))
net.initialize()
x = mx.nd.array(np.random.randn(8, 5).astype("float32"))
y = mx.nd.array(np.random.randint(0, 3, (8,)).astype("float32"))
net(x)
tr = gluon.Trainer(net.collect_params(), "sgd", {"learning_rate": 0.1},
                   block=net, loss=gloss.SoftmaxCrossEntropyLoss())
assert tr.sharded is not None, "env promotion did not arm"
mgr = CheckpointManager(os.environ["MXTPU_CKPT_DIR"], keep_last=3)
resilience.install_preemption_handler()
hdr = tr.restore_sharded_checkpoint(mgr)
if hdr is not None:
    print("PZ_RESUMED gen=%s from_step=%d" % (gen, tr.step_count), flush=True)
loss = None
for step in range(tr.step_count + 1, 11):
    loss = float(tr.step_batch(x, y).asscalar())
    if step % 2 == 0:
        tr.save_sharded_checkpoint(mgr)
    resilience.maybe_preempt_exit(
        emergency_save=lambda: tr.emergency_sharded_checkpoint(mgr))
mgr.close()
tr.sync_params()
wsum = sum(float(np.abs(v.data().asnumpy()).sum())
           for v in net.collect_params().values())
print("PZ_OK gen=%s steps=%d wsum=%.8f loss=%.6f"
      % (gen, tr.step_count, wsum, loss), flush=True)
"""


def test_launch_preempt_zero_compile_resume(tmp_path):
    """Chaos e2e: the promoted whole-step trainer is preempted at step 7
    under tools/launch.py --compile-cache; the emergency sharded
    checkpoint restarts the group for free and generation 1 finishes
    training with ZERO jit_compile events (same topology -> persistent
    executable cache hits) and the exact uninterrupted final weights."""
    worker = tmp_path / "worker.py"
    worker.write_text(_PREEMPT_ZC_WORKER)
    cache = tmp_path / "cache"
    cache.mkdir()

    def run(tag, fault=None):
        tbase = tmp_path / ("telemetry_" + tag)
        ckpt = tmp_path / ("ckpt_" + tag)
        tbase.mkdir()
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env.pop("MXTPU_TELEMETRY_DIR", None)
        if fault:
            env["MXTPU_FAULT_INJECT"] = fault
        proc = subprocess.run(
            [sys.executable, _LAUNCH, "-n", "1", "--max-restarts", "1",
             "--restart-backoff", "0.2",
             "--compile-cache", str(cache), "--sharded-step",
             "--env", "TRB_TDIR=%s" % tbase,
             "--env", "MXTPU_CKPT_DIR=%s" % ckpt,
             "--env", "PYTHONPATH=%s" % _ROOT,
             "--", sys.executable, str(worker)],
            env=env, capture_output=True, text=True, timeout=420)
        return proc, proc.stdout + proc.stderr, tbase

    def events(tbase, gen):
        counts = {}
        gdir = tbase / ("gen%d" % gen)
        if not gdir.is_dir():
            return counts
        for name in os.listdir(gdir):
            if not name.endswith(".jsonl"):
                continue
            with open(gdir / name) as f:
                for line in f:
                    try:
                        rec = json.loads(line)
                    except ValueError:
                        continue
                    if rec.get("kind") == "event":
                        ev = rec.get("event")
                        counts[ev] = counts.get(ev, 0) + 1
        return counts

    proc, out, _ = run("ref")
    assert proc.returncode == 0, out[-4000:]
    ref_line = [ln for ln in out.splitlines() if "PZ_OK gen=0" in ln]
    assert ref_line, out[-4000:]

    proc, out, tbase = run("pre", fault="preempt@step=7,grace=30")
    assert proc.returncode == 0, out[-4000:]
    assert "group preempted (rc=83)" in out, out[-4000:]
    assert "PZ_RESUMED gen=1 from_step=7" in out, out[-4000:]
    ok_line = [ln for ln in out.splitlines() if "PZ_OK gen=1" in ln]
    assert ok_line, out[-4000:]
    # identical final weights and last-step loss, reported identically
    assert ok_line[0].split("wsum=")[1] == ref_line[0].split("wsum=")[1]
    e1 = events(tbase, 1)
    assert e1.get("jit_compile", 0) == 0, e1       # zero-compile resume
    assert e1.get("compile_persist_hit", 0) > 0, e1
    # the emergency checkpoint itself was recorded
    e0 = events(tbase, 0)
    assert e0.get("preempt_checkpoint", 0) >= 1, e0
