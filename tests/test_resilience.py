"""Fault-tolerance layer tests (ISSUE 2 acceptance):

  * unit: CheckpointManager atomicity, retention, corruption detection,
    crash-consistent nd.save + truncated-load diagnostics, fault-spec
    parsing and the corrupt_ckpt injection action;
  * launcher: --max-restarts exhaustion and recovery (no jax needed —
    fast);
  * group (guarded — skip-with-reason when the box can't spawn jax process
    groups): kill-rank-1-mid-training resume-equivalence, and the bounded
    rendezvous: a worker whose peer never arrives fails with MXNetError
    within MXTPU_RENDEZVOUS_TIMEOUT (+ margin) instead of hanging.
"""
import os
import re
import subprocess
import sys
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.base import MXNetError
from mxnet_tpu.parallel import resilience
from mxnet_tpu.parallel.resilience import CheckpointManager, fault_spec

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_LAUNCH = os.path.join(_ROOT, "tools", "launch.py")
_WORKER = os.path.join(_ROOT, "tests", "resilience_worker.py")


def _worker_env(**extra):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    env.setdefault("MXTPU_RENDEZVOUS_TIMEOUT", "60")
    env.update(extra)
    return env


# --------------------------------------------------------------------------
# runtime guard: can this box spawn a real 2-process jax group?
# --------------------------------------------------------------------------

_GROUP_PROBE = None


def _group_support():
    """One cached probe per session: a minimal 2-rank rendezvous. Sandboxes
    that can't bind localhost sockets or fork process groups skip the group
    tests WITH the probe's diagnostic instead of timing out for minutes."""
    global _GROUP_PROBE
    if _GROUP_PROBE is None:
        body = ("import jax;"
                "from mxnet_tpu.parallel import collectives;"
                "collectives.init_process_group();"
                "assert jax.process_count()==2; print('GROUP_PROBE_OK')")
        try:
            proc = subprocess.run(
                [sys.executable, _LAUNCH, "-n", "2", "--",
                 sys.executable, "-c", body],
                env=_worker_env(MXTPU_RENDEZVOUS_TIMEOUT="45",
                                PYTHONPATH=_ROOT),
                capture_output=True, text=True, timeout=180)
            out = proc.stdout + proc.stderr
            ok = proc.returncode == 0 and out.count("GROUP_PROBE_OK") == 2
            _GROUP_PROBE = (ok, "" if ok else out[-1500:])
        except subprocess.TimeoutExpired as e:
            _GROUP_PROBE = (False, "probe timed out: %s" % e)
    return _GROUP_PROBE


def _require_group_support():
    ok, why = _group_support()
    if not ok:
        pytest.skip("box can't spawn jax process groups: %s" % why)


# --------------------------------------------------------------------------
# unit: crash-consistent files + CheckpointManager
# --------------------------------------------------------------------------

def test_nd_save_is_atomic_and_truncation_diagnosable(tmp_path):
    f = str(tmp_path / "w.params")
    mx.nd.save(f, {"a": mx.nd.array([1.0, 2.0, 3.0])})
    # no temp litter after a successful save
    assert [n for n in os.listdir(tmp_path) if ".tmp-" in n] == []
    # a failed save leaves the previous complete file untouched
    before = open(f, "rb").read()

    class Boom(Exception):
        pass

    orig = np.savez
    try:
        def exploding(fh, **kw):
            fh.write(b"partial")
            raise Boom()
        np.savez = exploding
        with pytest.raises(Boom):
            mx.nd.save(f, {"a": mx.nd.array([9.0])})
    finally:
        np.savez = orig
    assert open(f, "rb").read() == before
    assert [n for n in os.listdir(tmp_path) if ".tmp-" in n] == []
    # truncation (simulating a pre-atomic-format partial copy) raises a
    # diagnosable MXNetError, not a bare zipfile traceback
    with open(f, "r+b") as fh:
        fh.truncate(os.path.getsize(f) // 2)
    with pytest.raises(MXNetError, match="truncated or corrupt"):
        mx.nd.load(f)


def test_block_save_parameters_crash_consistent(tmp_path):
    from mxnet_tpu.gluon import nn

    net = nn.Dense(3, in_units=4)
    net.initialize()
    f = str(tmp_path / "net.params")
    net.save_parameters(f)
    net2 = nn.Dense(3, in_units=4)
    net2.load_parameters(f)
    np.testing.assert_allclose(net2.weight.data().asnumpy(),
                               net.weight.data().asnumpy())
    assert [n for n in os.listdir(tmp_path) if ".tmp-" in n] == []


def _save_step(mgr, step, val):
    return mgr.save(
        step,
        save_params=lambda fn: mx.nd.save(fn, {"w": mx.nd.array([val] * 4)}),
        save_states=lambda fn: open(fn, "wb").write(b"S%d" % step),
        meta={"epoch": step})


def test_checkpoint_retention_and_latest(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep_last=2)
    for s in (1, 2, 3, 4, 5):
        assert _save_step(mgr, s, float(s)) is not None
    names = sorted(os.listdir(str(tmp_path)))
    assert names == ["ckpt-00000004", "ckpt-00000005"], names
    step, path = mgr.latest()
    assert step == 5
    header = mgr.read_meta(path)
    assert header["meta"]["epoch"] == 5
    assert header["rng"]["seed"] == mx.random.current_seed()


def test_checkpoint_corruption_detection_falls_back(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep_last=3)
    for s in (2, 4):
        _save_step(mgr, s, float(s))
    _, newest = mgr.latest()
    pf = os.path.join(newest, "data.params")
    with open(pf, "r+b") as fh:
        fh.seek(os.path.getsize(pf) // 2)
        fh.write(b"\xde\xad")
    # latest() routes around the corrupt step...
    step, _ = mgr.latest()
    assert step == 2
    # ...explicit restore of the corrupt one refuses loudly
    with pytest.raises(MXNetError, match="failed verification"):
        mgr.restore(step=4)
    # restore of the valid one returns the right payload
    got = {}
    header = mgr.restore(
        load_params=lambda fn: got.update(w=mx.nd.load(fn)["w"].asnumpy()),
        load_states=lambda fn: got.update(s=open(fn, "rb").read()))
    assert header["step"] == 2
    np.testing.assert_allclose(got["w"], 2.0)
    assert got["s"] == b"S2"


def test_checkpoint_partial_write_invisible(tmp_path):
    """A staging dir left by a killed save is never discovered and is swept
    by the next save."""
    mgr = CheckpointManager(str(tmp_path), keep_last=3)
    _save_step(mgr, 1, 1.0)
    stale = os.path.join(str(tmp_path), ".tmp-ckpt-00000009-dead")
    os.makedirs(stale)
    open(os.path.join(stale, "data.params"), "wb").write(b"torn")
    assert mgr.latest()[0] == 1
    _save_step(mgr, 2, 2.0)
    assert not os.path.exists(stale)
    assert mgr.latest()[0] == 2


def test_checkpoint_rank_gating(tmp_path, monkeypatch):
    monkeypatch.setenv("MXTPU_PROCESS_ID", "1")
    mgr = CheckpointManager(str(tmp_path), keep_last=2)
    assert _save_step(mgr, 1, 1.0) is None          # non-zero rank: no write
    assert os.listdir(str(tmp_path)) == []
    mgr2 = CheckpointManager(str(tmp_path), keep_last=2, rank0_only=False)
    assert _save_step(mgr2, 1, 1.0) is not None


def test_fault_spec_parsing(monkeypatch):
    assert fault_spec("kill@step=7,rank=1") == [
        {"action": "kill", "step": 7, "rank": 1, "gen": 0, "code": 42,
         "dir": None, "batch": None, "replica": None, "ms": 1000,
         "after": None, "rps": 100, "duration": 2, "grace": None}]
    # the preemption / mid-checkpoint actions ride the same grammar
    pe, kc = fault_spec("preempt@step=7,rank=1,grace=30 "
                        "kill_during_ckpt@step=4,rank=0")
    assert (pe["action"], pe["step"], pe["rank"], pe["grace"]) == \
        ("preempt", 7, 1, 30)
    assert (kc["action"], kc["step"], kc["rank"], kc["grace"]) == \
        ("kill_during_ckpt", 4, 0, None)
    assert fault_spec("exc@step=3 corrupt_ckpt@step=5,dir=/tmp/x")[1]["dir"] \
        == "/tmp/x"
    # serving actions key on batch=/replica= instead of step=/rank=
    kr, wr, sl = fault_spec("kill_replica@batch=3,replica=0 "
                            "wedge_replica@batch=5,replica=1,gen=0 "
                            "slow_reply@batch=2,ms=500")
    assert (kr["action"], kr["batch"], kr["replica"]) == ("kill_replica", 3, 0)
    assert (wr["action"], wr["batch"], wr["replica"]) == ("wedge_replica",
                                                         5, 1)
    assert (sl["action"], sl["batch"], sl["ms"], sl["replica"]) == \
        ("slow_reply", 2, 500, None)
    with pytest.raises(MXNetError, match="unknown action"):
        fault_spec("explode@step=1")
    with pytest.raises(MXNetError, match="needs a step"):
        fault_spec("kill@rank=1")
    with pytest.raises(MXNetError, match="needs a batch"):
        fault_spec("kill_replica@step=3")
    # hook is inert without the env var
    monkeypatch.delenv("MXTPU_FAULT_INJECT", raising=False)
    monkeypatch.setattr(resilience, "_fault_cache", resilience._UNPARSED)
    resilience.maybe_inject_fault(1)


def test_fault_inject_exc_and_gen_gating(monkeypatch):
    monkeypatch.setenv("MXTPU_FAULT_INJECT", "exc@step=3,rank=0")
    monkeypatch.setattr(resilience, "_fault_cache", resilience._UNPARSED)
    resilience.maybe_inject_fault(2)                 # wrong step: no-op
    with pytest.raises(MXNetError, match="injected fault"):
        resilience.maybe_inject_fault(3)
    # a restarted generation must NOT re-trigger the same fault
    monkeypatch.setenv("MXTPU_RESTART_GENERATION", "1")
    resilience.maybe_inject_fault(3)
    # wrong rank: no-op
    monkeypatch.setenv("MXTPU_RESTART_GENERATION", "0")
    monkeypatch.setenv("MXTPU_PROCESS_ID", "1")
    resilience.maybe_inject_fault(3)


def test_fault_inject_corrupt_ckpt_action(tmp_path, monkeypatch):
    mgr = CheckpointManager(str(tmp_path), keep_last=3)
    for s in (1, 2):
        _save_step(mgr, s, float(s))
    monkeypatch.setenv("MXTPU_FAULT_INJECT",
                       "corrupt_ckpt@step=9,dir=%s" % tmp_path)
    monkeypatch.setattr(resilience, "_fault_cache", resilience._UNPARSED)
    resilience.maybe_inject_fault(9)
    # the newest checkpoint is now damaged; discovery falls back to step 1
    assert mgr.latest()[0] == 1


def test_trainer_states_roundtrip_and_step_cursor(tmp_path):
    from mxnet_tpu import autograd, gluon
    from mxnet_tpu.gluon import nn

    net = nn.Dense(1, in_units=4, use_bias=False)
    net.initialize(mx.init.Normal(0.5))
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.1, "momentum": 0.9})
    x = mx.nd.array(np.random.RandomState(0).normal(size=(8, 4)))
    y = mx.nd.array(np.ones((8, 1), np.float32))
    l2 = gluon.loss.L2Loss()
    for _ in range(3):
        with autograd.record():
            loss = l2(net(x), y)
        loss.backward()
        tr.step(8)
    assert tr.step_count == 3
    f = str(tmp_path / "t.states")
    p = str(tmp_path / "t.params")
    tr.save_states(f)
    net.save_parameters(p)
    net2 = nn.Dense(1, in_units=4, use_bias=False)
    net2.initialize(mx.init.Normal(0.5))
    net2.load_parameters(p)
    tr2 = gluon.Trainer(net2.collect_params(), "sgd",
                        {"learning_rate": 0.1, "momentum": 0.9})
    tr2.load_states(f)
    assert tr2.step_count == 3
    # one more step on both: the restored momentum must drive the restored
    # trainer to EXACTLY the same weights as the uninterrupted one
    for net_i, tr_i in ((net, tr), (net2, tr2)):
        with autograd.record():
            loss = l2(net_i(x), y)
        loss.backward()
        tr_i.step(8)
    np.testing.assert_array_equal(net.weight.data().asnumpy(),
                                  net2.weight.data().asnumpy())


# --------------------------------------------------------------------------
# launcher supervision (no jax in the children — fast)
# --------------------------------------------------------------------------

def test_launcher_max_restarts_exhaustion():
    t0 = time.time()
    proc = subprocess.run(
        [sys.executable, _LAUNCH, "-n", "2", "--max-restarts", "2",
         "--restart-backoff", "0.1", "--",
         sys.executable, "-c", "import sys; sys.exit(3)"],
        capture_output=True, text=True, timeout=120)
    out = proc.stdout + proc.stderr
    assert proc.returncode == 3, out
    assert out.count("spawning generation") == 2, out
    assert "restart(s) exhausted" in out, out
    assert time.time() - t0 < 60


def test_launcher_restart_recovers_with_fresh_generation():
    body = ("import os,sys;"
            "g=int(os.environ['MXTPU_RESTART_GENERATION']);"
            "print('gen',g,'port',os.environ['MXTPU_COORDINATOR'],flush=True);"
            "sys.exit(0 if g==1 else 5)")
    proc = subprocess.run(
        [sys.executable, _LAUNCH, "-n", "2", "--max-restarts", "3",
         "--restart-backoff", "0.1", "--",
         sys.executable, "-c", body],
        capture_output=True, text=True, timeout=120)
    out = proc.stdout + proc.stderr
    assert proc.returncode == 0, out
    # fresh rendezvous port per generation
    ports = set(re.findall(r"port 127\.0\.0\.1:(\d+)", out))
    assert len(ports) >= 2, out
    # per-rank log prefixes make the post-mortem attributable
    assert "[rank 0]" in out and "[rank 1]" in out, out


def test_launcher_one_dead_rank_tears_down_group():
    """Rank 1 exits nonzero immediately; rank 0 would sleep forever — the
    supervisor must SIGTERM/SIGKILL it rather than wait."""
    body = ("import os,sys,time;"
            "sys.exit(7) if os.environ['MXTPU_PROCESS_ID']=='1' "
            "else time.sleep(600)")
    t0 = time.time()
    proc = subprocess.run(
        [sys.executable, _LAUNCH, "-n", "2", "--",
         sys.executable, "-c", body],
        capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert time.time() - t0 < 60, "teardown took too long"


# --------------------------------------------------------------------------
# group tests (guarded)
# --------------------------------------------------------------------------

def test_rendezvous_timeout_is_bounded(tmp_path):
    """Acceptance: a worker whose peer never arrives fails with a clear
    MXNetError within MXTPU_RENDEZVOUS_TIMEOUT (+ margin) instead of
    hanging the group forever. Single process — exercises the client dial
    against a coordinator nobody serves, so it runs even on boxes that
    can't form full groups."""
    import socket
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]  # freed on close; nobody will serve it
    body = ("from mxnet_tpu.parallel import collectives;"
            "collectives.init_process_group()")
    timeout_s = 8
    t0 = time.time()
    proc = subprocess.run(
        [sys.executable, "-c", body],
        env=_worker_env(MXTPU_COORDINATOR="127.0.0.1:%d" % port,
                        MXTPU_NUM_WORKERS="2", MXTPU_PROCESS_ID="1",
                        MXTPU_RENDEZVOUS_TIMEOUT=str(timeout_s),
                        PYTHONPATH=_ROOT),
        capture_output=True, text=True, timeout=180)
    wall = time.time() - t0
    out = proc.stdout + proc.stderr
    assert proc.returncode != 0, out
    assert "MXNetError" in out and "rendezvous failed" in out, out[-2000:]
    # margin: interpreter + jax import dominate; the dial itself is bounded
    assert wall < timeout_s + 60, "took %.0fs" % wall


def test_kill_worker_resume_equivalence(tmp_path):
    """THE acceptance test: rank 1 is killed at step 7 of 12; the launcher
    restarts the group; generation 1 auto-resumes from the last atomic
    checkpoint (step 6) and final weights match an uninterrupted run."""
    _require_group_support()

    def run(ckpt_dir, fault=None, max_restarts=0):
        extra = {"MXTPU_CKPT_DIR": str(ckpt_dir), "PYTHONPATH": _ROOT}
        if fault:
            extra["MXTPU_FAULT_INJECT"] = fault
        cmd = [sys.executable, _LAUNCH, "-n", "2"]
        if max_restarts:
            cmd += ["--max-restarts", str(max_restarts),
                    "--restart-backoff", "0.2"]
        cmd += ["--", sys.executable, _WORKER]
        proc = subprocess.run(cmd, env=_worker_env(**extra),
                              capture_output=True, text=True, timeout=420)
        return proc, proc.stdout + proc.stderr

    proc_a, out_a = run(tmp_path / "a")
    assert proc_a.returncode == 0, out_a[-4000:]
    sums_a = dict(re.findall(
        r"RESILIENCE_OK rank=(\d)/2 gen=0 steps=12 wsum=(-?[\d.]+)", out_a))
    assert set(sums_a) == {"0", "1"}, out_a[-4000:]
    assert len(set(sums_a.values())) == 1, sums_a

    proc_b, out_b = run(tmp_path / "b", fault="kill@step=7,rank=1",
                        max_restarts=2)
    assert proc_b.returncode == 0, out_b[-4000:]
    # generation 0 died and generation 1 resumed from the checkpoint
    assert "spawning generation 1" in out_b, out_b[-4000:]
    resumed = re.findall(r"RESILIENCE_RESUMED rank=\d gen=1 from_step=(\d+)",
                         out_b)
    assert resumed and all(s == "6" for s in resumed), out_b[-4000:]
    sums_b = dict(re.findall(
        r"RESILIENCE_OK rank=(\d)/2 gen=1 steps=12 wsum=(-?[\d.]+)", out_b))
    assert set(sums_b) == {"0", "1"}, out_b[-4000:]
    # resumed run converges to the SAME weights as the uninterrupted run
    assert set(sums_b.values()) == set(sums_a.values()), (sums_a, sums_b)


def test_module_fit_auto_resume(tmp_path):
    """module.fit(checkpoint_dir=..., resume='auto'): a second fit picks up
    at the saved epoch cursor and reproduces the uninterrupted model."""
    import mxnet_tpu.symbol as S

    def mlp():
        x = S.Variable("data")
        h = S.FullyConnected(x, num_hidden=8, name="fc1")
        h = S.Activation(h, act_type="relu")
        h = S.FullyConnected(h, num_hidden=2, name="fc2")
        return S.SoftmaxOutput(h, name="softmax")

    rng = np.random.RandomState(0)
    X = rng.uniform(-1, 1, (128, 6)).astype(np.float32)
    Y = (X.sum(axis=1) > 0).astype(np.float32)

    def fit(ckpt_dir, num_epoch, resume=None):
        # identical init draws for every fit() call: resume-equivalence
        # compares a fresh 4-epoch run against a 2-epoch + resumed run
        mx.random.seed(42)
        np.random.seed(42)
        train = mx.io.NDArrayIter(X, Y, batch_size=32,
                                  label_name="softmax_label")
        mod = mx.mod.Module(mlp(), context=mx.cpu())
        mod.fit(train, num_epoch=num_epoch, optimizer="sgd",
                optimizer_params={"learning_rate": 0.1},
                checkpoint_dir=str(ckpt_dir), resume=resume)
        return mod.get_params()[0]

    # uninterrupted 4-epoch run
    ref = fit(tmp_path / "ref", 4)
    # interrupted: 2 epochs, then resume to 4 in a fresh Module
    fit(tmp_path / "resume", 2)
    mgr = CheckpointManager(str(tmp_path / "resume"))
    assert mgr.latest()[0] == 1  # epochs 0..1 done, newest ckpt at epoch 1
    got = fit(tmp_path / "resume", 4, resume="auto")
    for k in ref:
        np.testing.assert_allclose(got[k].asnumpy(), ref[k].asnumpy(),
                                   rtol=1e-6, atol=1e-7, err_msg=k)
