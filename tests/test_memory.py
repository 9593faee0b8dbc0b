"""Memory observability (telemetry/memory.py, docs/observability.md §Memory).

Coverage map:
  * gauge/snapshot contract — enabled vs MXTPU_TELEMETRY=0 (subprocess),
    NDArray live accounting, budget parsing units;
  * signal-safety — a SIGUSR1 dump from a live process carries the memory
    snapshot (acceptance criterion: every hang/OOM dump says what was
    resident), and the mxlint signal-safety walk covers memory.py;
  * per-executable attribution — artifact-header roundtrip of
    memory_analysis figures across the persistent tier, including a
    zero-compile reload in a second registry;
  * serving budget — over-budget load rejected with the typed
    MemoryBudgetError (507), warn: mode publishes, within-budget load
    publishes with a footprint in describe();
  * donation verifier — positive (aliasable donated buffer) and negative
    (donation XLA cannot alias) cases through the registry fill hook.
"""
import json
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT) if _ROOT not in sys.path else None

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import nd  # noqa: E402
from mxnet_tpu import compile as mxc  # noqa: E402
from mxnet_tpu.telemetry import memory  # noqa: E402


def _clean_env(**extra):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    env.pop("MXTPU_TELEMETRY_DIR", None)
    env.pop("MXTPU_SERVE_MEMORY_BUDGET", None)
    env["PYTHONPATH"] = _ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env.update(extra)
    return env


# ---------------------------------------------------------------------------
# units: live accounting, budget parsing, figures math
# ---------------------------------------------------------------------------

def test_ndarray_live_accounting():
    import gc

    count0, bytes0 = memory.ndarray_live()
    a = nd.zeros((256,), dtype="float32")        # 1024 bytes
    b = nd.zeros((128,), dtype="float32")        # 512 bytes
    count1, bytes1 = memory.ndarray_live()
    assert count1 - count0 >= 2
    assert bytes1 - bytes0 >= 1024 + 512
    # buffer swap to a different size adjusts bytes, not count
    a._set_data(b._data)
    count2, bytes2 = memory.ndarray_live()
    assert count2 == count1
    assert bytes2 == bytes1 - 512
    del a, b
    gc.collect()
    count3, bytes3 = memory.ndarray_live()
    assert count3 <= count1 - 2
    assert bytes3 <= bytes2 - 1024


def test_process_memory_and_sample():
    proc = memory.read_process_memory()
    assert proc is not None and proc.get("rss", 0) > 0
    assert proc.get("vmhwm", 0) > 0  # /proc or getrusage fallback
    out = memory.sample()
    assert out is not None
    snap = mx.telemetry.snapshot()
    assert snap["mxtpu_process_rss_bytes"]["value"] > 0
    assert snap["mxtpu_ndarray_live"]["value"] >= 0


def test_parse_bytes_and_budget(monkeypatch):
    assert memory.parse_bytes("1024") == 1024
    assert memory.parse_bytes("512K") == 512 << 10
    assert memory.parse_bytes("1.5G") == int(1.5 * (1 << 30))
    assert memory.parse_bytes("24g") == 24 << 30
    assert memory.parse_bytes("junk") is None
    monkeypatch.delenv("MXTPU_SERVE_MEMORY_BUDGET", raising=False)
    assert memory.serve_memory_budget() == (None, False)
    monkeypatch.setenv("MXTPU_SERVE_MEMORY_BUDGET", "2M")
    assert memory.serve_memory_budget() == (2 << 20, False)
    monkeypatch.setenv("MXTPU_SERVE_MEMORY_BUDGET", "warn:2M")
    assert memory.serve_memory_budget() == (2 << 20, True)
    monkeypatch.setenv("MXTPU_SERVE_MEMORY_BUDGET", "garbage")
    assert memory.serve_memory_budget() == (None, False)


def test_figures_math():
    a = {"arguments": 100, "outputs": 10, "temp": 50, "generated_code": 5,
         "alias": 0}
    b = {"arguments": 200, "outputs": 20, "temp": 80}
    s = memory.sum_figures([a, b])
    assert s["arguments"] == 300 and s["temp"] == 130
    # footprint subtracts aliased (donated) bytes arguments+outputs count twice
    assert memory.footprint_bytes({"arguments": 100, "outputs": 100,
                                   "temp": 10, "alias": 100}) == 110
    # model footprint: one weight copy (max arguments) + per-bucket privates
    fp = memory.model_footprint({1: a, 2: b})
    assert fp == 200 + (10 + 50 + 5) + (20 + 80)


def test_snapshot_shape():
    snap = memory.snapshot()
    assert set(snap) >= {"process", "devices", "ndarray",
                         "executables_by_temp", "donation"}
    assert snap["ndarray"]["live"] >= 0


def test_disabled_is_noop_subprocess():
    """MXTPU_TELEMETRY=0 turns the whole layer into no-ops: no gauges
    published, live accounting parked at zero, sample() returns None."""
    body = (
        "import mxnet_tpu as mx\n"
        "from mxnet_tpu import nd\n"
        "from mxnet_tpu.telemetry import memory\n"
        "a = nd.zeros((1024,))\n"
        "assert memory.ndarray_live() == (0, 0), memory.ndarray_live()\n"
        "assert memory.sample() is None\n"
        "assert memory.observe_step_delta() is None\n"
        "snap = mx.telemetry.snapshot()\n"
        "assert 'mxtpu_process_rss_bytes' not in snap, sorted(snap)\n"
        "print('DISABLED_OK')\n"
    )
    out = subprocess.run([sys.executable, "-c", body],
                         env=_clean_env(MXTPU_TELEMETRY="0"),
                         capture_output=True, text=True, timeout=120)
    assert "DISABLED_OK" in out.stdout, out.stdout + out.stderr


# ---------------------------------------------------------------------------
# signal safety + the dump's memory block (acceptance)
# ---------------------------------------------------------------------------

def test_sigusr1_dump_contains_memory_snapshot(tmp_path):
    """Acceptance: a SIGUSR1 dump from a hung run contains the memory
    snapshot — RSS gauges, NDArray live accounting and the top-N
    executables — without killing the process."""
    if not hasattr(signal, "SIGUSR1"):
        pytest.skip("no SIGUSR1 on this platform")
    body = (
        "import time\n"
        "import mxnet_tpu.telemetry as t\n"
        "from mxnet_tpu import nd\n"
        "keep = [nd.zeros((4096,)) for _ in range(4)]\n"
        "x = nd.zeros((64, 64))\n"
        "y = (x * 2 + 1).asnumpy()  # fills an executable via the registry\n"
        "t.record_step(7)\n"
        "print('READY', flush=True)\n"
        "time.sleep(120)\n"
    )
    proc = subprocess.Popen(
        [sys.executable, "-c", body],
        env=_clean_env(MXTPU_TELEMETRY_DIR=str(tmp_path)),
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
    try:
        line = proc.stdout.readline()
        assert "READY" in line, line
        proc.send_signal(signal.SIGUSR1)
        dump = os.path.join(str(tmp_path),
                            "flightrec-rank0-pid%d.json" % proc.pid)
        deadline = time.time() + 30
        while time.time() < deadline and not os.path.exists(dump):
            assert proc.poll() is None, "process died on SIGUSR1"
            time.sleep(0.1)
        assert os.path.exists(dump), os.listdir(str(tmp_path))
        data = json.load(open(dump))
        mem = data["memory"]
        assert mem["process"]["rss"] > 0
        assert mem["ndarray"]["live"] >= 5
        assert mem["ndarray"]["live_bytes"] >= 4 * 4096 * 4
        assert isinstance(mem["executables_by_temp"], list)
        assert proc.poll() is None  # dump-on-signal, not die-on-signal
    finally:
        proc.kill()
        proc.wait(timeout=30)


def test_mxlint_signal_safety_walks_memory_module():
    """The dump path's new memory.snapshot() leg stays signal-safe: the
    mxlint walker covers telemetry/memory.py and the real tree is clean
    for the rule."""
    from ci.mxlint import Repo
    from ci.mxlint.checkers.signal_safety import (_SCOPE_FILES,
                                                  SignalSafetyChecker)

    assert "mxnet_tpu/telemetry/memory.py" in _SCOPE_FILES
    findings = [f for f in SignalSafetyChecker().run(Repo(_ROOT))
                if "memory" in f.path]
    assert findings == [], [str(f) for f in findings]


# ---------------------------------------------------------------------------
# per-executable attribution: artifact-header roundtrip
# ---------------------------------------------------------------------------

def test_artifact_header_memory_roundtrip(tmp_path):
    """AOT fills persist their memory_analysis figures in the MXTPUEXE1
    header; a second registry (cold memory tier, warm disk tier) reads
    them back WITHOUT compiling and re-records attribution."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.compile import persist
    from mxnet_tpu.compile.registry import Registry

    d = str(tmp_path / "cache")
    os.makedirs(os.path.join(d, "objects"), exist_ok=True)
    key = mxc.ExecutableKey("op", "memtest", shapes=((64, 64), "float32"))
    args = (jnp.zeros((64, 64)),)

    reg1 = Registry(persist_dir=d)
    mark = memory.recorded_mark()
    fn = reg1.get_or_build(key, lambda: jax.jit(lambda x: (x @ x) * 2),
                           label="memtest", example_args=args)
    np.testing.assert_allclose(np.asarray(fn(*args)), np.zeros((64, 64)))
    recorded = memory.recorded_since(mark)
    assert recorded and recorded[0]["arguments"] > 0

    # the header carries the figures
    digest = key.digest(jax.default_backend(), jax.__version__)
    header = persist.read_header(persist.artifact_path(d, digest))
    assert header["memory"]["arguments"] == recorded[0]["arguments"]
    assert set(header["memory"]) >= {"arguments", "outputs", "temp"}

    # zero-compile reload in a fresh registry still knows the footprint
    reg2 = Registry(persist_dir=d)
    mark2 = memory.recorded_mark()
    fn2 = reg2.get_or_build(key, lambda: jax.jit(lambda x: (x @ x) * 2),
                            label="memtest", example_args=args)
    np.testing.assert_allclose(np.asarray(fn2(*args)), np.zeros((64, 64)))
    again = memory.recorded_since(mark2)
    assert again and again[0]["arguments"] == recorded[0]["arguments"]
    # attribution is reachable by key for the touch-bracket reload path
    assert memory.lookup_key(key) is not None


def test_touch_bracket_attributes_memory_tier_hits(tmp_path):
    """A warm over already-resident executables (pure memory-tier hits,
    zero fills) still attributes figures via the registry touch log."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.compile.registry import Registry

    d = str(tmp_path / "cache")
    os.makedirs(os.path.join(d, "objects"), exist_ok=True)
    key = mxc.ExecutableKey("op", "touchtest", shapes=((32,), "float32"))
    args = (jnp.zeros((32,)),)
    reg = Registry(persist_dir=d)
    reg.get_or_build(key, lambda: jax.jit(lambda x: x + 1),
                     label="touchtest", example_args=args)
    # second resolution: a hit — no fill, but the bracket sees the key
    mark = memory.recorded_mark()
    reg.begin_touch_log()
    try:
        assert reg.lookup(key) is not None
    finally:
        touched = reg.end_touch_log()
    figures = memory.bucket_figures(touched, memory.recorded_since(mark))
    assert figures.get("arguments", 0) > 0


# ---------------------------------------------------------------------------
# serving memory budget
# ---------------------------------------------------------------------------

@pytest.fixture
def _mlp_artifact(tmp_path):
    from mxnet_tpu.gluon import nn

    net = nn.HybridSequential()
    net.add(nn.Dense(64, activation="relu"), nn.Dense(8))
    net.initialize()
    net(nd.zeros((2, 16)))
    prefix = str(tmp_path / "mlp")
    net.export(prefix, epoch=0)
    return prefix


def test_serving_memory_budget(monkeypatch, tmp_path, _mlp_artifact):
    """In-process load path: footprint computed from the warm's figures;
    over-budget rejected with the typed 507; warn: publishes; generous
    budget publishes."""
    from mxnet_tpu.serving import MemoryBudgetError, ModelRepository

    monkeypatch.setenv("MXTPU_COMPILE_CACHE", str(tmp_path / "cache"))
    monkeypatch.delenv("MXTPU_SERVE_MEMORY_BUDGET", raising=False)
    repo = ModelRepository()
    m = repo.load("m", _mlp_artifact, input_shapes={"data": (16,)},
                  max_batch=4)
    footprint = m.memory_bytes
    assert footprint and footprint > 0
    desc = m.describe()["memory"]
    assert desc["total_bytes"] == footprint
    assert set(desc["per_bucket"]) == {"1", "2", "4"}
    assert all(f["arguments"] > 0 for f in desc["per_bucket"].values())
    repo.unload("m", timeout=10)

    monkeypatch.setenv("MXTPU_SERVE_MEMORY_BUDGET", str(footprint // 2))
    with pytest.raises(MemoryBudgetError) as exc:
        repo.load("m", _mlp_artifact, input_shapes={"data": (16,)},
                  max_batch=4)
    assert exc.value.status == 507
    assert "m" not in repo.names()  # rejected loads never publish

    monkeypatch.setenv("MXTPU_SERVE_MEMORY_BUDGET",
                       "warn:%d" % (footprint // 2))
    m2 = repo.load("m", _mlp_artifact, input_shapes={"data": (16,)},
                   max_batch=4)
    assert m2.memory_bytes == footprint  # canary mode still published
    repo.unload("m", timeout=10)

    monkeypatch.setenv("MXTPU_SERVE_MEMORY_BUDGET", str(footprint * 3))
    m3 = repo.load("m", _mlp_artifact, input_shapes={"data": (16,)},
                   max_batch=4)
    assert m3.memory_bytes == footprint
    repo.unload("m", timeout=10)


def test_pooled_footprint_counts_replica_copies(monkeypatch):
    """Each replica process holds a full copy of weights + executables,
    so a pooled model's budget charge and gauge are footprint × N."""
    from mxnet_tpu.serving import MemoryBudgetError, ModelRepository
    from mxnet_tpu.serving.model_repository import ServedModel

    figures = {"arguments": 1000, "outputs": 100, "temp": 200,
               "generated_code": 0, "alias": 0}

    def stub_runner(arrays, bucket, n):
        return [np.zeros((n, 1), np.float32)]

    m = ServedModel("pooledstub", 1, stub_runner, [1], {"data": (1,)},
                    meta={"replicas": 3})
    m.set_bucket_memory({1: figures})
    per_copy = memory.model_footprint({1: figures})
    assert m.memory_bytes == per_copy
    assert m.resident_copies == 3
    assert m.effective_memory_bytes == 3 * per_copy
    desc = m.describe()["memory"]
    assert desc["copies"] == 3 and desc["effective_bytes"] == 3 * per_copy
    # admission charges the effective figure: 2 copies fit, 3 do not
    repo = ModelRepository()
    monkeypatch.setenv("MXTPU_SERVE_MEMORY_BUDGET", str(2 * per_copy))
    with pytest.raises(MemoryBudgetError) as exc:
        repo.add(m)
    assert "x 3 replica" in str(exc.value)
    assert "pooledstub" not in repo.names()
    m.close(drain=False, timeout=0)


def test_budget_counts_resident_models(monkeypatch, tmp_path,
                                       _mlp_artifact):
    """The budget is cumulative: a second model that would overflow the
    remaining headroom is rejected even though it fits alone."""
    from mxnet_tpu.serving import MemoryBudgetError, ModelRepository

    monkeypatch.setenv("MXTPU_COMPILE_CACHE", str(tmp_path / "cache"))
    monkeypatch.delenv("MXTPU_SERVE_MEMORY_BUDGET", raising=False)
    repo = ModelRepository()
    m = repo.load("a", _mlp_artifact, input_shapes={"data": (16,)},
                  max_batch=4)
    footprint = m.memory_bytes
    assert footprint
    monkeypatch.setenv("MXTPU_SERVE_MEMORY_BUDGET",
                       str(int(footprint * 1.5)))
    with pytest.raises(MemoryBudgetError):
        repo.load("b", _mlp_artifact, input_shapes={"data": (16,)},
                  max_batch=4)
    repo.unload("a", timeout=10)


# ---------------------------------------------------------------------------
# donation verifier
# ---------------------------------------------------------------------------

def test_donation_verifier_positive():
    """A donated buffer XLA can alias verifies at ~100%."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.compile.registry import Registry

    key = mxc.ExecutableKey("dist_step", "don_pos",
                            shapes=((128, 128), "float32"),
                            donation=(0,), sharded=True, no_persist=True)
    reg = Registry()
    args = (jax.ShapeDtypeStruct((128, 128), "float32"),
            jax.ShapeDtypeStruct((128, 128), "float32"))
    reg.get_or_build(
        key,
        lambda: jax.jit(lambda w, x: (w + 0.1 * x, (x * 2).sum()),
                        donate_argnums=(0,)),
        label="don_pos", example_args=args)
    rep = memory.last_donation_report()
    assert rep is not None and rep["kind"] == "dist_step"
    assert rep["declared_bytes"] == 128 * 128 * 4
    assert rep["aliased_fraction"] >= 0.99 and rep["ok"]


def test_donation_verifier_negative():
    """A donation XLA cannot alias (dtype change blocks reuse) is flagged:
    aliased fraction ~0, ok=False, and the donation_unaliased event
    lands in the flight-recorder ring."""
    import jax

    from mxnet_tpu import telemetry
    from mxnet_tpu.compile.registry import Registry

    key = mxc.ExecutableKey("dist_step", "don_neg",
                            shapes=((64, 64), "float32"),
                            donation=(0,), sharded=True, no_persist=True)
    reg = Registry()
    args = (jax.ShapeDtypeStruct((64, 64), "float32"),
            jax.ShapeDtypeStruct((64, 64), "float32"))
    reg.get_or_build(
        key,
        lambda: jax.jit(
            lambda w, x: ((w + x).astype("bfloat16"), (x * 2).sum()),
            donate_argnums=(0,)),
        label="don_neg", example_args=args)
    rep = memory.last_donation_report()
    assert rep is not None and rep["declared_bytes"] == 64 * 64 * 4
    assert rep["aliased_fraction"] < 0.5 and not rep["ok"]
    events = [e for e in telemetry.events()
              if e["event"] == "donation_unaliased"]
    assert events and events[-1]["fields"]["key_kind"] == "dist_step"


@pytest.mark.parametrize("kind,temp,fires", [
    ("lm_decode", 32768, True),         # a whole pool of temporaries
    ("lm_prefill", 8193, True),         # just over a quarter of it
    ("lm_decode", 8192, False),         # a quarter exactly: in place
    ("dist_step", 32768, False),        # not an engine executable
])
def test_kv_pool_in_place_gauge_and_event(kind, temp, fires):
    """The decode engine's executables publish their temporaries at fill
    (`mxtpu_serve_exe_temp_bytes{kind}`), and temporaries over a quarter
    of the donated KV pool record `kv_pool_not_in_place`: figures faked
    large, since a sound engine program never shows them."""
    import jax

    from mxnet_tpu import telemetry

    key = mxc.ExecutableKey(kind, "pool_%s_%d" % (kind, temp),
                            donation=(1,), no_persist=True)
    pool = ((jax.ShapeDtypeStruct((8, 8, 64), "float32"),
             jax.ShapeDtypeStruct((8, 8, 64), "float32")),)   # 32 KiB
    before = len([e for e in telemetry.events()
                  if e["event"] == "kv_pool_not_in_place"])
    rep = memory.verify_donation(key, (None, pool),
                                 {"alias": 32768, "temp": temp})
    assert rep["ok"] and rep["declared_bytes"] == 32768
    gauge = 'mxtpu_serve_exe_temp_bytes{kind="%s"}' % kind
    events = [e for e in telemetry.events()
              if e["event"] == "kv_pool_not_in_place"]
    if kind == "dist_step":
        assert gauge not in telemetry.snapshot() and "temp_bytes" not in rep
    else:
        assert telemetry.snapshot()[gauge]["value"] == temp
        assert rep["temp_bytes"] == temp
    assert len(events) - before == (1 if fires else 0)
    if fires:
        f = events[-1]["fields"]
        assert (f["key_kind"], f["temp_bytes"], f["kv_bytes"]) == (
            kind, temp, 32768)


def test_engine_fill_publishes_pool_temporaries():
    """A real engine fill goes through the same hook: the tiny engine's
    decode and prefill programs report temporaries far under a quarter of
    its pool and no event."""
    from mxnet_tpu import telemetry
    from mxnet_tpu.gluon.model_zoo.transformer import lm_mini
    from mxnet_tpu.serving import TransformerLMEngine

    lm = lm_mini(vocab_size=64)
    lm.initialize()
    eng = TransformerLMEngine(lm=lm, num_pages=64, page_size=8,
                              max_prompt=8, max_new_tokens=8,
                              decode_buckets=[2], prefill_buckets=[8])
    before = len([e for e in telemetry.events()
                  if e["event"] == "kv_pool_not_in_place"])
    eng.warm()
    snap = telemetry.snapshot()
    for kind in ("lm_prefill", "lm_decode"):
        temp = snap['mxtpu_serve_exe_temp_bytes{kind="%s"}' % kind]["value"]
        assert 0 <= temp < eng.kv_bytes() // 4, (kind, temp)
        assert snap['mxtpu_donation_alias_bytes{kind="%s"}' % kind][
            "value"] >= eng.kv_bytes()
    assert len([e for e in telemetry.events()
                if e["event"] == "kv_pool_not_in_place"]) == before


def test_distributed_trainer_step_verifies_donation():
    """The real fused-step fill runs the verifier: donated param +
    optimizer buffers are fully aliased (ROADMAP item 1's invariant)."""
    from mxnet_tpu.gluon import loss as gloss, nn
    from mxnet_tpu.parallel import DistributedTrainer, make_mesh

    net = nn.HybridSequential()
    net.add(nn.Dense(32, activation="relu"), nn.Dense(4))
    net.initialize()
    net(nd.zeros((4, 8)))
    tr = DistributedTrainer(net, "sgd", {"learning_rate": 0.1},
                            loss=gloss.SoftmaxCrossEntropyLoss(),
                            mesh=make_mesh([("dp", -1)]))
    x = nd.array(np.random.RandomState(0).rand(8, 8).astype("float32"))
    y = nd.array(np.arange(8) % 4)
    tr.step(x, y)
    rep = memory.last_donation_report()
    assert rep is not None and rep["kind"] == "dist_step"
    assert rep["ok"], rep
    # the fused step's figures landed in the executable table
    kinds = {e["kind"] for e in memory.executables_top(20)}
    assert "dist_step" in kinds

