"""Training goodput accounting tests (ISSUE 18 acceptance):

  * unit: the step bracket's phase accounting is exhaustive (phases sum to
    wall, `other` absorbs the remainder, never negative), nested phases
    don't double-count, a stale bracket from a raised step is replaced,
    out-of-step attribution reduces the `between_steps` gap, finalize()
    salvages an abandoned bracket at exit;
  * wiring: the fused ShardedTrainer path and module.fit both publish
    `mxtpu_step_phase_seconds` / `mxtpu_goodput_*` — and module.fit's
    legacy two-phase split (mxtpu_data_wait_seconds_total{src=fit})
    agrees with the goodput attributor's data_wait within 10%;
  * checkpoint stalls land in the `checkpoint_stall` phase under both
    MXTPU_CKPT_ASYNC=0 (full blocking write) and =1 (submit only);
  * surfaces: /statusz gains a `training` block, flight-recorder dumps
    carry a `goodput` payload, MXTPU_SLO_GOODPUT_FLOOR registers the
    gauge-floor objective;
  * tools/goodput_report.py: synthetic-ledger unit (coverage segments,
    preempt labeling, problem detection) and the END-TO-END: a 2-process
    tools/launch.py run with `preempt@step=` fault injection whose report
    decomposes >=90% of each generation's wall and labels the preempt
    downtime (`--check` contract).
"""
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import mxnet_tpu as mx  # noqa: F401  (conftest pins CPU before jax loads)
from mxnet_tpu import telemetry
from mxnet_tpu.telemetry import goodput

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_LAUNCH = os.path.join(_ROOT, "tools", "launch.py")
_EWORKER = os.path.join(_ROOT, "tests", "elastic_worker.py")


def _tools():
    sys.path.insert(0, os.path.join(_ROOT, "tools"))
    try:
        import goodput_report
    finally:
        sys.path.pop(0)
    return goodput_report


@pytest.fixture(autouse=True)
def _fresh_accountant():
    goodput._reset_for_tests()
    # materialize the metric handles so totals() reads the registry's
    # cumulative values from the start — deltas in these tests would
    # otherwise swallow counts published by earlier tests in the process
    if goodput._enabled():
        goodput._metrics()
    yield
    goodput._reset_for_tests()


def _phases_delta(before):
    t = goodput.totals()
    return {p: round(v - before["phases"].get(p, 0.0), 6)
            for p, v in t["phases"].items()
            if v - before["phases"].get(p, 0.0) > 1e-9}


# --------------------------------------------------------------------------
# unit: the step bracket
# --------------------------------------------------------------------------

def test_phases_exhaustive_and_sum_to_wall():
    goodput.step_start(kind="unit")
    with goodput.phase("data_wait"):
        time.sleep(0.02)
    goodput.mark_launch()
    with goodput.phase("compute"):
        time.sleep(0.03)
    time.sleep(0.01)  # unattributed -> `other`
    out = goodput.step_end(step=1)
    wall = out.pop("wall")
    assert set(out) <= set(goodput.PHASES)
    assert abs(sum(out.values()) - wall) < 1e-9  # exhaustive by contract
    assert out["data_wait"] >= 0.02
    assert out["compute"] >= 0.03
    assert out["other"] >= 0.009
    assert all(v >= 0.0 for v in out.values())


def test_nested_phase_not_double_counted():
    goodput.step_start(kind="unit")
    with goodput.phase("compute"):
        # an op resolving through the compile registry mid-step
        with goodput.phase("compile"):
            time.sleep(0.03)
        time.sleep(0.01)
    out = goodput.step_end()
    assert out["compile"] >= 0.03
    # outer `compute` kept only its own slice, not the nested compile
    assert out["compute"] < 0.025
    assert abs(sum(v for p, v in out.items() if p != "wall")
               - out["wall"]) < 1e-9


def test_mark_launch_claims_host_dispatch():
    goodput.step_start(kind="unit")
    time.sleep(0.02)  # Python glue before the executable launches
    goodput.mark_launch()
    goodput.mark_launch()  # idempotent: second call must not re-claim
    with goodput.phase("compute"):
        time.sleep(0.01)
    out = goodput.step_end()
    assert out["host_dispatch"] >= 0.018
    assert out["host_dispatch"] < 0.05


def test_stale_bracket_from_raised_step_is_replaced():
    goodput.step_start(kind="unit")
    with goodput.phase("compute"):
        time.sleep(0.05)
    # the step raised before step_end; the NEXT step must not inherit it
    goodput.step_start(kind="unit")
    time.sleep(0.01)
    out = goodput.step_end()
    assert out["wall"] < 0.04  # the abandoned 0.05s did not leak in
    assert "compute" not in out


def test_out_of_step_add_reduces_between_steps_gap():
    goodput.step_start(kind="unit")
    time.sleep(0.005)
    goodput.step_end()
    before = goodput.totals()
    time.sleep(0.04)  # idle between steps...
    goodput.add("checkpoint_stall", 0.015)  # ...partly claimed by a stall
    goodput.step_start(kind="unit")
    time.sleep(0.005)
    goodput.step_end()
    d = _phases_delta(before)
    assert d.get("checkpoint_stall", 0.0) >= 0.015
    # the between_steps gap is the idle MINUS the claimed stall
    assert 0.0 < d.get("between_steps", 0.0) < 0.04


def test_finalize_salvages_abandoned_bracket():
    goodput.step_start(kind="unit")
    with goodput.phase("collective"):  # e.g. blocked on a dead peer
        time.sleep(0.02)
    before = goodput.totals()
    goodput.finalize()
    after = goodput.totals()
    assert after["phases"].get("collective", 0.0) \
        - before["phases"].get("collective", 0.0) >= 0.02
    assert after["wall"] > before["wall"]
    goodput.finalize()  # idempotent: no bracket left
    assert goodput.totals() == after


def test_disabled_is_inert(monkeypatch):
    monkeypatch.setenv("MXTPU_GOODPUT", "0")
    before = goodput.totals()
    goodput.step_start(kind="unit")
    with goodput.phase("compute"):
        time.sleep(0.005)
    assert goodput.step_end() is None
    assert goodput.totals() == before  # nothing published
    block = goodput.statusz_block()
    assert block["enabled"] is False


# --------------------------------------------------------------------------
# checkpoint stalls
# --------------------------------------------------------------------------

@pytest.mark.parametrize("async_on", ["0", "1"])
def test_checkpoint_stall_attribution(tmp_path, monkeypatch, async_on):
    from mxnet_tpu.parallel.resilience import CheckpointManager

    monkeypatch.setenv("MXTPU_CKPT_ASYNC", async_on)
    payload = {"w": np.random.RandomState(0).standard_normal(1 << 16)}
    before = goodput.totals()
    goodput.step_start(kind="unit")
    mgr = CheckpointManager(str(tmp_path), keep_last=2)
    mgr.save_sharded_async(1, payload, rank=0, world_size=1)
    out = goodput.step_end()
    mgr.close()
    assert out.get("checkpoint_stall", 0.0) > 0.0
    d = _phases_delta(before)
    assert d.get("checkpoint_stall", 0.0) > 0.0


# --------------------------------------------------------------------------
# trainer wiring
# --------------------------------------------------------------------------

def test_sharded_trainer_publishes_goodput():
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon import nn, loss as gloss

    ctx = mx.cpu()
    with ctx:
        net = nn.HybridSequential(prefix="gp_")
        with net.name_scope():
            net.add(nn.Dense(16, activation="relu", prefix="fc1_"))
            net.add(nn.Dense(4, prefix="fc2_"))
        net.initialize(ctx=ctx)
    x = mx.nd.array(np.random.RandomState(0)
                    .uniform(-1, 1, (8, 8)).astype(np.float32))
    y = mx.nd.array(np.random.RandomState(1)
                    .randint(0, 4, (8,)).astype(np.float32))
    net(x)
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.05}, sharded=True, block=net,
                       loss=gloss.SoftmaxCrossEntropyLoss())
    before = goodput.totals()
    for _ in range(3):
        tr.step_batch(x, y).asnumpy()
    d = _phases_delta(before)
    assert d.get("compute", 0.0) > 0.0
    snap = telemetry.snapshot()
    hist = snap.get('mxtpu_step_phase_seconds{phase="compute"}')
    assert hist and hist.get("count", 0) >= 3
    frac = snap.get("mxtpu_goodput_fraction")
    assert frac and 0.0 < frac["value"] <= 1.0


def test_fit_wiring_agrees_with_legacy_split():
    X = np.random.RandomState(0).uniform(-1, 1, (512, 16)) \
        .astype(np.float32)
    Y = np.random.RandomState(1).randint(0, 4, (512,)).astype(np.float32)
    data = mx.sym.var("data")
    sym = mx.sym.FullyConnected(data, num_hidden=16, name="gfit_fc1")
    sym = mx.sym.SoftmaxOutput(sym, name="softmax")
    it = mx.io.NDArrayIter(X, Y, batch_size=64, shuffle=True,
                           label_name="softmax_label")

    def fit_wait():
        s = telemetry.snapshot()
        rec = s.get('mxtpu_data_wait_seconds_total{src="fit"}') or {}
        return float(rec.get("value") or 0.0)

    w0 = fit_wait()
    before = goodput.totals()
    mod = mx.mod.Module(sym, context=mx.cpu())
    mod.fit(it, num_epoch=3, optimizer="sgd",
            optimizer_params={"learning_rate": 0.1})
    d = _phases_delta(before)
    legacy_wait = fit_wait() - w0
    assert d.get("compute", 0.0) > 0.0
    # the two accountants measure the same iterator wait independently
    assert legacy_wait > 0.0
    assert abs(d.get("data_wait", 0.0) - legacy_wait) <= 0.1 * legacy_wait


# --------------------------------------------------------------------------
# surfaces: /statusz, dumps, SLO floor
# --------------------------------------------------------------------------

def test_statusz_training_block():
    from mxnet_tpu.telemetry import slo

    goodput.step_start(kind="unit")
    with goodput.phase("compute"):
        time.sleep(0.01)
    goodput.step_end()
    payload = slo.statusz_payload()
    block = payload.get("training")
    assert block and block["enabled"]
    assert block["window_steps"] == 1
    assert 0.0 < block["goodput_fraction"] <= 1.0
    assert block["totals"]["wall"] > 0.0


def test_dump_contains_goodput(tmp_path):
    from mxnet_tpu.telemetry import recorder

    goodput.step_start(kind="unit")
    with goodput.phase("data_wait"):
        time.sleep(0.01)
    goodput.step_end()
    path = recorder.dump("goodput-test", path=str(tmp_path / "dump.json"))
    with open(path) as f:
        payload = json.load(f)
    block = payload["goodput"]
    assert block["window_steps"] == 1
    assert block["top_stall_phase"] == "data_wait"
    assert block["totals"]["phases"]["data_wait"] >= 0.01


def test_slo_goodput_floor_objective(monkeypatch):
    from mxnet_tpu.telemetry import slo

    monkeypatch.setenv("MXTPU_SLO_GOODPUT_FLOOR", "0.5")
    slo._STATE.wired_train.discard("gp_test")
    slo.wire_training("gp_test")
    try:
        by_name = {o.name: o for o in slo.objectives()}
        obj = by_name.get("train-goodput-floor")
        assert obj is not None
        assert obj.kind == "gauge_floor"
        assert obj.metric == "mxtpu_goodput_fraction"
        assert obj.threshold == 0.5
    finally:
        slo._STATE.objectives.pop("train-goodput-floor", None)
        slo._STATE.wired_train.discard("gp_test")


# --------------------------------------------------------------------------
# the start-up account (ISSUE 46): ring ``startup`` of whole span records
# --------------------------------------------------------------------------

def _spans(*names):
    recs = goodput.window("startup")
    return [r for r in recs if not names or r["name"] in names]


def test_spans_nest_name_their_parent_and_give_self_time():
    with goodput.span("engine_build", pool_bytes=7) as outer:
        time.sleep(0.02)
        with goodput.span("program", label="p") as inner:
            time.sleep(0.03)
            inner.fields["tier"] = "persist_hit"    # writable until the end
    child, parent = _spans()
    assert (child["name"], parent["name"]) == ("program", "engine_build")
    assert child["parent"] == parent["id"] and parent["parent"] is None
    assert parent["t0"] <= child["t0"] <= child["t1"] <= parent["t1"]
    assert parent["pool_bytes"] == 7 and child["tier"] == "persist_hit"
    assert not child["after_ready"]
    # nothing was subtracted when the spans were written ...
    assert parent["t1"] - parent["t0"] == pytest.approx(outer.elapsed)
    assert outer.elapsed >= 0.05 and inner.elapsed >= 0.03
    # ... a reader takes self time as duration less children
    own = goodput.self_seconds(_spans())
    assert own["program"] == pytest.approx(inner.elapsed)
    assert own["engine_build"] == pytest.approx(outer.elapsed - inner.elapsed)


def test_a_span_on_a_second_thread_does_not_adopt_the_first_threads_parent():
    import threading

    def other():
        with goodput.span("artifact_read"):
            pass

    with goodput.span("engine_build"):
        t = threading.Thread(target=other)
        t.start()
        t.join()
        with goodput.span("program"):
            pass
    by_name = {r["name"]: r for r in _spans()}
    assert by_name["artifact_read"]["parent"] is None
    assert by_name["program"]["parent"] == by_name["engine_build"]["id"]


def test_spans_after_ready_are_kept_and_marked_as_after_it():
    with goodput.span("artifact_read"):
        pass
    goodput.ready(model="lm/1")
    with goodput.span("artifact_read"):      # a second model, later
        pass
    goodput.ready(model="lm/2")
    recs = _spans()
    assert [r["name"] for r in recs] == ["artifact_read", "ready",
                                         "artifact_read", "ready"]
    assert [r["after_ready"] for r in recs] == [False, False, True, True]
    assert recs[1]["t0"] == recs[1]["t1"] and recs[1]["model"] == "lm/1"
    # what the first mark published is the first start-up's, for good
    block = goodput.startup_block()
    assert block["ready"] and block["spans"] == 1
    assert set(block["phases"]) == {"artifact_read", "total"}


def test_a_back_dated_span_adopts_what_its_thread_wrote_inside_it():
    t0 = time.perf_counter()
    with goodput.span("program", label="inside"):
        pass
    with goodput.span("import", t0=t0):          # as mxnet_tpu/__init__.py
        pass
    inside, whole = _spans()
    assert whole["name"] == "import" and whole["t0"] == t0
    assert inside["parent"] == whole["id"] and whole["parent"] is None


def test_the_package_import_is_the_accounts_first_span():
    out = subprocess.run(
        [sys.executable, "-c",
         "import mxnet_tpu\n"
         "from mxnet_tpu.telemetry import goodput\n"
         "r = goodput.window('startup')\n"
         "assert [x['name'] for x in r] == ['import'], r\n"
         "assert r[0]['t1'] - r[0]['t0'] > 0.05 and r[0]['parent'] is None\n"
         "assert goodput._STARTUP_T0 == r[0]['t0']\n"
         "print('ok')"],
        capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.stdout.strip() == "ok", out.stderr[-2000:]


def test_startup_account_writes_nothing_with_the_accountant_off(monkeypatch):
    import jax
    import jax.numpy as jnp

    monkeypatch.setenv("MXTPU_GOODPUT", "0")
    with goodput.span("engine_build") as sp:
        jax.jit(lambda x: x * 3 + 46)(jnp.ones(3))
        time.sleep(0.005)
    goodput.ready(model="lm/1")
    assert goodput.window("startup") == []
    assert sp.elapsed >= 0.005      # the owner's stamps work all the same
    assert goodput.startup_block() == {"ready": False, "spans": 0}


def test_a_span_is_an_annotation_where_a_profiler_session_is_on(monkeypatch):
    seen = []

    class FakeTraceMe:
        def __init__(self, name, **kw):
            self.name = name

        @staticmethod
        def is_enabled():
            return True

        def __enter__(self):
            seen.append(("enter", self.name))

        def __exit__(self, *exc):
            seen.append(("exit", self.name))

    monkeypatch.setattr(goodput, "_trace_me", lambda: FakeTraceMe)
    with goodput.span("artifact_read"):
        with goodput.span("program"):
            pass
    assert seen == [("enter", "mxtpu.startup.artifact_read"),
                    ("enter", "mxtpu.startup.program"),
                    ("exit", "mxtpu.startup.program"),
                    ("exit", "mxtpu.startup.artifact_read")]


@pytest.mark.parametrize("held", [True, False])
def test_jax_stages_are_children_of_the_span_that_holds_the_compile(held):
    import contextlib

    import jax
    import jax.numpy as jnp

    x = jnp.ones((4, 4)) + (2.0 if held else 3.0)  # eager glue, before
    jax.block_until_ready(x)
    goodput._reset_for_tests()
    fn = jax.jit(lambda a: jnp.tanh(a) @ a + (46.0 if held else 47.0))
    with goodput.span("program", label="unit") if held \
            else contextlib.nullcontext():
        fn(x).block_until_ready()
    stages = _spans("trace", "lower", "backend_compile")
    assert [r["name"] for r in stages] == ["trace", "lower",
                                           "backend_compile"]
    prog = _spans("program")
    for r in stages:
        assert r["parent"] == (prog[0]["id"] if held else None)
        assert r["t1"] > r["t0"] and "<lambda>" in r["fun_name"]
        if held:
            assert prog[0]["t0"] <= r["t0"] and r["t1"] <= prog[0]["t1"]
    # jnp.tanh and @ are jitted functions traced inside the lambda's trace:
    # their seconds are its own, no span of theirs is written
    assert stages[0]["t0"] < stages[1]["t0"] < stages[2]["t0"]
    assert stages[2]["cache"] == "off"     # jax's cache is not armed here
    n = len(goodput.window("startup"))
    fn(x).block_until_ready()               # an execution fires nothing
    assert len(goodput.window("startup")) == n


@pytest.mark.parametrize("verdict", ["hit", "miss", "off"])
def test_the_listeners_write_what_jax_says_of_its_cache(verdict):
    from jax import monitoring

    with goodput.span("import"):    # arms the listeners if nothing has yet
        pass
    goodput._reset_for_tests()
    if verdict == "hit":
        monitoring.record_event("/jax/compilation_cache/cache_hits")
        monitoring.record_event_duration_secs(
            "/jax/compilation_cache/cache_retrieval_time_sec", 0.125)
    elif verdict == "miss":
        monitoring.record_event("/jax/compilation_cache/cache_misses")
    t_before = time.perf_counter()
    monitoring.record_event_duration_secs(
        "/jax/core/compile/backend_compile_duration", 0.5,
        fun_name="jit(unit)")
    (rec,) = goodput.window("startup")
    assert rec["name"] == "backend_compile" and rec["cache"] == verdict
    assert rec["fun_name"] == "jit(unit)" and rec["parent"] is None
    # the end is the listener's call on the rings' clock, the start the
    # handed duration before it
    assert rec["t1"] >= t_before
    assert rec["t1"] - rec["t0"] == pytest.approx(0.5)
    assert rec.get("retrieval_s") == (0.125 if verdict == "hit" else None)
    # a verdict is one compile's: the next one does not inherit it
    monitoring.record_event_duration_secs(
        "/jax/core/compile/backend_compile_duration", 0.25, fun_name="next")
    assert goodput.window("startup")[-1]["cache"] == "off"
    assert "retrieval_s" not in goodput.window("startup")[-1]


def test_a_second_compile_after_clear_caches_reads_cache_hit(tmp_path):
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    x = jnp.arange(12.0).reshape(3, 4)
    jax.block_until_ready(x)
    try:
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        cc.reset_cache()
        said = []
        for _ in range(2):
            goodput._reset_for_tests()
            jax.jit(lambda a: jnp.cos(a) * 46.25)(x).block_until_ready()
            said.append([r["cache"] for r in _spans("backend_compile")])
            jax.clear_caches()
        assert said[0] == ["miss"]
        if said[1] != ["hit"]:
            pytest.skip("this backend's cache did not serve the program: "
                        "%r (the listeners' part is the test above)" % said)
        (rec,) = _spans("backend_compile")
        assert rec["retrieval_s"] > 0.0
        assert rec["retrieval_s"] <= rec["t1"] - rec["t0"] + 1e-3
    finally:
        for k, v in was.items():
            jax.config.update(k, v)
        cc.reset_cache()


def _tiny_sharded_trainer(prefix):
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon import nn, loss as gloss

    ctx = mx.cpu()
    with ctx:
        net = nn.HybridSequential(prefix=prefix)
        with net.name_scope():
            net.add(nn.Dense(8, activation="relu", prefix="fc1_"))
            net.add(nn.Dense(3, prefix="fc2_"))
        net.initialize(ctx=ctx)
    x = mx.nd.array(np.random.RandomState(0)
                    .uniform(-1, 1, (8, 5)).astype(np.float32))
    y = mx.nd.array(np.random.RandomState(1)
                    .randint(0, 3, (8,)).astype(np.float32))
    net(x)
    goodput._reset_for_tests()
    goodput._metrics()               # totals() reads from here on
    with goodput.span("import"):     # stands for the package's, long past
        time.sleep(0.01)
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.05}, sharded=True, block=net,
                       loss=gloss.SoftmaxCrossEntropyLoss())
    return tr, x, y


def test_the_sharded_trainers_start_up_and_nothing_after_the_first_step():
    tr, x, y = _tiny_sharded_trainer("su46_")
    before = goodput.totals()
    n_before = len(_spans("program"))
    tr.step_batch(x, y).asnumpy()
    recs = sorted(_spans(), key=lambda r: r["t0"])
    top = [r["name"] for r in recs if r["parent"] is None]
    assert top[:2] == ["import", "trainer_build"]
    assert top[-2:] == ["first_run", "ready"]
    first = [r for r in recs if r["name"] == "first_run"]
    progs = [r for r in recs if r["name"] == "program"
             and r.get("label") == "dist_trainer_step"]
    assert len(first) == 1 and len(progs) == 1
    assert progs[0]["parent"] == first[0]["id"]
    assert progs[0]["kind"] == "sharded_step"
    assert progs[0]["tier"] == "memory_miss"
    # the fused step's three stages lie under its program span, whole
    ids = {r["id"]: r for r in recs}

    def under(r, span_id):
        while r["parent"] is not None:
            if r["parent"] == span_id:
                return True
            r = ids[r["parent"]]
        return False

    held = [r["name"] for r in recs if under(r, progs[0]["id"])]
    for stage in ("trace", "lower", "backend_compile"):
        assert stage in held
    assert recs[-1]["name"] == "ready" and recs[-1]["trainer"] == "dist"
    # the registry's clock and the account's are one: the compile phase's
    # seconds are the program spans', to the letter
    assert _phases_delta(before)["compile"] == pytest.approx(
        sum(r["t1"] - r["t0"] for r in _spans("program")[n_before:]),
        abs=1e-5)
    n = len(goodput.window("startup"))
    for _ in range(5):
        tr.step_batch(x, y).asnumpy()
    assert len(goodput.window("startup")) == n


def test_first_step_startup_is_worked_out_from_the_account():
    from mxnet_tpu.telemetry import slo

    tr, x, y = _tiny_sharded_trainer("su46b_")
    t_first = goodput.window("startup")[0]["t0"]
    n0 = len([e for e in telemetry.events()
              if e["event"] == "goodput_first_step"])
    tr.step_batch(x, y).asnumpy()
    events = [e for e in telemetry.events()
              if e["event"] == "goodput_first_step"]
    assert len(events) == n0 + 1
    fields = events[-1]["fields"]
    step = goodput.window("dist")[0]
    # first span's start to the first step's start
    assert fields["startup_s"] == pytest.approx(step["t0"] - t_first,
                                                abs=2e-3)
    assert fields["startup_s"] >= 0.01 and fields["step_wall_s"] > 0.0
    assert events[-1]["ts"] == pytest.approx(time.time(), abs=60.0)
    block = slo.statusz_payload()["training"]
    assert block["first_step_startup_s"] == fields["startup_s"]
    assert not hasattr(goodput, "_PROC_T0")


def test_ready_sets_the_gauge_family_and_the_statusz_block():
    from mxnet_tpu.telemetry import slo

    assert slo.statusz_payload()["startup"] == {"ready": False, "spans": 0}
    with goodput.span("import"):
        time.sleep(0.01)
    with goodput.span("engine_build"):
        time.sleep(0.01)
        with goodput.span("program"):
            time.sleep(0.02)
    time.sleep(0.01)            # nobody's: in ``total`` only
    goodput.ready(model="lm/1")
    block = slo.statusz_payload()["startup"]
    assert block["ready"] and block["spans"] == 3
    ph = block["phases"]
    assert set(ph) == {"import", "engine_build", "program", "total"}
    assert ph["total"] >= ph["import"] + ph["engine_build"] + ph["program"] \
        + 0.009
    assert ph["program"] >= 0.02 and ph["engine_build"] < 0.02
    snap = telemetry.snapshot()
    for name, seconds in ph.items():
        g = snap['mxtpu_startup_phase_seconds{phase="%s"}' % name]
        assert g["value"] == pytest.approx(seconds, abs=1e-3)
    assert "startup: " in slo.render_statusz("text")[1].decode()


# --------------------------------------------------------------------------
# tools/goodput_report.py — synthetic ledger unit
# --------------------------------------------------------------------------

def _write_jsonl(path, records):
    with open(path, "w") as f:
        for rec in records:
            f.write(json.dumps(rec) + "\n")


def _synthetic_ledger(d, downtime_cause="preempt"):
    """Two generations: gen0 preempted (4s teardown window), gen1 clean."""
    ev = [
        {"kind": "event", "ts": 1000.0, "event": "launcher_generation_start",
         "fields": {"generation": 0}},
        {"kind": "event", "ts": 1006.0, "event": "launcher_teardown",
         "fields": {"generation": 0, "live": 1, "grace_s": 3.0}},
        {"kind": "event", "ts": 1008.0, "event": "launcher_generation_exit",
         "fields": {"generation": 0, "rc": 83, "preempted": True}},
        {"kind": "event", "ts": 1008.2, "event": "launcher_generation_start",
         "fields": {"generation": 1}},
        {"kind": "event", "ts": 1012.0, "event": "launcher_generation_exit",
         "fields": {"generation": 1, "rc": 0, "preempted": False}},
    ]
    if downtime_cause is not None:
        ev.insert(3, {"kind": "event", "ts": 1008.2,
                      "event": "launcher_downtime",
                      "fields": {"generation": 1, "cause": downtime_cause,
                                 "rc": 83, "down_s": 0.2}})
    _write_jsonl(os.path.join(d, "launcher-events.jsonl"), ev)

    def rank_file(pid, gen, t0, flush_ts, phases):
        metrics = {'mxtpu_goodput_phase_seconds_total{phase="%s"}' % p:
                   {"type": "counter", "value": v}
                   for p, v in phases.items()}
        metrics["mxtpu_goodput_wall_seconds_total"] = {
            "type": "counter", "value": sum(phases.values())}
        _write_jsonl(os.path.join(
            d, "telemetry-rank0-pid%d.jsonl" % pid), [
            # ts = t0 + spawn 0.5 + startup 1.8 + first step wall 0.5
            {"kind": "event", "ts": t0 + 2.8,
             "event": "goodput_first_step",
             "fields": {"trainer": "dist", "generation": gen,
                        "startup_s": 1.8, "step_wall_s": 0.5}},
            {"kind": "metrics", "ts": flush_ts, "rank": 0, "pid": pid,
             "generation": gen, "metrics": metrics},
        ])

    # gen0: spawn 0.5 + startup 1.8 + attributed 3.2 + shutdown 0.5
    # (flush 1005.5 -> teardown 1006) + teardown 2.0 = 8.0 = wall
    rank_file(100, 0, 1000.0, 1005.5,
              {"compute": 2.0, "data_wait": 0.7, "collective": 0.5})
    # gen1: spawn 0.5 + startup 1.8 + attributed 1.2 + shutdown 0.3
    # (flush 1011.7 -> exit 1012, no teardown event) = 3.8 of 3.8 wall
    rank_file(200, 1, 1008.2, 1011.7,
              {"compute": 1.0, "data_wait": 0.2})


def test_goodput_report_synthetic_clean(tmp_path):
    gr = _tools()
    _synthetic_ledger(str(tmp_path))
    rep = gr.build_report(str(tmp_path), min_coverage=0.9)
    assert rep["problems"] == []
    g0, g1 = rep["generations"]
    assert g0["preempted"] and g0["rc"] == 83
    assert g0["teardown_s"] == pytest.approx(2.0)
    assert g0["coverage"] >= 0.99
    assert g0["ranks"][0]["shutdown_s"] == pytest.approx(0.5)
    assert g1["downtime_before"]["cause"] == "preempt"
    assert g1["coverage"] >= 0.99
    assert "teardown_s" not in g1  # clean generations emit no teardown
    assert rep["job"]["generations"] == 2
    assert rep["job"]["downtime_s"] == pytest.approx(0.2)
    # goodput = mean rank compute / generation wall
    assert g0["goodput_fraction"] == pytest.approx(2.0 / 8.0)


def test_goodput_report_synthetic_problems(tmp_path):
    gr = _tools()
    # mislabeled downtime after a preemption
    _synthetic_ledger(str(tmp_path), downtime_cause="crash")
    rep = gr.build_report(str(tmp_path))
    assert any("labeled 'crash'" in p for p in rep["problems"])
    # missing downtime event entirely
    for f in os.listdir(str(tmp_path)):
        os.unlink(os.path.join(str(tmp_path), f))
    _synthetic_ledger(str(tmp_path), downtime_cause=None)
    rep = gr.build_report(str(tmp_path))
    assert any("without a launcher_downtime" in p for p in rep["problems"])


def test_goodput_report_low_coverage_fails_check(tmp_path):
    gr = _tools()
    _synthetic_ledger(str(tmp_path))
    # gut the attribution: a broken accountant must fail --check even
    # though the trailer (flush-anchored) would still span the window
    path = os.path.join(str(tmp_path), "telemetry-rank0-pid100.jsonl")
    recs = [json.loads(l) for l in open(path)]
    for rec in recs:
        if rec["kind"] == "metrics":
            for key in rec["metrics"]:
                rec["metrics"][key]["value"] = 0.001
    _write_jsonl(path, recs)
    rep = gr.build_report(str(tmp_path), min_coverage=0.9)
    assert any("coverage" in p for p in rep["problems"])
    assert gr.main(["--dir", str(tmp_path), "--check"]) == 1


# --------------------------------------------------------------------------
# END-TO-END: 2-rank launch.py with an injected preemption
# --------------------------------------------------------------------------

def test_e2e_preempt_goodput_report(tmp_path):
    ckpt = tmp_path / "ckpt"
    tel = tmp_path / "tel"
    ckpt.mkdir()
    tel.mkdir()
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": _ROOT,
        "MXTPU_CKPT_DIR": str(ckpt),
        "MXTPU_TELEMETRY_DIR": str(tel),
        "MXTPU_TEST_TOTAL_STEPS": "12",
        "MXTPU_FAULT_INJECT": "preempt@step=7,rank=1,grace=30",
        "MXTPU_TEARDOWN_GRACE": "3",
        "MXTPU_CKPT_SHARD_TIMEOUT_S": "60",
        "MXTPU_RENDEZVOUS_TIMEOUT": "60",
    })
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, _LAUNCH, "-n", "2", "--max-restarts", "1",
         "--restart-backoff", "0.2", "--",
         sys.executable, _EWORKER],
        env=env, capture_output=True, text=True, timeout=300)
    out = proc.stdout + proc.stderr
    assert proc.returncode == 0, out[-4000:]
    assert out.count("ELASTIC_OK") == 2, out[-4000:]

    gr = _tools()
    rep = gr.build_report(str(tel), min_coverage=0.9)
    assert rep["problems"] == [], (rep["problems"], out[-4000:])
    gens = rep["generations"]
    assert len(gens) == 2
    assert gens[0]["preempted"]
    dt = gens[1]["downtime_before"]
    assert dt["cause"] == "preempt" and dt["rc"] == 83
    for g in gens:
        assert g["coverage"] >= 0.9
        assert g["goodput_fraction"] is not None
        assert g["mean_phases_s"].get("compute", 0.0) > 0.0
    # the report's per-rank phases ARE the counters from each rank's final
    # flush — re-parse independently and compare
    ranks = gr.load_ranks(str(tel))
    for g in gens:
        for row in g["ranks"]:
            rec = ranks[(g["generation"], row["rank"])]
            assert row["attributed_s"] == pytest.approx(
                sum(rec["phases"].values()), abs=1e-3)
    # --check passes on the real artifacts (the acceptance contract)
    assert gr.main(["--dir", str(tel), "--check"]) == 0
