"""The phase accountant over both hot loops (ISSUE 26): the decode
scheduler's lap is a ``serve`` bracket of `telemetry.goodput` whose phases
are exhaustive, whose whole record joins ``goodput.window("serve")`` and
whose bracket and phases are annotations in the profiler's trace; a trainer
step rides on the same accountant (``mxtpu.dist.step``); a lap that stalls
writes itself down; request tracing costs an untraced lap no call.

CPU only, tiny sizes: what is asserted here is structure (names, nesting,
sums, counts), never a time.
"""
import glob
import os
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.gluon.model_zoo.transformer import lm_mini
from mxnet_tpu.serving import GenerateScheduler, TransformerLMEngine
from mxnet_tpu.serving import generate as gen
from mxnet_tpu.telemetry import goodput, tracing


class StubEngine:
    """No-model engine (tests/test_generate.py has its twin): prefill
    answers (sum(prompt)+1) mod vocab, decode answers last+1 mod vocab;
    ``sleeps`` holds one sleep per decode step, consumed in order."""

    def __init__(self, buckets=(1, 2, 4), sleeps=()):
        self.vocab_size = 64
        self.buckets = list(buckets)
        self.page_size = 2
        self.num_pages = 8 * max(buckets)
        self.max_prompt = 4
        self.max_new_tokens = 8
        self.max_pages_per_seq = 6
        self.eos_id = None
        self.sleeps = list(sleeps)

    def warm(self):
        return 0.0

    def prefill(self, tokens, page_row, sampling, key):
        return (sum(tokens) + 1) % self.vocab_size

    def decode_step(self, tokens, *rest):
        if self.sleeps:
            time.sleep(self.sleeps.pop(0))
        return ((np.asarray(tokens) + 1) % self.vocab_size).astype(np.int32)


def _laps(model):
    return [r for r in goodput.window("serve") if r["model"] == model]


# ---------------------------------------------------------------------------
# the accountant itself
# ---------------------------------------------------------------------------

@pytest.fixture
def fresh_accountant():
    goodput._reset_for_tests()
    # materialise the metric handles, so that totals() reads the registry's
    # cumulative values from the start (earlier tests of the process
    # published some)
    goodput._metrics()
    yield
    goodput._reset_for_tests()


def test_a_bracket_keeps_the_phases_of_its_kind(fresh_accountant):
    before = goodput.totals()["phases"].get("compile", 0.0)
    wall0 = goodput.totals()["wall"]
    goodput.step_start(kind="serve")
    goodput.add("decode_wait", 0.25)
    goodput.add("compute", 0.5)         # a training phase: not a lap's
    goodput.add("compile", 0.125)       # ... but still cumulative
    out = goodput.step_end(n=3)
    assert set(out) <= set(goodput.SERVE_PHASES) | {"wall"}
    assert out["decode_wait"] == 0.25
    assert goodput.totals()["phases"]["compile"] - before == \
        pytest.approx(0.125)
    # outside any bracket a lap's phase has no home
    goodput.add("decode_wait", 1.0)
    assert "decode_wait" not in goodput.totals()["phases"]
    assert goodput.totals()["wall"] == wall0
    goodput.step_start(kind="dist", step=7)
    goodput.add("decode_wait", 0.25)    # not a training step's
    goodput.add("compute", 0.125)
    out = goodput.step_end()
    assert "decode_wait" not in out and out["compute"] == 0.125
    # a lap leaves the training gauge, counters and /statusz alone
    assert goodput.totals()["wall"] - wall0 == pytest.approx(out["wall"])
    assert goodput.statusz_block()["window_steps"] == 1
    (lap,), (step,) = goodput.window("serve"), goodput.window("dist")
    assert lap["n"] == 3 and step["step"] == 7
    for rec in (lap, step):
        assert rec["t1"] >= rec["t0"] and rec["traced"] is False
        assert 0.0 <= rec["cpu_s"] <= rec["t1"] - rec["t0"] + 0.05
    assert goodput.window("nothing") == []


def test_the_ring_is_bounded_and_the_window_is_a_copy(fresh_accountant):
    for i in range(goodput._RING_LEN + 5):
        goodput.step_start(kind="serve")
        goodput.step_end(step=i)
    ring = goodput.window("serve")
    assert len(ring) == goodput._RING_LEN
    assert ring[0]["step"] == 5 and ring[-1]["step"] == goodput._RING_LEN + 4
    ring.clear()
    assert len(goodput.window("serve")) == goodput._RING_LEN


def test_a_phase_keeps_its_stamps_for_its_owner(fresh_accountant):
    goodput.step_start(kind="serve")
    with goodput.phase("prefill_host") as outer:
        with goodput.phase("prefill_wait") as inner:
            time.sleep(0.01)
    out = goodput.step_end()
    # elapsed is the whole block; the phase is what is left of it
    assert outer.elapsed >= inner.elapsed >= 0.01
    assert out["prefill_wait"] == pytest.approx(inner.elapsed)
    assert out["prefill_host"] == pytest.approx(
        outer.elapsed - inner.elapsed, abs=1e-9)
    assert outer.t0 <= inner.t0
    # with no bracket open a phase still times its block
    with goodput.phase("prefill_host") as alone:
        pass
    assert alone.elapsed >= 0.0


# ---------------------------------------------------------------------------
# the scheduler's laps, traced by the profiler and not
# ---------------------------------------------------------------------------

def _trainer():
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon import nn, loss as gloss

    ctx = mx.cpu()
    with ctx:
        net = nn.HybridSequential(prefix="lapacct_")
        with net.name_scope():
            net.add(nn.Dense(8, activation="relu", prefix="fc1_"))
            net.add(nn.Dense(4, prefix="fc2_"))
        net.initialize(ctx=ctx)
    x = mx.nd.array(np.random.RandomState(0)
                    .uniform(-1, 1, (8, 8)).astype(np.float32))
    y = mx.nd.array(np.random.RandomState(1)
                    .randint(0, 4, (8,)).astype(np.float32))
    net(x)
    tr = gluon.Trainer(net.collect_params(), "sgd", {"learning_rate": 0.05},
                       sharded=True, block=net,
                       loss=gloss.SoftmaxCrossEntropyLoss())
    return tr, x, y


def _host_events(trace_dir):
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("mxtpu."):
                    out.append({"line": (plane.name, line.name),
                                "name": ev.name, "start": ev.start_ns,
                                "end": ev.start_ns + ev.duration_ns})
    return out


@pytest.fixture(scope="module")
def traced_run(tmp_path_factory):
    """Laps of a scheduler on a tiny real engine and steps of a fused
    trainer: some before a profiler session, some inside it, some after."""
    import jax

    goodput._reset_for_tests()
    lm = lm_mini(vocab_size=96)
    lm.initialize(mx.init.Xavier())
    eng = TransformerLMEngine(lm=lm, num_pages=32, page_size=4, max_prompt=8,
                              max_new_tokens=12, max_batch=4)
    sched = GenerateScheduler(eng, name="lapacct/1", queue_depth=8)
    tr, x, y = _trainer()
    trace_dir = str(tmp_path_factory.mktemp("lapacct_trace"))

    def work():
        reqs = [sched.submit([3, 5, 7], max_new_tokens=6),
                sched.submit([2, 4], max_new_tokens=4)]
        for r in reqs:
            r.wait(60)
        for _ in range(2):
            tr.step_batch(x, y).asnumpy()

    try:
        work()
        edges = [time.perf_counter()]
        jax.profiler.start_trace(trace_dir)
        edges.append(time.perf_counter())
        try:
            work()
        finally:
            edges.append(time.perf_counter())
            jax.profiler.stop_trace()
        edges.append(time.perf_counter())
        work()
    finally:
        sched.close(drain=False, timeout=0)
    run = {"edges": edges, "laps": _laps("lapacct/1"),
           "steps": goodput.window("dist"), "events": _host_events(trace_dir)}
    goodput._reset_for_tests()
    return run


def test_a_laps_phases_sum_to_its_wall(traced_run):
    laps = traced_run["laps"]
    assert len(laps) >= 12
    for rec in laps:
        wall = rec["t1"] - rec["t0"]
        total = sum(rec["phases"].values())
        assert set(rec["phases"]) <= set(goodput.SERVE_PHASES)
        assert all(v >= 0.0 for v in rec["phases"].values())
        assert 0.99 * wall <= total <= wall * (1 + 1e-9) + 1e-9
        assert rec["prefills"] == rec["admitted"]
        if rec["admitted"]:
            assert rec["queue_wait_s"] > 0.0
            assert rec["phases"]["prefill_wait"] > 0.0
        if rec["n"]:
            assert rec["bucket"] >= rec["n"]
            assert rec["phases"]["decode_wait"] > 0.0
            assert rec["phases"]["decode_dispatch"] > 0.0
            assert rec["phases"]["build"] > 0.0
    # 2 requests a round, 3 rounds; one token comes from the prefill
    assert sum(r["admitted"] for r in laps) == 6
    assert sum(r["n"] for r in laps) == 3 * ((6 - 1) + (4 - 1))


def test_traced_is_true_only_for_laps_wholly_inside_the_session(traced_run):
    before_start, after_start, before_stop, after_stop = traced_run["edges"]
    inside = outside = 0
    for rec in traced_run["laps"] + traced_run["steps"]:
        if after_start <= rec["t0"] and rec["t1"] <= before_stop:
            assert rec["traced"] is True
            inside += 1
        elif rec["t0"] <= before_start or rec["t1"] >= after_stop:
            assert rec["traced"] is False
            outside += 1
    assert inside >= 6 and outside >= 12


def test_the_session_holds_laps_with_their_phases_nested(traced_run):
    events = traced_run["events"]
    laps = [e for e in events if e["name"] == "mxtpu.serve.lap"]
    traced = [r for r in traced_run["laps"] if r["traced"]]
    assert len(laps) >= len(traced) >= 4
    phases = [e for e in events if e["name"].startswith("mxtpu.serve.")
              and e["name"] != "mxtpu.serve.lap"]
    names = {e["name"].rsplit(".", 1)[1] for e in phases}
    assert {"admit", "prefill_host", "prefill_wait", "build",
            "decode_dispatch", "decode_wait", "retire"} <= names
    assert names <= set(goodput.SERVE_PHASES)
    for e in phases:
        assert any(lap["line"] == e["line"] and lap["start"] <= e["start"]
                   and e["end"] <= lap["end"] for lap in laps), e
    # the engine's wait lies inside the scheduler's dispatch phase
    for e in phases:
        if e["name"] == "mxtpu.serve.decode_wait":
            assert any(p["name"] == "mxtpu.serve.decode_dispatch"
                       and p["start"] <= e["start"] and e["end"] <= p["end"]
                       for p in phases)


def test_a_trainer_step_is_a_step_annotation_with_its_phases(traced_run):
    events = traced_run["events"]
    steps = [e for e in events if e["name"] == "mxtpu.dist.step"]
    assert len(steps) == 2          # the two steps inside the session
    inner = {e["name"] for e in events if e["name"].startswith("mxtpu.dist.")
             and any(s["start"] <= e["start"] and e["end"] <= s["end"]
                     for s in steps)}
    assert {"mxtpu.dist.step", "mxtpu.dist.data_wait",
            "mxtpu.dist.compute"} <= inner
    recs = traced_run["steps"]
    assert [r["step"] for r in recs] == [1, 2, 3, 4, 5, 6]
    assert [r["traced"] for r in recs] == [False, False, True, True,
                                           False, False]
    for r in recs:
        assert set(r["phases"]) <= set(goodput.PHASES)
        assert r["phases"]["compute"] > 0.0


# ---------------------------------------------------------------------------
# the scheduler's own metrics, the slow lap, request tracing's cost
# ---------------------------------------------------------------------------

def _hist(name, model, **labels):
    labels = dict(labels, model=model)
    key = "%s{%s}" % (name, ",".join('%s="%s"' % kv
                                     for kv in sorted(labels.items())))
    return telemetry.snapshot().get(key) or {"count": 0, "sum": 0.0}


def test_the_lap_feeds_the_schedulers_histograms():
    sched = GenerateScheduler(StubEngine(), name="lapacct/2", queue_depth=8)
    try:
        for r in [sched.submit([1, 2], max_new_tokens=5),
                  sched.submit([3], max_new_tokens=3)]:
            r.wait(10)
    finally:
        sched.close(drain=False, timeout=0)
    laps = _laps("lapacct/2")
    steps = sum(1 for r in laps if r["n"])
    assert steps == telemetry.snapshot()[
        'mxtpu_serve_decode_steps_total{model="lapacct/2"}']["value"]
    assert _hist("mxtpu_serve_decode_step_seconds",
                 "lapacct/2")["count"] == steps
    queue = _hist("mxtpu_serve_queue_seconds", "lapacct/2")
    assert queue["count"] == 2
    assert queue["sum"] == pytest.approx(
        sum(r["queue_wait_s"] for r in laps))
    for p in goodput.SERVE_PHASES:
        h = _hist("mxtpu_serve_lap_phase_seconds", "lapacct/2", phase=p)
        assert h["sum"] == pytest.approx(
            sum(r["phases"].get(p, 0.0) for r in laps), abs=1e-9)
    # a stub engine claims no wait: its whole call is the dispatch phase
    assert _hist("mxtpu_serve_lap_phase_seconds", "lapacct/2",
                 phase="decode_wait")["count"] == 0


def test_a_slow_lap_writes_itself_down_once(caplog):
    # six quick steps, one of 1.2 s, two quick ones
    eng = StubEngine(sleeps=[0.0] * 6 + [1.2, 0.0, 0.0])
    eng.max_new_tokens = 10
    sched = GenerateScheduler(eng, name="lapacct/3", queue_depth=8)
    n0 = len([e for e in telemetry.events()
              if e["event"] == "serve_slow_lap"])
    try:
        with caplog.at_level("WARNING", logger="mxnet_tpu.serving.generate"):
            sched.submit([1], max_new_tokens=10).wait(20)
    finally:
        sched.close(drain=False, timeout=0)
    slow = [e["fields"] for e in telemetry.events()
            if e["event"] == "serve_slow_lap"][n0:]
    assert len(slow) == 1
    (ev,) = slow
    assert ev["model"] == "lapacct/3" and ev["n"] == 1 and ev["prefills"] == 0
    assert ev["lap_s"] >= 1.2 > 5 * ev["median_lap_s"]
    assert ev["phases"]["decode_dispatch"] >= 1.2
    assert ev["cpu_s"] < 0.5            # asleep, not running
    assert sum("slow decode lap" in r.getMessage()
               for r in caplog.records) == 1


def test_an_untraced_lap_of_64_makes_no_emit_span_call(monkeypatch):
    calls = []
    real = tracing.emit_span

    def counting(name, *args, **kw):
        calls.append(name)
        return real(name, *args, **kw)

    monkeypatch.setattr(gen._tracing, "emit_span", counting)
    eng = StubEngine(buckets=(64,))
    gate = threading.Event()
    hold, eng.prefill = eng.prefill, lambda *a: (gate.wait(10), hold(*a))[1]
    sched = GenerateScheduler(eng, name="lapacct/4", queue_depth=128)
    try:
        # unsampled contexts, as the HTTP front end mints for every request
        reqs = [sched.submit([1 + i % 3], max_new_tokens=4,
                             trace=tracing.SpanRef("ab" * 8, "cd" * 4))
                for i in range(64)]
        gate.set()
        for r in reqs:
            r.wait(20)
        assert max(r["n"] for r in _laps("lapacct/4")) == 64
        assert calls == []
        # one recorded request among them pays for itself alone
        ref = tracing.SpanRef("12" * 8, "34" * 4, sampled=True)
        reqs = [sched.submit([2], max_new_tokens=4, trace=ref if i == 5
                             else None) for i in range(8)]
        for r in reqs:
            r.wait(20)
    finally:
        sched.close(drain=False, timeout=0)
        tracing.drain_pending()
    assert sorted(calls) == ["decode.prefill"] + ["decode.step"] * 3 \
        + ["serve.queue"]
