"""Elastic autoscaling serving tests (ISSUE 15, docs/serving.md
§Autoscaling): in-place `ReplicaPool` resize, the `Autoscaler`
controller's hysteresis + budget admission, repository budget-pressure
bin-packing (shrink/evict instead of 507), the `load_surge` chaos
action, the enriched 507 footprint breakdown, and THE tier-1 chaos e2e
(surge -> scale-up -> verdict recovery -> idle scale-down, zero 500s).

Everything runs on CPU with stub workers / tiny models and
milliseconds-scale SLO windows — the tier-1 budget has no headroom
(ROADMAP.md caution (a))."""
import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.base import MXNetError
from mxnet_tpu.parallel import resilience
from mxnet_tpu.serving import (
    Autoscaler, MemoryBudgetError, ModelRepository, ServedModel,
    ServingServer,
)
from mxnet_tpu.serving import autoscaler as autoscaler_mod
from mxnet_tpu.telemetry import slo


def _post_json(url, payload, timeout=15):
    body = json.dumps(payload).encode()
    req = urllib.request.Request(
        url, data=body, headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, json.loads(r.read())


def _stub_pool_model(name, replicas=1, stub_delay_ms=0, queue_depth=32,
                     max_batch=4, extra_env=None, **kw):
    """A pooled stub-echo model (x -> 2x), the cheap chaos vehicle."""
    args = ["--stub", "echo", "--input", "x=2", "--max-batch",
            str(max_batch)]
    if stub_delay_ms:
        args += ["--stub-delay-ms", str(stub_delay_ms)]
    kw.setdefault("heartbeat_ms", 500)
    kw.setdefault("backoff_ms", 50)
    kw.setdefault("teardown_grace", 1.0)
    kw.setdefault("spawn_timeout_s", 90)
    kw.setdefault("max_delay_ms", 1)
    return ServedModel.pooled(name, 1, None, replicas, worker_args=args,
                              queue_depth=queue_depth, extra_env=extra_env,
                              **kw)


# ---------------------------------------------------------------------------
# ReplicaPool in-place resize
# ---------------------------------------------------------------------------

def test_pool_resize_in_place_serves_through_both_sizes():
    """add_replica grows the pool without a reload (new member joins on
    ready; no shedding while it warms), remove_replica(drain=True)
    shrinks it with zero request loss; the `mxtpu_serve_replicas` gauge
    and live `resident_copies` track every resize."""
    model = _stub_pool_model("resize", replicas=1)
    repo = ModelRepository()
    repo.add(model)
    pool = model.pool
    try:
        assert pool.replica_ids() == [0]
        out = model.predict({"x": np.ones((1, 2), np.float32)},
                            timeout_ms=5000)
        assert np.all(out[0] == 2.0)

        rid = pool.add_replica()
        assert rid == 1 and pool.size == 2
        # joining member: the degraded gate must NOT shed while it warms
        # (expected stays at the pre-grow capacity)
        assert pool.expected_count >= 1
        assert pool.admission_gate(model._batcher.queue_depth - 1) is None
        deadline = time.monotonic() + 60
        while pool.healthy_count < 2 and time.monotonic() < deadline:
            time.sleep(0.05)
        assert pool.healthy_count == 2 and pool.expected_count == 2
        snap = telemetry.snapshot()
        assert snap['mxtpu_serve_replicas{model="resize/1"}']["value"] == 2
        assert model.resident_copies == 2  # live, not load-time meta
        out = model.predict({"x": np.full((1, 2), 3.0, np.float32)},
                            timeout_ms=5000)
        assert np.all(out[0] == 6.0)

        removed = pool.remove_replica(drain=True)
        assert removed == 1 and pool.size == 1
        assert pool.replica_ids() == [0]
        assert pool.healthy_count == 1
        assert model.resident_copies == 1
        snap = telemetry.snapshot()
        assert snap['mxtpu_serve_replicas{model="resize/1"}']["value"] == 1
        # the removed replica's per-replica gauges are retired, no ghosts
        assert 'mxtpu_serve_replica_generation{model="resize/1",' \
            'replica="1"}' not in snap
        out = model.predict({"x": np.full((1, 2), 5.0, np.float32)},
                            timeout_ms=5000)
        assert np.all(out[0] == 10.0)
        events = [e["event"] for e in telemetry.events()
                  if e["fields"].get("model") == "resize/1"]
        assert "serve_replica_add" in events
        assert "serve_replica_remove" in events
    finally:
        model.close(drain=False, timeout=0)


def test_admission_retry_after_tracks_post_resize_size():
    """Satellite (ISSUE 15): the degraded-admission ``Retry-After =
    ceil(N/h)`` is recomputed against the POST-resize pool size — no
    stale `self.size` read survives a resize."""
    from mxnet_tpu.serving.replica_pool import _DEAD

    model = _stub_pool_model("retrysz", replicas=3, queue_depth=30)
    pool = model.pool
    try:
        # degrade: 2 of 3 dead -> healthy 1, Retry-After = ceil(3/1) = 3
        with pool._lock:
            slots = pool._slots
            slots[0].state = _DEAD
            slots[1].state = _DEAD
        err = pool.admission_gate(29)
        assert err is not None and err.retry_after == 3, vars(err)

        # resize: drop one of the dead slots -> N=2, h=1 -> ceil(2/1)=2
        pool.remove_replica(replica_id=slots[1].id, drain=True,
                            timeout=5.0)
        assert pool.size == 2
        err = pool.admission_gate(29)
        assert err is not None and err.retry_after == 2, vars(err)
    finally:
        model.close(drain=False, timeout=0)


# ---------------------------------------------------------------------------
# Autoscaler controller units (fake pool — no subprocesses)
# ---------------------------------------------------------------------------

class _FakePool:
    def __init__(self, size=1):
        self.size = size
        self.added = 0
        self.removed = 0

    def add_replica(self):
        self.size += 1
        self.added += 1
        return self.size - 1

    def remove_replica(self, replica_id=None, drain=True, timeout=None,
                       floor=1):
        assert drain
        if self.size <= max(1, floor):
            raise MXNetError("cannot shrink below floor")
        self.size -= 1
        self.removed += 1
        return self.size


class _FakeModel:
    """Duck-typed ServedModel for controller units (repo.add-compatible)."""

    def __init__(self, name="fake", version=1, size=1, memory_bytes=None,
                 min_replicas=None, max_replicas=None):
        self.name, self.version = name, version
        self.pool = _FakePool(size)
        self.memory_bytes = memory_bytes
        self.min_replicas = min_replicas
        self.max_replicas = max_replicas
        self.pinned = False
        self.loaded_at = time.time()

    @property
    def resident_copies(self):
        return self.pool.size

    @property
    def effective_memory_bytes(self):
        if not self.memory_bytes:
            return None
        return self.memory_bytes * self.pool.size

    def pending(self):
        return 0

    def close(self, drain=True, timeout=None):
        return True

    def describe(self):
        return {"name": self.name, "version": self.version}


def _verdict(label, page, name="serve-p99"):
    return {"slo": "%s:%s" % (name, label), "page": page,
            "labels": {"model": label}}


def test_autoscaler_up_hysteresis_and_cooldown(monkeypatch):
    """Scale-up needs `up_windows` CONSECUTIVE breached laps; a single
    noisy window never scales, and the cooldown separates actions."""
    monkeypatch.delenv("MXTPU_SERVE_MEMORY_BUDGET", raising=False)
    repo = ModelRepository()
    m = _FakeModel("hys", size=1, max_replicas=4)
    repo.add(m)
    asc = Autoscaler(repo, interval_ms=100, up_windows=2, idle_s=3600,
                     cooldown_s=0.0, start=False)
    label = "hys/1"
    breach = [_verdict(label, True)]
    calm = [_verdict(label, False)]
    assert asc.evaluate_once(verdicts=breach) == []   # lap 1: not yet
    assert asc.evaluate_once(verdicts=calm) == []     # breach resets
    assert asc.evaluate_once(verdicts=breach) == []   # lap 1 again
    out = asc.evaluate_once(verdicts=breach)          # lap 2: scale up
    assert out and out[0]["action"] == "up" and m.pool.size == 2
    counters = telemetry.snapshot()
    assert counters['mxtpu_autoscale_decisions_total{action="up"}'][
        "value"] >= 1
    # cooldown: back-to-back sustained breach must wait it out
    asc.cooldown_s = 60.0
    asc.evaluate_once(verdicts=breach)
    assert asc.evaluate_once(verdicts=breach) == []
    assert m.pool.size == 2
    # ceiling: at max_replicas the decision is blocked, not up (the
    # breach stayed sustained through the cooldown, so the first
    # non-cooling lap decides)
    asc.cooldown_s = 0.0
    m.pool.size = 4
    out = asc.evaluate_once(verdicts=breach)
    assert out and out[0]["action"] == "blocked" \
        and out[0]["reason"] == "max_replicas"
    assert m.pool.size == 4


def test_autoscaler_up_blocked_by_memory_budget(monkeypatch):
    """A scale-up is admitted against MXTPU_SERVE_MEMORY_BUDGET headroom
    (one more full copy); without headroom (and nothing reclaimable) it
    records `autoscale_blocked` instead of growing."""
    repo = ModelRepository()
    m = _FakeModel("budg", size=2, memory_bytes=1000, max_replicas=8)
    repo.add(m)
    # resident = 2000; one more copy needs 1000 but headroom is 500
    monkeypatch.setenv("MXTPU_SERVE_MEMORY_BUDGET", "2500")
    asc = Autoscaler(repo, up_windows=1, idle_s=3600, cooldown_s=0.0,
                     start=False)
    out = asc.evaluate_once(verdicts=[_verdict("budg/1", True)])
    assert out and out[0]["action"] == "blocked" \
        and out[0]["reason"] == "memory_budget", out
    assert m.pool.size == 2 and m.pool.added == 0
    events = [e for e in telemetry.events()
              if e["event"] == "autoscale_blocked"
              and e["fields"].get("model") == "budg/1"]
    assert events and events[-1]["fields"]["needed_bytes"] == 1000
    # raise the budget: the same breach now scales
    monkeypatch.setenv("MXTPU_SERVE_MEMORY_BUDGET", "4000")
    out = asc.evaluate_once(verdicts=[_verdict("budg/1", True)])
    assert out and out[0]["action"] == "up" and m.pool.size == 3


def test_autoscaler_idle_scale_down_never_below_min(monkeypatch):
    """Sustained idle drains one replica per lap down to min_replicas —
    and no further."""
    monkeypatch.delenv("MXTPU_SERVE_MEMORY_BUDGET", raising=False)
    repo = ModelRepository()
    m = _FakeModel("idle", size=3, min_replicas=2)
    m.loaded_at = time.time() - 100.0  # cold since "long ago"
    repo.add(m)
    asc = Autoscaler(repo, up_windows=1, idle_s=0.05, cooldown_s=0.0,
                     start=False)
    out = asc.evaluate_once(verdicts=[])
    assert out and out[0]["action"] == "down" and m.pool.size == 2
    assert asc.evaluate_once(verdicts=[]) == []  # at the floor: stop
    assert m.pool.size == 2 and m.pool.removed == 1
    # a paging verdict keeps a hot model at size even when "old"
    m2 = _FakeModel("hot", size=3, min_replicas=1)
    m2.loaded_at = time.time() - 100.0
    repo.add(m2)
    asc2 = Autoscaler(repo, up_windows=99, idle_s=0.05, cooldown_s=0.0,
                      start=False)
    asc2.evaluate_once(verdicts=[_verdict("hot/1", True)])
    assert m2.pool.size == 3


def test_autoscaler_thread_lifecycle_named_and_joined():
    """PR-12 thread hygiene: the controller thread is named, and stop()
    joins it."""
    repo = ModelRepository()
    asc = Autoscaler(repo, interval_ms=50)
    assert asc.running()
    names = [t.name for t in threading.enumerate()]
    assert "mxtpu-autoscaler" in names
    t = asc._thread
    asc.stop()
    assert not asc.running()
    assert not t.is_alive()
    # describe() is a plain lock-free snapshot for /statusz
    d = asc.describe()
    assert d["running"] is False and "decisions" in d


# ---------------------------------------------------------------------------
# 507 footprint breakdown (satellite)
# ---------------------------------------------------------------------------

def test_memory_budget_error_carries_breakdown(monkeypatch):
    """The 507 names WHAT to evict: requested bytes, per-resident-model
    effective bytes, budget, headroom and shortfall ride both the
    message and the machine-readable details."""
    monkeypatch.setenv("MXTPU_SERVE_MEMORY_BUDGET", "3000")
    repo = ModelRepository()
    resident = _FakeModel("old", size=2, memory_bytes=1000)
    resident.loaded_at = time.time()  # fresh: not evictable
    repo.add(resident)
    newcomer = _FakeModel("new", size=1, memory_bytes=2000)
    with pytest.raises(MemoryBudgetError) as exc:
        repo.add(newcomer)
    e = exc.value
    assert e.status == 507
    d = e.details
    assert d["requested_bytes"] == 2000
    assert d["budget_bytes"] == 3000
    assert d["resident_bytes"] == 2000
    assert d["headroom_bytes"] == 1000
    assert d["shortfall_bytes"] == 1000
    assert d["resident_models"] == [{"model": "old/1",
                                     "effective_bytes": 2000,
                                     "copies": 2, "pinned": False}]
    # the operator-facing message carries the same story
    msg = str(e)
    for frag in ("needs 2000 bytes", "headroom", "old/1=2000 bytes (x2)",
                 "short 1000 bytes"):
        assert frag in msg, (frag, msg)


def test_http_507_body_ships_details():
    """Regression: a MemoryBudgetError surfacing through the HTTP layer
    answers 507 with the breakdown in the JSON body."""
    details = {"requested_bytes": 7, "budget_bytes": 5,
               "headroom_bytes": 0, "shortfall_bytes": 2,
               "resident_models": []}

    class _Repo:
        def get(self, name, version=None):
            raise MemoryBudgetError("no headroom", details=details)

        def pending(self):
            return 0

    srv = ServingServer(_Repo(), port=0, addr="127.0.0.1").start()
    try:
        with pytest.raises(urllib.error.HTTPError) as exc:
            _post_json("http://127.0.0.1:%d/v1/models/x:predict" % srv.port,
                       {"instances": [[1.0]]})
        assert exc.value.code == 507
        body = json.loads(exc.value.read())
        assert body["details"] == details
        assert "no headroom" in body["error"]
    finally:
        srv.shutdown()


# ---------------------------------------------------------------------------
# budget-pressure bin-packing: shrink + evict instead of 507
# ---------------------------------------------------------------------------

def test_reclaim_shrinks_cold_pool_before_evicting(monkeypatch):
    """Phase 1 of reclaim: a cold pooled model gives up replicas toward
    its min_replicas (each freeing one copy) before anything is
    evicted."""
    monkeypatch.delenv("MXTPU_SERVE_MEMORY_BUDGET", raising=False)
    repo = ModelRepository()
    cold = _FakeModel("coldpool", size=3, memory_bytes=100,
                      min_replicas=1)
    cold.loaded_at = time.time() - 1000.0
    repo.add(cold)
    monkeypatch.setenv("MXTPU_AUTOSCALE_IDLE_S", "0.1")
    monkeypatch.setenv("MXTPU_AUTOSCALE_EVICT_TTL_S", "3600")
    freed = repo.reclaim_memory(150, exclude="other/1")
    assert freed == 200 and cold.pool.size == 1
    assert "coldpool" in repo.names()  # shrunk, NOT evicted (TTL far)
    downs = [e for e in telemetry.events()
             if e["event"] == "autoscale_down"
             and e["fields"].get("model") == "coldpool/1"]
    assert len(downs) >= 2
    assert all(e["fields"]["reason"] == "budget_pressure" for e in downs)
    # pinned/min floors hold: nothing further to shrink, nothing evicted
    assert repo.reclaim_memory(1000, exclude="other/1") == 0
    assert cold.pool.size == 1 and "coldpool" in repo.names()


def test_load_evicts_idle_model_instead_of_507(monkeypatch, tmp_path):
    """THE bin-packing acceptance (ISSUE 15): under budget pressure a
    load evicts a cold (idle-beyond-TTL, unpinned) model instead of
    answering a flat 507 — and the evicted model reloads WARM via its
    persisted warmup manifest (zero jit compiles on the reload)."""
    from mxnet_tpu.gluon import nn

    monkeypatch.setenv("MXTPU_COMPILE_CACHE", str(tmp_path / "cache"))
    monkeypatch.delenv("MXTPU_SERVE_MEMORY_BUDGET", raising=False)

    def export(tag, seed):
        mx.random.seed(seed)
        net = nn.HybridSequential()
        net.add(nn.Dense(16, activation="relu"), nn.Dense(4))
        net.initialize()
        net(mx.nd.zeros((2, 8)))
        prefix = str(tmp_path / tag)
        net.export(prefix, epoch=0)
        return prefix

    prefix_a, prefix_b = export("a", 1), export("b", 2)
    repo = ModelRepository()
    a = repo.load("cold", prefix_a, input_shapes={"data": (8,)},
                  max_batch=2)
    footprint = a.effective_memory_bytes
    assert footprint and footprint > 0
    manifest = a.manifest_id
    assert manifest

    # budget fits ~1.5 models; "cold" is idle beyond the (tiny) TTL
    monkeypatch.setenv("MXTPU_SERVE_MEMORY_BUDGET",
                       str(int(footprint * 1.5)))
    monkeypatch.setenv("MXTPU_AUTOSCALE_EVICT_TTL_S", "0.05")
    time.sleep(0.1)
    b = repo.load("hot", prefix_b, input_shapes={"data": (8,)},
                  max_batch=2)
    assert b.warmed
    assert repo.names() == ["hot"], "cold must be evicted, not 507"
    evicts = [e for e in telemetry.events()
              if e["event"] == "autoscale_evict"
              and e["fields"].get("model") == "cold/1"]
    assert evicts and evicts[-1]["fields"]["freed_bytes"] == footprint

    # a pinned model is never evicted: the load 507s with the breakdown
    b.pinned = True
    time.sleep(0.1)
    with pytest.raises(MemoryBudgetError) as exc:
        repo.load("third", prefix_a, input_shapes={"data": (8,)},
                  max_batch=2)
    assert exc.value.details["resident_models"][0]["pinned"] is True
    blocked = [e for e in telemetry.events()
               if e["event"] == "autoscale_blocked"
               and e["fields"].get("model") == "third/1"]
    assert blocked
    b.pinned = False

    # the evicted model's manifest survived: reload is warm (zero jit
    # compiles — executables come back from the cache tiers). Budget is
    # raised so the reload needs no reclaim of its own.
    monkeypatch.setenv("MXTPU_SERVE_MEMORY_BUDGET", str(footprint * 3))
    misses = telemetry.get_registry().counter("mxtpu_jit_cache_miss_total")
    base = misses.value
    a2 = repo.load("cold", prefix_a, input_shapes={"data": (8,)},
                   max_batch=2)
    assert a2.warmed and misses.value - base == 0
    assert sorted(repo.names()) == ["cold", "hot"]
    for name in list(repo.names()):
        repo.unload(name, timeout=10)


# ---------------------------------------------------------------------------
# load_surge chaos action
# ---------------------------------------------------------------------------

def test_load_surge_spec_parses_and_validates():
    spec = resilience.fault_spec("load_surge@after=1,rps=250,duration=4")
    assert spec[0]["action"] == "load_surge"
    assert (spec[0]["after"], spec[0]["rps"], spec[0]["duration"]) \
        == (1, 250, 4)
    with pytest.raises(MXNetError, match="after="):
        resilience.fault_spec("load_surge@rps=10")
    with pytest.raises(MXNetError, match="unknown action"):
        resilience.fault_spec("load_tsunami@after=1")


def test_load_surge_fires_synthetic_open_loop_burst(monkeypatch):
    """The surge is REAL admissions: it moves the model's request
    counters/queue gauge through the normal batcher path, and sheds
    count as sheds, not exceptions."""
    monkeypatch.setenv("MXTPU_FAULT_INJECT",
                       "load_surge@after=0,rps=200,duration=1")
    monkeypatch.setattr(resilience, "_fault_cache", resilience._UNPARSED)
    reqs = telemetry.counter("mxtpu_serve_requests_total",
                             {"model": "surged/1"})
    base = reqs.value
    calls = []

    def runner(arrays, bucket, n):
        calls.append(n)
        return [arrays["x"]]

    model = ServedModel("surged", 1, runner, [1, 2, 4], {"x": (2,)})
    repo = ModelRepository()
    threads = []
    monkeypatch.setattr(
        resilience, "maybe_inject_load_surge",
        lambda m, _orig=resilience.maybe_inject_load_surge:
        threads.extend(_orig(m)) or threads)
    repo.add(model)
    assert threads, "surge thread must arm at publish"
    assert all(t.name == "mxtpu-fault-load-surge" for t in threads)
    for t in threads:
        t.join(timeout=30)
    assert not any(t.is_alive() for t in threads)
    done = [e for e in telemetry.events()
            if e["event"] == "fault_load_surge_done"
            and e["fields"].get("model") == "surged"]
    assert done, "surge must record its completion event"
    fired = done[-1]["fields"]["fired"]
    assert fired > 50  # ~200 rps x 1s, CPU-box slack
    assert reqs.value - base == fired
    assert model.drain(10.0)  # the open-loop tail resolves
    assert sum(calls) == fired  # every admission reached the runner
    model.close(drain=False, timeout=0)
    monkeypatch.setattr(resilience, "_fault_cache", resilience._UNPARSED)


# ---------------------------------------------------------------------------
# THE tier-1 chaos e2e (acceptance): surge -> scale-up -> recovery ->
# idle scale-down, zero 500s, zero lost requests
# ---------------------------------------------------------------------------

def test_autoscale_chaos_surge_e2e(monkeypatch):
    """ISSUE 15 acceptance: a `load_surge` injection against a 1-replica
    stub pool drives a queue/p99 SLO breach; the autoscaler scales the
    pool up IN PLACE within one slow window; the verdict recovers; the
    surge ends and sustained idle drains the pool back to min_replicas —
    with zero 500s and every closed-loop request resolved."""
    # tiny SLO windows so breach AND recovery fit in seconds (the
    # test_slo e2e cadence)
    monkeypatch.setenv("MXTPU_SLO_WINDOW_MS", "200")
    monkeypatch.setenv("MXTPU_SLO_EVAL_MS", "150")
    monkeypatch.setenv("MXTPU_SLO_FAST_WINDOWS", "2")
    monkeypatch.setenv("MXTPU_SLO_SLOW_WINDOW_S", "30")
    monkeypatch.setenv("MXTPU_SLO_SERVE_P99_MS", "400")
    monkeypatch.setenv("MXTPU_SERVE_TIMEOUT_MS", "3000")
    slo.stop()  # fresh evaluator picks up the test cadence
    # the surge: open-loop 250 rps for 3s against a pool whose single
    # 40ms-per-batch replica can do ~100 rps — queue + p99 must breach
    monkeypatch.setenv("MXTPU_FAULT_INJECT",
                       "load_surge@after=0,rps=250,duration=3")
    monkeypatch.setattr(resilience, "_fault_cache", resilience._UNPARSED)

    model = _stub_pool_model("elastic", replicas=1, stub_delay_ms=40,
                             queue_depth=64, max_batch=4)
    model.min_replicas = 1
    model.max_replicas = 3
    repo = ModelRepository()
    srv = ServingServer(repo, port=0, addr="127.0.0.1").start()
    asc = srv.attach_autoscaler(Autoscaler(
        repo, interval_ms=250, up_windows=2, idle_s=2.0, cooldown_s=1.0))
    url = "http://127.0.0.1:%d" % srv.port
    pool = model.pool
    t_surge = time.monotonic()
    repo.add(model)  # publish arms the surge thread
    codes, bad, lock = {}, [], threading.Lock()

    def client(tid, n=12):
        for i in range(n):
            x = float(tid * 100 + i)
            try:
                code, resp = _post_json(
                    url + "/v1/models/elastic:predict",
                    {"inputs": {"x": [[x, x]]}, "timeout_ms": 3000},
                    timeout=20)
                ok = resp["outputs"][0][0] == [2 * x, 2 * x]
            except urllib.error.HTTPError as e:
                e.read()
                code, ok = e.code, True  # deterministic rejection
            with lock:
                codes[code] = codes.get(code, 0) + 1
                if not ok:
                    bad.append((tid, i))
            time.sleep(0.03)

    try:
        threads = [threading.Thread(target=client, args=(t,))
                   for t in range(3)]
        for t in threads:
            t.start()
        # 1) the breach scales the pool up within one slow window (30s)
        deadline = time.monotonic() + 30
        while pool.size < 2 and time.monotonic() < deadline:
            time.sleep(0.05)
        scale_up_s = time.monotonic() - t_surge
        assert pool.size >= 2, \
            "autoscaler never scaled up (decisions: %s)" % (
                asc.describe()["decisions"],)
        assert scale_up_s < 30.0
        ups = [d for d in asc.describe()["decisions"]
               if d["action"] == "up"]
        assert ups and ups[0]["slos"], "the up decision names its SLOs"
        deadline = time.monotonic() + 30
        while pool.healthy_count < pool.size \
                and time.monotonic() < deadline:
            time.sleep(0.05)
        assert pool.healthy_count == pool.size

        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
        # 2) verdicts recover once the surge backlog clears
        objective = "serve-p99:elastic/1"
        recovered = None
        deadline = time.monotonic() + 30
        while recovered is None and time.monotonic() < deadline:
            v = next((v for v in slo.verdicts()
                      if v["slo"] == objective), None)
            if v is not None and v["healthy"] and not v["no_data"]:
                recovered = v
            time.sleep(0.1)
        assert recovered is not None, "p99 verdict never recovered"

        # 3) sustained idle drains back to min_replicas, zero loss
        deadline = time.monotonic() + 30
        while pool.size > 1 and time.monotonic() < deadline:
            time.sleep(0.1)
        assert pool.size == 1, asc.describe()["decisions"]
        downs = [d for d in asc.describe()["decisions"]
                 if d["action"] == "down"]
        assert downs and downs[-1]["reason"] == "idle"

        # 4) zero 500s, every request resolved deterministically, and
        # the pool still answers correctly at its scaled-down size
        assert not bad, bad
        assert set(codes) <= {200, 429, 503, 504}, codes
        assert codes.get(200, 0) >= 10, codes
        code, resp = _post_json(
            url + "/v1/models/elastic:predict",
            {"inputs": {"x": [[7.0, 7.0]]}, "timeout_ms": 5000})
        assert code == 200 and resp["outputs"][0][0] == [14.0, 14.0]
        # /statusz explains the decisions
        with urllib.request.urlopen(url + "/statusz", timeout=10) as r:
            doc = json.loads(r.read())
        acts = [d["action"] for d in doc["autoscaler"]["decisions"]]
        assert "up" in acts and "down" in acts
        counters = telemetry.snapshot()
        assert counters['mxtpu_autoscale_decisions_total{action="up"}'][
            "value"] >= 1
        assert counters['mxtpu_autoscale_decisions_total{action="down"}'][
            "value"] >= 1
    finally:
        srv.shutdown()  # stops + joins the autoscaler too
        model.close(drain=False, timeout=0)
        slo.stop()
        monkeypatch.setattr(resilience, "_fault_cache",
                            resilience._UNPARSED)
    assert not asc.running()


# ---------------------------------------------------------------------------
# scale-down drain with in-flight GENERATION requests (satellite)
# ---------------------------------------------------------------------------

def test_scale_down_drains_inflight_generation(tmp_path):
    """A pooled LM's draining replica finishes (or fails over exactly
    once) the long decodes it holds; every output still matches the
    one-request oracle and KV pages return to 0 on the survivor."""
    from mxnet_tpu.gluon.model_zoo.transformer import lm_mini
    from mxnet_tpu.serving import save_lm
    from mxnet_tpu.serving.generate import ServedLM

    lm = lm_mini(vocab_size=64)
    lm.initialize(mx.init.Xavier())
    prefix = save_lm(lm, str(tmp_path / "lm"))

    def oracle(prompt, n):
        toks = list(prompt)
        out = []
        for _ in range(n):
            logits = lm(mx.nd.array([toks], dtype="int32")).asnumpy()[0, -1]
            t = int(np.argmax(logits))
            out.append(t)
            toks.append(t)
        return out

    # gen_outstanding=2: a replica holds at most two of the four clients'
    # requests, so the other replica has to take the rest
    model = ServedLM.load(
        "lmdrain", 1, prefix, replicas=2, queue_depth=16,
        pool_kwargs=dict(heartbeat_ms=500, backoff_ms=50,
                         teardown_grace=1.0, spawn_timeout_s=120,
                         gen_outstanding=2),
        num_pages=32, page_size=4, max_prompt=8, max_new_tokens=16,
        max_batch=4)
    pool = model.pool
    try:
        # the autoscaler's signals exist ROUTER-side for pooled LMs: the
        # p99 objective registered at load, and the admission counter
        # that drives the idle clock (a busy LM pool must never read as
        # eternally cold — review finding)
        assert any(o.name == "serve-p99:lmdrain/1"
                   for o in slo.objectives())
        reqs = telemetry.counter("mxtpu_serve_requests_total",
                                 {"model": "lmdrain/1"})
        reqs_base = reqs.value
        prompts = [[3, 5], [2, 9, 4], [7], [1, 2, 3]]
        budgets = [12, 10, 14, 11]
        oracles = [oracle(p, n) for p, n in zip(prompts, budgets)]
        served = [0] * len(prompts)
        errors = []
        removed_evt = threading.Event()

        def client(i):
            # closed loop: ask again until the replica is removed, so that
            # decodes are in flight whenever the test looks, and at removal
            try:
                while True:
                    last = removed_evt.is_set()
                    out = model.generate(prompts[i],
                                         max_new_tokens=budgets[i],
                                         timeout_ms=90000)
                    # exactly-once: a call resolves once, output == oracle
                    assert out["tokens"] == oracles[i], \
                        (i, out["tokens"], oracles[i])
                    served[i] += 1
                    if last:
                        return
            except Exception as e:  # pragma: no cover - failure detail
                errors.append((i, repr(e)))

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        # wait until the router has decodes in flight on BOTH replicas
        inflight = [telemetry.gauge("mxtpu_serve_replica_inflight",
                                    {"model": "lmdrain/1", "replica": str(r)})
                    for r in pool.replica_ids()]
        deadline = time.monotonic() + 60
        while not all(g.value > 0 for g in inflight):
            assert time.monotonic() < deadline and not errors, \
                ([g.value for g in inflight], errors)
            time.sleep(0.001)
        removed = pool.remove_replica(drain=True, timeout=60)
        removed_evt.set()
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads)
        assert not errors, errors
        assert all(n >= 1 for n in served), served
        assert pool.size == 1
        survivor = pool.replica_ids()[0]
        assert survivor != removed
        # KV pages fully reclaimed on the survivor
        deadline = time.monotonic() + 30
        stats = None
        while time.monotonic() < deadline:
            stats = pool.replica_stats(survivor, timeout=10)
            if stats and stats["kv_pages_used"] == 0:
                break
            time.sleep(0.1)
        assert stats is not None and stats["kv_pages_used"] == 0, stats
        assert stats["pending"] == 0
        # the shrunk pool still generates correctly
        out = model.generate(prompts[0], max_new_tokens=budgets[0],
                             timeout_ms=90000)
        assert out["tokens"] == oracles[0]
        # traffic moved the router-side idle clock + latency series
        assert reqs.value - reqs_base == sum(served) + 1
        snap = telemetry.snapshot()
        hist = snap.get('mxtpu_serve_request_seconds{model="lmdrain/1"}')
        assert hist and hist["count"] >= len(prompts)
        # the idle clock is stamped by the first roll that sees the counter
        # move: roll now rather than wait out the throttle
        telemetry.core.roll_windows(force=True)
        age = autoscaler_mod.request_age_s("lmdrain/1")
        assert age is not None and age < 30.0
    finally:
        model.close(drain=False, timeout=0)
    # objectives retired with the model: no ghost verdicts on /statusz
    assert not any(o.name == "serve-p99:lmdrain/1"
                   for o in slo.objectives())
