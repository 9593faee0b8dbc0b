"""SLO engine tests (docs/observability.md §SLOs): windowed-delta ring
math, burn-rate computation against synthetic traffic, spec parsing,
the /statusz surface, and THE acceptance e2e — a pooled serving run
with injected `slow_reply` faults flips the latency verdict to
breaching within one fast window, /statusz reports it with a burn rate
and an exemplar trace id, and the verdict recovers after the fault
clears.

Everything runs on CPU with tiny windows (the tier-1 budget has no
headroom — ROADMAP.md): unit tests drive rolls with synthetic
timestamps instead of sleeping, and the e2e uses a stub-echo replica
pool, not a real model.
"""
import json
import re
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from mxnet_tpu import telemetry
from mxnet_tpu.telemetry import core, recorder, slo
from mxnet_tpu.telemetry.core import Counter, Gauge, Histogram
from mxnet_tpu.telemetry.slo import Objective, SLOSpecError


# ---------------------------------------------------------------------------
# windowed-delta ring math
# ---------------------------------------------------------------------------

def test_counter_window_roll_rate_and_partial_coverage():
    c = Counter("mxtpu_test_win_total")
    t0 = time.time()
    assert c.windowed_delta(60, t0) is None  # no ring before the first roll
    c.inc(10)
    c._roll(t0, 8)
    c.inc(20)
    c._roll(t0 + 10, 8)
    c.inc(5)
    # full coverage: baseline is the newest entry at-or-before the cutoff
    # (t0+10, cumulative 30) — the window sees only the 5 since
    delta, elapsed = c.windowed_delta(10, t0 + 20)
    assert delta == 5 and abs(elapsed - 10) < 1e-6
    assert c.windowed_rate(10, t0 + 20) == pytest.approx(0.5)
    # a wider window reaches the older baseline (t0, cumulative 10)
    delta, elapsed = c.windowed_delta(15, t0 + 20)
    assert delta == 25 and abs(elapsed - 20) < 1e-6
    # window wider than the ring: partial coverage diffs against the
    # OLDEST entry and reports the real elapsed, not the asked window
    delta, elapsed = c.windowed_delta(10_000, t0 + 20)
    assert delta == 25 and abs(elapsed - 20) < 1e-6


def test_counter_ring_expiry_is_bounded():
    c = Counter("mxtpu_test_win_expiry_total")
    t0 = time.time()
    for i in range(10):  # maxlen 4: the first rolls age out
        c.inc(1)
        c._roll(t0 + i, 4)
    assert len(c._win) == 4
    # baseline can only be as old as the oldest surviving entry (t0+6)
    delta, elapsed = c.windowed_delta(1000, t0 + 9)
    assert delta == 3 and abs(elapsed - 3) < 1e-6


def test_counter_staleness_tracking():
    c = Counter("mxtpu_test_stale_total")
    t0 = time.time()
    c.inc()
    c._roll(t0, 8)
    c._roll(t0 + 5, 8)       # no growth: changed stamp stays at t0
    assert c.seconds_since_change(t0 + 5) == pytest.approx(5.0)
    c.inc()
    c._roll(t0 + 7, 8)       # growth seen at this roll
    assert c.seconds_since_change(t0 + 9) == pytest.approx(2.0)


def test_histogram_window_quantile_and_empty_window():
    h = Histogram("mxtpu_test_win_seconds")
    t0 = time.time()
    assert h.windowed(60, t0) is None
    for v in (0.01, 0.01, 0.01):
        h.observe(v)
    h._roll(t0, 16)
    for v in (0.01, 0.01, 0.01, 0.4):
        h.observe(v)
    w = h.windowed(60, t0 + 10)
    assert w["count"] == 4 and w["sum"] == pytest.approx(0.43)
    assert w["rate"] == pytest.approx(0.4)
    # 3/4 at 10ms, 1/4 at 400ms: the p99 lands in the 0.25..0.5 bucket
    q99 = h.windowed_quantile(0.99, 60, t0 + 10)
    assert 0.25 < q99 <= 0.5
    assert h.windowed_quantile(0.5, 60, t0 + 10) <= 0.01
    # a later roll with no traffic: the window over the quiet period is
    # EMPTY (count 0, quantile None) — the old observations aged out
    h._roll(t0 + 20, 16)
    w2 = h.windowed(5, t0 + 24)
    assert w2["count"] == 0
    assert h.windowed_quantile(0.99, 5, t0 + 24) is None


def test_gauge_window_stats():
    g = Gauge("mxtpu_test_win_gauge")
    t0 = time.time()
    assert g.windowed_stats(60, t0) is None  # live value alone is no window
    g.set(5)
    g._roll(t0, 8)
    g.set(15)
    g._roll(t0 + 1, 8)
    g.set(10)
    s = g.windowed_stats(60, t0 + 2)
    assert s["min"] == 5 and s["max"] == 15 and s["samples"] == 3
    assert s["avg"] == pytest.approx(10.0)
    # a narrow window keeps only fresh samples + the live value
    s2 = g.windowed_stats(1.5, t0 + 2)
    assert s2["min"] == 10 and s2["samples"] == 2


def test_roll_windows_throttle_and_force():
    c = core.get_registry().counter("mxtpu_test_roll_throttle_total")
    assert core.roll_windows(force=True) > 0
    n_immediate = core.roll_windows()  # throttled: within the resolution
    assert n_immediate == 0
    assert core.roll_windows(force=True) > 0
    assert c._win is not None and len(c._win) >= 2


# ---------------------------------------------------------------------------
# burn-rate computation against synthetic traffic
# ---------------------------------------------------------------------------

def _mk_latency_obj(model, threshold=0.1, fast=(60.0,), slow=3600.0):
    return Objective("t-p99:%s" % model, "latency_quantile",
                     metric="mxtpu_serve_request_seconds",
                     labels={"model": model}, quantile=0.99,
                     threshold=threshold, fast_windows=list(fast),
                     slow_window=slow)


def test_latency_burn_rate_breach_and_recovery_synthetic():
    reg = core.get_registry()
    h = reg.histogram("mxtpu_serve_request_seconds", {"model": "syn/1"})
    obj = _mk_latency_obj("syn/1")
    t0 = time.time()
    # healthy traffic: 50 fast requests, then a roll snapshot
    for _ in range(50):
        h.observe(0.01)
    h._roll(t0, 256)
    v = slo._eval_objective(obj, t0 + 1)
    # the window between the roll and now is empty — no data, healthy
    assert v["healthy"] and v["no_data"]
    # slow traffic: half the window's requests over the 100ms threshold
    for _ in range(5):
        h.observe(0.01)
    for _ in range(5):
        h.observe(0.4, exemplar="feedfacecafebeef")
    v = slo._eval_objective(obj, t0 + 30)
    assert not v["healthy"] and v["page"]
    # bad fraction 0.5 against a 1% budget: burn ~50x
    assert v["burn_rate"] == pytest.approx(50.0, rel=0.05)
    assert v["value"] > 0.25  # windowed p99 reflects the slow half
    assert v["exemplar_trace"] == "feedfacecafebeef"
    assert v["budget_remaining"] == 0.0
    # the fault clears: a roll captures the bad epoch as baseline, fresh
    # traffic is all fast — the verdict recovers within one window
    h._roll(t0 + 60, 256)
    for _ in range(20):
        h.observe(0.01)
    v = slo._eval_objective(obj, t0 + 100)
    assert v["healthy"] and not v["page"] and not v["no_data"]
    assert v["burn_rate"] == 0.0
    # the SLOW window still remembers the incident: budget stays charred
    # even though the fast windows (and the page verdict) recovered
    assert v["budget_remaining"] < 1.0


def test_multiwindow_page_needs_every_fast_window():
    reg = core.get_registry()
    h = reg.histogram("mxtpu_serve_request_seconds", {"model": "mw/1"})
    obj = _mk_latency_obj("mw/1", fast=(10.0, 100.0), slow=3600.0)
    t0 = time.time()
    h._roll(t0, 256)
    for _ in range(10):
        h.observe(0.4)
    h._roll(t0 + 50, 256)   # bad burst, then quiet
    v = slo._eval_objective(obj, t0 + 70)
    # the 100s window still burns, but the 10s window is empty — the
    # blip does NOT page (SRE multi-window), though the long window shows
    assert not v["page"] and v["healthy"]
    assert v["windows"]["10s"]["no_data"]
    assert v["windows"]["100s"]["burn"] > 1.0


def test_error_rate_burn_synthetic():
    reg = core.get_registry()
    good = reg.counter("mxtpu_serve_requests_total", {"model": "er/1"})
    bad = reg.counter("mxtpu_serve_rejected_total",
                      {"model": "er/1", "reason": "deadline"})
    obj = Objective("t-avail:er/1", "error_rate",
                    bad=[("mxtpu_serve_rejected_total", {"model": "er/1"})],
                    total=[("mxtpu_serve_requests_total", {"model": "er/1"}),
                           ("mxtpu_serve_rejected_total", {"model": "er/1"})],
                    budget=0.01, fast_windows=[60.0], slow_window=3600.0)
    t0 = time.time()
    good.inc(100)
    good._roll(t0, 64)
    bad._roll(t0, 64)
    good.inc(90)
    bad.inc(10)
    v = slo._eval_objective(obj, t0 + 30)
    assert not v["healthy"]
    assert v["value"] == pytest.approx(0.1)          # 10 bad / 100 total
    assert v["burn_rate"] == pytest.approx(10.0)     # vs 1% budget
    # quiet period (rolls continue, no traffic) => no verdict, not a
    # breach — absent traffic must never read as burning
    good._roll(t0 + 60, 64)
    bad._roll(t0 + 60, 64)
    v2 = slo._eval_objective(obj, t0 + 10_000)
    assert v2["no_data"] and v2["healthy"]


def test_gauge_ceiling_and_floor_objectives():
    reg = core.get_registry()
    g = reg.gauge("mxtpu_serve_queue_depth", {"model": "gc/1"})
    ceiling = Objective("t-queue:gc/1", "gauge_ceiling",
                        metric="mxtpu_serve_queue_depth",
                        labels={"model": "gc/1"}, threshold=8.0,
                        budget=0.25, fast_windows=[60.0], slow_window=3600.0)
    t0 = time.time()
    g.set(2)
    g._roll(t0, 64)
    v = slo._eval_objective(ceiling, t0 + 1)
    assert v["healthy"] and not v["no_data"]
    # every sample over the ceiling: violation fraction 1.0 vs 0.25 budget
    for i in range(3):
        g.set(30)
        g._roll(t0 + 2 + i, 64)
    v = slo._eval_objective(ceiling, t0 + 6)
    assert v["page"] and v["burn_rate"] >= 2.0
    assert v["value"] == 30
    floor = Objective("t-floor:gc/1", "gauge_floor",
                      metric="mxtpu_serve_queue_depth",
                      labels={"model": "gc/1"}, threshold=100.0,
                      budget=0.25, fast_windows=[60.0], slow_window=3600.0)
    v = slo._eval_objective(floor, t0 + 6)  # all samples under the floor
    assert v["page"] and v["value"] == 2


def test_staleness_objective():
    reg = core.get_registry()
    c = reg.counter("mxtpu_steps_total", {"kind": "stale-test"})
    obj = Objective("t-stale", "staleness", metric="mxtpu_steps_total",
                    labels={"kind": "stale-test"}, threshold=30.0,
                    fast_windows=[60.0], slow_window=3600.0)
    t0 = time.time()
    c.inc()
    c._roll(t0, 64)
    assert slo._eval_objective(obj, t0 + 10)["healthy"]  # 10s < 30s
    v = slo._eval_objective(obj, t0 + 100)               # 100s stale
    assert v["page"] and v["value"] == pytest.approx(100.0, abs=1.0)
    assert v["burn_rate"] == pytest.approx(100.0 / 30.0, rel=0.05)


# ---------------------------------------------------------------------------
# spec parsing: malformed JSON / unknown kind / unknown metric are EAGER
# ---------------------------------------------------------------------------

def test_spec_malformed_json_is_typed_error(tmp_path):
    p = tmp_path / "bad.json"
    p.write_text("{not json")
    with pytest.raises(SLOSpecError, match="not valid JSON"):
        slo.load_spec(str(p))
    missing = tmp_path / "nope.json"
    with pytest.raises(SLOSpecError, match="cannot read"):
        slo.load_spec(str(missing))
    p2 = tmp_path / "shape.json"
    p2.write_text(json.dumps({"objectives": "not-a-list"}))
    with pytest.raises(SLOSpecError, match="objectives"):
        slo.load_spec(str(p2))


def test_spec_unknown_kind_and_metric_fail_eagerly():
    with pytest.raises(SLOSpecError, match="unknown kind"):
        Objective("x", "quantile_of_vibes",
                  metric="mxtpu_serve_request_seconds", threshold=1.0)
    with pytest.raises(SLOSpecError, match="unknown metric"):
        Objective("x", "latency_quantile",
                  metric="mxtpu_totally_made_up_seconds", threshold=1.0)
    with pytest.raises(SLOSpecError, match="not a valid mxtpu"):
        Objective("x", "latency_quantile", metric="http_requests_total",
                  threshold=1.0)
    # the escape hatch: bespoke instrumentation may opt out of the catalog
    obj = Objective("x", "latency_quantile",
                    metric="mxtpu_totally_made_up_seconds", threshold=1.0,
                    allow_unknown_metric=True)
    assert obj.metric == "mxtpu_totally_made_up_seconds"


def test_spec_field_validation():
    with pytest.raises(SLOSpecError, match="threshold"):
        Objective("x", "latency_quantile",
                  metric="mxtpu_serve_request_seconds")
    with pytest.raises(SLOSpecError, match="quantile"):
        Objective("x", "latency_quantile",
                  metric="mxtpu_serve_request_seconds", threshold=0.1,
                  quantile=1.5)
    with pytest.raises(SLOSpecError, match="budget"):
        Objective("x", "error_rate",
                  bad=["mxtpu_serve_rejected_total"],
                  total=["mxtpu_serve_requests_total"])
    with pytest.raises(SLOSpecError, match="unknown key"):
        Objective.from_spec({"name": "x", "kind": "latency_quantile",
                             "metric": "mxtpu_serve_request_seconds",
                             "treshold_ms": 100})
    with pytest.raises(SLOSpecError, match="threshold OR"):
        Objective.from_spec({"name": "x", "kind": "latency_quantile",
                             "metric": "mxtpu_serve_request_seconds",
                             "threshold": 0.1, "threshold_ms": 100})


def test_spec_roundtrip_registers_objectives(tmp_path):
    p = tmp_path / "slo.json"
    p.write_text(json.dumps({"objectives": [
        {"name": "spec-p99", "kind": "latency_quantile",
         "metric": "mxtpu_serve_request_seconds",
         "labels": {"model": "spec/1"}, "quantile": 0.95,
         "threshold_ms": 200},
        {"name": "spec-avail", "kind": "error_rate", "availability": 0.99,
         "bad": [{"metric": "mxtpu_serve_rejected_total",
                  "labels": {"model": "spec/1"}}],
         "total": [{"metric": "mxtpu_serve_requests_total",
                    "labels": {"model": "spec/1"}}]},
    ]}))
    try:
        objs = slo.load_spec(str(p))
        assert [o.name for o in objs] == ["spec-p99", "spec-avail"]
        assert objs[0].threshold == pytest.approx(0.2)
        assert objs[0].quantile == 0.95
        assert objs[1].budget == pytest.approx(0.01)
        names = {o.name for o in slo.objectives()}
        assert {"spec-p99", "spec-avail"} <= names
    finally:
        slo.unregister("spec-p99")
        slo.unregister("spec-avail")


# ---------------------------------------------------------------------------
# evaluator: gauges, transition events, the alerts ring, flight recorder
# ---------------------------------------------------------------------------

def test_evaluator_publishes_gauges_events_and_alerts():
    reg = core.get_registry()
    h = reg.histogram("mxtpu_serve_request_seconds", {"model": "pub/1"})
    obj = _mk_latency_obj("pub/1", fast=(60.0,), slow=3600.0)
    slo.register(obj)
    slo.stop()  # drive transitions manually: single-writer, deterministic
    try:
        t0 = time.time()
        h._roll(t0, 256)
        for _ in range(10):
            h.observe(0.4, exemplar="deadbeef00000001")
        slo._evaluate_and_publish(t0 + 30)
        snap = telemetry.snapshot()
        assert snap['mxtpu_slo_healthy{slo="t-p99:pub/1"}']["value"] == 0
        assert snap['mxtpu_slo_burn_rate{slo="t-p99:pub/1"}']["value"] \
            >= 1.0
        breaches = [e for e in telemetry.events()
                    if e["event"] == "slo_breach"
                    and e["fields"].get("slo") == "t-p99:pub/1"]
        assert breaches, "breach transition must land in the event ring"
        assert breaches[-1]["fields"]["exemplar_trace"] == \
            "deadbeef00000001"
        # re-evaluating while still breaching must NOT re-emit the event
        slo._evaluate_and_publish(t0 + 31)
        assert len([e for e in telemetry.events()
                    if e["event"] == "slo_breach"
                    and e["fields"].get("slo") == "t-p99:pub/1"]) == \
            len(breaches)
        # recovery: quiet epoch rolls by, fresh traffic is fast
        h._roll(t0 + 60, 256)
        for _ in range(10):
            h.observe(0.01)
        slo._evaluate_and_publish(t0 + 90)
        snap = telemetry.snapshot()
        assert snap['mxtpu_slo_healthy{slo="t-p99:pub/1"}']["value"] == 1
        recovered = [e for e in telemetry.events()
                     if e["event"] == "slo_recovered"
                     and e["fields"].get("slo") == "t-p99:pub/1"]
        assert recovered and recovered[-1]["fields"]["burned_for_s"] > 0
        # both transitions in the bounded alerts ring, oldest first
        kinds = [a["event"] for a in recorder.alerts()
                 if a["fields"].get("slo") == "t-p99:pub/1"]
        assert kinds[-2:] == ["slo_breach", "slo_recovered"]
    finally:
        slo.unregister(obj.name)


def test_unregister_retires_published_gauges():
    """A model unloaded while breaching must not export a permanently
    breaching mxtpu_slo_healthy series forever."""
    reg = core.get_registry()
    h = reg.histogram("mxtpu_serve_request_seconds", {"model": "gone/1"})
    obj = _mk_latency_obj("gone/1")
    slo.register(obj)
    slo.stop()
    t0 = time.time()
    h._roll(t0, 64)
    for _ in range(5):
        h.observe(0.4)
    slo._evaluate_and_publish(t0 + 30)
    key = 'mxtpu_slo_healthy{slo="%s"}' % obj.name
    assert telemetry.snapshot()[key]["value"] == 0  # breaching
    slo.unregister_model("gone/1")
    snap = telemetry.snapshot()
    assert key not in snap
    assert 'mxtpu_slo_burn_rate{slo="%s"}' % obj.name not in snap
    assert not any(o.labels.get("model") == "gone/1"
                   for o in slo.objectives())


def test_spec_objective_survives_model_unload_reload(tmp_path):
    """An operator's spec objective scoped to a model must come back on
    reload — not silently revert to the env-default built-in."""
    p = tmp_path / "spec.json"
    p.write_text(json.dumps({"objectives": [
        {"name": "serve-p99:reload/1", "kind": "latency_quantile",
         "metric": "mxtpu_serve_request_seconds",
         "labels": {"model": "reload/1"}, "threshold_ms": 123}]}))
    try:
        slo.load_spec(str(p))
        slo.wire_serving_objectives("reload/1", queue_depth=8)
        by_name = {o.name: o for o in slo.objectives()}
        assert by_name["serve-p99:reload/1"].threshold == \
            pytest.approx(0.123)  # spec beats the built-in default
        slo.unregister_model("reload/1")  # the model unloads
        assert "serve-p99:reload/1" not in {o.name
                                            for o in slo.objectives()}
        slo.wire_serving_objectives("reload/1", queue_depth=8)  # reload
        by_name = {o.name: o for o in slo.objectives()}
        assert by_name["serve-p99:reload/1"].threshold == \
            pytest.approx(0.123), "spec objective lost on reload"
    finally:
        slo.unregister_model("reload/1")
        with slo._REG_LOCK:
            slo._STATE.spec_objectives.pop("serve-p99:reload/1", None)


def test_spec_load_failure_is_not_latched(tmp_path, monkeypatch):
    """A typo'd MXTPU_SLO_SPEC fails the triggering load EAGERLY — and a
    corrected file must be retried by the next load, not silently skipped
    for the process lifetime."""
    p = tmp_path / "spec.json"
    p.write_text("{broken")
    monkeypatch.setenv("MXTPU_SLO_SPEC", str(p))
    saved = dict(slo._STATE.objectives)
    slo.clear()  # resets the spec_loaded latch for this test
    try:
        with pytest.raises(SLOSpecError):
            slo._ensure_spec()
        # operator fixes the file; the SAME process retries and registers
        p.write_text(json.dumps({"objectives": [
            {"name": "latched-p99", "kind": "latency_quantile",
             "metric": "mxtpu_serve_request_seconds",
             "threshold_ms": 100}]}))
        slo._ensure_spec()
        assert any(o.name == "latched-p99" for o in slo.objectives())
    finally:
        slo.clear()
        with slo._REG_LOCK:
            slo._STATE.objectives.update(saved)


def test_flightrec_dump_carries_alerts_ring(tmp_path):
    recorder.record_alert("slo_breach", {"slo": "dump-test",
                                         "burn_rate": 9.9})
    path = recorder.dump("test-alerts", path=str(tmp_path / "fr.json"))
    assert path is not None
    doc = json.loads((tmp_path / "fr.json").read_text())
    assert "alerts" in doc
    mine = [a for a in doc["alerts"]
            if a["fields"].get("slo") == "dump-test"]
    assert mine and mine[-1]["event"] == "slo_breach"
    assert mine[-1]["fields"]["burn_rate"] == 9.9


# ---------------------------------------------------------------------------
# /statusz
# ---------------------------------------------------------------------------

def test_statusz_payload_sections():
    reg = core.get_registry()
    h = reg.histogram("mxtpu_serve_request_seconds", {"model": "szp/1"})
    for _ in range(5):
        h.observe(0.02, exemplar="0123456789abcdef")
    core.roll_windows(force=True)
    obj = _mk_latency_obj("szp/1")
    slo.register(obj)
    try:
        p = slo.statusz_payload(extra={"server": {"port": 1}})
        for key in ("slo", "rates", "pools", "compile_cache", "memory",
                    "slowest_exemplars", "server"):
            assert key in p, key
        assert any(v["slo"] == obj.name for v in p["slo"]["verdicts"])
        assert "szp/1" in p["rates"]["serving"]
        row = p["rates"]["serving"]["szp/1"]
        assert row["p99_ms"] is None or row["p99_ms"] >= 0
        assert any(e["trace"] == "0123456789abcdef"
                   for e in p["slowest_exemplars"])
        # text rendering covers the same document without raising
        text = slo._render_text(p)
        assert "statusz @" in text and obj.name in text
    finally:
        slo.unregister(obj.name)


def test_statusz_on_telemetry_exporter():
    port = telemetry.start_http_server(port=0)
    assert port
    with urllib.request.urlopen(
            "http://127.0.0.1:%d/statusz" % port, timeout=10) as r:
        assert r.status == 200
        assert r.headers["Content-Type"].startswith("application/json")
        doc = json.loads(r.read())
    assert doc["version"] == 1 and "slo" in doc and "rates" in doc
    with urllib.request.urlopen(
            "http://127.0.0.1:%d/statusz?format=text" % port,
            timeout=10) as r:
        assert r.status == 200
        assert r.read().startswith(b"statusz @")


# ---------------------------------------------------------------------------
# overhead: the hot path must not notice the SLO engine (PR-3 bar)
# ---------------------------------------------------------------------------

def test_slo_enabled_vs_disabled_step_overhead_under_2pct():
    """Same shape as the PR-3 acceptance: per-call observe_step cost,
    enabled minus disabled, as a fraction of a realistic ~1ms step — but
    measured WITH the SLO engine armed (objectives registered, rings
    rolled, evaluator running). The dispatch hot path is unchanged by
    design; this pins it."""
    reg = core.get_registry()
    h = reg.histogram("mxtpu_serve_request_seconds", {"model": "ovh/1"})
    h.observe(0.001)
    obj = _mk_latency_obj("ovh/1")
    slo.register(obj)  # starts the evaluator
    core.roll_windows(force=True)
    assert slo.running()

    def per_call_cost(chunks=40, inner=500):
        best = float("inf")
        for _ in range(chunks):
            t0 = time.perf_counter()
            for i in range(inner):
                telemetry.observe_step(0.001, examples=32, step=i,
                                       kind="slo-bench")
            best = min(best, (time.perf_counter() - t0) / inner)
        return best

    try:
        telemetry.observe_step(0.001, examples=32, step=0,
                               kind="slo-bench")  # warm
        cost_on = per_call_cost()
        telemetry.set_enabled(False)
        try:
            cost_off = per_call_cost()
        finally:
            telemetry.set_enabled(True)
        cost = max(0.0, cost_on - cost_off)
        a = np.random.rand(384, 384).astype(np.float32)
        a.dot(a)
        step = min((lambda t0=time.perf_counter(): (
            [a.dot(a) for _ in range(10)],
            (time.perf_counter() - t0) / 10)[1])() for _ in range(20))
        overhead = cost / step
        assert overhead < 0.02, \
            "SLO-armed per-step overhead %.3f%% (cost %.2fus vs step " \
            "%.0fus)" % (overhead * 100.0, cost * 1e6, step * 1e6)
    finally:
        slo.unregister(obj.name)


# ---------------------------------------------------------------------------
# THE acceptance e2e: slow_reply fault -> latency verdict flips ->
# /statusz reports burn rate + exemplar trace -> recovery after clear
# ---------------------------------------------------------------------------

def _get_statusz(port):
    with urllib.request.urlopen(
            "http://127.0.0.1:%d/statusz" % port, timeout=10) as r:
        assert r.status == 200
        return json.loads(r.read())


def _post_predict(port, name, x, timeout_ms):
    body = json.dumps({"inputs": {"x": [[x]]},
                       "timeout_ms": timeout_ms}).encode()
    req = urllib.request.Request(
        "http://127.0.0.1:%d/v1/models/%s:predict" % (port, name),
        data=body, headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=30) as r:
        return r.status, json.loads(r.read())


def test_statusz_e2e_slow_reply_breach_and_recovery(monkeypatch):
    """ISSUE 14 acceptance: a pooled serving run with injected
    `slow_reply` faults flips the latency SLO verdict to breaching
    within one fast window, /statusz reports it with a burn rate and an
    exemplar trace id, and the verdict recovers after the fault clears."""
    from mxnet_tpu.serving import ModelRepository, ServedModel, \
        ServingServer

    # tiny windows so breach AND recovery fit in seconds, not minutes
    monkeypatch.setenv("MXTPU_SLO_WINDOW_MS", "200")
    monkeypatch.setenv("MXTPU_SLO_EVAL_MS", "150")
    monkeypatch.setenv("MXTPU_SLO_FAST_WINDOWS", "3")
    monkeypatch.setenv("MXTPU_SLO_SLOW_WINDOW_S", "30")
    monkeypatch.setenv("MXTPU_SLO_SERVE_P99_MS", "1000")
    slo.stop()  # fresh evaluator picks up the test cadence

    tracing = telemetry.tracing
    tracing.configure(sample=1.0)  # exemplars need recorded traces
    faults = " ".join("slow_reply@batch=%d,ms=1500" % b
                      for b in range(1, 5))
    model = ServedModel.pooled(
        "sloe2e", 1, None, 2,
        worker_args=["--stub", "echo", "--input", "x=1", "--max-batch", "2"],
        heartbeat_ms=500, backoff_ms=50, teardown_grace=1.0,
        spawn_timeout_s=90, max_delay_ms=1, queue_depth=64,
        extra_env={"MXTPU_FAULT_INJECT": faults})
    repo = ModelRepository()
    repo.add(model)
    srv = ServingServer(repo, port=0, addr="127.0.0.1").start()
    objective = "serve-p99:sloe2e/1"
    try:
        assert any(o.name == objective for o in slo.objectives())
        assert slo.running()

        def verdict_of(doc):
            for v in doc["slo"]["verdicts"]:
                if v["slo"] == objective:
                    return v
            return None

        # phase 1: slow replies (1.5s >> the 1s p99 objective) until the
        # evaluator pages. Each request is its own batch (max_delay 1ms,
        # sequential sends), so the per-replica batch counter walks
        # through the injected range deterministically.
        t_first_slow = time.monotonic()
        breach = None
        for i in range(8):
            code, _ = _post_predict(srv.port, "sloe2e", float(i),
                                    timeout_ms=20000)
            assert code == 200
            deadline = time.monotonic() + 2.0
            while breach is None and time.monotonic() < deadline:
                v = verdict_of(_get_statusz(srv.port))
                if v is not None and v["page"]:
                    breach = v
                    break
                time.sleep(0.05)
            if breach is not None:
                break
        assert breach is not None, \
            "latency verdict never flipped to breaching"
        # flipped within one fast window of the slow traffic (+ slack for
        # a loaded box — the window itself is 3s)
        assert time.monotonic() - t_first_slow < 30.0
        assert breach["burn_rate"] >= 1.0
        assert breach["value"] is not None and breach["value"] > 1.0
        assert re.fullmatch(r"[0-9a-f]{16}", breach["exemplar_trace"] or \
                            ""), breach["exemplar_trace"]
        # the breach transition reached the alerts ring and /statusz
        doc = _get_statusz(srv.port)
        alerts = [a for a in doc["slo"]["alerts"]
                  if a["fields"].get("slo") == objective]
        assert alerts and alerts[-1]["event"] == "slo_breach"
        assert doc["server"]["port"] == srv.port
        # pool health generations ride the lock-free gauge table
        assert doc["pools"].get("sloe2e/1", {}).get("size") == 2

        # phase 2: the fault range is exhausted — fast traffic only, and
        # the verdict recovers once the bad epoch slides out of the fast
        # window
        recovered = None
        deadline = time.monotonic() + 30.0
        while recovered is None and time.monotonic() < deadline:
            code, _ = _post_predict(srv.port, "sloe2e", 1.0,
                                    timeout_ms=20000)
            assert code == 200
            v = verdict_of(_get_statusz(srv.port))
            if v is not None and v["healthy"] and not v["no_data"]:
                recovered = v
                break
            time.sleep(0.1)
        assert recovered is not None, "verdict never recovered"
        assert not recovered["page"]
        doc = _get_statusz(srv.port)
        alerts = [a for a in doc["slo"]["alerts"]
                  if a["fields"].get("slo") == objective]
        assert alerts[-1]["event"] == "slo_recovered"
    finally:
        tracing.configure()
        srv.shutdown()
        model.close(drain=False, timeout=0)
        slo.stop()
