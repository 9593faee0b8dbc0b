"""Distributed tracing (telemetry/tracing.py) + automatic FLOP accounting.

Units: id/header/wire codecs, sampling decisions, cross-thread context
propagation, the always-sample-on-slow hatch, the lock-free active-span
table the flight recorder snapshots, histogram trace-id exemplars, and
cost-analysis FLOP extraction. The tier-1 e2e at the bottom drives ONE
HTTP request through a 2-replica stub pool and asserts the merged
perfetto trace crosses all three serving roles (server, router, worker)
with correct parentage — everything stays milliseconds-small: the suite
wall-time budget has no headroom (ROADMAP.md).
"""
import importlib.util
import json
import os
import threading
import time
import urllib.request

import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.telemetry import flops, tracing

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def clean_tracing():
    """Give each test a pristine tracing module and put it back after."""
    tracing.configure()
    tracing.drain_pending()
    tracing._BUFFER.clear()
    yield tracing
    tracing.configure()
    tracing.drain_pending()
    tracing._BUFFER.clear()


# ---------------------------------------------------------------------------
# ids, header, wire codecs
# ---------------------------------------------------------------------------

def test_header_roundtrip(clean_tracing):
    ref = tracing.SpanRef("ab" * 8, "cd" * 4, sampled=True)
    parsed = tracing.parse_header(tracing.header_value(ref))
    assert (parsed.trace_id, parsed.span_id, parsed.sampled) == \
        (ref.trace_id, ref.span_id, True)
    unsampled = tracing.parse_header(
        tracing.header_value(tracing.SpanRef("ab" * 8, "cd" * 4)))
    assert unsampled.sampled is False


@pytest.mark.parametrize("bad", [
    "", "garbage", "zz" * 8 + "-" + "cd" * 4 + "-01",   # non-hex trace
    "abc", "a-b", "--", "ab-cd", None,
])
def test_malformed_header_is_none_not_error(clean_tracing, bad):
    """A bad client header must start a fresh trace, never 500."""
    assert tracing.parse_header(bad) is None


def test_wire_roundtrip(clean_tracing):
    ref = tracing.SpanRef("12" * 8, "34" * 4, sampled=True)
    back = tracing.from_wire(tracing.to_wire(ref))
    assert (back.trace_id, back.span_id, back.sampled) == \
        ("12" * 8, "34" * 4, True)
    assert tracing.to_wire(None) is None
    assert tracing.from_wire(None) is None


# ---------------------------------------------------------------------------
# sampling
# ---------------------------------------------------------------------------

def test_sample_rate_zero_is_noop(clean_tracing):
    tracing.configure(sample=0.0)
    with tracing.root("unit.root") as sp:
        assert not sp.recorded
        with tracing.span("unit.child") as ch:
            assert not ch.recorded
    assert tracing.drain_pending() == []
    # ids still exist for correlation even when nothing records
    assert len(tracing.mint().trace_id) == tracing.TRACE_ID_LEN


def test_sample_rate_one_records_tree(clean_tracing):
    tracing.configure(sample=1.0)
    with tracing.root("unit.root", component="train",
                      attrs={"step": 7}) as sp:
        assert sp.recorded and sp.parent_id is None
        with tracing.span("unit.child") as ch:
            assert ch.trace_id == sp.trace_id
            assert ch.parent_id == sp.span_id
            assert ch.component == "train"  # inherited lane
    recs = tracing.drain_pending()
    assert [r["name"] for r in recs] == ["unit.child", "unit.root"]
    child, root = recs
    assert root["parent"] is None and root["attrs"] == {"step": 7}
    assert child["parent"] == root["span"]
    assert child["trace"] == root["trace"]
    assert root["dur_us"] >= child["dur_us"] >= 0


def test_incoming_sampled_ref_overrides_local_rate(clean_tracing):
    """An upstream process's sampled flag wins over local rate 0."""
    tracing.configure(sample=0.0)
    ref = tracing.SpanRef("ee" * 8, "ff" * 4, sampled=True)
    with tracing.root("unit.inherited", ref=tracing.mint(ref)) as sp:
        assert sp.recorded
        assert sp.trace_id == "ee" * 8 and sp.parent_id == "ff" * 4
    (rec,) = tracing.drain_pending()
    assert rec["trace"] == "ee" * 8


# ---------------------------------------------------------------------------
# cross-thread propagation
# ---------------------------------------------------------------------------

def test_capture_propagates_across_threads(clean_tracing):
    tracing.configure(sample=1.0)
    out = {}

    def worker(ref):
        # the worker thread has no span of its own ...
        assert tracing.current() is None
        # ... but parents under the captured admission context
        with tracing.span("unit.other_thread", parent=ref) as sp:
            out["trace"] = sp.trace_id
            out["parent"] = sp.parent_id
        out["sid"] = tracing.emit_span("unit.retro", time.time(), 0.001,
                                       ref)

    with tracing.root("unit.root") as root_sp:
        ref = tracing.capture()
        assert ref.span_id == root_sp.span_id
        t = threading.Thread(target=worker, args=(ref,))
        t.start()
        t.join()
    assert out["trace"] == root_sp.trace_id
    assert out["parent"] == root_sp.span_id
    recs = {r["name"]: r for r in tracing.drain_pending()}
    assert set(recs) == {"unit.other_thread", "unit.retro", "unit.root"}
    assert recs["unit.retro"]["span"] == out["sid"]
    assert recs["unit.retro"]["parent"] == root_sp.span_id
    # capture outside any span is None
    assert tracing.capture() is None


def test_child_ref_pre_mints_the_wire_id(clean_tracing):
    """The router mints the dispatch span id BEFORE the wire send; the
    record emitted later under that id keeps the pre-minted identity."""
    tracing.configure(sample=1.0)
    with tracing.root("unit.root") as sp:
        ref = tracing.child_ref(sp)
        sid = tracing.emit_span("unit.dispatch", time.time(), 0.002, sp,
                                span_id=ref.span_id)
        assert sid == ref.span_id
    recs = {r["name"]: r for r in tracing.drain_pending()}
    assert recs["unit.dispatch"]["span"] == ref.span_id
    # an unrecorded parent pre-mints nothing
    assert tracing.child_ref(None) is None


# ---------------------------------------------------------------------------
# always-sample-on-slow hatch
# ---------------------------------------------------------------------------

def test_slow_hatch_emits_only_overrunning_traces(clean_tracing):
    tracing.configure(slow_ms=40.0)
    # fast root: buffered spans are discarded at the verdict
    with tracing.root("unit.fast"):
        with tracing.span("unit.fast_child"):
            pass
    assert tracing.drain_pending() == []
    assert tracing._BUFFER == {}
    # slow root: the whole buffered tree lands, marked slow
    with tracing.root("unit.slow"):
        with tracing.span("unit.slow_child"):
            time.sleep(0.06)
    recs = tracing.drain_pending()
    assert sorted(r["name"] for r in recs) == ["unit.slow",
                                               "unit.slow_child"]
    assert all(r.get("slow") for r in recs)
    assert tracing._BUFFER == {}


# ---------------------------------------------------------------------------
# active-span table (flight recorder integration)
# ---------------------------------------------------------------------------

def test_active_spans_snapshot(clean_tracing):
    tracing.configure(sample=1.0)
    me = str(threading.get_ident())
    assert me not in tracing.active_spans()
    with tracing.root("unit.outer", component="train"):
        with tracing.span("unit.inner"):
            snap = tracing.active_spans()[me]
            assert [s["name"] for s in snap] == ["unit.outer",
                                                 "unit.inner"]
            assert snap[0]["component"] == "train"
            assert all(s["open_s"] >= 0 for s in snap)
    # table holds no entries for idle threads (bounded by construction)
    assert me not in tracing.active_spans()
    tracing.drain_pending()


def test_flight_recorder_dump_carries_active_spans(clean_tracing, tmp_path):
    tracing.configure(sample=1.0)
    with tracing.root("unit.hung_phase", component="train"):
        path = telemetry.dump("unit-test", path=str(tmp_path / "fr.json"))
        data = json.load(open(path))
        spans = data["active_spans"][str(threading.get_ident())]
        assert [s["name"] for s in spans] == ["unit.hung_phase"]
    tracing.drain_pending()


# ---------------------------------------------------------------------------
# histogram exemplars
# ---------------------------------------------------------------------------

def test_histogram_exemplars_link_buckets_to_traces(clean_tracing):
    reg = telemetry.get_registry()
    h = reg.histogram("unit_exemplar_seconds", {"case": "a"},
                      bounds=(0.1, 1.0))
    h.observe(0.05)                      # untraced: no exemplar
    h.observe(0.05, exemplar="t" * 16)   # traced, bucket 0.1
    h.observe(5.0, exemplar="u" * 16)    # traced, tail bucket
    ex = h.exemplars()
    assert ex["0.1"]["trace"] == "t" * 16
    assert ex["+Inf"]["trace"] == "u" * 16 and ex["+Inf"]["value"] == 5.0
    assert h.snapshot()["exemplars"] == ex
    # last-exemplar-wins per bucket (OpenMetrics semantics)
    h.observe(0.06, exemplar="v" * 16)
    assert h.exemplars()["0.1"]["trace"] == "v" * 16


def test_current_trace_id_feeds_exemplars(clean_tracing):
    tracing.configure(sample=1.0)
    assert tracing.current_trace_id() is None
    with tracing.root("unit.root") as sp:
        assert tracing.current_trace_id() == sp.trace_id
    tracing.drain_pending()


# ---------------------------------------------------------------------------
# automatic FLOP accounting
# ---------------------------------------------------------------------------

def test_cost_analysis_flops_shapes():
    assert flops.cost_analysis_flops({"flops": 12.0}) == 12.0
    assert flops.cost_analysis_flops(
        [{"flops": 3.0}, {"flops": 4.0}, {"other": 1}]) == 7.0
    assert flops.cost_analysis_flops({}) is None
    assert flops.cost_analysis_flops(None) is None
    assert flops.cost_analysis_flops({"flops": -1.0}) is None


def test_instrument_accumulates_matmul_flops():
    """A known matmul: 2*m*k*n FLOPs, memoized per shape signature."""
    import jax
    import jax.numpy as jnp

    if not flops.enabled():
        pytest.skip("MXTPU_TRACE_FLOPS disabled in this environment")
    f = flops.instrument(jax.jit(lambda a, b: a @ b))
    a = jnp.ones((8, 16), jnp.float32)
    b = jnp.ones((16, 4), jnp.float32)
    flops.take_step_delta()  # reset the step mark
    f(a, b)
    one = flops.take_step_delta()
    assert one == pytest.approx(2 * 8 * 16 * 4, rel=0.25)
    # second call with the SAME signature: dict hit, same accumulation
    f(a, b)
    assert flops.take_step_delta() == pytest.approx(one)
    memo = f._flops_memo
    assert len(memo._by_sig) == 1
    # a new shape signature pays one more analysis
    f(jnp.ones((2, 16), jnp.float32), b)
    assert len(memo._by_sig) == 2


def test_observe_step_publishes_auto_flops():
    """With no manual set_step_flops, observe_step attributes the FLOPs
    accumulated since the last step (the auto MFU numerator)."""
    if not flops.enabled():
        pytest.skip("MXTPU_TRACE_FLOPS disabled in this environment")
    flops.take_step_delta()
    flops.accumulate(3.5e9)
    telemetry.observe_step(0.5, examples=4, kind="tracing_unit")
    snap = telemetry.snapshot()
    key = 'mxtpu_step_flops_auto{kind="tracing_unit"}'
    assert snap[key]["value"] == pytest.approx(3.5e9)
    assert flops.last_step_flops() == pytest.approx(3.5e9)


def test_nd_op_dispatch_feeds_the_accumulator():
    """ops._jitted executables are instrumented: running an op moves the
    process-wide FLOP counter."""
    if not flops.enabled():
        pytest.skip("MXTPU_TRACE_FLOPS disabled in this environment")
    a = mx.nd.ones((16, 32))
    b = mx.nd.ones((32, 8))
    mx.nd.dot(a, b).asnumpy()  # may or may not be the cache fill
    before = flops.total()
    mx.nd.dot(a, b).asnumpy()
    assert flops.total() - before == pytest.approx(2 * 16 * 32 * 8,
                                                   rel=0.25)


# ---------------------------------------------------------------------------
# trace_merge: spans + mixed/old formats
# ---------------------------------------------------------------------------

def _load_trace_merge():
    spec = importlib.util.spec_from_file_location(
        "trace_merge", os.path.join(_ROOT, "tools", "trace_merge.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_trace_merge_mixed_spans_chrome_and_old_format(tmp_path):
    tm = _load_trace_merge()
    # new-format JSONL: two processes of one trace (+ a torn tail line)
    srv = [{"kind": "span", "name": "serve.request", "trace": "t1",
            "span": "s1", "parent": None, "component": "server",
            "ts": 100.0, "dur_us": 900.0, "pid": 10, "rank": 0,
            "thread": "http"},
           {"kind": "span", "name": "serve.dispatch", "trace": "t1",
            "span": "s2", "parent": "s1", "component": "router",
            "ts": 100.1, "dur_us": 500.0, "pid": 10, "rank": 0,
            "thread": "dispatch"},
           {"kind": "metrics", "ts": 100.2, "metrics": {}}]
    wrk = [{"kind": "span", "name": "serve.compute", "trace": "t1",
            "span": "s3", "parent": "s2", "component": "worker",
            "ts": 100.2, "dur_us": 300.0, "pid": 11, "rank": 0,
            "thread": "MainThread"},
           # a second, unrelated trace the --trace filter must drop
           {"kind": "span", "name": "serve.compute", "trace": "t2",
            "span": "s9", "parent": None, "component": "worker",
            "ts": 200.0, "dur_us": 10.0, "pid": 11, "rank": 0,
            "thread": "MainThread"}]
    (tmp_path / "srv.jsonl").write_text(
        "\n".join(json.dumps(r) for r in srv) + '\n{"kind": "spa')
    (tmp_path / "wrk.jsonl").write_text(
        "\n".join(json.dumps(r) for r in wrk) + "\n")
    # launcher-shaped span record (event wrapper)
    (tmp_path / "launcher-events.jsonl").write_text(json.dumps(
        {"kind": "event", "event": "span", "ts": 99.9,
         "fields": {"name": "launch.generation", "trace": "t1",
                    "span": "s0", "parent": None, "component": "launcher",
                    "ts": 99.9, "dur_us": 2e6, "pid": 9}}) + "\n")
    # old-format (span-less) telemetry JSONL: tolerated, contributes zero
    (tmp_path / "old.jsonl").write_text(
        json.dumps({"kind": "metrics", "ts": 1.0, "metrics": {}}) + "\n")
    # a chrome-trace profiler dump rides along untouched
    (tmp_path / "prof.json").write_text(json.dumps({"traceEvents": [
        {"name": "op", "ph": "X", "ts": 5, "dur": 2, "pid": 0, "tid": 1}]}))

    out = str(tmp_path / "merged.json")
    assert tm.main([str(tmp_path / "srv.jsonl"), str(tmp_path / "wrk.jsonl"),
                    str(tmp_path / "launcher-events.jsonl"),
                    str(tmp_path / "old.jsonl"), str(tmp_path / "prof.json"),
                    "-o", out]) == 0
    merged = json.load(open(out))["traceEvents"]
    xs = [e for e in merged if e.get("ph") == "X"]
    # 4 spans of t1 + 1 span of t2 + 1 chrome event
    assert len(xs) == 6
    lanes = {e["args"]["name"] for e in merged
             if e.get("ph") == "M" and e["name"] == "process_name"}
    assert {"server (pid 10)", "router (pid 10)", "worker (pid 11)",
            "launcher (pid 9)"} <= lanes
    spans = {e["args"]["span"]: e for e in xs
             if "span" in e.get("args", {})}
    assert spans["s3"]["args"]["parent"] == "s2"
    assert spans["s2"]["args"]["parent"] == "s1"

    # --trace renders exactly one request
    out2 = str(tmp_path / "one.json")
    assert tm.main([str(tmp_path / "srv.jsonl"), str(tmp_path / "wrk.jsonl"),
                    "-o", out2, "--trace", "t1"]) == 0
    one = [e for e in json.load(open(out2))["traceEvents"]
           if e.get("ph") == "X"]
    assert {e["args"]["trace"] for e in one} == {"t1"}
    assert len(one) == 3


# ---------------------------------------------------------------------------
# tier-1 e2e: one HTTP request, three serving roles, one merged trace
# ---------------------------------------------------------------------------

def _emitted():
    """The span records emitted and not yet flushed (this process runs no
    flusher: it has no telemetry directory)."""
    return list(tracing._PENDING)


def _post_with_headers(url, payload, timeout=15):
    body = json.dumps(payload).encode()
    req = urllib.request.Request(url, data=body,
                                 headers={"Content-Type":
                                          "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, json.loads(r.read()), dict(r.headers)


def test_trace_e2e_one_request_three_roles(clean_tracing, tmp_path):
    """THE acceptance e2e (ISSUE 7): one request against a 2-replica stub
    pool yields ONE trace whose spans cross server, router and worker —
    the worker lane coming from a different OS process over the
    supervisor wire protocol — merged into one perfetto timeline."""
    from mxnet_tpu.serving import ModelRepository, ServedModel, ServingServer

    tdir = tmp_path / "tm"
    tracing.configure(sample=1.0)
    model = ServedModel.pooled(
        "traced", 1, None, 2,
        worker_args=["--stub", "echo", "--input", "x=2", "--max-batch", "4"],
        heartbeat_ms=500, backoff_ms=50, teardown_grace=1.0,
        spawn_timeout_s=90, max_delay_ms=2, queue_depth=16,
        extra_env={"MXTPU_TELEMETRY_DIR": str(tdir),
                   "MXTPU_TELEMETRY_FLUSH_S": "0.25"})
    repo = ModelRepository()
    repo.add(model)
    srv = ServingServer(repo, port=0, addr="127.0.0.1").start()
    try:
        url = "http://127.0.0.1:%d/v1/models/traced:predict" % srv.port
        code, resp, headers = _post_with_headers(
            url, {"inputs": {"x": [[3.0, 4.0]]}, "timeout_ms": 5000})
        assert code == 200 and resp["outputs"][0][0] == [6.0, 8.0]
        # header contract: the reply names its trace
        hdr = headers.get(tracing.HEADER) or headers.get(
            tracing.HEADER.title())
        assert hdr, headers
        tid = tracing.parse_header(hdr).trace_id

        # local (server+router) spans: wait for the request root to close
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline and not any(
                s["name"] == "serve.request" and s["trace"] == tid
                for s in _emitted()):
            time.sleep(0.02)
        local = {s["name"]: s for s in _emitted() if s["trace"] == tid}
        assert {"serve.request", "serve.queue", "serve.assembly",
                "serve.dispatch", "serve.unpad"} <= set(local), \
            sorted(local)

        # worker spans arrive via the worker process's telemetry JSONL
        worker_spans = []
        deadline = time.monotonic() + 15
        while time.monotonic() < deadline and not worker_spans:
            for fname in (os.listdir(str(tdir))
                          if os.path.isdir(str(tdir)) else []):
                if not fname.endswith(".jsonl"):
                    continue
                for line in open(os.path.join(str(tdir), fname)):
                    try:
                        rec = json.loads(line)
                    except ValueError:
                        continue
                    if rec.get("kind") == "span" and rec.get("trace") == tid:
                        worker_spans.append(rec)
            if not worker_spans:
                time.sleep(0.1)
        assert worker_spans, "worker never flushed its compute span"
        compute = worker_spans[0]
        assert compute["name"] == "serve.compute"
        assert compute["component"] == "worker"

        # parentage: request -> {queue, assembly, dispatch, unpad},
        # dispatch -> compute (across the wire)
        root = local["serve.request"]
        assert root["parent"] is None and root["component"] == "server"
        for name in ("serve.queue", "serve.assembly", "serve.dispatch",
                     "serve.unpad"):
            assert local[name]["parent"] == root["span"], name
            assert local[name]["component"] == "router"
        assert compute["parent"] == local["serve.dispatch"]["span"]
        # ... and the worker lane really is another OS process
        assert compute["pid"] != root["pid"]
        assert len(local) + len(worker_spans) >= 5

        # one merged perfetto timeline with the three role lanes
        telemetry.flush(str(tdir))  # server+router spans -> JSONL
        tm = _load_trace_merge()
        out = str(tmp_path / "merged.json")
        files = [os.path.join(str(tdir), f) for f in os.listdir(str(tdir))
                 if f.endswith(".jsonl")]
        assert tm.main(files + ["-o", out, "--trace", tid]) == 0
        merged = json.load(open(out))["traceEvents"]
        xs = [e for e in merged if e.get("ph") == "X"]
        assert len(xs) >= 5
        assert {e["args"]["trace"] for e in xs} == {tid}
        comps = {e["cat"] for e in xs}
        assert comps >= {"server", "router", "worker"}, comps
        lane_pids = {e["pid"] for e in xs}
        assert len(lane_pids) >= 3  # one lane per (component, os-pid)
    finally:
        srv.shutdown()
        model.close(drain=False, timeout=0)


def test_incoming_header_is_honored_end_to_end(clean_tracing, tmp_path):
    """A client that already traces keeps its ids: the reply echoes the
    incoming trace id and the recorded root parents under the client's
    span (rate 0 locally — the incoming sampled flag wins)."""
    from mxnet_tpu.serving import ModelRepository, ServedModel, ServingServer
    import numpy as np
    from mxnet_tpu import gluon

    tracing.configure(sample=0.0)
    # in-process model: this test is about admission, no pool needed
    net = gluon.nn.Dense(2)
    net.initialize()
    x = mx.nd.zeros((1, 2))
    net(x)

    def runner(arrays, bucket, n):
        return [np.asarray(net(mx.nd.array(arrays["x"])).asnumpy())]

    model = ServedModel("hdr", 1, runner, [1, 2],
                        example_shapes={"x": (2,)},
                        input_dtypes={"x": "float32"}, max_delay_ms=1)
    model.warm()
    repo = ModelRepository()
    repo.add(model)
    srv = ServingServer(repo, port=0, addr="127.0.0.1").start()
    try:
        url = "http://127.0.0.1:%d/v1/models/hdr:predict" % srv.port
        client_ref = tracing.SpanRef("5a" * 8, "6b" * 4, sampled=True)
        body = json.dumps({"inputs": {"x": [[1.0, 2.0]]}}).encode()
        req = urllib.request.Request(
            url, data=body,
            headers={"Content-Type": "application/json",
                     tracing.HEADER: tracing.header_value(client_ref)})
        with urllib.request.urlopen(req, timeout=15) as r:
            assert r.status == 200
            echoed = tracing.parse_header(r.headers[tracing.HEADER])
        assert echoed.trace_id == client_ref.trace_id
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline and not any(
                s["name"] == "serve.request" for s in _emitted()):
            time.sleep(0.02)
        roots = [s for s in _emitted() if s["name"] == "serve.request"]
        assert roots and roots[0]["trace"] == client_ref.trace_id
        assert roots[0]["parent"] == client_ref.span_id
    finally:
        srv.shutdown()
        model.close(drain=False, timeout=0)
