"""The trace reduction against a small recorded trace (a slice of a real
``resnet50_train_bs256 --trace 1`` run on a TPU v5e), and on synthetic events
where the answer is known by construction."""
import json
import os

import pytest

from chipbench.lib import trace

HERE = os.path.dirname(os.path.abspath(__file__))


def _recorded():
    with open(os.path.join(HERE, "data", "recorded_trace.json")) as f:
        return json.load(f)["events"]


def test_parse_op_reads_name_opcode_and_shape():
    text = ("%fusion.10 = bf16[256,112,112,64]{0,3,2,1:T(8,128)(2,1)} "
            "fusion(pred[256,112,112,64]{0,3,2,1:T(8,128)(4,1)} %copy.2030)")
    assert trace.parse_op(text) == {"name": "fusion.10", "op": "fusion",
                                    "shape": "bf16[256,112,112,64]"}
    tup = ("%slice-start.348 = ((bf16[256,1,1,1024]{3,2,1,0:T(2,128)(2,1)}), "
           "bf16[64,1,1,1024]{3,2,1,0}) async-start(bf16[1]{0} %x)")
    assert trace.parse_op(tup)["op"] == "async-start"
    assert trace.parse_op(tup)["shape"] == "bf16[256,1,1,1024]"
    mosaic = ("%_jvp__.107 = bf16[3211264,128]{1,0:T(8,128)(2,1)} "
              "custom-call(bf16[3211264,128]{1,0} %p), "
              "custom_call_target=\"tpu_custom_call\"")
    assert trace.classify(trace.parse_op(mosaic)) == "mosaic"
    assert trace.classify({"op": "all-reduce-start"}) == "collective"
    assert trace.classify({"op": "copy-done"}) == "copy"
    assert trace.classify({"op": "iota"}) == "other"


def test_recorded_trace_reduces_to_the_same_numbers_by_another_route():
    events = _recorded()
    dev = [e for e in events if e["plane"] == "/device:TPU:0"]
    assert len(dev) > 400 and all(e["line"] == "XLA Ops" for e in dev)
    r = trace.reduce(events, 1)
    # busy time by discretisation on a 50 ns grid, not by merging intervals
    lo = min(e["start"] for e in dev)
    hi = max(e["start"] + e["dur"] for e in dev)
    step = 50e-9
    cells = bytearray(int((hi - lo) / step) + 2)
    for e in dev:
        a = int((e["start"] - lo) / step)
        b = int((e["start"] + e["dur"] - lo) / step)
        for i in range(a, max(b, a + 1)):
            cells[i] = 1
    assert r["window_s"] == pytest.approx(hi - lo)
    assert r["busy_s"] == pytest.approx(sum(cells) * step, rel=0.02)
    assert 0 < r["busy_s"] <= r["window_s"]
    # this slice of a ResNet-50 step: device busy, Mosaic kernels a fifth to
    # a half of it, no collective on one chip
    assert r["busy_s"] / r["window_s"] > 0.99
    assert 0.2 < r["mosaic_s"] / r["busy_s"] < 0.5
    assert r["collective_s"] == 0 and r["collective_exposed_s"] == 0
    assert sum(r["class_s"].values()) == pytest.approx(
        sum(e["dur"] for e in dev))
    ops = r["breakdown"]["device_ops"]
    assert len(ops) <= 10 and ops[0][0].startswith("all_")
    assert all(isinstance(n, str) and s > 0 for n, s in ops)
    assert len(r["breakdown"]["idle_gaps"]) <= 10


def _ev(plane, name, start, dur, op=None):
    e = {"plane": plane, "line": "XLA Ops", "name": name, "start": start,
         "dur": dur}
    if op:
        e["op"] = op
    return e


def test_idle_gaps_exposed_collectives_and_two_chips():
    d0, d1, host = "/device:TPU:0", "/device:TPU:1", "/host:CPU"
    events = [
        _ev(d0, "fusion.1", 0.0, 1.0, "fusion"),
        _ev(d0, "all-reduce.1", 0.5, 1.0, "all-reduce"),   # 0.5 s exposed
        _ev(d0, "copy.1", 3.0, 1.0, "copy"),               # gap 1.5 .. 3.0
        _ev(d1, "fusion.1", 0.0, 2.0, "fusion"),
        _ev(d1, "copy.1", 3.0, 1.0, "copy"),
        _ev(host, "chipbench.next_feed", 1.4, 1.0),
    ]
    r = trace.reduce(events, 2)
    assert r["devices"] == 2 and r["window_s"] == pytest.approx(4.0)
    assert r["busy_s"] == pytest.approx((2.5 + 3.0) / 2)
    assert r["collective_s"] == pytest.approx(1.0)
    assert r["collective_exposed_s"] == pytest.approx(0.5)
    assert r["breakdown"]["idle_gaps"][0] == ["chipbench.next_feed",
                                              pytest.approx(1.5)]
    one = trace.reduce(events, 1)
    assert one["devices"] == 1 and one["busy_s"] == pytest.approx(2.5)
    assert trace.reduce([e for e in events if e["plane"] == host], 1) is None


def test_a_gap_is_named_by_the_innermost_span_that_covers_most_of_it():
    dev, host = "/device:TPU:0", "/host:CPU"
    events = [
        _ev(dev, "fusion.1", 0.0, 1.0), _ev(dev, "fusion.2", 1.010, 1.0),
        _ev(dev, "fusion.3", 2.012, 0.5), _ev(dev, "fusion.4", 2.520, 0.5),
        _ev(dev, "fusion.5", 3.021, 0.5),
        _ev(host, "mxtpu.serve.lap", 0.5, 0.508),
        _ev(host, "mxtpu.serve.decode_dispatch", 0.5, 0.502),
        _ev(host, "mxtpu.serve.decode_wait", 0.6, 0.402),   # ends at 1.002
        _ev(host, "mxtpu.serve.retire", 1.002, 0.004),
        _ev(host, "mxtpu.serve.lap", 1.0085, 0.9),
        _ev(host, "mxtpu.serve.admit", 1.0085, 0.0005),
        # a gap wholly inside a dispatch nested in a lap; the lap's own span
        # starts earlier and ends later
        _ev(host, "mxtpu.serve.lap", 2.4, 0.7),
        _ev(host, "mxtpu.serve.decode_dispatch", 2.5, 0.03),
        # the training harness's wait, beside a step of the program's that
        # ended before the gap
        _ev(host, "mxtpu.dist.step", 2.9, 0.1),
        _ev(host, "chipbench.wait_inflight", 3.01, 0.02),
    ]
    gaps = trace.composition(events)
    assert [g for g, _ in gaps] == pytest.approx([0.010, 0.008, 0.002, 0.001])
    assert gaps[0][1] == pytest.approx({
        "mxtpu.serve.decode_wait": 0.002, "mxtpu.serve.retire": 0.004,
        "mxtpu.serve.lap": 0.003, trace.BETWEEN: 0.0005,
        "mxtpu.serve.admit": 0.0005})
    assert trace.reduce(events, 1)["breakdown"]["idle_gaps"] == [
        ["mxtpu.serve.retire", pytest.approx(0.010)],
        ["mxtpu.serve.decode_dispatch", pytest.approx(0.008)],
        [trace.BETWEEN, pytest.approx(0.002)],
        ["chipbench.wait_inflight", pytest.approx(0.001)]]
    assert trace.composition(events, top=1) == gaps[:1]
    assert all(n.startswith(trace.ANNOTATION_PREFIX) for n in
               ("mxtpu.serve.lap", "chipbench.next_feed"))
    assert not "jax.block_until_ready".startswith(trace.ANNOTATION_PREFIX)
