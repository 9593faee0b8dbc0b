"""The readers of the program's own lap account on a hand-made ring."""
import os

import pytest

from chipbench import run as bench_run
from chipbench.lib import laps

HERE = os.path.dirname(os.path.abspath(__file__))
SERVE = {"kind": "serve"}


def _lap(t0, wall, traced=True, n=4, admitted=0, queue_wait_s=0.0, cpu_s=0.0,
         **phases):
    phases["other"] = wall - sum(phases.values())
    return {"t0": t0, "t1": t0 + wall, "phases": phases, "traced": traced,
            "cpu_s": cpu_s, "n": n, "bucket": 4, "prefills": admitted,
            "admitted": admitted, "queue_wait_s": queue_wait_s,
            "model": "lm/1", "step": None}


def _ring(monkeypatch, records):
    from mxnet_tpu.telemetry import goodput

    monkeypatch.setattr(goodput, "window", lambda kind: list(records[kind]))


def _read(name, facts):
    return bench_run.load_reader(name)(facts)


def test_the_serving_readers_on_a_hand_made_ring(monkeypatch):
    # 12 traced laps of 100 ms: 80 ms blocked on the device in the step and,
    # in every third lap, one prefill (5 ms of host, 10 ms of wait) admitted
    # after 30 ms in the queue; 8 ms of CPU in 20 (or 10) ms of host time
    ring = [_lap(0.0, 9.0, traced=False, decode_wait=1.0)]
    for i in range(12):
        if i % 3 == 0:
            ring.append(_lap(10 + i, 0.1, admitted=1, queue_wait_s=0.03,
                             cpu_s=0.008, admit=0.001, prefill_host=0.005,
                             prefill_wait=0.01, build=0.002,
                             decode_dispatch=0.001, decode_wait=0.07,
                             retire=0.003))
        else:
            ring.append(_lap(10 + i, 0.1, cpu_s=0.008, admit=0.001,
                             build=0.002, decode_dispatch=0.004,
                             decode_wait=0.08, retire=0.003))
    ring.append(_lap(30.0, 0.05, n=0, admit=0.04))      # a lap with no step
    _ring(monkeypatch, {"serve": ring, "dist": []})
    assert _read("decode_step_span_ms", SERVE) == pytest.approx(
        (4 * 71 + 8 * 84) / 12)
    wall, wait = 12 * 0.1 + 0.05, 4 * 0.08 + 8 * 0.08
    assert _read("sched_host_share.serve", SERVE) == pytest.approx(
        100 * (1 - wait / wall))
    assert _read("sched_host_cpu_share.serve", SERVE) == pytest.approx(
        100 * 12 * 0.008 / (wall - wait))
    assert _read("sched_host_ms.admit", SERVE) == pytest.approx(
        (12 * 1 + 40) / 13)
    assert _read("sched_host_ms.prefill_host", SERVE) == pytest.approx(
        4 * 5 / 13)
    assert _read("sched_host_ms.build", SERVE) == pytest.approx(12 * 2 / 13)
    assert _read("sched_host_ms.decode_dispatch", SERVE) == pytest.approx(
        (4 * 1 + 8 * 4) / 13)
    assert _read("sched_host_ms.retire", SERVE) == pytest.approx(12 * 3 / 13)
    assert _read("queue_wait_ms", SERVE) == pytest.approx(30.0)
    # a serving cell has no trainer's steps, a training cell no laps
    assert _read("step_host_ms", SERVE) is None
    assert _read("decode_step_span_ms", {"kind": "train"}) is None


def test_the_training_reader_on_a_hand_made_ring(monkeypatch):
    steps = [{"t0": float(i), "t1": i + 0.009 + 0.001 * (i % 2),
              "phases": {"compute": 0.008}, "traced": 2 <= i < 18,
              "cpu_s": 0.009, "step": i} for i in range(20)]
    _ring(monkeypatch, {"dist": steps, "serve": []})
    assert _read("step_host_ms", {"kind": "train"}) == pytest.approx(9.5)


NEW = ["decode_step_span_ms", "sched_host_share.serve",
       "sched_host_cpu_share.serve", "sched_host_ms.admit",
       "sched_host_ms.prefill_host", "sched_host_ms.build",
       "sched_host_ms.decode_dispatch", "sched_host_ms.retire",
       "queue_wait_ms", "step_host_ms"]


@pytest.mark.parametrize("metric", NEW)
def test_too_few_traced_laps_or_no_ring_leave_the_metric_out(
        monkeypatch, metric):
    from mxnet_tpu.telemetry import goodput

    entry = {m["name"]: m for m in bench_run.load_json(os.path.join(
        bench_run.ROOT, "BENCHMARK.json"))["per_layer"]}[metric]
    assert entry["source"] == "program_span" and entry["workloads"]
    kind = "train" if metric == "step_host_ms" else "serve"
    few = [_lap(float(i), 0.1, admitted=1, queue_wait_s=0.01,
                decode_wait=0.08) for i in range(laps.MIN_TRACED - 1)]
    many = few + [_lap(50.0, 0.1, admitted=1, queue_wait_s=0.01,
                       decode_wait=0.08)]
    _ring(monkeypatch, {"serve": few, "dist": few})
    assert _read(metric, {"kind": kind}) is None
    _ring(monkeypatch, {"serve": many, "dist": many})
    assert _read(metric, {"kind": kind}) is not None
    # an untraced run: the same laps outside any profiler session
    _ring(monkeypatch, {k: [dict(r, traced=False) for r in many]
                        for k in ("serve", "dist")})
    assert _read(metric, {"kind": kind}) is None
    # a program without the ring (the parent of the PR that brought this)
    monkeypatch.delattr(goodput, "window")
    assert _read(metric, {"kind": kind}) is None
