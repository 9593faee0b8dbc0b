"""The readers of the program's start-up account on hand-made rings."""
import os

import pytest

from chipbench import run as bench_run
from chipbench.lib import startup

METRICS = ["setup_import_s", "setup_load_s", "setup_trace_lower_s",
           "setup_backend_compile_s", "setup_cache_miss_programs",
           "setup_first_run_s", "setup_outside_program_s"]
_IDS = iter(range(1, 10 ** 6))


def _span(name, t0, t1, parent=None, **fields):
    rec = {"name": name, "t0": t0, "t1": t1, "id": next(_IDS),
           "parent": None if parent is None else parent["id"],
           "after_ready": False}
    rec.update(fields)
    return rec


def _ring(monkeypatch, records):
    from mxnet_tpu.telemetry import goodput

    monkeypatch.setattr(goodput, "window",
                        lambda kind: list(records.get(kind, ())))


def _read(name, facts):
    return bench_run.load_reader(name)(facts)


def _serving_ring(cache=("miss", "hit")):
    """A serving set-up on a clock that starts at 100 s: import 2 s; the
    harness's reference weights compile with nothing open (1 s lowering,
    3 s in the backend); artifact out 4 s and back 6 s (a compile of 1 s
    inside the read); the engine 2 s; two buckets' first runs of 10 s and
    5 s holding a program span each; ready at 141 s; then a second model."""
    imp = _span("import", 101.0, 103.0)
    loose = [_span("lower", 104.0, 105.0, fun_name="jit(make_weights)"),
             _span("backend_compile", 105.0, 108.0, cache=cache[0],
                   fun_name="jit(make_weights)")]
    write = _span("artifact_write", 110.0, 114.0, bytes=10, arrays=2)
    read = _span("artifact_read", 114.0, 120.0, bytes=10, arrays=2)
    in_read = _span("backend_compile", 115.0, 116.0, read, cache=cache[1])
    build = _span("engine_build", 120.0, 122.0, pool_bytes=64)
    run1 = _span("first_run", 124.0, 134.0, label="lm_prefill:l8")
    prog1 = _span("program", 124.5, 132.5, run1, label="lm_prefill:l8",
                  kind="lm_prefill", tier="memory_miss")
    trace1 = _span("trace", 124.5, 126.5, prog1, fun_name="fn")
    # an eager compile inside the trace: the trace's child
    eager = _span("backend_compile", 125.0, 125.5, trace1, cache=cache[1])
    lower1 = _span("lower", 126.5, 127.5, prog1, fun_name="jit(fn)")
    comp1 = _span("backend_compile", 127.5, 132.0, prog1, cache=cache[0],
                  fun_name="jit(fn)")
    run2 = _span("first_run", 135.0, 140.0, label="lm_decode:b4")
    prog2 = _span("program", 135.0, 135.5, run2, label="lm_decode:b4",
                  kind="lm_decode", tier="memory_miss")
    # a lazy entry: the compile happens at the call, outside the program
    late = [_span("trace", 135.5, 136.5, run2, fun_name="fn2"),
            _span("backend_compile", 136.5, 138.5, run2, cache=cache[1],
                  fun_name="jit(fn2)", retrieval_s=1.9)]
    ready = _span("ready", 141.0, 141.0, model="lm/1")
    after = [_span("artifact_read", 150.0, 170.0, bytes=99, arrays=9),
             _span("backend_compile", 171.0, 181.0, cache="miss"),
             _span("ready", 182.0, 182.0, model="lm/2")]
    for r in after:
        r["after_ready"] = True
    # children are written before their parents, as the program does
    return [imp] + loose + [write, in_read, read, build, eager, trace1,
                            lower1, comp1, prog1, run1, prog2] + late \
        + [run2, ready] + after


def test_the_readers_on_a_hand_made_serving_ring(monkeypatch):
    _ring(monkeypatch, {"startup": _serving_ring()})
    facts = {"kind": "serve", "setup_s": 60.0}     # process start at 100 s
    assert _read("setup_import_s", facts) == pytest.approx(2.0)
    # artifact 4 + (6 - 1 of compile inside the read) + engine 2
    assert _read("setup_load_s", facts) == pytest.approx(11.0)
    # loose lower 1 + trace (2 - 0.5 eager compile) + lower 1 + late trace 1
    assert _read("setup_trace_lower_s", facts) == pytest.approx(4.5)
    # loose 3 + in the read 1 + eager 0.5 + 4.5 + late 2
    assert _read("setup_backend_compile_s", facts) == pytest.approx(11.0)
    assert _read("setup_cache_miss_programs", facts) == 2
    # run 1: 10 - program 8; run 2: 5 - program 0.5 - late 3
    assert _read("setup_first_run_s", facts) == pytest.approx(3.5)
    # top level: import 2, loose 4, write 4, read 6, engine 2, runs 10 + 5
    assert _read("setup_outside_program_s", facts) == pytest.approx(
        60.0 - 33.0)
    # every second of the account is counted once
    own = startup.self_seconds(startup.spans())
    assert sum(own.values()) == pytest.approx(33.0)
    assert own["program"] == pytest.approx(0.5 + 0.5)


def test_spans_after_the_first_ready_are_left_out(monkeypatch):
    ring = _serving_ring()
    _ring(monkeypatch, {"startup": ring})
    kept = startup.spans()
    assert all(r["t1"] <= 141.0 for r in kept)
    assert not [r for r in kept if r["name"] == "ready"]
    assert len(kept) == len(ring) - 4
    # a span that began before the mark and ended after it is not set-up's
    straddler = _span("artifact_read", 139.0, 150.0, bytes=1, arrays=1)
    _ring(monkeypatch, {"startup": ring + [straddler]})
    assert len(startup.spans()) == len(kept)


def test_a_warm_cache_reads_no_missed_program_and_an_unarmed_one_nothing(
        monkeypatch):
    facts = {"kind": "serve", "setup_s": 60.0}
    _ring(monkeypatch, {"startup": _serving_ring(cache=("hit", "hit"))})
    assert _read("setup_cache_miss_programs", facts) == 0
    _ring(monkeypatch, {"startup": _serving_ring(cache=("off", "off"))})
    assert _read("setup_cache_miss_programs", facts) is None
    # the seconds are there all the same
    assert _read("setup_backend_compile_s", facts) == pytest.approx(11.0)


def test_the_training_cells_load_is_its_trainer_build(monkeypatch):
    imp = _span("import", 1.0, 1.5)
    build = _span("trainer_build", 5.0, 8.0)
    inside = _span("program", 6.0, 6.5, build, label="zeros", kind="op",
                   tier="memory_miss")
    run = _span("first_run", 9.0, 30.0, label="dist_trainer_step")
    prog = _span("program", 9.5, 29.5, run, label="dist_trainer_step",
                 kind="sharded_step", tier="persist_hit")
    stages = [_span("trace", 9.5, 14.5, prog), _span("lower", 14.5, 18.5, prog),
              _span("backend_compile", 18.5, 29.0, prog, cache="hit",
                    retrieval_s=10.0)]
    ready = _span("ready", 30.0, 30.0, trainer="dist")
    _ring(monkeypatch, {"startup": [imp, inside, build] + stages
                        + [prog, run, ready]})
    facts = {"kind": "train", "setup_s": 40.0}
    assert _read("setup_load_s", facts) == pytest.approx(2.5)
    assert _read("setup_trace_lower_s", facts) == pytest.approx(9.0)
    assert _read("setup_backend_compile_s", facts) == pytest.approx(10.5)
    assert _read("setup_cache_miss_programs", facts) == 0
    assert _read("setup_first_run_s", facts) == pytest.approx(1.0)
    assert _read("setup_outside_program_s", facts) == pytest.approx(
        40.0 - 0.5 - 3.0 - 21.0)


def test_two_threads_spans_are_covered_once(monkeypatch):
    a = _span("artifact_read", 10.0, 20.0, bytes=1, arrays=1)
    b = _span("program", 15.0, 25.0, label="x", kind="op", tier="memory_miss")
    c = _span("trace", 30.0, 31.0)
    assert startup.covered_s([a, b, c]) == pytest.approx(16.0)
    # a child whose parent fell out of the ring stands as a top-level span
    orphan = _span("lower", 40.0, 42.0, parent={"id": 10 ** 7})
    assert startup.covered_s([a, b, c, orphan]) == pytest.approx(18.0)


@pytest.mark.parametrize("metric", METRICS)
def test_an_empty_ring_no_mark_or_no_ring_leave_the_metric_out(
        monkeypatch, metric):
    from mxnet_tpu.telemetry import goodput

    entry = {m["name"]: m for m in bench_run.load_json(os.path.join(
        bench_run.ROOT, "BENCHMARK.json"))["per_layer"]}[metric]
    assert entry["moves"] == "setup_s" and entry["layer"] == "start-up"
    assert entry["better"] == "lower" and len(entry["workloads"]) == 5
    facts = {"kind": "serve", "setup_s": 60.0}
    # the parent of the PR that brought the account: a ring function that
    # knows no such kind
    _ring(monkeypatch, {})
    assert _read(metric, facts) is None
    # spans and no mark yet: the program never said it was ready
    _ring(monkeypatch, {"startup": [r for r in _serving_ring()
                                    if r["name"] != "ready"]})
    assert _read(metric, facts) is None
    _ring(monkeypatch, {"startup": _serving_ring()})
    assert _read(metric, facts) is not None
    # a program without the accountant's rings at all
    monkeypatch.delattr(goodput, "window")
    assert _read(metric, facts) is None
