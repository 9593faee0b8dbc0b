"""Each cell walked at tiny size on the CPU through the harness's own entry:
the result line's shape, no device metric without a chip, a throw-away cell
added by files alone, and `correct` coming out false when the timed path is
broken underneath."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from chipbench import run as bench_run

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELLS = ["resnet50_train_bs256", "gpt2s_chat_open", "gpt2s_docs_closed"]
KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _rehearse(workload, trace=0, seed=3000000007, root=ROOT, **kw):
    opts = bench_run.Options(seed, 6.0, trace, rehearse=True, **kw)
    return bench_run.run_cell(workload, opts, root=root)


@pytest.mark.parametrize("workload", CELLS)
def test_rehearsal_prints_the_contracts_line(workload):
    bench = _bench()
    assert [w["name"] for w in bench["workloads"]] == CELLS
    for trace in (0, 1):
        result = _rehearse(workload, trace)
        assert KEYS <= set(result) and result["rehearsal"] is True
        assert result["correct"] is True and result["failed"] == 0
        assert result["attempted"] > 0
        assert result["device"]["platform"] == "cpu"
        listed = bench["per_layer"] if trace else bench["end_to_end"]
        by_name = {m["name"]: m for m in listed}
        assert "setup_s" in result["metrics"] or trace
        for name, m in result["metrics"].items():
            assert workload in by_name[name].get("workloads", [workload])
            assert m["unit"] == by_name[name]["unit"]
            # a CPU run reports counts, never a time, a rate or a share
            if by_name[name]["source"] != "program_counter":
                assert m["value"] is None
            else:
                assert m["value"] is not None
        json.dumps(result)


def test_without_a_chip_the_command_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"),
         "--workload", "gpt2s_chat_open", "--seed", "1", "--seconds", "2",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert "correct" not in p.stdout
    assert "no accelerator" in p.stderr


def _serve_mix(mix):
    mix["rehearse"]["clients"] = 2


def _open_loop_mix(mix):
    # the same pairs offered at a rate, whatever the server does
    mix["loop"] = "open"
    mix["rehearse"].update(rate_rps=20.0, burst=8)


def _four_chip_mix(mix):
    # a global batch of the mix's own, on the configuration's dp=4 mesh
    mix["batch"] = 1024
    mix["rehearse"]["batch"] = 8


@pytest.mark.parametrize("base,alter,config,chips,metric,source,reader", [
    ("gpt2s_docs_closed", _serve_mix, "gpt2_small", 1, "throwaway_requests",
     "program_counter", "    return float(facts['serve']['prefills'])\n"),
    ("gpt2s_chat_open", _open_loop_mix, "gpt2_small", 1, "throwaway_steps",
     "program_counter", "    return float(facts['serve']['decode_steps'])\n"),
    ("resnet50_train_bs256", _four_chip_mix, "resnet50_v1", 4,
     "throwaway_rows_a_chip", "program_counter",
     "    return facts['window']['slices'] and 8.0 / facts['chips']\n")])
def test_a_cell_a_mix_and_a_metric_are_added_by_files_alone(
        tmp_path, base, alter, config, chips, metric, source, reader):
    """A later PR adds a traffic mix, a cell and a per-layer metric as new
    files plus one entry each; no file that is there is edited.  The second
    case is an open loop and the third a cell on four chips, of which the
    benchmark has none."""
    root = str(tmp_path)
    shutil.copytree(os.path.join(ROOT, "chipbench"),
                    os.path.join(root, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = _bench()
    with open(os.path.join(root, "chipbench", "traffic",
                           base + ".json")) as f:
        mix = json.load(f)
    alter(mix)
    with open(os.path.join(root, "chipbench", "traffic",
                           "throwaway_mix.json"), "w") as f:
        json.dump(mix, f)
    with open(os.path.join(root, "chipbench", "metrics", metric + ".py"),
              "w") as f:
        f.write("def read(facts):\n" + reader)
    bench["workloads"].append({
        "name": "throwaway", "config": config, "traffic": "throwaway_mix",
        "chips": chips, "why": "test"})
    moves = next(m["name"] for m in bench["end_to_end"]
                 if base in m.get("workloads", []))
    bench["per_layer"].append({
        "name": metric, "unit": "n", "better": "higher", "source": source,
        "layer": "decode engine", "moves": moves, "workloads": ["throwaway"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    result = _rehearse("throwaway", trace=1, root=root)
    assert result["correct"] is True
    assert result["device"]["count"] == chips
    assert result["metrics"][metric]["value"] > 0
    # metrics listed for other cells only are left out of this line
    assert set(result["metrics"]) == {metric}


def _mixes_judged_on_their_rate_alone():
    """(cell, its traffic, its top decode bucket) for every serving cell
    whose one end-to-end metric besides `setup_s` is `output_token_rate`."""
    bench = _bench()
    files = {c["name"]: c["file"] for c in bench["configs"]}
    out = []
    for w in bench["workloads"]:
        judged = {m["name"] for m in bench["end_to_end"]
                  if w["name"] in m.get("workloads", [w["name"]])}
        if judged != {"output_token_rate", "setup_s"}:
            continue
        with open(os.path.join(ROOT, "chipbench", "traffic",
                               w["traffic"] + ".json")) as f:
            mix = json.load(f)
        with open(os.path.join(ROOT, files[w["config"]])) as f:
            engine = dict(json.load(f)["engine"], **mix["engine"])
        out.append((w["name"], mix, max(engine["decode_buckets"])))
    return out


@pytest.mark.parametrize("cell,mix,top_bucket",
                         _mixes_judged_on_their_rate_alone(),
                         ids=lambda v: v if isinstance(v, str) else "")
def test_a_mix_judged_on_its_rate_alone_keeps_the_decode_batch_full(
        cell, mix, top_bucket):
    """Such a cell reads the engine only while more requests wait than the
    engine completes; otherwise it reads its own offer (PERF.md, PR 29 and
    PR 36).  A client's thread and a handler's wait for every request
    outstanding, and the chip machine kills a process at 4096 threads: they
    stay under three quarters of that, and under the queue's depth, or
    requests are refused.  A closed loop keeps at least four full decode
    batches of clients, all of them started before the window opens; it
    stays saturated at any engine speed.  An open loop offers at least 1.5
    times the rate a sweep found sustained, and the backlog that builds by
    the end of a traced run is held to the same two limits."""
    from chipbench.runners import serve

    if mix["loop"] == "closed":
        waiting = mix["clients"]
        assert waiting >= 4 * top_bucket, (cell, waiting)
        assert mix["ramp_s"] <= mix["warm_s"], cell
    else:
        assert mix["rate_rps"] >= 1.5 * mix["sustained_rps"] > 0, cell
        horizon = serve.horizon_s(mix, _bench()["run_seconds"], trace=True)
        waiting = mix["burst"] + \
            (mix["rate_rps"] - mix["sustained_rps"]) * horizon
    assert waiting <= mix["queue_depth"], (cell, waiting)
    assert 2 * waiting < 0.75 * 4096, (cell, waiting)


def _zero_learning_rate(trainer):
    # a step that returns its state unchanged
    trainer.set_learning_rate(0.0)


def _alter_tokens(model):
    # a token altered where it is produced
    engine = model.scheduler.engine
    step = engine.decode_step

    def broken(tokens, *rest):
        return (step(tokens, *rest) + 1) % engine.vocab_size

    engine.decode_step = broken


@pytest.mark.parametrize("workload,breaker", [
    ("resnet50_train_bs256", _zero_learning_rate),
    ("gpt2s_chat_open", _alter_tokens)])
def test_correct_is_false_when_the_timed_path_is_broken(workload, breaker,
                                                         capsys):
    result = _rehearse(workload, break_step=breaker)
    assert result["correct"] is False
    assert "FAIL" in capsys.readouterr().out
