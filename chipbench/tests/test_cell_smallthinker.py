"""The cell `smallthinker_mixed_closed` walked at its tiny sizes on the CPU
through the harness's own entry, traced and not; `correct` coming out false
when the engine ignores the window, rotates a NoPE layer or feeds the router
the post-attention rows; the cut's arithmetic; and the work functions and
readers the cell brings."""
import json
import os

import pytest

from chipbench import run as bench_run
from chipbench.lib import smallthinker_work as work

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "smallthinker_mixed_closed"
NEW = ["step_mfu.swa_moe.serve", "decode_hbm_roofline.swa_moe.serve",
       "kv_rows_streamed_share.serve", "ring_pages_peak_share.serve",
       "moe_load_max_over_mean.swa_moe.serve"]
# readers of another model's counts: they must not list this cell
OTHERS = ["step_mfu.serve", "decode_hbm_roofline.serve",
          "moe_load_max_over_mean.serve", "state_slots_peak_share.serve",
          "step_mfu.mla_moe.serve", "decode_hbm_roofline.mla_moe.serve",
          "moe_load_max_over_mean.mla_moe.serve",
          "latent_rows_per_step.serve"]


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _config():
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "smallthinker_21b_a3b.json")) as f:
        return json.load(f)


def _sizes():
    return _config()["sizes"]


def _rehearse(trace=0, seed=3000003919, **kw):
    opts = bench_run.Options(seed, 4.0, trace, rehearse=True, **kw)
    return bench_run.run_cell(CELL, opts)


@pytest.mark.parametrize("trace", [0, 1])
def test_smallthinker_rehearsal_is_correct_and_prints_the_contracts_line(trace):
    bench = _bench()
    result = _rehearse(trace)
    assert result["rehearsal"] is True and result["correct"] is True
    assert result["failed"] == 0 and result["attempted"] > 0
    listed = {m["name"]: m for m in
              (bench["per_layer"] if trace else bench["end_to_end"])}
    for name, m in result["metrics"].items():
        assert CELL in listed[name].get("workloads", [CELL])
        # a CPU run reports counts, never a time, a rate or a share
        if listed[name]["source"] != "program_counter":
            assert m["value"] is None
        else:
            assert m["value"] is not None
    if trace:
        got = result["metrics"]
        assert not set(OTHERS) & set(got)
        # prompts of 5 to 32 tokens through a window of 8: the window layers
        # stream fewer rows than full layers would, and some ring is held
        assert 25.0 < got["kv_rows_streamed_share.serve"]["value"] < 100.0
        assert 0.0 < got["ring_pages_peak_share.serve"]["value"] <= 100.0
        assert got["moe_load_max_over_mean.swa_moe.serve"]["value"] >= 1.0
        assert got["decode_batch_mean"]["value"] > 4.0
    else:
        assert set(result["metrics"]) == {"output_token_rate", "setup_s"}
    json.dumps(result)


def _rebuilt(model, change):
    """The engine's two programs rebuilt from a changed description (under a
    fingerprint of their own) and warmed again, so that nothing compiles in
    the window: what a program with that fault would serve."""
    import copy

    engine = model.scheduler.engine
    desc = copy.deepcopy(engine.description)
    change(desc)
    engine.description = desc
    engine._fingerprint = "broken:" + engine._fingerprint
    engine.warm()


def _ignore_window(model):
    # the window layers attend over whatever their rings hold, and a
    # prompt over its whole triangle
    _rebuilt(model, lambda d: [l.__setitem__("window", 1 << 20)
                               for l in d["layers"] if l.get("window")])


def _rotate_nope(model):
    _rebuilt(model, lambda d: [l.__setitem__("rotary", True)
                               for l in d["layers"]])


def _router_post(model):
    _rebuilt(model, lambda d: d["experts"].pop("router_rows"))


@pytest.mark.parametrize("breaker", [_ignore_window, _rotate_nope,
                                     _router_post],
                         ids=["window_ignored", "nope_layer_rotated",
                              "router_fed_post_attention_rows"])
def test_smallthinker_correct_is_false_when_the_timed_path_is_broken(breaker, capsys):
    result = _rehearse(break_step=breaker)
    assert result["correct"] is False
    assert "FAIL" in capsys.readouterr().out


def test_the_smallthinker_cut_is_the_issues_arithmetic():
    """8 layers of the published widths: 398 627 840 parameters a layer,
    3 966 937 600 in all (7.93 GB of bfloat16); 4096 B a token in the two
    full layers, 12 288 B in the six window layers."""
    s = _sizes()
    assert work.layer_counts(s) == {"layers": 8, "window": 6, "full": 2}
    assert work.attention_params(s) == 20971520
    assert work.expert_params(s) == 5898240
    assert work.layer_params(s) == 398627840
    assert work.total_params(s) == 3966937600
    assert work.active_layer_params(s) == 56524800
    assert work.kv_bytes_per_token(s) == (4096, 12288)
    assert work.non_expert_weight_bytes(s) == 1116165120
    every = work.decode_step_bytes(s, 8 * 64, 0, 0)
    assert 7.15e9 < every < 7.16e9
    engine = _config()["engine"]
    assert engine["num_pages"] * engine["page_size"] * 4096 == 2415919104
    assert engine["window_pages"] * engine["page_size"] * 12288 == 3019898880
    assert s["sliding_window_size"] // engine["page_size"] + 1 == 65
    # a band's pairs, not a square's
    assert work.causal_pairs(8192) == 8192 * 8193 / 2
    assert work.causal_pairs(8192, 4096) == 4096 * 4097 / 2 + 4096 * 4096
    assert work.causal_pairs(100, 4096) == 100 * 101 / 2
    ops, byts = work.moe_grouped_ffn_work(s, 384, 64)
    assert ops == 2 * 384 * 3 * 2560 * 768 and byts > 64 * 11796480
    ops, byts = work.prompt_attention_work(s, 8192, 4096)
    assert ops == 4 * 28 * 128 * (4096 * 4097 / 2 + 4096 * 4096)
    assert byts == (2 * 28 + 2 * 4) * 128 * 2 * 8192
    ops, byts = work.paged_attention_decode_work(s, 64, 64 * 4096)
    assert ops == 4 * 28 * 128 * 64 * 4096
    assert byts == 2 * 512 * 2 * 64 * 4096 + 2 * 64 * 3584 * 2
    # the published file states the whole model beside the cut
    config = _config()
    assert config["num_hidden_layers"] == 8
    assert config["published"]["num_hidden_layers"] == 52
    assert len(config["rope_layout"]) == len(
        config["sliding_window_layout"]) == 52
    assert list(config["reduced"]) == ["num_hidden_layers"]


def test_the_smallthinker_mix_is_the_issues():
    with open(os.path.join(ROOT, "chipbench", "traffic", CELL + ".json")) as f:
        mix = json.load(f)
    pairs = mix["pairs"]
    long, short = pairs[:96], pairs[96:]
    assert len(pairs) == 128 and mix["clients"] == 256
    assert all(4096 <= p <= 12288 and 128 <= n <= 768 for p, n in long)
    assert all(64 <= p <= 1024 and 64 <= n <= 512 for p, n in short)
    assert len(mix["engine"]["prefill_buckets"]) <= 5
    assert max(mix["engine"]["prefill_buckets"]) == 12288
    assert mix["check_requests"] == 16 and mix["check_pad_to"] == 13312
    assert max(p + n for p, n in pairs) <= mix["check_pad_to"]


def _load(name):
    return bench_run.load_reader(name)


def _lap(**kw):
    rec = {"t0": 0.0, "t1": 0.06, "traced": True, "n": 64, "prefills": 1,
           "prefill_tokens": 8192, "context_tokens": 64 * 8000,
           "window_tokens": 64 * 4000, "ring_pages": 3600,
           "moe_pairs": 8 * 384, "moe_experts_hit": 8 * 64,
           "moe_load_max": 18,
           "phases": {"prefill_host": 0.002, "prefill_wait": 0.01,
                      "decode_dispatch": 0.002, "decode_wait": 0.043}}
    rec.update(kw)
    return rec


def _facts(**kw):
    facts = {"kind": "serve", "platform": "tpu", "chips": 1,
             "device_kind": "TPU v5 lite", "config": _config()}
    facts.update(kw)
    return facts


def test_smallthinker_readers_on_synthetic_laps(monkeypatch):
    from mxnet_tpu.telemetry import goodput

    laps = [_lap() for _ in range(12)]
    monkeypatch.setattr(goodput, "window", lambda kind: laps)
    got = {name: _load(name)(_facts()) for name in NEW}
    s = _sizes()
    assert got["kv_rows_streamed_share.serve"] == pytest.approx(
        100 * (2 * 8000 + 6 * 4000) / (8 * 8000))
    assert got["ring_pages_peak_share.serve"] == pytest.approx(
        100 * 3600 / 3840)
    assert got["moe_load_max_over_mean.swa_moe.serve"] == pytest.approx(3.0)
    flops = (8192 + 64) * 2 * 8 * 56524800 + 65 * 2 * 2560 * 151936 \
        + 4 * 28 * 128 * (
            2 * (64 * 8000 + work.causal_pairs(8192))
            + 6 * (64 * 4000 + work.causal_pairs(8192, 4096)))
    assert work.lap_flops(s, 8192, 1, 64, 64 * 8000, 64 * 4000) == flops
    assert got["step_mfu.swa_moe.serve"] == pytest.approx(
        100 * flops / 0.06 / 197e12)
    byts = 1116165120 + 512 * 11796480 + 64 * 8000 * 4096 \
        + 64 * 4000 * 12288
    assert got["decode_hbm_roofline.swa_moe.serve"] == pytest.approx(
        100 * byts / 0.045 / 819e9)
    assert 0 < got["step_mfu.swa_moe.serve"] < 100
    assert 0 < got["decode_hbm_roofline.swa_moe.serve"] < 100
    # a rehearsal has no chip whose peak to take; its counts are read
    cpu = {name: _load(name)(_facts(platform="cpu")) for name in NEW}
    assert cpu["step_mfu.swa_moe.serve"] is None
    assert cpu["decode_hbm_roofline.swa_moe.serve"] is None
    assert cpu["kv_rows_streamed_share.serve"] is not None


def test_smallthinker_readers_find_nothing_on_another_configurations_facts(monkeypatch):
    """Another configuration's sizes (LFM2's and GigaChat3's laps carry the
    expert counts too), the parent's laps (no ``window_tokens``, no
    ``ring_pages``), another kind of cell and an untraced run all read as
    None; and the readers of the other models' counts read None here."""
    from mxnet_tpu.telemetry import goodput

    laps = [_lap() for _ in range(12)]
    monkeypatch.setattr(goodput, "window", lambda kind: laps)
    for other in ("lfm2_24b_a2b", "gigachat3_702b_a36b", "gpt2_small"):
        with open(os.path.join(ROOT, "chipbench", "configs",
                               other + ".json")) as f:
            facts = _facts(config=json.load(f))
        for name in NEW:
            assert _load(name)(facts) is None, (name, other)
    for name in NEW:
        assert _load(name)(_facts(kind="train")) is None, name
    for name in OTHERS:
        if name != "state_slots_peak_share.serve":
            assert _load(name)(_facts()) is None, name
    old = [{k: v for k, v in _lap().items()
            if k not in ("window_tokens", "ring_pages")} for _ in range(12)]
    monkeypatch.setattr(goodput, "window", lambda kind: old)
    for name in NEW:
        if name != "moe_load_max_over_mean.swa_moe.serve":
            assert _load(name)(_facts()) is None, name
    assert _load("state_slots_peak_share.serve")(_facts()) is None
    monkeypatch.setattr(goodput, "window", lambda kind: [])
    for name in NEW:
        assert _load(name)(_facts()) is None, name


def test_benchmark_lists_the_smallthinker_cell_where_its_readers_read():
    bench = _bench()
    cells = [w for w in bench["workloads"] if w["name"] == CELL]
    assert len(cells) == 1 and cells[0]["chips"] == 1
    assert cells[0]["config"] == "smallthinker_21b_a3b"
    entry = [c for c in bench["configs"]
             if c["name"] == "smallthinker_21b_a3b"][0]
    assert entry["reduced"] == ["num_hidden_layers"]
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "output_token_rate"
    for name in OTHERS:
        assert CELL not in by_name[name]["workloads"]
    rate = [m for m in bench["end_to_end"]
            if m["name"] == "output_token_rate"][0]
    assert CELL in rate["workloads"] and rate["bound"] == 0.05
    for m in bench["per_layer"]:
        if m["moves"] == "output_token_rate":
            assert "workloads" in m, m["name"]
