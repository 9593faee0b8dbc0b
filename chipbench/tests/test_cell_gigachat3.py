"""The cell `gigachat3_rag_closed` walked at its tiny sizes on the CPU through
the harness's own entry, traced and not; `correct` coming out false when a
served token is altered or the rotary part of the cached rows is dropped in
decode; and the work functions and readers it brings."""
import json
import os

import pytest

from chipbench import run as bench_run
from chipbench.lib import mla_moe_work

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "gigachat3_rag_closed"
NEW = ["step_mfu.mla_moe.serve", "decode_hbm_roofline.mla_moe.serve",
       "latent_rows_per_step.serve", "moe_load_max_over_mean.mla_moe.serve"]
# readers that take their counts from LFM2's shapes, or read state this
# model does not have: not this cell's
NOT_HERE = ["step_mfu.serve", "decode_hbm_roofline.serve",
            "moe_load_max_over_mean.serve", "state_slots_peak_share.serve"]


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _config():
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "gigachat3_702b_a36b.json")) as f:
        return json.load(f)


def _sizes():
    return _config()["sizes"]


def _rehearse(trace=0, seed=3000004307, **kw):
    if trace:
        # the lap readers want 10 traced laps: on a busy test machine the
        # rehearsal's half second of tracing may hold fewer
        kw.setdefault("overrides", {"traffic.trace_s": 2.0})
    opts = bench_run.Options(seed, 4.0, trace, rehearse=True, **kw)
    return bench_run.run_cell(CELL, opts)


@pytest.mark.parametrize("trace", [0, 1])
def test_gigachat3_rehearsal_is_correct_and_prints_the_contracts_line(trace):
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
        assert not set(NOT_HERE) & set(got)
        # 8 rows of at most 24 cached tokens each
        assert 8.0 <= got["latent_rows_per_step.serve"]["value"] <= 8 * 24
        # the counters ride out of every step: 8 rows x 4 experts x 2
        # expert layers, of which the 8 held of 16 get about half
        assert 1.0 <= got["moe_pairs_per_expert.serve"]["value"] <= 8.0
        # the busiest of the 8 held over the mean: at least even, at most
        # every pair of a step on one expert of one layer
        assert 1.0 <= got["moe_load_max_over_mean.mla_moe.serve"]["value"] \
            <= 16.0
        assert got["decode_batch_mean"]["value"] == 8.0
        # a share of a peak has no value without a chip: left out here
        assert "step_mfu.mla_moe.serve" not in got
    else:
        assert set(result["metrics"]) == {"output_token_rate", "setup_s"}
    json.dumps(result)


def _alter_tokens(model):
    # a token altered where it is produced
    engine = model.scheduler.engine
    step = engine.decode_step

    def broken(tokens, *rest, **kw):
        return (step(tokens, *rest, **kw) + 1) % engine.vocab_size

    engine.decode_step = broken


def _drop_rotary(model):
    # before every decode step the rotary lanes of every cached row are
    # wiped: the absorbed path attends over the compressed part alone
    engine = model.scheduler.engine
    step = engine.decode_step
    rank = engine.description["latent"]["kv_rank"]

    def broken(*args, **kw):
        engine._kv = tuple(a.at[:, :, rank:].set(0) for a in engine._kv)
        return step(*args, **kw)

    engine.decode_step = broken


@pytest.mark.parametrize("breaker", [_alter_tokens, _drop_rotary],
                         ids=["token_altered", "rotary_part_dropped"])
def test_gigachat3_correct_is_false_when_the_timed_path_is_broken(breaker,
                                                                  capsys):
    result = _rehearse(break_step=breaker)
    assert result["correct"] is False
    assert "FAIL" in capsys.readouterr().out


def test_the_gigachat3_cut_is_the_issues_arithmetic():
    """One dense and four expert layers of the published widths with 16 of
    256 experts and an eighth of the vocabulary: 4291 M parameters = 8.58
    GB; 2.72 GB of them a decode step reads whatever it routes; 139264
    operations a cached row a layer; 1152 live bytes a row a layer."""
    s = _sizes()
    assert mla_moe_work.layer_counts(s) == {"layers": 5, "dense": 1,
                                            "experts": 4}
    assert mla_moe_work.attention_params(s) == (
        7168 * 1536 + 1536 * 64 * 192 + 7168 * 576 + 512 * 64 * 320
        + 64 * 192 * 7168) == 132579328
    assert mla_moe_work.expert_params(s) == 3 * 7168 * 2048 == 44040192
    layer = 132579328 + 44040192 + 256 * 7168 + 16 * 44040192
    assert round(layer / 1e4) == 88310                  # 883.10 M
    total = (132579328 + 3 * 7168 * 18432) + 4 * layer + 2 * 16032 * 7168
    assert mla_moe_work.total_params(s) == total
    assert round(total / 1e6) == 4291 and round(2 * total / 1e7) == 858
    assert round(mla_moe_work.non_expert_weight_bytes(s) / 1e7) == 272
    assert mla_moe_work.decode_attention_flops_per_row(s) == 139264
    assert mla_moe_work.prefill_attention_flops_per_pair(s) == 2 * 64 * 384
    assert mla_moe_work.cache_bytes_per_token(s) == 5 * 1152
    every = mla_moe_work.decode_step_bytes(s, 4 * 16, 310000)
    assert every == (mla_moe_work.non_expert_weight_bytes(s)
                     + 64 * 88080384 + 310000 * 5760)
    ops, byts = mla_moe_work.paged_latent_attention_decode_work(
        s, 128, 310000)
    assert ops == 310000 * 139264
    assert byts == 310000 * 1152 + 128 * 64 * (576 + 512) * 2
    ops, byts = mla_moe_work.moe_grouped_ffn_work(s, 64, 16)
    assert ops == 2 * 64 * 44040192 and byts == 16 * 88080384 + 64 * 7168 * 6
    # the configuration file says what the arithmetic says
    config = _config()
    assert config["n_routed_experts"] == s["num_experts_held"] == 16
    assert s["n_routed_experts"] == 256 and "num_experts" not in s
    entry = [c for c in _bench()["configs"]
             if c["name"] == "gigachat3_702b_a36b"][0]
    assert set(entry["reduced"]) == set(config["reduced"])
    pool = config["engine"]["num_pages"] * config["engine"]["page_size"] \
        * 5 * 640 * 2
    assert pool == 3355443200


def _load(name):
    return bench_run.load_reader(name)


def _lap(**kw):
    rec = {"t0": 0.0, "t1": 0.060, "traced": True, "n": 128, "prefills": 1,
           "prefill_tokens": 2048, "prefill_moe_pairs": 4096,
           "context_tokens": 300000, "moe_pairs": 256, "moe_experts_hit": 62,
           "moe_load_max": 9,
           "phases": {"prefill_host": 0.004, "prefill_wait": 0.030,
                      "decode_dispatch": 0.003, "decode_wait": 0.019}}
    rec.update(kw)
    return rec


def test_gigachat3_readers_on_synthetic_laps(monkeypatch):
    from mxnet_tpu.telemetry import goodput

    laps = [_lap() for _ in range(12)]
    monkeypatch.setattr(goodput, "window", lambda kind: laps)
    s = _sizes()
    facts = {"kind": "serve", "platform": "tpu", "chips": 1,
             "device_kind": "TPU v5 lite", "config": {"sizes": s}}
    got = {name: _load(name)(facts) for name in NEW}
    assert got["latent_rows_per_step.serve"] == 300000.0
    # 256 pairs over 4 layers x 16 held = 4 each; the busiest got 9
    assert got["moe_load_max_over_mean.mla_moe.serve"] == 9 / 4.0
    flops = (2 * (2048 + 128) * mla_moe_work.non_expert_params(s)
             + 2 * (4096 + 256) * 44040192
             + 5 * (300000 * 139264 + 2048 * 1024 * 2 * 64 * 384))
    assert got["step_mfu.mla_moe.serve"] == pytest.approx(
        100 * flops / 0.060 / 197e12)
    byts = mla_moe_work.decode_step_bytes(s, 62, 300000)
    assert got["decode_hbm_roofline.mla_moe.serve"] == pytest.approx(
        100 * byts / 0.022 / 819e9)
    assert 0 < got["step_mfu.mla_moe.serve"] < 100
    assert 0 < got["decode_hbm_roofline.mla_moe.serve"] < 100
    # a rehearsal has no chip whose peak to take; the counter still reads
    cpu = dict(facts, platform="cpu")
    assert _load("step_mfu.mla_moe.serve")(cpu) is None
    assert _load("decode_hbm_roofline.mla_moe.serve")(cpu) is None
    assert _load("latent_rows_per_step.serve")(cpu) == 300000.0
    assert _load("moe_load_max_over_mean.mla_moe.serve")(cpu) == 9 / 4.0


def test_gigachat3_readers_find_nothing_elsewhere(monkeypatch):
    """Another configuration's sizes (LFM2's: no latent rank), the parent's
    laps (no expert counts, no prefill tokens), another kind of cell and an
    untraced run all read as None; and LFM2's readers read None for this
    configuration, whose sizes spell the expert count another way."""
    from chipbench.lib import lfm2_work
    from mxnet_tpu.telemetry import goodput

    with open(os.path.join(ROOT, "chipbench", "configs",
                           "lfm2_24b_a2b.json")) as f:
        lfm2 = json.load(f)["sizes"]
    laps = [_lap() for _ in range(12)]
    monkeypatch.setattr(goodput, "window", lambda kind: laps)
    base = {"kind": "serve", "platform": "tpu", "chips": 1,
            "device_kind": "TPU v5 lite"}
    mine = dict(base, config={"sizes": _sizes()})
    for name in NEW:
        assert _load(name)(dict(base, config={"sizes": lfm2})) is None, name
        assert _load(name)(dict(mine, kind="train")) is None, name
    assert lfm2_work.sizes_of(mine) is None
    for name in ("step_mfu.serve", "decode_hbm_roofline.serve"):
        assert _load(name)(mine) is None, name
    old = [{"t0": 0.0, "t1": 0.02, "traced": True, "n": 64, "prefills": 1,
            "phases": {"decode_dispatch": 0.003, "decode_wait": 0.01}}
           for _ in range(12)]
    monkeypatch.setattr(goodput, "window", lambda kind: old)
    for name in NEW:
        assert _load(name)(mine) is None, name
    monkeypatch.setattr(goodput, "window", lambda kind: [])
    for name in NEW:
        assert _load(name)(mine) is None, name
