"""The cell `lfm2_reason_closed` walked at its tiny sizes on the CPU through
the harness's own entry, traced and not; `correct` coming out false when a
token is altered or a sequence's convolution state is not carried from one
decode step to the next; and the work functions and readers it brings."""
import json
import os

import pytest

from chipbench import run as bench_run
from chipbench.lib import lfm2_work

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "lfm2_reason_closed"
NEW = ["moe_pairs_per_expert.serve", "moe_load_max_over_mean.serve",
       "state_slots_peak_share.serve", "prefill_lap_share.serve",
       "step_mfu.serve", "decode_hbm_roofline.serve"]


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _sizes():
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "lfm2_24b_a2b.json")) as f:
        return json.load(f)["sizes"]


def _rehearse(trace=0, seed=3000003907, **kw):
    opts = bench_run.Options(seed, 4.0, trace, rehearse=True, **kw)
    return bench_run.run_cell(CELL, opts)


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_is_correct_and_prints_the_contracts_line(trace):
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
        # the counters ride out of every step: 8 rows x 2 experts x 3
        # expert layers over at most 8 experts a layer
        assert 2.0 <= got["moe_pairs_per_expert.serve"]["value"] <= 8.0
        assert got["moe_load_max_over_mean.serve"]["value"] >= 1.0
        assert got["state_slots_peak_share.serve"]["value"] == 100.0
        assert got["decode_batch_mean"]["value"] == 8.0
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


def _drop_slots(model):
    # every decode row reads the inert slot: the convolution state that the
    # prefill and the steps before wrote is not carried
    engine = model.scheduler.engine
    step = engine.decode_step

    def broken(*args, seq_slots=None, **kw):
        return step(*args, seq_slots=None, **kw)

    engine.decode_step = broken


@pytest.mark.parametrize("breaker", [_alter_tokens, _drop_slots],
                         ids=["token_altered", "slot_not_carried"])
def test_correct_is_false_when_the_timed_path_is_broken(breaker, capsys):
    result = _rehearse(break_step=breaker)
    assert result["correct"] is False
    assert "FAIL" in capsys.readouterr().out


def test_the_cut_is_the_issues_arithmetic():
    """9 layers of the published widths: 5178 M parameters, 648 M of them
    active a token; a decode step that hits every expert reads 10.4 GB."""
    s = _sizes()
    n = lfm2_work.layer_counts(s)
    assert n == {"attention": 2, "conv": 7, "dense": 1, "experts": 8}
    attn, conv = lfm2_work.operator_params(s)
    assert (attn, conv) == (10485760, 16783360)
    total = (n["attention"] * attn + n["conv"] * conv
             + 3 * 2048 * 11776 + n["experts"] * (64 * 2048 + 64
                                                  * lfm2_work.expert_params(s))
             + 65536 * 2048)
    assert round(total / 1e6) == 5178
    assert lfm2_work.active_params_per_token(s) == 648062976
    every = lfm2_work.decode_step_bytes(s, 128, 8 * 64, 0)
    assert 10.35e9 < every < 10.40e9
    assert lfm2_work.kv_bytes_per_token(s) == 4096
    assert lfm2_work.slot_bytes_per_sequence(s) == 57344
    ops, byts = lfm2_work.moe_grouped_ffn_work(s, 512, 64)
    assert ops == 2 * 512 * 3 * 2048 * 1536 and byts > 64 * 18874368
    ops, byts = lfm2_work.paged_attention_decode_work(s, 128, 128 * 400)
    assert ops == 4 * 2048 * 128 * 400
    assert byts == 2 * 512 * 2 * 128 * 400 + 2 * 128 * 2048 * 2


def _load(name):
    return bench_run.load_reader(name)


def _lap(**kw):
    rec = {"t0": 0.0, "t1": 0.025, "traced": True, "n": 128, "prefills": 1,
           "prefill_tokens": 128, "context_tokens": 128 * 400,
           "moe_pairs": 8 * 512, "moe_experts_hit": 8 * 64,
           "moe_load_max": 16, "state_slots": 128,
           "phases": {"prefill_host": 0.002, "prefill_wait": 0.003,
                      "decode_dispatch": 0.001, "decode_wait": 0.017}}
    rec.update(kw)
    return rec


def test_readers_on_synthetic_laps(monkeypatch):
    from mxnet_tpu import telemetry
    from mxnet_tpu.telemetry import goodput

    laps = [_lap() for _ in range(12)]
    monkeypatch.setattr(goodput, "window", lambda kind: laps)
    telemetry.gauge("mxtpu_serve_state_slots_total",
                    {"model": "lm/1"}).set(128)
    facts = {"kind": "serve", "platform": "tpu", "chips": 1,
             "device_kind": "TPU v5 lite", "config": {"sizes": _sizes()}}
    got = {name: _load(name)(facts) for name in NEW}
    assert got["moe_pairs_per_expert.serve"] == 8.0
    assert got["moe_load_max_over_mean.serve"] == 2.0
    assert got["state_slots_peak_share.serve"] == 100.0
    assert got["prefill_lap_share.serve"] == pytest.approx(20.0)
    s = _sizes()
    flops = 256 * lfm2_work.token_flops(s) + lfm2_work.attention_flops(
        s, 128 * 400 + 128 * 64)
    assert got["step_mfu.serve"] == pytest.approx(
        100 * flops / 0.025 / 197e12)
    byts = lfm2_work.decode_step_bytes(s, 128, 512, 128 * 400)
    assert got["decode_hbm_roofline.serve"] == pytest.approx(
        100 * byts / 0.018 / 819e9)
    assert 0 < got["step_mfu.serve"] < 100
    assert 0 < got["decode_hbm_roofline.serve"] < 100


def test_readers_find_nothing_in_a_program_without_the_fields(monkeypatch):
    """The parent's laps (no expert counts, no slots, no prefill tokens),
    another kind of cell and an untraced run all read as None."""
    from mxnet_tpu.telemetry import goodput

    old = [{"t0": 0.0, "t1": 0.02, "traced": True, "n": 64, "prefills": 1,
            "phases": {"decode_dispatch": 0.003, "decode_wait": 0.01}}
           for _ in range(12)]
    monkeypatch.setattr(goodput, "window", lambda kind: old)
    facts = {"kind": "serve", "platform": "tpu", "chips": 1,
             "device_kind": "TPU v5 lite", "config": {"sizes": _sizes()}}
    for name in NEW:
        assert _load(name)(facts) is None, name
        assert _load(name)(dict(facts, kind="train")) is None, name
    monkeypatch.setattr(goodput, "window", lambda kind: [])
    for name in NEW:
        assert _load(name)(facts) is None, name
