"""The benchmark's own arithmetic: the rate, slices and the median,
percentiles, the FLOP table, the generator's due times and its multiset."""
import collections
import json
import os

import pytest

from chipbench.lib import flops, loadgen, slices

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)


def _steady(n, dt, stall_at=None, stall=0.0):
    t, out = 0.0, [0.0]
    for i in range(n):
        t += dt + (stall if i == stall_at else 0.0)
        out.append(t)
    return out


def test_a_stall_is_in_the_rate_and_not_in_the_median_slice():
    quiet = _steady(128, 0.3)
    stalled = _steady(128, 0.3, stall_at=57, stall=0.6)
    for done in (quiet, stalled):
        edges, work = slices.step_slices(done, 8, 256)
        s = slices.summary(edges, work)
        assert s["slices"] == 16
        assert s["median_rate"] == pytest.approx(256 / 0.3)
    q = slices.summary(*slices.step_slices(quiet, 8, 256))
    s = slices.summary(*slices.step_slices(stalled, 8, 256))
    assert q["stall_share_pct"] == pytest.approx(0.0, abs=1e-9)
    # one 0.6 s stall in 38.4 s of steps: the mean falls by 0.6 / 39.0
    assert s["mean_rate"] == pytest.approx(128 * 256 / 39.0)
    assert s["stall_share_pct"] == pytest.approx(100 * 0.6 / 39.0)
    assert s["min_rate"] == pytest.approx(8 * 256 / (8 * 0.3 + 0.6))


@pytest.mark.parametrize("metric,kind", [("train_throughput", "train"),
                                         ("output_token_rate", "serve")])
def test_the_end_to_end_rate_is_all_work_over_all_time(metric, kind):
    from chipbench import run as bench_run

    stalled = _steady(128, 0.3, stall_at=57, stall=0.6)
    window = slices.summary(*slices.step_slices(stalled, 8, 256))
    value = bench_run.load_reader(metric)({"kind": kind, "window": window})
    assert value == pytest.approx(128 * 256 / 39.0)
    assert value < window["median_rate"]


def test_steps_after_the_last_whole_slice_are_dropped():
    edges, work = slices.step_slices(_steady(100, 0.25), 8, 10)
    assert len(work) == 12 and edges[-1] == pytest.approx(96 * 0.25)


def test_fewer_than_twelve_slices_is_an_error():
    with pytest.raises(ValueError):
        slices.summary(*slices.step_slices(_steady(88, 0.3), 8, 1))


def test_cut_counts_uneven_slices_by_their_own_length():
    assert slices.cut([0.0, 2.0, 5.0], [10, 30]) == [5.0, 10.0]


def test_percentile_and_samples_beyond():
    v, beyond = slices.percentile(list(range(1, 201)), 95)
    assert v == pytest.approx(190.05) and beyond == 10


def test_resnet50_macs_match_the_published_counts():
    args = dict(layers=[3, 4, 6, 3], channels=[64, 256, 512, 1024, 2048],
                classes=1000, hw=224)
    macs = sum(m for _, m in flops.resnet_v1_layers(**args))
    # He et al. 2015, Table 1: 3.8e9 multiply-adds (stride in the 1x1)
    assert macs == pytest.approx(3.8e9, rel=0.02)
    # the variant with the stride in the 3x3 is the often quoted 4.1e9
    v15 = sum(m for _, m in flops.resnet_v1_layers(stride_in_3x3=True, **args))
    assert v15 == pytest.approx(4.1e9, rel=0.01)
    assert flops.resnet_v1_train_flops_per_image(**args) == 6 * macs
    assert len(flops.resnet_v1_layers(**args)) == 1 + 16 * 3 + 4 + 1


def test_transformer_flops_per_token():
    f = flops.transformer_lm_flops_per_token(768, 3072, 12, 50257, 0)
    # 2 x (85M layer weights + 38.6M head)
    assert f == pytest.approx(2 * (12 * (4 * 768 ** 2 + 2 * 768 * 3072)
                                   + 768 * 50257))


def _traffic(name):
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", ["gpt2s_chat_open", "gpt2s_docs_closed"])
def test_every_seed_offers_the_same_multiset(name):
    t = _traffic(name)
    want = collections.Counter(tuple(p) for p in t["pairs"])
    assert sum(want.values()) == 128
    plans = [loadgen.plan(dict(t, max_requests=256, burst=0, rate_rps=1000.0),
                          seed, 50257, 0.256) for seed in (1, 3000000001)]
    for plan in plans:
        first = collections.Counter((len(r["prompt"]), r["new"])
                                    for r in plan[:128])
        assert first == want
    a, b = plans
    assert [len(r["prompt"]) for r in a[:128]] != \
        [len(r["prompt"]) for r in b[:128]]
    assert a[0]["prompt"] != b[0]["prompt"]


def test_open_loop_due_times():
    t = dict(_traffic("gpt2s_chat_open"), loop="open", rate_rps=50.0, burst=10)
    plan = loadgen.plan(t, 7, 100, 20.0)
    dues = [r["due"] for r in plan]
    assert dues[:10] == [0.0] * 10 and dues == sorted(dues)
    assert dues[-1] >= 20.0 > dues[-2]
    gaps = [b - a for a, b in zip(dues[10:], dues[11:])]
    assert sum(gaps) / len(gaps) == pytest.approx(1 / 50.0, rel=0.15)
    again = loadgen.plan(t, 7, 100, 20.0)
    assert [r["due"] for r in again] == dues
    assert json.loads(plan[0]["body"])["max_new_tokens"] == plan[0]["new"]


def test_closed_loop_has_no_due_times():
    plan = loadgen.plan(dict(_traffic("gpt2s_docs_closed"), max_requests=200),
                        3, 1000, 10.0)
    assert len(plan) == 200 and all(r["due"] is None for r in plan)
    assert {r["new"] for r in plan} == {16}
    assert {len(r["prompt"]) for r in plan} == set(range(512, 961, 64))
