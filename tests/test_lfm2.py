"""LFM2-MoE through the zoo block and the generation engine, against the
benchmark's plain reference (chipbench/reference/lfm2_moe.py), at a small
size on the CPU: seeded random weights, logits not tokens.

The reference draws its weights as bfloat16 values; a float32 zoo block or
engine holds the same numbers upcast, a bfloat16 one the numbers themselves.
"""
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from chipbench.models import lfm2_moe as factory
from chipbench.reference import lfm2_moe as ref
from mxnet_tpu.base import MXNetError
from mxnet_tpu.gluon.model_zoo.lfm2 import Lfm2LM
from mxnet_tpu.ops import pallas_kernels as pk
from mxnet_tpu.ops.contrib import sigmoid_topk_moe
from mxnet_tpu.serving.generate import (GenerateScheduler, StateSlotPool,
                                        TransformerLMEngine, load_lm,
                                        save_lm)

SIZES = {"vocab_size": 128, "hidden_size": 128, "intermediate_size": 256,
         "moe_intermediate_size": 128, "num_experts": 8,
         "num_experts_per_tok": 2, "num_attention_heads": 4,
         "num_key_value_heads": 2,
         "layer_types": ["conv", "full_attention", "conv", "conv",
                         "full_attention"],
         "num_dense_layers": 1, "conv_L_cache": 3, "norm_eps": 1e-5,
         "rope_theta": 1000000, "max_position_embeddings": 256,
         "routed_scaling_factor": 1, "norm_topk_prob": True}
SEED = 3000003911
GEOMETRY = dict(num_pages=48, page_size=4, max_prompt=16, max_new_tokens=8,
                decode_buckets=[2, 4], prefill_buckets=[8, 16])

# Tolerances on logits (their spread over the vocabulary is 0.23): float32
# against float32 differs by the order of summation only and reads 9e-7 at
# its worst position; TOL_F32 is ten times that.  A bfloat16 engine rounds
# every activation to 8 bits of mantissa, some thirty roundings deep: the
# worst logit of a position is off by 0.010-0.012 at the median position
# (three seeds), and by 0.2-0.3 at the one or two positions of sixteen where
# a near-tie for the router's last place flips an expert, which is why the
# statistic is the median over positions and not the maximum.  The reference
# itself computed one precision lower, in float8 e4m3 (3 bits), reads
# 0.16-0.29 at the median position.  TOL_BF16 lies between the two medians,
# a factor of four from either.
TOL_F32 = 1e-5
TOL_BF16 = 0.04


@pytest.fixture(scope="module")
def weights():
    return ref.make_weights(SEED, SIZES)


def _zoo(weights, dtype):
    lm = Lfm2LM(dtype=dtype, **SIZES)
    have = {n[len(lm.prefix):]: p for n, p in lm.collect_params().items()}
    want = {factory._zoo_name(k): v for k, v in weights.items()}
    assert set(have) == set(want)
    for name, p in have.items():
        p.adopt(want[name].astype(dtype))
    return lm


def _tokens(n, seed=0):
    return np.random.RandomState(seed).randint(
        0, SIZES["vocab_size"], n).astype(np.int32)


def _reference_logits(weights, tokens, control=None):
    return np.asarray(ref.forward(weights, jnp.asarray(tokens), SIZES,
                                  control))


def test_zoo_block_matches_the_reference(weights):
    lm = _zoo(weights, "float32")
    toks = _tokens(13)
    got = lm(mx.nd.array(toks[None], dtype="int32")).asnumpy()[0]
    want = _reference_logits(weights, toks)
    assert got.dtype == np.float32 and got.shape == want.shape
    assert np.abs(got - want).max() < TOL_F32


def _drive(engine, toks, n_prompt, slot, pages):
    """Logits of every position of ``toks``: the first ``n_prompt`` through
    one prefill, the rest one decode step each (row 0 of the smallest
    bucket; the other rows are padding), through ``pages`` and ``slot``."""
    ps, maxp = engine.page_size, engine.max_pages_per_seq
    b = engine.buckets[0]
    page_row = np.zeros(maxp, np.int32)
    page_row[:len(pages)] = pages
    out = [engine.prefill_logits(toks[:n_prompt].tolist(), page_row, slot)]
    for pos in range(n_prompt, len(toks)):
        tokens, positions = np.zeros(b, np.int32), np.zeros(b, np.int32)
        dest_pages = np.full(b, engine.num_pages, np.int32)
        dest_slots, lengths = np.zeros(b, np.int32), np.zeros(b, np.int32)
        tables = np.zeros((b, maxp), np.int32)
        seq_slots = np.full(b, engine.state_slots + 1, np.int32)
        tokens[0], positions[0] = toks[pos], pos
        dest_pages[0], dest_slots[0] = page_row[pos // ps], pos % ps
        tables[0], lengths[0], seq_slots[0] = page_row, pos + 1, slot
        out.append(engine.decode_logits(tokens, positions, dest_pages,
                                        dest_slots, tables, lengths,
                                        seq_slots)[:1])
    return np.concatenate(out)


@pytest.mark.parametrize("n_prompt", [1, 5, 11])
def test_prefill_and_decode_match_the_reference(weights, n_prompt):
    """Prompts that do not fill their bucket (8 or 16): pages and slot hold
    the prompt's own positions, not the padding's."""
    engine = TransformerLMEngine(lm=_zoo(weights, "float32"), **GEOMETRY)
    toks = _tokens(n_prompt + 6, seed=n_prompt)
    got = _drive(engine, toks, n_prompt, slot=1, pages=[7, 3, 9, 11, 2])
    assert np.abs(got - _reference_logits(weights, toks)).max() < TOL_F32


def test_a_slot_and_pages_reused_after_a_retire_carry_nothing_over(weights):
    engine = TransformerLMEngine(lm=_zoo(weights, "float32"), **GEOMETRY)
    first, second = _tokens(14, seed=1), _tokens(9, seed=2)
    _drive(engine, first, 9, slot=2, pages=[4, 5, 6, 8])
    got = _drive(engine, second, 2, slot=2, pages=[4, 5, 6, 8])
    assert np.abs(got - _reference_logits(weights, second)).max() < TOL_F32


def test_a_slot_not_carried_breaks_the_logits(weights):
    """The check has teeth: decode steps that read another slot than the
    prefill wrote are off by far more than the tolerance."""
    engine = TransformerLMEngine(lm=_zoo(weights, "float32"), **GEOMETRY)
    toks = _tokens(12, seed=3)
    ps, maxp = engine.page_size, engine.max_pages_per_seq
    page_row = np.zeros(maxp, np.int32)
    page_row[:4] = [1, 2, 3, 4]
    engine.prefill_logits(toks[:8].tolist(), page_row, 0)
    b = engine.buckets[0]
    args = [np.zeros(b, np.int32) for _ in range(2)]
    args[0][0], args[1][0] = toks[8], 8
    dest_pages = np.full(b, engine.num_pages, np.int32)
    dest_pages[0] = page_row[8 // ps]
    tables = np.zeros((b, maxp), np.int32)
    tables[0] = page_row
    lengths = np.zeros(b, np.int32)
    lengths[0] = 9
    wrong = np.full(b, engine.state_slots + 1, np.int32)
    wrong[0] = 3                       # a slot nobody wrote: zeros
    got = engine.decode_logits(args[0], args[1], dest_pages,
                               np.zeros(b, np.int32), tables, lengths, wrong)
    want = _reference_logits(weights, toks[:9])[8]
    assert np.abs(got[0] - want).max() > 100 * TOL_F32


def test_precision_float32_tightens_and_float8_breaks(weights):
    """The bfloat16 engine (the configuration's precision) is inside
    TOL_BF16 of the reference; the float32 engine is thousands of times
    closer; the reference computed in float8 is outside."""
    toks = _tokens(16, seed=4)
    want = _reference_logits(weights, toks)
    errs = {}
    for dtype in ("float32", "bfloat16"):
        engine = TransformerLMEngine(lm=_zoo(weights, dtype), **GEOMETRY)
        assert engine.kv_dtype == dtype
        assert all(a.dtype == jnp.dtype(dtype) for a in
                   jax.tree_util.tree_leaves((engine._params, engine._kv,
                                              engine._slots)))
        err = np.abs(_drive(engine, toks, 10, 0, [1, 2, 3, 4]) - want)
        # float32: the worst position; bfloat16: the median position
        errs[dtype] = err.max() if dtype == "float32" \
            else np.median(err.max(axis=1))
    errs["fp8"] = np.median(np.abs(
        _reference_logits(weights, toks, "fp8") - want).max(axis=1))
    assert errs["float32"] < TOL_F32 < errs["bfloat16"] < TOL_BF16 \
        < errs["fp8"], errs


# ---------------------------------------------------------------------------
# grouped-query paged attention: the kernel (interpret mode) and its oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype,heads,kv,d,maxp,lengths", [
    ("float32", 4, 2, 32, 3, [5, 33, 48]),
    ("float32", 8, 2, 64, 3, [5, 33, 48]),
    ("bfloat16", 8, 4, 32, 3, [5, 33, 48]),
    ("float32", 4, 4, 32, 3, [5, 33, 48]),
    # the kernel walks blocks of 8 pages: a table of 19 (two blocks and a
    # part), rows that end in the first, second and third block, a row one
    # token into a block, an inert row
    ("float32", 8, 2, 64, 19, [5, 129, 304]),
    ("bfloat16", 32, 8, 64, 19, [0, 128, 257]),
    ("float32", 4, 2, 32, 11, [176, 1, 0])])
def test_grouped_query_paged_attention_matches_its_oracle(
        monkeypatch, dtype, heads, kv, d, maxp, lengths):
    monkeypatch.setenv("MXTPU_PALLAS_DECODE", "1")
    rng = np.random.RandomState(heads * d)
    b, ps, pages = 3, 16, 12
    cp = -(-kv * d // 128) * 128
    q = jnp.asarray(rng.randn(b, heads, d), dtype)
    kp = jnp.asarray(rng.randn(pages, ps, cp), dtype)
    vp = jnp.asarray(rng.randn(pages, ps, cp), dtype)
    tables = jnp.asarray(rng.randint(0, pages, (b, maxp)), jnp.int32)
    n = lengths[1]
    live = np.asarray(lengths) > 0
    lengths = jnp.asarray(lengths, jnp.int32)
    assert pk._paged_kernel_takes(d, ps, cp, dtype, heads // kv)
    got = pk.paged_attention(q, kp, vp, tables, lengths, kv_heads=kv)
    want = pk.paged_attention_reference(q, kp, vp, tables, lengths,
                                        1.0 / np.sqrt(d), kv)
    # the oracle by hand for one query head: it reads KV head i // group
    i, g = heads - 1, heads // kv
    k = np.asarray(kp, np.float32)[np.asarray(tables[1])].reshape(
        maxp * ps, cp)[:n, (i // g) * d:(i // g + 1) * d]
    v = np.asarray(vp, np.float32)[np.asarray(tables[1])].reshape(
        maxp * ps, cp)[:n, (i // g) * d:(i // g + 1) * d]
    s = k @ np.asarray(q, np.float32)[1, i] / np.sqrt(d)
    p = np.exp(s - s.max())
    by_hand = (p / p.sum()) @ v
    # float32: both sum in float32 in another order; bfloat16: the output
    # is rounded to 8 bits of mantissa
    tol = 2e-5 if dtype == "float32" else 2e-2
    assert np.abs(np.asarray(want, np.float32)[1, i] - by_hand).max() < tol
    assert np.abs(np.asarray(got, np.float32)
                  - np.asarray(want, np.float32))[live].max() < tol
    assert not np.any(np.asarray(got, np.float32)[~live])


# ---------------------------------------------------------------------------
# the drop-free expert layer
# ---------------------------------------------------------------------------

def _moe_weights(e=8, c=128, f=128, seed=0, bias=0.3):
    rng = np.random.RandomState(seed)
    arr = lambda *s, std=0.1: jnp.asarray(rng.randn(*s) * std, jnp.float32)
    return dict(gate=arr(e, c), bias=arr(e, std=bias), w1=arr(e, f, c),
                w3=arr(e, f, c), w2=arr(e, f, c))


def _moe_by_loop(x, w, k):
    """Every expert over every token, masked: nobody dropped."""
    s = jax.nn.sigmoid(x @ w["gate"].T)
    _, sel = jax.lax.top_k(s + w["bias"], k)
    g = jnp.take_along_axis(s, sel, 1)
    g = g / (g.sum(1, keepdims=True) + 1e-6)
    out = jnp.zeros_like(x)
    for e in range(w["gate"].shape[0]):
        ge = (g * (sel == e)).sum(1)
        h = jax.nn.silu(x @ w["w1"][e].T) * (x @ w["w3"][e].T)
        out = out + ge[:, None] * (h @ w["w2"][e])
    return out, sel


@pytest.mark.parametrize("kernel", ["0", "1"], ids=["jnp", "pallas"])
def test_expert_layer_drops_nobody_when_one_expert_takes_every_token(
        monkeypatch, kernel):
    """A selection bias of +10 on expert 5 sends all 40 tokens there (and
    weighs nothing: the gates are the plain scores): a capacity-bound layer
    would drop most of them."""
    monkeypatch.setenv("MXTPU_PALLAS_DECODE", kernel)
    w = _moe_weights(bias=0.0)
    w["bias"] = w["bias"].at[5].set(10.0)
    x = jnp.asarray(np.random.RandomState(1).randn(40, 128), jnp.float32)
    out, stats = sigmoid_topk_moe(x, w["gate"], w["bias"], w["w1"], w["w3"],
                                  w["w2"], k=2)
    want, sel = _moe_by_loop(x, w, 2)
    assert bool((sel == 5).any(axis=1).all())
    assert int(stats[0]) == 80 and int(stats[2]) == 40
    # float32 on both sides, another order of summation
    assert np.abs(np.asarray(out - want)).max() < 1e-5


def test_eight_shares_of_eight_experts_add_up_to_the_uncut_layer():
    """64 experts, 4 a token; each of 8 holders routes over all 64 and
    computes its own 8: the parts add up to the whole layer, and to the
    plain loop over all experts."""
    w = _moe_weights(e=64, seed=2, bias=0.05)
    x = jnp.asarray(np.random.RandomState(3).randn(24, 128), jnp.float32)
    whole, stats = sigmoid_topk_moe(x, w["gate"], w["bias"], w["w1"],
                                    w["w3"], w["w2"], k=4)
    parts, pairs = 0.0, 0
    for share in range(8):
        o = share * 8
        part, st = sigmoid_topk_moe(x, w["gate"], w["bias"],
                                    w["w1"][o:o + 8], w["w3"][o:o + 8],
                                    w["w2"][o:o + 8], k=4, expert_offset=o)
        parts, pairs = parts + part, pairs + int(st[0])
    assert pairs == int(stats[0]) == 24 * 4
    assert np.abs(np.asarray(parts - whole)).max() < 1e-5
    assert np.abs(np.asarray(whole - _moe_by_loop(x, w, 4)[0])).max() < 1e-5


def test_zoo_block_holding_a_share_computes_its_part(weights):
    """`num_experts_held` / `expert_offset` on the zoo block: two holders
    of 4 experts each; the expert layers' parts differ, the shapes say
    what is held."""
    lm = Lfm2LM(dtype="float32", num_experts_held=4, expert_offset=4,
                **SIZES)
    shapes = {n: p.shape for n, p in lm.collect_params().items()}
    assert shapes[lm.prefix + "layer1_expert_w1"] == (4, 128, 128)
    assert shapes[lm.prefix + "layer1_gate_weight"] == (8, 128)
    assert lm.description()["experts"] == {
        "total": 8, "held": 4, "offset": 4, "per_token": 2, "scaling": 1.0,
        "norm_topk": True}
    with pytest.raises(MXNetError):
        Lfm2LM(num_experts_held=6, expert_offset=4, **SIZES)


def test_padding_rows_route_nowhere():
    w = _moe_weights()
    x = jnp.asarray(np.random.RandomState(4).randn(16, 128), jnp.float32)
    valid = jnp.arange(16) < 5
    out, stats = sigmoid_topk_moe(x, w["gate"], w["bias"], w["w1"], w["w3"],
                                  w["w2"], k=2, valid=valid)
    assert int(stats[0]) == 10            # 5 rows x 2 experts, no more
    assert float(jnp.abs(out[5:]).max()) == 0.0
    assert np.abs(np.asarray(out[:5] - _moe_by_loop(x, w, 2)[0][:5])).max() \
        < 1e-5


# ---------------------------------------------------------------------------
# artifact, repository, scheduler
# ---------------------------------------------------------------------------

def test_artifact_header_names_the_block_and_keeps_bfloat16(weights,
                                                            tmp_path):
    import json

    lm = _zoo(weights, "bfloat16")
    prefix = save_lm(lm, os.path.join(tmp_path, "lm"))
    with open(prefix + "-lmconfig.json") as f:
        header = json.load(f)
    assert header["arch"] == "lfm2"
    assert header["description"]["dtype"] == "bfloat16"
    assert [l["operator"] for l in header["description"]["layers"]] == [
        "conv", "attention", "conv", "conv", "attention"]
    assert [l["ffn"] for l in header["description"]["layers"]] == [
        "dense"] + ["experts"] * 4
    back = load_lm(prefix)
    assert type(back) is Lfm2LM
    for (n, p), (_, q) in zip(sorted(lm.collect_params().items()),
                              sorted(back.collect_params().items())):
        a, b = p.data()._data, q.data()._data
        assert b.dtype == jnp.bfloat16 and bool((a == b).all()), n


def test_served_through_the_repository_tokens_are_the_references_best(
        weights, tmp_path):
    """save_lm -> ModelRepository.load(generate=True) -> generate: more
    requests than state slots, so slots are reused; every served token's
    reference logit is the reference's best (float32 engine: within
    TOL_F32 of it), and pages and slots all come back."""
    from mxnet_tpu.serving import ModelRepository

    prefix = save_lm(_zoo(weights, "float32"), os.path.join(tmp_path, "lm"))
    repo = ModelRepository()
    model = repo.load("lfm2", prefix, generate=True, queue_depth=32,
                      generate_opts=GEOMETRY)
    try:
        sched = model.scheduler
        assert isinstance(sched.slots, StateSlotPool)
        assert sched.slots.num_slots == 4
        prompts = [_tokens(3 + 2 * i, seed=10 + i).tolist() for i in range(6)]
        got = [None] * len(prompts)

        def ask(i):
            got[i] = model.generate(prompts[i], max_new_tokens=6)["tokens"]

        threads = [threading.Thread(target=ask, args=(i,))
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
        for prompt, new in zip(prompts, got):
            assert len(new) == 6
            logits = _reference_logits(weights, np.int32(prompt + new))
            for j, tok in enumerate(new):
                row = logits[len(prompt) + j - 1]
                assert row.max() - row[tok] < TOL_F32
        assert sched.allocator.used_pages == 0
        assert sched.slots.used_slots == 0
        assert model.describe()["generate"]["state_slots"] == 4
    finally:
        repo.unload("lfm2", timeout=5.0)


def test_artifact_is_written_in_the_descriptions_dtype(weights, tmp_path):
    """A float32 block that adopted bfloat16 arrays (the benchmark's factory
    under a float32 override) is saved and loaded as float32, value for
    value."""
    lm = Lfm2LM(dtype="float32", **SIZES)
    have = {n[len(lm.prefix):]: p for n, p in lm.collect_params().items()}
    for k, v in weights.items():
        have[factory._zoo_name(k)].adopt(v)          # bfloat16 as drawn
    back = load_lm(save_lm(lm, os.path.join(tmp_path, "lm")))
    for name, p in back.collect_params().items():
        got = p.data()._data
        want = weights[[k for k in weights if back.prefix
                        + factory._zoo_name(k) == name][0]]
        assert got.dtype == jnp.float32
        assert bool((got == want.astype(jnp.float32)).all()), name


class _SlotStub:
    """No-model engine with state slots: records the slot of every call."""

    def __init__(self, fail_prefill=False, step_sleep=0.0):
        self.vocab_size, self.buckets = 64, [1, 2, 4]
        self.page_size, self.num_pages = 2, 24
        self.max_prompt, self.max_new_tokens = 4, 8
        self.max_pages_per_seq, self.eos_id = 6, None
        self.state_slots = 4
        self.fail_prefill, self.step_sleep = fail_prefill, step_sleep
        self.prefill_slots, self.step_slots = [], []

    def warm(self):
        return 0.0

    def prefill(self, tokens, page_row, sampling, key, slot=None):
        self.prefill_slots.append(slot)
        if self.fail_prefill:
            raise RuntimeError("no such prompt")
        return 1

    def decode_step(self, tokens, positions, dest_pages, dest_slots, tables,
                    lengths, temps, top_ks, top_ps, key, seq_slots=None):
        time.sleep(self.step_sleep)
        self.step_slots.append(np.array(seq_slots))
        return (np.asarray(tokens) + 1).astype(np.int32) % 64

    def geometry(self):
        return {}


def _settled(sched):
    deadline = time.monotonic() + 5
    while time.monotonic() < deadline and (
            sched.allocator.used_pages or sched.slots.used_slots):
        time.sleep(0.01)
    return sched.allocator.used_pages, sched.slots.used_slots


@pytest.mark.parametrize("how", ["retire", "abort", "deadline",
                                 "prefill_failure"])
def test_slots_and_pages_both_return(how):
    eng = _SlotStub(fail_prefill=how == "prefill_failure",
                    step_sleep=0.0 if how == "retire" else 0.02)
    sched = GenerateScheduler(eng, name="slotstub/%s" % how, queue_depth=16)
    try:
        deadline = time.monotonic() + 0.08 if how == "deadline" else None
        reqs = [sched.submit([1 + i], max_new_tokens=8, deadline=deadline)
                for i in range(6)]
        if how == "abort":
            time.sleep(0.05)                   # mid-decode
            assert sched.abort_pending() >= 1
        for r in reqs:
            if how == "retire":
                assert len(r.wait(10)) == 8
            else:
                with pytest.raises(Exception):
                    r.wait(10)
        assert _settled(sched) == (0, 0)
        # six requests through four slots: slots were handed out again, and
        # every live row of a step names a slot of its own, padding rows the
        # row past the inert one
        assert set(eng.prefill_slots) <= {0, 1, 2, 3}
        if how == "retire":
            assert len(eng.prefill_slots) == 6
        for slots in eng.step_slots:
            live = slots[slots <= 3]
            assert len(set(live.tolist())) == len(live)
            assert set(slots[slots > 3].tolist()) <= {5}
    finally:
        sched.close(drain=False, timeout=0)


def test_state_slot_pool_refuses_a_double_free():
    pool = StateSlotPool(2, name="pool/test")
    a, b = pool.alloc(), pool.alloc()
    assert {a, b} == {0, 1} and pool.alloc() is None
    pool.free(a)
    with pytest.raises(MXNetError):
        pool.free(a)
    assert pool.used_slots == 1


def test_gpt2_engine_has_no_slots_and_the_same_programs():
    """The 2019 decoder through the same engine class: no state slots, the
    pool alone at argnum 1, no expert counts behind the tokens."""
    from mxnet_tpu.gluon.model_zoo.transformer import lm_mini

    lm = lm_mini(vocab_size=64, max_length=32)
    lm.initialize(mx.init.Normal(0.1))
    engine = TransformerLMEngine(lm=lm, num_pages=16, page_size=4,
                                 max_prompt=8, max_new_tokens=8,
                                 decode_buckets=[2], prefill_buckets=[8])
    assert engine.state_slots == 0 and engine._slots == ()
    assert engine._state() is engine._kv and not engine.has_experts
    assert engine.description["layers"] == [
        {"operator": "attention", "ffn": "dense"}] * 2
    sched = GenerateScheduler(engine, name="gpt2/slots", queue_depth=4)
    try:
        assert sched.slots is None
        assert len(sched.submit([1, 2, 3], max_new_tokens=4).wait(60)) == 4
    finally:
        sched.close(drain=False, timeout=0)
