"""GigaChat3 (`deepseek_v3`: latent attention, group-limited routing, a
shared expert, a multi-token-prediction module) through the zoo block and
the generation engine, against the benchmark's plain reference
(chipbench/reference/gigachat3.py), at a small size on the CPU: seeded
random weights, logits and never sampled tokens.

The reference draws its weights as bfloat16 values; a float32 zoo block or
engine holds the same numbers upcast, a bfloat16 one the numbers themselves.
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from chipbench.models import gigachat3 as factory
from chipbench.reference import gigachat3 as ref
from mxnet_tpu.base import MXNetError
from mxnet_tpu.gluon.model_zoo.gigachat3 import GigaChat3LM
from mxnet_tpu.ops import nn as opsnn
from mxnet_tpu.ops import pallas_kernels as pk
from mxnet_tpu.ops.contrib import sigmoid_topk_moe
from mxnet_tpu.serving.generate import (GenerateScheduler,
                                        TransformerLMEngine, load_lm,
                                        save_lm)
from mxnet_tpu.telemetry import goodput

YARN = {"beta_fast": 32, "beta_slow": 1, "factor": 4, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 16,
        "rope_type": "yarn"}
SIZES = {"vocab_size": 128, "hidden_size": 64, "intermediate_size": 128,
         "moe_intermediate_size": 32, "num_hidden_layers": 3,
         "first_k_dense_replace": 1, "n_routed_experts": 16,
         "num_experts_held": 8, "expert_offset": 0, "n_shared_experts": 1,
         "num_experts_per_tok": 4, "n_group": 4, "topk_group": 2,
         "routed_scaling_factor": 2.5, "norm_topk_prob": True,
         "num_attention_heads": 4, "q_lora_rank": 48, "kv_lora_rank": 32,
         "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 24,
         "rms_norm_eps": 1e-6, "rope_theta": 100000, "rope_scaling": YARN,
         "max_position_embeddings": 256, "num_nextn_predict_layers": 1}
SEED = 3000004311
GEOMETRY = dict(num_pages=24, page_size=8, max_prompt=16, max_new_tokens=8,
                decode_buckets=[2, 4], prefill_buckets=[8, 16])

# Tolerances on logits (their spread over the vocabulary is 0.9): float32
# against float32 differs by the order of summation only (the absorbed
# decode path contracts kv_b before the cache where the reference contracts
# it after) and reads 2.7e-6 at its worst position; TOL_F32 is eight times
# that.  A bfloat16 engine rounds every activation, the cached row and the
# absorbed query to 8 bits of mantissa: the worst logit of a position is off
# by 0.02-0.03 at the median position, and by several tenths where a
# near-tie for the router's last place flips an expert, which is why the
# statistic is the median over positions.  The reference itself computed one
# precision lower, in float8 e4m3 (3 bits), reads 0.3-0.5 there.  TOL_BF16
# lies between the two medians.
TOL_F32 = 2e-5
TOL_BF16 = 0.1


@pytest.fixture(scope="module")
def weights():
    return ref.make_weights(SEED, SIZES)


def _zoo(weights, dtype, **over):
    return factory.build(dict(SIZES, **over), weights, dtype)


def _tokens(n, seed=0):
    return np.random.RandomState(seed).randint(
        0, SIZES["vocab_size"], n).astype(np.int32)


def _reference_logits(weights, tokens, control=None):
    return np.asarray(ref.forward(weights, jnp.asarray(tokens), SIZES,
                                  control))


def test_zoo_block_matches_the_reference(weights):
    lm = _zoo(weights, "float32")
    toks = _tokens(21)
    got = lm(mx.nd.array(toks[None], dtype="int32")).asnumpy()[0]
    want = _reference_logits(weights, toks)
    assert got.dtype == np.float32 and got.shape == want.shape
    assert np.abs(got - want).max() < TOL_F32


def test_prediction_module_matches_the_reference(weights):
    """Row i of the module's logits predicts token i + 2 from the stream at
    i and the embedding of token i + 1; a model built without the module
    says so."""
    lm = _zoo(weights, "float32")
    toks = _tokens(14, seed=5)
    got = lm.mtp_logits(mx.nd.array(toks[None], dtype="int32")).asnumpy()[0]
    want = np.asarray(ref.forward_mtp(weights, jnp.asarray(toks), SIZES))
    assert got.shape == want.shape == (13, SIZES["vocab_size"])
    assert np.abs(got - want).max() < TOL_F32
    # and it is another function than the main head's
    assert np.abs(want - _reference_logits(weights, toks)[:13]).max() > 0.1
    bare = {k: v for k, v in weights.items() if not k.startswith("mtp.")}
    with pytest.raises(MXNetError):
        _zoo(bare, "float32", num_nextn_predict_layers=0).mtp_logits(
            mx.nd.array(toks[None], dtype="int32"))


def _drive(engine, toks, n_prompt, pages):
    """Logits of every position of ``toks``: the first ``n_prompt`` through
    one prefill (the expanded path), the rest one decode step each through
    the latent pool (the absorbed path; row 0 of the smallest bucket, the
    other rows padding)."""
    ps, maxp = engine.page_size, engine.max_pages_per_seq
    b = engine.buckets[0]
    page_row = np.zeros(maxp, np.int32)
    page_row[:len(pages)] = pages
    out = [engine.prefill_logits(toks[:n_prompt].tolist(), page_row)]
    for pos in range(n_prompt, len(toks)):
        tokens, positions = np.zeros(b, np.int32), np.zeros(b, np.int32)
        dest_pages = np.full(b, engine.num_pages, np.int32)
        dest_slots, lengths = np.zeros(b, np.int32), np.zeros(b, np.int32)
        tables = np.zeros((b, maxp), np.int32)
        tokens[0], positions[0] = toks[pos], pos
        dest_pages[0], dest_slots[0] = page_row[pos // ps], pos % ps
        tables[0], lengths[0] = page_row, pos + 1
        out.append(engine.decode_logits(tokens, positions, dest_pages,
                                        dest_slots, tables, lengths)[:1])
    return np.concatenate(out)


@pytest.mark.parametrize("n_prompt,kernel,buckets", [
    (1, "0", [8, 16]), (5, "1", [8, 16]), (11, "0", [8, 16]),
    (11, "1", [8, 16]), (5, "0", [12, 20]), (13, "0", [12, 20])])
def test_prefill_then_absorbed_decode_match_the_reference(
        weights, monkeypatch, n_prompt, kernel, buckets):
    """Prompts that do not fill their bucket, buckets that end on a page's
    edge (8, 16) and inside a page (12, 20: the prefill writes whole pages
    either way), decode steps that cross a page's edge; through the
    dense-gather oracle and through the Pallas kernel (interpret mode)."""
    monkeypatch.setenv("MXTPU_PALLAS_DECODE", kernel)
    engine = TransformerLMEngine(
        lm=_zoo(weights, "float32"),
        **dict(GEOMETRY, prefill_buckets=buckets, max_prompt=buckets[-1]))
    assert engine.latent and len(engine._kv) == 3
    assert all(a.shape == (24, 8, 128) for a in engine._kv)   # one a layer
    toks = _tokens(n_prompt + 8, seed=n_prompt)
    got = _drive(engine, toks, n_prompt, pages=[7, 3, 9])
    assert np.abs(got - _reference_logits(weights, toks)).max() < TOL_F32


def test_pages_reused_after_a_retire_carry_nothing_over(weights):
    engine = TransformerLMEngine(lm=_zoo(weights, "float32"), **GEOMETRY)
    first, second = _tokens(20, seed=1), _tokens(9, seed=2)
    _drive(engine, first, 12, pages=[4, 5, 6])
    got = _drive(engine, second, 2, pages=[4, 5, 6])
    assert np.abs(got - _reference_logits(weights, second)).max() < TOL_F32


def test_a_cached_row_without_its_rotary_part_breaks_the_logits(weights):
    """The check has teeth: the same steps over pages whose rotary lanes
    were wiped are off by far more than the tolerance."""
    engine = TransformerLMEngine(lm=_zoo(weights, "float32"), **GEOMETRY)
    toks = _tokens(12, seed=3)
    rank = SIZES["kv_lora_rank"]
    _drive(engine, toks[:9], 8, pages=[1, 2])
    engine._kv = tuple(a.at[:, :, rank:].set(0) for a in engine._kv)
    ps, maxp, b = engine.page_size, engine.max_pages_per_seq, 2
    page_row = np.zeros(maxp, np.int32)
    page_row[:2] = [1, 2]
    tokens, positions = np.zeros(b, np.int32), np.zeros(b, np.int32)
    tokens[0], positions[0] = toks[9], 9
    dest_pages = np.full(b, engine.num_pages, np.int32)
    dest_pages[0] = page_row[9 // ps]
    dest_slots = np.asarray([9 % ps, 0], np.int32)
    tables = np.zeros((b, maxp), np.int32)
    tables[0] = page_row
    got = engine.decode_logits(tokens, positions, dest_pages, dest_slots,
                               tables, np.asarray([10, 0], np.int32))
    want = _reference_logits(weights, toks[:10])[9]
    assert np.abs(got[0] - want).max() > 100 * TOL_F32


def test_precision_float32_tightens_and_float8_breaks(weights):
    """The bfloat16 engine (the configuration's precision) is inside
    TOL_BF16 of the reference; the float32 engine is thousands of times
    closer; the reference computed in float8 is outside."""
    toks = _tokens(20, seed=4)
    want = _reference_logits(weights, toks)
    errs = {}
    for dtype in ("float32", "bfloat16"):
        engine = TransformerLMEngine(lm=_zoo(weights, dtype), **GEOMETRY)
        assert engine.kv_dtype == dtype
        assert all(a.dtype == jnp.dtype(dtype) for a in
                   jax.tree_util.tree_leaves((engine._params, engine._kv)))
        err = np.abs(_drive(engine, toks, 12, [1, 2, 3]) - want)
        errs[dtype] = err.max() if dtype == "float32" \
            else np.median(err.max(axis=1))
    errs["fp8"] = np.median(np.abs(
        _reference_logits(weights, toks, "fp8") - want).max(axis=1))
    assert errs["float32"] < TOL_F32 < errs["bfloat16"] < TOL_BF16 \
        < errs["fp8"], errs


# ---------------------------------------------------------------------------
# the latent decode kernel (interpret mode) and its oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype,ps,per_step", [
    ("float32", 8, 1), ("float32", 8, 3), ("float32", 16, None),
    ("bfloat16", 16, 2), ("bfloat16", 16, None)])
def test_paged_latent_attention_matches_its_oracle(monkeypatch, dtype, ps,
                                                   per_step):
    """Ragged lengths, a length-0 row (zeros out), a last page partly
    filled, a sequence that fills its every page; the pages a grid step
    takes forced to 1, to a count that does not divide the table, and left
    to the kernel's own rule."""
    monkeypatch.setenv("MXTPU_PALLAS_DECODE", "1")
    if per_step:
        monkeypatch.setattr(pk, "latent_pages_per_step",
                            lambda ps, maxp: per_step)
    rng = np.random.RandomState(ps)
    b, h, rank, rot, pages, maxp = 5, 4, 64, 32, 20, 5
    pool = np.zeros((pages, ps, 128), np.float32)
    pool[..., :rank + rot] = rng.randn(pages, ps, rank + rot)
    pool = jnp.asarray(pool, dtype)
    q = jnp.asarray(rng.randn(b, h, rank + rot), dtype)
    tables = jnp.asarray(rng.randint(0, pages, (b, maxp)), jnp.int32)
    lengths = jnp.asarray([5, 2 * ps + 1, 5 * ps, 0, 3 * ps], jnp.int32)
    assert pk._latent_kernel_takes(ps, 128, dtype)
    got = pk.paged_latent_attention(q, pool, tables, lengths, 0.17, rank)
    want = pk.paged_latent_attention_reference(q, pool, tables, lengths,
                                               0.17, rank)
    assert got.shape == (b, h, rank) and got.dtype == q.dtype
    # the oracle by hand for one head of the ragged row
    rows = np.asarray(pool, np.float32)[np.asarray(tables[1])].reshape(
        maxp * ps, 128)[:2 * ps + 1]
    s = rows[:, :rank + rot] @ np.asarray(q, np.float32)[1, 2] * 0.17
    p = np.exp(s - s.max())
    by_hand = (p / p.sum()) @ rows[:, :rank]
    # float32: both sum in float32 in another order; bfloat16: the
    # probabilities and the output are rounded to 8 bits of mantissa
    tol = 2e-5 if dtype == "float32" else 3e-2
    assert np.abs(np.asarray(want, np.float32)[1, 2] - by_hand).max() < tol
    assert np.abs(np.asarray(got, np.float32)
                  - np.asarray(want, np.float32)).max() < tol
    assert float(jnp.abs(got[3].astype(jnp.float32)).max()) == 0.0
    assert float(jnp.abs(want[3].astype(jnp.float32)).max()) == 0.0


def test_shapes_the_latent_kernel_cannot_take_go_to_the_oracle(monkeypatch):
    monkeypatch.setenv("MXTPU_PALLAS_DECODE", "1")
    assert not pk._latent_kernel_takes(4, 128, "float32")
    assert not pk._latent_kernel_takes(8, 128, "bfloat16")
    assert pk.latent_pages_per_step(128, 38) == 2
    assert pk.latent_pages_per_step(16, 2) == 2
    rng = np.random.RandomState(0)
    pool = jnp.asarray(rng.randn(6, 4, 128), jnp.float32)
    q = jnp.asarray(rng.randn(2, 2, 96), jnp.float32)
    tables = jnp.asarray([[0, 1], [2, 3]], jnp.int32)
    lengths = jnp.asarray([3, 8], jnp.int32)
    got = pk.paged_latent_attention(q, pool, tables, lengths, 0.2, 64)
    want = pk.paged_latent_attention_reference(q, pool, tables, lengths,
                                               0.2, 64)
    assert np.array_equal(np.asarray(got), np.asarray(want))
    with pytest.raises(ValueError):
        pk.paged_latent_attention(q, pool, tables, lengths, 0.2, 100)


# ---------------------------------------------------------------------------
# blockwise prefill attention, YaRN frequencies, rotary on a part of the head
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("block", [16, 24, 64, 100])
def test_blockwise_causal_attention_equals_the_whole(block):
    """Query blocks that divide the length, that do not, and one that holds
    it all: the same numbers as the (H, L, L) form; values narrower than
    keys, fewer KV heads than query heads."""
    rng = np.random.RandomState(block)
    q = jnp.asarray(rng.randn(64, 4, 24), jnp.float32)
    k = jnp.asarray(rng.randn(64, 2, 24), jnp.float32)
    v = jnp.asarray(rng.randn(64, 2, 16), jnp.float32)
    whole = opsnn.causal_attention(q, k, v, 0.3)
    got = opsnn.causal_attention(q, k, v, 0.3, block=block)
    assert got.shape == whole.shape == (64, 4, 16)
    assert np.abs(np.asarray(got - whole)).max() < 2e-6


def test_yarn_frequencies_and_partial_rotary():
    """The published numbers: 64 rotary lanes, theta 1e5, factor 64 over
    4096: pairs 0-8 keep their frequency (they turn more than 32 times in
    4096 positions), pairs 19-31 have it divided by 64, a ramp between; the
    softmax scale is 0.144680. The program's frequencies are the
    reference's, and lanes before ``start`` pass through `rope`."""
    got = opsnn.yarn_inv_freq(64, 1e5, 64, 4096, 32, 1)
    plain = 1e5 ** (-np.arange(32) * 2.0 / 64)
    assert np.allclose(got[:9], plain[:9], rtol=1e-6)
    assert np.allclose(got[19:], plain[19:] / 64, rtol=1e-6)
    assert np.all(np.diff(got / plain) <= 1e-6) and got[13] < plain[13]
    scaling = {"beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
               "mscale_all_dim": 1, "original_max_position_embeddings": 4096}
    assert np.allclose(got, np.asarray(ref.yarn_inv_freq(64, 1e5, scaling)),
                       rtol=1e-6)
    sizes = dict(SIZES, qk_nope_head_dim=128, qk_rope_head_dim=64,
                 rope_scaling=scaling)
    assert abs(ref.softmax_scale(sizes) - 0.144680) < 5e-7
    assert abs(opsnn.yarn_mscale(64, 1) - 1.41589) < 5e-6
    x = jnp.asarray(np.random.RandomState(0).randn(5, 2, 24), jnp.float32)
    pos = jnp.arange(5) + 3
    out = opsnn.rope(x, pos, 1e5, start=16, yarn=(4, 16, 32, 1))
    assert np.array_equal(np.asarray(out[..., :16]), np.asarray(x[..., :16]))
    assert np.abs(np.asarray(out[..., 16:]) - np.asarray(
        opsnn.rope(x[..., 16:], pos, 1e5, yarn=(4, 16, 32, 1)))).max() == 0
    assert np.abs(np.asarray(out[..., 16:]) - np.asarray(
        ref._rope(x[..., 16:], pos, SIZES))).max() < 1e-6
    with pytest.raises(MXNetError):
        GigaChat3LM(**dict(SIZES, rope_scaling=dict(YARN, mscale_all_dim=0)))


# ---------------------------------------------------------------------------
# group-limited routing and the shares of one layer
# ---------------------------------------------------------------------------

def _layer_weights(seed=0, e=16, c=64, f=32):
    rng = np.random.RandomState(seed)

    def arr(*s, std=0.1):
        return jnp.asarray(rng.randn(*s) * std, jnp.float32)

    return {"router": arr(e, c), "expert_bias": arr(e, std=0.05),
            "experts.w_gate": arr(e, f, c), "experts.w_up": arr(e, f, c),
            "experts.w_down": arr(e, f, c), "shared.w_gate": arr(f, c),
            "shared.w_up": arr(f, c), "shared.w_down": arr(c, f)}


def _routed(x, w, sizes, offset=0, held=None, **kw):
    held = held or w["experts.w_gate"].shape[0]
    sl = slice(offset, offset + held)
    return sigmoid_topk_moe(
        x, w["router"], w["expert_bias"], w["experts.w_gate"][sl],
        w["experts.w_up"][sl], w["experts.w_down"][sl],
        k=sizes["num_experts_per_tok"], expert_offset=offset,
        routed_scaling_factor=2.5, n_group=sizes["n_group"],
        topk_group=sizes["topk_group"], gate_eps=1e-20, **kw)


def test_group_limited_selection_on_crafted_scores():
    """16 experts in 4 groups of 4, 2 groups kept, 3 experts a token. The
    router is the identity, so a row of x is the row of logits. Expert 1
    has a low score and a selection bias of +1: it is selected, and weighed
    by its score alone. Expert 12 has the highest score of all but stands
    alone in its group, whose two best sum to less than group 0's and group
    1's: its group is dropped and it is not selected."""
    sizes = {"n_group": 4, "topk_group": 2, "num_experts_per_tok": 3,
             "routed_scaling_factor": 2.5, "norm_topk_prob": True}
    logits = np.full((1, 16), -4.0, np.float32)
    logits[0, [0, 1, 2]] = [1.5, -1.0, 1.0]       # group 0
    logits[0, [4, 5]] = [1.2, 1.1]                # group 1
    logits[0, 12] = 3.0                           # group 3, alone
    bias = np.zeros(16, np.float32)
    bias[1] = 1.0
    s = jax.nn.sigmoid(jnp.asarray(logits))
    gates = np.asarray(ref.route(s, jnp.asarray(bias), sizes))[0]
    chosen = set(np.nonzero(gates)[0])
    assert chosen == {0, 1, 4}, chosen            # 1 by its bias, 12 dropped
    s = np.asarray(s)[0]
    assert np.allclose(gates[[0, 1, 4]],
                       2.5 * s[[0, 1, 4]] / s[[0, 1, 4]].sum(), rtol=1e-6)
    # without groups the strong expert is in, and the biased one still is
    free = np.asarray(ref.route(jnp.asarray(s[None]), jnp.asarray(bias),
                                dict(sizes, n_group=1)))[0]
    assert set(np.nonzero(free)[0]) == {0, 1, 12}
    # the program's layer selects and weighs as the reference's gates do
    w = _layer_weights(1)
    w["router"], w["expert_bias"] = jnp.eye(16, 64), jnp.asarray(bias)
    x = jnp.zeros((1, 64), jnp.float32).at[:, :16].set(logits)
    got, stats = _routed(x, w, sizes)
    want = sum(gates[e] * (
        (jax.nn.silu(x @ w["experts.w_gate"][e].T)
         * (x @ w["experts.w_up"][e].T)) @ w["experts.w_down"][e])
        for e in (0, 1, 4))
    assert int(stats[0]) == 3 and int(stats[1]) == 3
    assert np.abs(np.asarray(got - want)).max() < 1e-6
    assert float(jnp.abs(want).max()) > 1e-3


def test_the_parts_of_four_holders_and_the_shared_expert_add_up():
    """16 routed experts, 4 a token, 4 groups of which 2 are kept; each of
    4 holders routes over all 16 with the groups, normalises over all 4
    selected and computes its own 4; the parts summed, with the shared
    expert counted once, are the uncut reference's layer."""
    sizes = dict(SIZES, num_experts_held=16)
    w = _layer_weights(2)
    x = jnp.asarray(np.random.RandomState(3).randn(24, 64), jnp.float32)
    whole = np.asarray(ref._experts({"l." + k: v for k, v in w.items()},
                                    "l.", x, sizes, False))
    parts, pairs = 0.0, 0
    for offset in (0, 4, 8, 12):
        part, st = _routed(x, w, sizes, offset, 4)
        parts, pairs = parts + part, pairs + int(st[0])
        # a holder alone is not the layer
        assert np.abs(np.asarray(part) - whole).max() > 1e-3
    assert pairs == 24 * 4
    shared = opsnn.swiglu_ffn(x, w["shared.w_gate"], w["shared.w_up"],
                              w["shared.w_down"])
    assert np.abs(np.asarray(parts + shared) - whole).max() < 1e-5
    # and the reference given one share computes that share
    share = ref._experts({"l." + k: (v[4:8] if k.startswith("experts.")
                                     else v) for k, v in w.items()},
                         "l.", x, dict(sizes, num_experts_held=4,
                                       expert_offset=4), False)
    part, _ = _routed(x, w, sizes, 4, 4)
    assert np.abs(np.asarray(part + shared - share)).max() < 1e-5


def test_zoo_block_holding_a_share_says_what_it_holds(weights):
    lm = GigaChat3LM(dtype="float32", **dict(SIZES, num_experts_held=4,
                                             expert_offset=8))
    shapes = {n[len(lm.prefix):]: p.shape
              for n, p in lm.collect_params().items()}
    assert shapes["layer1_expert_w1"] == (4, 32, 64)
    assert shapes["layer1_gate_weight"] == (16, 64)
    assert shapes["layer1_shared_w2"] == (64, 32)
    assert shapes["layer0_w1"] == (128, 64) and "layer0_gate_weight" \
        not in shapes
    assert shapes["layer0_kv_a_weight"] == (40, 64)
    assert shapes["layer0_kv_b_weight"] == (4 * 40, 32)
    desc = lm.description()
    assert desc["attention"] == "latent" and desc["head"] == "own"
    assert desc["latent"] == {"q_rank": 48, "kv_rank": 32, "nope": 16,
                              "rope": 8, "v": 24}
    assert desc["experts"] == {
        "total": 16, "held": 4, "offset": 8, "per_token": 4, "scaling": 2.5,
        "norm_topk": True, "groups": 4, "topk_groups": 2, "gate_eps": 1e-20,
        "shared": 1}
    assert [l["ffn"] for l in desc["layers"]] == ["dense", "experts",
                                                  "experts"]
    with pytest.raises(MXNetError):
        GigaChat3LM(**dict(SIZES, num_experts_held=6, expert_offset=12))
    with pytest.raises(MXNetError):
        GigaChat3LM(**dict(SIZES, n_group=5))


# ---------------------------------------------------------------------------
# artifact, scheduler
# ---------------------------------------------------------------------------

def test_artifact_round_trip_and_the_scheduler_counts_prefill_pairs(
        weights, tmp_path):
    """`save_lm` -> `load_lm` keeps bfloat16 and names the block; a request
    through the scheduler leaves the prompt's pairs computed here in its
    lap's record (16 experts, 8 held: not the prompt's length x 4 x 2)."""
    lm = _zoo(weights, "bfloat16")
    prefix = save_lm(lm, os.path.join(tmp_path, "lm"))
    with open(prefix + "-lmconfig.json") as f:
        header = json.load(f)
    assert header["arch"] == "gigachat3"
    assert header["description"]["dtype"] == "bfloat16"
    back = load_lm(prefix)
    assert type(back).__name__ == "GigaChat3LM" and back.mtp is not None
    for (name, a), (_, b) in zip(
            sorted(lm._collect_params_with_prefix().items()),
            sorted(back._collect_params_with_prefix().items())):
        assert np.array_equal(a.data().asnumpy(), b.data().asnumpy()), name
    engine = TransformerLMEngine(lm=back, **GEOMETRY)
    sched = GenerateScheduler(engine, name="gigachat3-test/1", warm=False)
    try:
        prompt = _tokens(11, seed=6).tolist()
        out = sched.submit(prompt, max_new_tokens=5).wait(120)
        assert len(out) == 5
        laps = [r for r in goodput.window("serve")
                if r.get("model") == "gigachat3-test/1"]
        pairs = sum(r.get("prefill_moe_pairs", 0) for r in laps)
        assert 0 < pairs < 11 * 4 * 2
        assert pairs == engine.last_prefill_moe_pairs
        steps = [r for r in laps if r.get("n")]
        assert steps and all("moe_pairs" in r and r["context_tokens"] >= 11
                             for r in steps)
    finally:
        sched.close(drain=False)
