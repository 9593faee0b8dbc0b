"""SmallThinker through the zoo block and the generation engine, against the
benchmark's plain reference (chipbench/reference/smallthinker.py), at a small
size on the CPU: seeded random weights, logits not tokens. One period (full
NoPE, three window layers), width 64, 4 / 2 heads of 16, 8 experts of which
2, a window of 8 keys, pages of 4: a ring of 3 pages.

The reference draws its weights as bfloat16 values; a float32 zoo block or
engine holds the same numbers upcast.
"""
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from chipbench.models import smallthinker as factory
from chipbench.reference import smallthinker as ref
from mxnet_tpu.base import MXNetError
from mxnet_tpu.gluon.model_zoo.smallthinker import (SmallThinkerLM,
                                                    smallthinker_mini)
from mxnet_tpu.ops import nn as opsnn
from mxnet_tpu.ops import pallas_kernels as pk
from mxnet_tpu.ops.contrib import sigmoid_topk_moe
from mxnet_tpu.serving.generate import (GenerateScheduler, KVPageAllocator,
                                        TransformerLMEngine, load_lm,
                                        save_lm)

SIZES = {"vocab_size": 128, "hidden_size": 64, "head_dim": 16,
         "num_attention_heads": 4, "num_key_value_heads": 2,
         "moe_ffn_hidden_size": 32, "moe_num_primary_experts": 8,
         "moe_num_active_primary_experts": 2,
         "sliding_window_layout": [0, 1, 1, 1], "rope_layout": [0, 1, 1, 1],
         "sliding_window_size": 8, "rms_norm_eps": 1e-6,
         "rope_theta": 1500000, "max_position_embeddings": 256}
SEED = 3000003917
WINDOW, PS, RING = 8, 4, 3
GEOMETRY = dict(num_pages=64, page_size=PS, max_prompt=32, max_new_tokens=40,
                decode_buckets=[2, 4], prefill_buckets=[16, 32],
                window_pages=12)

# Tolerances on logits (their spread over the vocabulary is 0.87): float32
# against float32 differs by the order of summation only and reads 2e-6 to
# 9e-6 at its worst position over contexts of 70 tokens; TOL_F32 is 5e-5.
# What it has to catch reads far above it: a window off by one key 0.05 to
# 0.4 (measured below, `test_a_window_off_by_one_key_is_seen`), a ring one
# page short or the window ignored 1 to 3, and the reference in bfloat16 in
# place of float32 0.02 to 0.05 (8 bits of mantissa through four layers).
TOL_F32 = 5e-5


@pytest.fixture(scope="module")
def weights():
    return ref.make_weights(SEED, SIZES)


def _zoo(weights, dtype="float32", **kw):
    lm = SmallThinkerLM(dtype=dtype, **dict(SIZES, **kw))
    have = {n[len(lm.prefix):]: p for n, p in lm.collect_params().items()}
    want = {factory._zoo_name(k): v for k, v in weights.items()}
    assert set(have) == set(want)
    for name, p in have.items():
        p.adopt(want[name].astype(dtype))
    return lm


@pytest.fixture(scope="module")
def engine(weights):
    # a prompt's attention in blocks of 8, so that a 32-token bucket walks
    # four blocks and the band cuts key blocks away
    block, pk._PROMPT_BLOCK = pk._PROMPT_BLOCK, 8
    try:
        eng = TransformerLMEngine(lm=_zoo(weights), **GEOMETRY)
        for lp in eng.prefill_buckets:          # traced at the small block
            eng.prefill_logits([1] * lp, np.zeros(eng.max_pages_per_seq,
                                                  np.int32))
        yield eng
    finally:
        pk._PROMPT_BLOCK = block


def engine_lm():
    lm = smallthinker_mini()
    lm.initialize()
    return lm


def _tokens(n, seed=0):
    return np.random.RandomState(seed).randint(
        0, SIZES["vocab_size"], n).astype(np.int32)


def _reference_logits(weights, tokens, control=None, sizes=SIZES, **kw):
    return np.asarray(ref.forward(weights, jnp.asarray(tokens), sizes,
                                  control, **kw))


def test_zoo_block_matches_the_reference(weights):
    toks = _tokens(45)                       # five windows and more
    got = _zoo(weights)(mx.nd.array(toks[None], dtype="int32")).asnumpy()[0]
    want = _reference_logits(weights, toks)
    assert got.dtype == np.float32 and got.shape == want.shape
    assert np.abs(got - want).max() < TOL_F32
    # what the block would compute were one of its three departures from a
    # plain decoder missing lies far outside the tolerance
    for kw in (dict(control="nowindow"), dict(breaks=("rope_everywhere",)),
               dict(breaks=("router_post",))):
        assert np.abs(_reference_logits(weights, toks, **kw)
                      - want).max() > 0.5, kw


def test_a_window_off_by_one_key_is_seen(weights):
    toks = _tokens(45, seed=1)
    want = _reference_logits(weights, toks)
    for w in (7, 9):
        off = _reference_logits(weights, toks,
                                sizes=dict(SIZES, sliding_window_size=w))
        assert np.abs(off - want)[:7].max() == 0.0     # inside every window
        assert np.abs(off - want).max() > 100 * TOL_F32, w


def test_the_description_says_what_is_per_layer():
    d = smallthinker_mini().description()
    assert [l["window"] for l in d["layers"]] == [None, 8, 8, 8]
    assert [l["rotary"] for l in d["layers"]] == [False, True, True, True]
    assert d["head"] == "own" and d["positions"] == "rotary"
    assert d["experts"]["scores"] == "softmax_selected"
    assert d["experts"]["router_rows"] == "operator"
    assert d["experts"]["activation"] == "relu"
    with pytest.raises(MXNetError):
        SmallThinkerLM(sliding_window_layout=(0, 1), rope_layout=(0, 1, 1))


# ---------------------------------------------------------------------------
# prefill, then decode, through both page groups
# ---------------------------------------------------------------------------

def _rows(engine, pages, ring):
    page_row = np.zeros(engine.max_pages_per_seq, np.int32)
    page_row[:len(pages)] = pages
    ring_row = np.zeros(engine.ring_pages, np.int32)
    ring_row[:len(ring)] = ring
    return page_row, ring_row


def _step(engine, rows):
    """One decode step of the smallest bucket that holds ``rows``: (token,
    position, page_row, ring_row) each; the other rows are padding."""
    ps, ring = engine.page_size, engine.ring_pages
    b = min(x for x in engine.buckets if x >= len(rows))
    tokens, positions = np.zeros(b, np.int32), np.zeros(b, np.int32)
    dest_pages = np.full(b, engine.num_pages, np.int32)
    ring_dest = np.full(b, engine.window_pages, np.int32)
    dest_slots, lengths = np.zeros(b, np.int32), np.zeros(b, np.int32)
    tables = np.zeros((b, engine.max_pages_per_seq), np.int32)
    ring_tables = np.zeros((b, ring), np.int32)
    for i, (tok, pos, page_row, ring_row) in enumerate(rows):
        tokens[i], positions[i], lengths[i] = tok, pos, pos + 1
        dest_pages[i], dest_slots[i] = page_row[pos // ps], pos % ps
        ring_dest[i] = ring_row[(pos // ps) % ring]
        tables[i], ring_tables[i] = page_row, ring_row
    return engine.decode_logits(
        tokens, positions, dest_pages, dest_slots, tables, lengths,
        ring_dest=ring_dest, ring_tables=ring_tables)[:len(rows)]


def test_prefill_and_decode_match_the_reference(weights, engine):
    """Three sequences in one batch: a prompt shorter than the window, one
    that fills a ring exactly and one longer than the ring (29 tokens: the
    prefill writes only the last three pages it reaches), each decoded to
    5 windows and more so that every ring wraps several times; rows join
    the batch at different lengths, as the scheduler's do."""
    prompts = [3, 12, 29]
    toks = [_tokens(n + 41, seed=n) for n in prompts]
    held = [_rows(engine, range(18 * i, 18 * i + 18), ring)
            for i, ring in enumerate([[5, 2, 9], [0, 7, 11], [3, 10, 1]])]
    got = [[engine.prefill_logits(t[:n].tolist(), rows[0], ring_row=rows[1])]
           for t, n, rows in zip(toks, prompts, held)]
    for step in range(41):
        out = _step(engine, [(t[n + step], n + step) + rows
                             for t, n, rows in zip(toks, prompts, held)])
        for i in range(len(prompts)):
            got[i].append(out[i:i + 1])
    for t, g in zip(toks, got):
        assert np.abs(np.concatenate(g)
                      - _reference_logits(weights, t)).max() < TOL_F32


def test_decode_through_the_kernel_and_the_ring(weights, monkeypatch):
    """Pages of 4 float32 rows are no sublane tile, so the engine above
    decodes through the oracle; pages of 8 and a window of 16 (a ring of 3
    again) go through the Pallas kernel (interpret mode): a prompt longer
    than the ring, then steps until the ring has wrapped twice more."""
    monkeypatch.setenv("MXTPU_PALLAS_DECODE", "1")
    sizes = dict(SIZES, sliding_window_size=16)
    eng = TransformerLMEngine(
        lm=_zoo(weights, sliding_window_size=16), num_pages=24, page_size=8,
        max_prompt=32, max_new_tokens=32, decode_buckets=[2],
        prefill_buckets=[32])
    assert eng.ring_pages == 3 and eng.window_pages == 6
    assert eng.kernel_form["kernel"] == "paged_attention_decode"
    toks = _tokens(29 + 28, seed=11)
    held = _rows(eng, range(8, 16), [4, 0, 2])
    got = [eng.prefill_logits(toks[:29].tolist(), held[0], ring_row=held[1])]
    for pos in range(29, len(toks)):
        got.append(_step(eng, [(toks[pos], pos) + held]))
    # the kernel's scores go through the MXU as two bfloat16 terms (16 bits
    # of mantissa, 1e-5 of a score): ten times the oracle's tolerance
    assert np.abs(np.concatenate(got) - _reference_logits(
        weights, toks, sizes=sizes)).max() < 10 * TOL_F32


def test_a_ring_one_page_short_or_a_window_ignored_fails(weights, engine):
    """The same drive with the engine's ring cut to two pages (token t - 8
    is overwritten while it is live), and with the decode steps reading
    window layers through the growing table's geometry: both leave the
    tolerance by orders of magnitude."""
    toks = _tokens(40, seed=5)
    want = _reference_logits(weights, toks)
    rows = _rows(engine, range(18), [3, 7, 1])
    short = (rows[0], np.array([3, 7, 3], np.int32))   # entry 2 aliases 0
    for held in (rows, short):
        got = [engine.prefill_logits(toks[:12].tolist(), held[0],
                                     ring_row=held[1])]
        for pos in range(12, 40):
            got.append(_step(engine, [(toks[pos], pos) + held]))
        err = np.abs(np.concatenate(got) - want).max()
        assert (err < TOL_F32) if held is rows else (err > 0.05), err
    assert np.abs(_reference_logits(weights, toks, "nowindow")
                  - want).max() > 0.5


def test_bfloat16_in_place_of_float32_fails_the_tolerance(weights):
    lm = _zoo(weights, "bfloat16")
    toks = _tokens(40, seed=6)
    got = lm(mx.nd.array(toks[None], dtype="int32")).asnumpy()[0]
    assert np.abs(got - _reference_logits(weights, toks)).max() > 20 * TOL_F32


def test_lm_without_window_layers_has_one_page_group(weights):
    from mxnet_tpu.gluon.model_zoo.lfm2 import lfm2_mini

    lm = lfm2_mini()
    lm.initialize()
    eng = TransformerLMEngine(lm=lm, num_pages=16, page_size=4,
                              max_prompt=8, max_new_tokens=8,
                              decode_buckets=[2], prefill_buckets=[8])
    geo = eng.geometry()
    assert (geo["window"], geo["ring_pages"], geo["window_pages"],
            geo["window_kv_bytes"]) == (0, 0, 0, 0)
    assert all(a.shape[0] == 16 for pair in eng._kv for a in pair)
    assert "window_pages" not in dict(eng._key("lm_decode", ()).static)


def test_geometry_and_pool_bytes_of_the_two_groups(engine):
    geo = engine.geometry()
    assert geo["window"] == WINDOW and geo["ring_pages"] == RING
    assert geo["window_pages"] == 12 and geo["num_pages"] == 64
    lanes = 128                      # 2 KV heads of 16, padded to a tile
    assert geo["window_kv_bytes"] == 3 * 2 * 12 * PS * lanes * 4
    assert geo["kv_bytes"] == geo["window_kv_bytes"] \
        + 1 * 2 * 64 * PS * lanes * 4
    assert dict(engine._key("lm_decode", ()).static)["window_pages"] == 12
    with pytest.raises(MXNetError):
        TransformerLMEngine(lm=engine_lm(), **dict(GEOMETRY, window_pages=2))


# ---------------------------------------------------------------------------
# the scheduler: two page groups reserved at admission, freed at retirement
# ---------------------------------------------------------------------------

class _RingStub:
    """No-model engine with a window group: records every call's ring."""

    def __init__(self, window_pages=6, fail_prefill=False, step_sleep=0.0):
        self.vocab_size, self.buckets = 64, [1, 2, 4]
        self.page_size, self.num_pages = 2, 64
        self.max_prompt, self.max_new_tokens = 8, 8
        self.max_pages_per_seq, self.eos_id = 8, None
        self.window, self.ring_pages = 4, 3
        self.window_pages = window_pages
        self.fail_prefill, self.step_sleep = fail_prefill, step_sleep
        self.rings, self.steps = [], []

    def warm(self):
        return 0.0

    def prefill(self, tokens, page_row, sampling, key, ring_row=None):
        self.rings.append(np.array(ring_row))
        if self.fail_prefill:
            raise RuntimeError("no such prompt")
        return 1

    def decode_step(self, tokens, positions, dest_pages, dest_slots, tables,
                    lengths, temps, top_ks, top_ps, key, ring_dest=None,
                    ring_tables=None):
        time.sleep(self.step_sleep)
        self.steps.append((np.array(positions), np.array(lengths),
                           np.array(ring_dest), np.array(ring_tables)))
        return (np.asarray(tokens) + 1).astype(np.int32) % 64

    def geometry(self):
        return {}


def _settled(sched):
    deadline = time.monotonic() + 5
    while time.monotonic() < deadline and (
            sched.allocator.used_pages or sched.window_allocator.used_pages):
        time.sleep(0.01)
    return sched.allocator.used_pages, sched.window_allocator.used_pages


@pytest.mark.parametrize("how", ["retire", "abort", "prefill_failure"])
def test_both_groups_are_whole_again(how):
    eng = _RingStub(fail_prefill=how == "prefill_failure",
                    step_sleep=0.0 if how == "retire" else 0.02)
    sched = GenerateScheduler(eng, name="ringstub/%s" % how, queue_depth=16)
    try:
        reqs = [sched.submit([1 + i] * 5, max_new_tokens=8)
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
        assert len(sched.window_allocator._free) == 6
    finally:
        sched.close(drain=False, timeout=0)


def test_admission_waits_when_only_the_window_group_is_short():
    """Six window pages are two rings: the growing group has room for eight
    sequences (7 pages each of 64), the batch for four, and still only two
    run at a time; a step names each row's ring page by the position's page
    modulo the ring, and the lap record carries the window's tokens."""
    from mxnet_tpu.telemetry import goodput

    eng = _RingStub(step_sleep=0.005)
    sched = GenerateScheduler(eng, name="ringstub/wait", queue_depth=16)
    try:
        reqs = [sched.submit([1 + i] * 5, max_new_tokens=8)
                for i in range(5)]
        for r in reqs:
            assert len(r.wait(20)) == 8
        assert _settled(sched) == (0, 0)
        assert max(int((lengths > 0).sum())
                   for _, lengths, _, _ in eng.steps) == 2
        for positions, lengths, ring_dest, ring_tables in eng.steps:
            for i in np.flatnonzero(lengths):
                assert ring_dest[i] == ring_tables[i][
                    positions[i] // 2 % 3]
            assert set(ring_dest[lengths == 0].tolist()) <= {6}
        # a request of 13 tokens holds 7 growing pages and a ring of 3
        assert all(len(set(r.tolist())) == 3 for r in eng.rings)
        laps = [r for r in goodput.window("serve")
                if r.get("model") == "ringstub/wait" and r.get("n")]
        assert laps and all(
            r["window_tokens"] <= 4 * r["n"] < r["context_tokens"]
            and r["ring_pages"] in (3, 6) for r in laps)
    finally:
        sched.close(drain=False, timeout=0)


def test_a_short_request_holds_fewer_pages_than_the_ring():
    eng = _RingStub()
    sched = GenerateScheduler(eng, name="ringstub/short", queue_depth=4)
    try:
        assert len(sched.submit([1, 2], max_new_tokens=2).wait(10)) == 2
        assert [int((r > 0).sum()) for r in eng.rings] in ([1], [2])
        assert len(sched._reserve(type("R", (), {
            "tokens": [1, 2], "max_new_tokens": 2})())[1]) == 2
    finally:
        sched.close(drain=False, timeout=0)


def test_window_group_gauges_are_its_own():
    from mxnet_tpu import telemetry

    full = KVPageAllocator(8, 4, name="gauges/1")
    ring = KVPageAllocator(6, 4, name="gauges/1", group="window")
    got = ring.alloc(3)
    labels = {"model": "gauges/1"}
    assert telemetry.gauge("mxtpu_serve_kv_pages_used", labels).value == 0
    assert telemetry.gauge("mxtpu_serve_kv_pages_total", labels).value == 8
    assert telemetry.gauge("mxtpu_serve_kv_window_pages_used",
                           labels).value == 3
    assert telemetry.gauge("mxtpu_serve_kv_window_pages_total",
                           labels).value == 6
    assert telemetry.gauge("mxtpu_serve_kv_window_occupancy",
                           labels).value == 0.5
    ring.free(got)
    assert full.used_pages == ring.used_pages == 0


def test_served_through_the_repository_tokens_are_the_references_best(
        weights, tmp_path):
    """save_lm -> ModelRepository.load(generate=True) -> generate: prompts
    shorter and longer than the ring, six requests through a window group
    of four rings; every served token's reference logit is the reference's
    best (float32 engine: within TOL_F32 of it), and both groups come back
    whole."""
    from mxnet_tpu.serving import ModelRepository

    prefix = save_lm(_zoo(weights), os.path.join(tmp_path, "lm"))
    assert type(load_lm(prefix)) is SmallThinkerLM
    repo = ModelRepository()
    model = repo.load("st", prefix, generate=True, queue_depth=32,
                      generate_opts=dict(GEOMETRY, max_new_tokens=24))
    try:
        sched = model.scheduler
        assert sched.window_allocator.num_pages == 12
        prompts = [_tokens(3 + 5 * i, seed=20 + i).tolist() for i in range(6)]
        got = [None] * len(prompts)

        def ask(i):
            got[i] = model.generate(prompts[i], max_new_tokens=24)["tokens"]

        threads = [threading.Thread(target=ask, args=(i,))
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
        for prompt, new in zip(prompts, got):
            assert len(new) == 24
            logits = _reference_logits(weights, np.int32(prompt + new))
            for j, tok in enumerate(new):
                row = logits[len(prompt) + j - 1]
                assert row.max() - row[tok] < TOL_F32
        assert sched.allocator.used_pages == 0
        assert sched.window_allocator.used_pages == 0
        kv = model.describe()["kv"]
        assert kv["window_pages_total"] == 12 and kv["window_pages_used"] == 0
        assert model.describe()["generate"]["ring_pages"] == RING
    finally:
        repo.unload("st", timeout=5.0)


# ---------------------------------------------------------------------------
# the decode kernel's lower bound: the kernel (interpret mode) and its oracle
# ---------------------------------------------------------------------------

def _ring_case(rng, b, heads, kv, d, ps, ring, dtype, pages=24):
    cp = -(-kv * d // 128) * 128
    q = jnp.asarray(rng.randn(b, heads, d), dtype)
    kp = jnp.asarray(rng.randn(pages, ps, cp), dtype)
    vp = jnp.asarray(rng.randn(pages, ps, cp), dtype)
    # a sequence's ring entries are pages of its own
    tables = jnp.asarray(np.stack([rng.permutation(pages)[:ring]
                                   for _ in range(b)]), jnp.int32)
    return q, kp, vp, tables, cp


@pytest.mark.parametrize("group", [1, 4, 7])
@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("edge", [0, 1, 15, 16, 17],
                         ids=lambda e: "edge%d" % e)
def test_paged_attention_lower_bound_matches_its_oracle(monkeypatch, group, d,
                                                        edge):
    """A window of 48 keys in pages of 16 (a ring of 4): rows shorter than
    the window, of exactly the window, and several rings long, with the
    window's first key ``edge`` rows into a page: the first row of a page,
    its second, its last, the next page's first and second."""
    monkeypatch.setenv("MXTPU_PALLAS_DECODE", "1")
    rng = np.random.RandomState(group * d + edge)
    kv, ps, window, ring = 2, 16, 48, 4
    heads = kv * group
    q, kp, vp, tables, cp = _ring_case(rng, 5, heads, kv, d, ps, ring,
                                       "float32")
    lengths = np.array([5, window, 160 + edge, 0, 48 + 16 * 5 + edge],
                       np.int32)
    starts = np.maximum(lengths - window, 0).astype(np.int32)
    assert pk._paged_kernel_takes(d, ps, cp, "float32", group, 5, ring)
    got = pk.paged_attention(q, kp, vp, tables, jnp.asarray(lengths),
                             kv_heads=kv, starts=jnp.asarray(starts))
    want = pk.paged_attention_reference(
        q, kp, vp, tables, jnp.asarray(lengths), 1.0 / np.sqrt(d), kv,
        jnp.asarray(starts))
    # the oracle by hand for the last query head of row 2: token t lies in
    # ring entry (t // ps) % ring
    i, b = heads - 1, 2
    t = np.arange(starts[b], lengths[b])
    page = np.asarray(tables)[b, (t // ps) % ring]
    lanes = slice((i // group) * d, (i // group + 1) * d)
    k = np.asarray(kp)[page, t % ps][:, lanes]
    v = np.asarray(vp)[page, t % ps][:, lanes]
    s = k @ np.asarray(q)[b, i] / np.sqrt(d)
    p = np.exp(s - s.max())
    assert np.abs(np.asarray(want)[b, i] - (p / p.sum()) @ v).max() < 2e-5
    live = lengths > 0
    # float32 on both sides, another order of summation
    assert np.abs(np.asarray(got) - np.asarray(want))[live].max() < 2e-5
    assert not np.any(np.asarray(got)[~live])


def test_lower_bound_of_zero_is_the_plain_call(monkeypatch):
    """With every start 0 and a table that holds the whole sequence the
    ring form and the plain form agree, kernel and oracle."""
    monkeypatch.setenv("MXTPU_PALLAS_DECODE", "1")
    rng = np.random.RandomState(0)
    q, kp, vp, tables, _ = _ring_case(rng, 3, 8, 2, 64, 16, 6, "bfloat16")
    lengths = jnp.asarray([5, 96, 33], jnp.int32)
    zeros = jnp.zeros(3, jnp.int32)
    plain = pk.paged_attention(q, kp, vp, tables, lengths, kv_heads=2)
    ringed = pk.paged_attention(q, kp, vp, tables, lengths, kv_heads=2,
                                starts=zeros)
    assert np.abs(np.asarray(plain, np.float32)
                  - np.asarray(ringed, np.float32)).max() < 2e-2
    a = pk.paged_attention_reference(q, kp, vp, tables, lengths, 0.125, 2)
    b = pk.paged_attention_reference(q, kp, vp, tables, lengths, 0.125, 2,
                                     zeros)
    assert np.abs(np.asarray(a, np.float32)
                  - np.asarray(b, np.float32)).max() < 2e-2


# ---------------------------------------------------------------------------
# banded prefill attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("block", [None, 8, 16])
@pytest.mark.parametrize("window", [1, 8, 13, 64])
def test_banded_causal_attention_is_the_masked_square(block, window):
    rng = np.random.RandomState(window)
    l, h, kv, d = 40, 4, 2, 16
    q = jnp.asarray(rng.randn(l, h, d), jnp.float32)
    k = jnp.asarray(rng.randn(l, kv, d), jnp.float32)
    v = jnp.asarray(rng.randn(l, kv, d), jnp.float32)
    got = opsnn.causal_attention(q, k, v, block=block, window=window)
    i, j = np.arange(l)[:, None], np.arange(l)[None, :]
    live = (j <= i) & (j > i - window)
    s = np.einsum("qhd,khd->hqk", np.asarray(q),
                  np.repeat(np.asarray(k), h // kv, 1)) / 4.0
    p = np.where(live, np.exp(s - s.max(-1, keepdims=True)), 0.0)
    want = np.einsum("hqk,khd->qhd", p / p.sum(-1, keepdims=True),
                     np.repeat(np.asarray(v), h // kv, 1))
    assert np.abs(np.asarray(got) - want).max() < 2e-5


@pytest.mark.parametrize("window", [None, 96, 128, 300])
def test_prompt_attention_kernel_is_the_banded_causal_attention(monkeypatch,
                                                                window):
    """The splash-attention kernel (interpret mode) over a prompt of four
    blocks of 128, two KV heads of 128 with two query heads each, against the
    jnp form: a triangle, and bands that end inside a block, at a block's
    edge and two blocks back."""
    monkeypatch.setenv("MXTPU_PALLAS_DECODE", "1")
    monkeypatch.setattr(pk, "_PROMPT_BLOCK", 128)
    rng = np.random.RandomState(7)
    l, h, kv, d = 512, 4, 2, 128
    q = jnp.asarray(rng.randn(l, h, d), jnp.float32)
    k = jnp.asarray(rng.randn(l, kv, d), jnp.float32)
    v = jnp.asarray(rng.randn(l, kv, d), jnp.float32)
    assert pk._prompt_kernel_takes(l, d)
    got = pk.prompt_attention(q, k, v, 0.088, window)
    want = opsnn.causal_attention(q, k, v, 0.088, window=window)
    # float32 on both sides, another order of summation
    assert np.abs(np.asarray(got - want)).max() < 2e-5
    # a head off the lane tile, or a prompt of no whole blocks: the jnp form
    assert not pk._prompt_kernel_takes(l, 64)
    assert not pk._prompt_kernel_takes(l + 8, d)
    short = pk.prompt_attention(q[:40, :, :64], k[:40, :, :64],
                                v[:40, :, :64], 0.125, 8)
    assert np.abs(np.asarray(short - opsnn.causal_attention(
        q[:40, :, :64], k[:40, :, :64], v[:40, :, :64], 0.125,
        window=8))).max() < 2e-5


def test_a_band_multiplies_no_key_block_outside_it():
    """16 query blocks of 8 against a window of 8: every block's product
    holds 15 keys at most (its band), not the whole prefix."""
    q = jnp.zeros((128, 4, 4), jnp.float32)
    k = jnp.zeros((128, 2, 4), jnp.float32)
    jaxpr = jax.make_jaxpr(lambda q, k: opsnn.causal_attention(
        q, k, k, block=8, window=8))(q, k)
    products = [e for e in jaxpr.jaxpr.eqns
                if e.primitive.name == "dot_general"]
    assert len(products) == 32
    assert max(n for e in products for v in e.invars
               for n in v.aval.shape) == 15


# ---------------------------------------------------------------------------
# the expert layer: ReGLU, the softmax over the selected, the router's rows
# ---------------------------------------------------------------------------

def _moe_weights(e=8, c=128, f=128, seed=0):
    rng = np.random.RandomState(seed)
    arr = lambda *s, std=0.1: jnp.asarray(rng.randn(*s) * std, jnp.float32)
    return dict(gate=arr(e, c, std=0.3), w1=arr(e, f, c), w3=arr(e, f, c),
                w2=arr(e, f, c))


def _moe_by_loop(x, rows, w, k):
    """Every expert over every token, masked: the router reads ``rows``."""
    z = rows @ w["gate"].T
    top, sel = jax.lax.top_k(z, k)
    g = jax.nn.softmax(top, axis=1)
    out = jnp.zeros_like(x)
    for e in range(w["gate"].shape[0]):
        ge = (g * (sel == e)).sum(1)
        h = jax.nn.relu(x @ w["w1"][e].T) * (x @ w["w3"][e].T)
        out = out + ge[:, None] * (h @ w["w2"][e])
    return out


@pytest.mark.parametrize("kernel", ["0", "1"], ids=["jnp", "pallas"])
def test_router_and_reglu_experts_match_a_plain_loop(monkeypatch, kernel):
    monkeypatch.setenv("MXTPU_PALLAS_DECODE", kernel)
    w = _moe_weights()
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(40, 128), jnp.float32)
    rows = jnp.asarray(rng.randn(40, 128), jnp.float32)
    out, stats = sigmoid_topk_moe(
        x, w["gate"], None, w["w1"], w["w3"], w["w2"], rows, k=2,
        scores="softmax_selected", activation="relu")
    assert int(stats[0]) == 80
    # float32 on both sides, another order of summation
    assert np.abs(np.asarray(out - _moe_by_loop(x, rows, w, 2))).max() < 1e-5
    # the router fed the experts' rows, or silu in place of relu, is
    # another layer
    for kw in (dict(router_data=None), dict(router_data=rows,
                                            activation="silu")):
        other, _ = sigmoid_topk_moe(
            x, w["gate"], None, w["w1"], w["w3"], w["w2"], k=2,
            **dict(dict(scores="softmax_selected", activation="relu",
                        router_data=rows), **kw))
        assert np.abs(np.asarray(other - out)).max() > 1e-2


@pytest.mark.parametrize("activation", ["silu", "relu"])
def test_grouped_ffn_kernel_matches_its_oracle(monkeypatch, activation):
    monkeypatch.setenv("MXTPU_PALLAS_DECODE", "1")
    rng = np.random.RandomState(2)
    tm, tiles, c, f, e = 8, 5, 128, 256, 4
    xs = jnp.asarray(rng.randn(tiles * tm, c), jnp.float32)
    w1, w3, w2 = (jnp.asarray(rng.randn(e, f, c) * 0.1, jnp.float32)
                  for _ in range(3))
    te = jnp.asarray([0, 0, 2, 3, 3], jnp.int32)
    nt = jnp.asarray([4], jnp.int32)
    got = pk.moe_grouped_ffn(xs, te, nt, w1, w3, w2, tm, activation)
    want = pk.moe_grouped_ffn_reference(xs, te, nt, w1, w3, w2, tm,
                                        activation)
    assert np.abs(np.asarray(got - want)).max() < 1e-4
    assert float(jnp.abs(got[4 * tm:]).max()) == 0.0
    a = np.asarray(xs[:tm]) @ np.asarray(w1[0]).T
    gate = np.maximum(a, 0) if activation == "relu" else a / (1 + np.exp(-a))
    by_hand = (gate * (np.asarray(xs[:tm]) @ np.asarray(w3[0]).T)) \
        @ np.asarray(w2[0])
    assert np.abs(np.asarray(want[:tm]) - by_hand).max() < 1e-4
    with pytest.raises(ValueError):
        pk.moe_grouped_ffn(xs, te, nt, w1, w3, w2, tm, "gelu")


def test_four_shares_of_sixteen_experts_add_up_to_the_uncut_layer():
    """64 experts, 6 a token, the router over the operator's rows; each of 4
    holders routes over all 64 and computes its own 16: the parts add up to
    the whole layer, and to the plain loop over all experts."""
    w = _moe_weights(e=64, seed=2)
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(24, 128), jnp.float32)
    rows = jnp.asarray(rng.randn(24, 128), jnp.float32)
    kw = dict(k=6, scores="softmax_selected", activation="relu")
    whole, stats = sigmoid_topk_moe(x, w["gate"], None, w["w1"], w["w3"],
                                    w["w2"], rows, **kw)
    parts, pairs = 0.0, 0
    for share in range(4):
        o = share * 16
        part, st = sigmoid_topk_moe(x, w["gate"], None, w["w1"][o:o + 16],
                                    w["w3"][o:o + 16], w["w2"][o:o + 16],
                                    rows, expert_offset=o, **kw)
        parts, pairs = parts + part, pairs + int(st[0])
    assert pairs == int(stats[0]) == 24 * 6
    assert np.abs(np.asarray(parts - whole)).max() < 1e-5
    assert np.abs(np.asarray(whole - _moe_by_loop(x, rows, w, 6))).max() \
        < 1e-5


def test_zoo_block_holding_a_share_computes_its_part(weights):
    lm = SmallThinkerLM(dtype="float32", num_experts_held=4, expert_offset=4,
                        **SIZES)
    shapes = {n: p.shape for n, p in lm.collect_params().items()}
    assert shapes[lm.prefix + "layer1_expert_w1"] == (4, 32, 64)
    assert shapes[lm.prefix + "layer1_gate_weight"] == (8, 64)
    ex = lm.description()["experts"]
    assert (ex["total"], ex["held"], ex["offset"]) == (8, 4, 4)
    # the two halves' expert layers add up to the whole's: reference and
    # block, one layer, through the engine's routing function
    sizes = dict(SIZES, sliding_window_layout=[1], rope_layout=[1])
    toks = _tokens(20, seed=9)
    whole = ref.hidden(ref.make_weights(SEED, sizes), jnp.asarray(toks), sizes)
    parts = []
    for o in (0, 4):
        s = dict(sizes, num_experts_held=4, expert_offset=o)
        w = {k: (v[o:o + 4] if ".experts." in k else v)
             for k, v in ref.make_weights(SEED, sizes).items()}
        parts.append(ref.hidden(w, jnp.asarray(toks), s))
    # x + attention is in both halves: counted once
    base = parts[0] + parts[1] - whole
    only_attn = dict(sizes, num_experts_held=0, expert_offset=0)
    w0 = {k: (v[:0] if ".experts." in k else v)
          for k, v in ref.make_weights(SEED, sizes).items()}
    assert np.abs(np.asarray(
        base - ref.hidden(w0, jnp.asarray(toks), only_attn))).max() < 1e-5
    with pytest.raises(MXNetError):
        SmallThinkerLM(num_experts_held=6, expert_offset=4, **SIZES)
