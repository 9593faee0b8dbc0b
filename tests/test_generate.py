"""serving.generate tests: KV page allocator units, paged decode-attention
Pallas-vs-jnp parity, sampling-op contracts, continuous-batching scheduler
semantics (stub engine), Transformer-LM engine greedy parity against the
gluon full-sequence oracle, the HTTP ``:generate`` surface, and THE
acceptance e2e: a 2-replica pooled LM under >=8 concurrent generations
with unequal budgets, late joiners, zero post-warm compiles and full
KV-page reclaim.

Everything runs on CPU with tiny configs (2 layers, d<=32, vocab<=128) —
the tier-1 budget has no headroom (ROADMAP.md).
"""
import json
import os
import tempfile
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.base import MXNetError
from mxnet_tpu.gluon.model_zoo.transformer import TransformerLM, lm_mini
from mxnet_tpu.serving import (
    DeadlineExceededError, GenerateScheduler, KVPageAllocator,
    ModelRepository, QueueFullError, ServedLM, ServingServer,
    TransformerLMEngine, load_lm, save_lm,
)


# ---------------------------------------------------------------------------
# KV page allocator units
# ---------------------------------------------------------------------------

def test_kv_allocator_alloc_free_roundtrip():
    a = KVPageAllocator(8, 4, name="alloc/1")
    assert a.pages_for(1) == 1 and a.pages_for(4) == 1
    assert a.pages_for(5) == 2 and a.pages_for(12) == 3
    g1 = a.alloc(3)
    g2 = a.alloc(5)
    assert len(g1) == 3 and len(g2) == 5
    assert not set(g1) & set(g2)          # disjoint grants
    assert a.free_pages == 0 and a.used_pages == 8
    assert a.alloc(1) is None             # exhausted: None, not partial
    a.free(g1)
    assert a.free_pages == 3
    g3 = a.alloc(2)
    assert set(g3) <= set(g1)             # freed pages are reused
    a.free(g3)
    a.free(g2)
    assert a.free_pages == 8 and a.used_pages == 0


def test_kv_allocator_fragmentation_interleaved():
    """Interleaved alloc/free must keep serving from a fragmented free
    list — pages are identity-only, any free page serves any grant."""
    a = KVPageAllocator(6, 2, name="alloc/2")
    grants = [a.alloc(2) for _ in range(3)]
    a.free(grants[1])                      # free the MIDDLE grant
    g = a.alloc(2)
    assert g is not None and set(g) == set(grants[1])
    # page-table reuse after sequence completion: all pages cycle
    a.free(grants[0])
    a.free(grants[2])
    a.free(g)
    seen = set()
    for _ in range(3):
        g = a.alloc(2)
        seen.update(g)
        a.free(g)
    assert a.used_pages == 0


def test_kv_allocator_double_free_raises():
    a = KVPageAllocator(4, 2, name="alloc/3")
    g = a.alloc(2)
    a.free(g)
    with pytest.raises(MXNetError):
        a.free(g)
    with pytest.raises(MXNetError):
        a.free([99])
    with pytest.raises(MXNetError):
        KVPageAllocator(0, 2)


def test_kv_allocator_gauges():
    a = KVPageAllocator(5, 2, name="allocg/1")
    snap = telemetry.snapshot()
    assert snap['mxtpu_serve_kv_pages_total{model="allocg/1"}'][
        "value"] == 5
    g = a.alloc(3)
    assert telemetry.snapshot()[
        'mxtpu_serve_kv_pages_used{model="allocg/1"}']["value"] == 3
    a.free(g)
    assert telemetry.snapshot()[
        'mxtpu_serve_kv_pages_used{model="allocg/1"}']["value"] == 0


# ---------------------------------------------------------------------------
# paged decode attention: Pallas (interpret) vs dense-gather jnp oracle,
# on the token-major pool (pages, page_size, Cp)
# ---------------------------------------------------------------------------

def _paged_inputs(seed, b, h, d, pages, ps, maxp, dtype):
    """q, the K and V pools (lanes past H*D zero, as the engine allocates
    them) and a random page table."""
    import jax.numpy as jnp

    rng = np.random.RandomState(seed)
    c = h * d
    cp = -(-c // 128) * 128
    pools = []
    for _ in "kv":
        pool = np.zeros((pages, ps, cp), np.float32)
        pool[..., :c] = rng.randn(pages, ps, c)
        pools.append(jnp.asarray(pool, dtype=dtype))
    q = jnp.asarray(rng.randn(b, h, d), dtype=dtype)
    tbl = jnp.asarray(rng.randint(0, pages, (b, maxp)), jnp.int32)
    return q, pools[0], pools[1], tbl


# ragged lengths: a FULL table, a page-straddling row, a 1-token row and an
# INERT row (length 0 — the scheduler's batch padding). The kernel walks a
# work list of live blocks (`pk.paged_pages_per_step` pages each: 8 pages of
# 16, 16 of 8), so the later cases put lengths on a block's edges, end the
# rows of one batch in different blocks, give the table a length that is no
# multiple of a block, and name page 0 in every dead entry, as the engine's
# tables do. float32 at 2e-5: a head's lanes are summed on the MXU as two
# bfloat16 terms (the lane butterfly's float32 sums read 2e-6)
_LENS = "full, a page and a row, one row, inert"
@pytest.mark.parametrize("h,d,ps,maxp,dtype,tol,lens,zero_dead", [
    (12, 64, 16, 6, "float32", 2e-5, _LENS, False),    # GPT-2 small: Cp 768
    (2, 32, 8, 5, "float32", 2e-5, _LENS, False),      # Cp padded to 128
    (3, 16, 8, 4, "float32", 2e-5, _LENS, False),      # odd head count
    (16, 128, 8, 3, "float32", 2e-5, _LENS, False),    # a head is a lane tile
    (2, 256, 8, 3, "float32", 2e-5, _LENS, False),     # a head is two tiles
    (12, 64, 16, 6, "bfloat16", 4e-2, _LENS, False),   # bf16: sublane tile 16
    # a table of 11 pages is a block of 8 and a part of one: the whole
    # table, exactly one block, one row into the next block, inert
    (12, 64, 16, 11, "float32", 2e-5, [176, 128, 129, 0], False),
    # rows that end in the third, second, first and second block
    (12, 64, 16, 20, "float32", 2e-5, [320, 130, 17, 255], False),
    # dead entries all name page 0; an inert row between live ones
    (12, 64, 16, 20, "float32", 2e-5, [129, 0, 320, 1], True),
    (12, 64, 16, 11, "bfloat16", 4e-2, [176, 0, 129, 128], True),
    # pages of 8: a block is 16 pages, the table 19
    (2, 32, 8, 19, "float32", 2e-5, [152, 128, 129, 1], False),
    # a table shorter than a block: the block is the table
    (2, 32, 8, 3, "float32", 2e-5, [24, 0, 0, 9], True),
], ids=["gpt2s-f32", "tiny-f32", "odd-heads-f32", "wide-head-f32",
        "two-tile-head-f32", "gpt2s-bf16", "table-off-the-block",
        "rows-end-in-different-blocks", "dead-entries-name-page-0",
        "dead-entries-name-page-0-bf16", "blocks-of-16-pages",
        "table-shorter-than-a-block"])
def test_paged_attention_pallas_vs_jnp(monkeypatch, h, d, ps, maxp, dtype,
                                       tol, lens, zero_dead):
    import jax.numpy as jnp

    from mxnet_tpu.ops import pallas_kernels as pk

    q, kp, vp, tbl = _paged_inputs(7, 4, h, d, 32, ps, maxp, dtype)
    if lens is _LENS:
        lens = [maxp * ps, ps + 1, 1, 0]
    if zero_dead:
        tbl = jnp.where(jnp.arange(maxp)[None, :]
                        < -(-np.asarray(lens)[:, None] // ps), tbl, 0)
    lens = jnp.asarray(lens, jnp.int32)
    live = np.asarray(lens) > 0
    assert pk._paged_kernel_takes(d, ps, kp.shape[-1], kp.dtype)
    ref = pk.paged_attention_reference(q, kp, vp, tbl, lens,
                                       1.0 / np.sqrt(d))
    monkeypatch.setenv("MXTPU_PALLAS_DECODE", "1")   # force the kernel
    out = pk.paged_attention(q, kp, vp, tbl, lens)
    assert out.shape == q.shape and out.dtype == q.dtype
    # live rows match to dtype tolerance; an inert row reads zeros
    err = np.max(np.abs(np.asarray(ref, np.float32)[live]
                        - np.asarray(out, np.float32)[live]))
    assert err < tol, err
    assert not np.any(np.asarray(out, np.float32)[~live])


def test_paged_attention_reference_is_dense_attention():
    """The oracle itself, against attention written out per sequence and
    head in numpy: the layout (token t of sequence b in page
    tables[b, t // ps] row t % ps, head h in lanes [h*D, (h+1)*D)) is
    pinned by something that does not share its code."""
    from mxnet_tpu.ops import pallas_kernels as pk

    b, h, d, ps, maxp = 3, 2, 32, 4, 3
    q, kp, vp, tbl = _paged_inputs(11, b, h, d, 8, ps, maxp, "float32")
    lens = [maxp * ps, 5, 1]
    out = np.asarray(pk.paged_attention_reference(
        q, kp, vp, tbl, np.asarray(lens, np.int32), 1.0 / np.sqrt(d)))
    qn, kn, vn, tn = (np.asarray(a) for a in (q, kp, vp, tbl))
    for bi in range(b):
        for hi in range(h):
            lanes = slice(hi * d, (hi + 1) * d)
            k = np.stack([kn[tn[bi, t // ps], t % ps, lanes]
                          for t in range(lens[bi])])
            v = np.stack([vn[tn[bi, t // ps], t % ps, lanes]
                          for t in range(lens[bi])])
            sc = k @ qn[bi, hi] / np.sqrt(d)
            w = np.exp(sc - sc.max())
            want = (w / w.sum()) @ v
            assert np.max(np.abs(out[bi, hi] - want)) < 1e-5


@pytest.mark.parametrize("h,d,ps,cp,dtype,why", [
    (2, 24, 8, 128, "float32", "head size no power of two"),
    (2, 32, 4, 128, "float32", "page smaller than a float32 sublane tile"),
    (2, 32, 8, 128, "bfloat16", "page smaller than a bf16 sublane tile"),
    (2, 32, 8, 64, "float32", "rows off the 128-lane tile"),
])
def test_paged_attention_refused_shapes_fall_to_reference(monkeypatch, h, d,
                                                          ps, cp, dtype, why):
    """Shapes the kernel cannot take go to the jnp path by themselves,
    even with the gate forcing the kernel: the choice is made from the
    shapes, and the kernel builder is never reached."""
    import jax.numpy as jnp

    from mxnet_tpu.ops import pallas_kernels as pk

    rng = np.random.RandomState(5)
    q = jnp.asarray(rng.randn(2, h, d), dtype=dtype)
    kp = jnp.asarray(rng.randn(8, ps, cp), dtype=dtype)
    vp = jnp.asarray(rng.randn(8, ps, cp), dtype=dtype)
    tbl = jnp.asarray(rng.randint(0, 8, (2, 3)), jnp.int32)
    lens = jnp.asarray([2 * ps + 1, 3], jnp.int32)
    assert not pk._paged_kernel_takes(d, ps, cp, kp.dtype), why

    def refuse(key):
        raise AssertionError("kernel built for refused shapes: %r" % (key,))

    monkeypatch.setattr(pk, "_paged_compiled", refuse)
    monkeypatch.setenv("MXTPU_PALLAS_DECODE", "1")
    out = pk.paged_attention(q, kp, vp, tbl, lens)
    ref = pk.paged_attention_reference(q, kp, vp, tbl, lens,
                                       1.0 / np.sqrt(d))
    assert np.array_equal(np.asarray(out, np.float32),
                          np.asarray(ref, np.float32))


@pytest.mark.parametrize("gate,latent,d,ps,cp,dtype,group,maxp,want", [
    # the GPT-2 cells and the LFM2 cell: blocks of 8 pages of 16
    ("1", False, 64, 16, 768, "float32", 1, 44,
     ("paged_attention_decode", 8, True)),
    ("1", False, 64, 16, 512, "bfloat16", 4, 64,
     ("paged_attention_decode", 8, True)),
    # pages of 8: 16 a block; a table shorter than a block
    ("1", False, 32, 8, 128, "float32", 1, 44,
     ("paged_attention_decode", 16, True)),
    ("1", False, 32, 16, 128, "float32", 1, 3,
     ("paged_attention_decode", 3, True)),
    # the latent kernel's own rule (GigaChat3's pages of 128)
    ("1", True, 576, 128, 640, "bfloat16", 64, 38,
     ("paged_latent_attention_decode", 2, True)),
    # refused shapes and a closed gate: the jnp path reads the whole table
    ("1", False, 24, 8, 128, "float32", 1, 6, (None, 6, False)),
    ("1", False, 32, 8, 128, "bfloat16", 1, 6, (None, 6, False)),
    ("0", False, 64, 16, 768, "float32", 1, 44, (None, 44, False)),
    ("auto", False, 64, 16, 768, "float32", 1, 44, (None, 44, False)),
])
def test_decode_attention_form_says_what_runs(monkeypatch, gate, latent, d,
                                              ps, cp, dtype, group, maxp,
                                              want):
    """What an engine publishes as `kernel_form` is decided by the
    functions the calls themselves decide by."""
    from mxnet_tpu.ops import pallas_kernels as pk

    monkeypatch.setenv("MXTPU_PALLAS_DECODE", gate)
    form = pk.decode_attention_form(latent, d, ps, cp, dtype, group, 64, maxp)
    assert (form["kernel"], form["pages_per_step"],
            form["lanes_on_mxu"]) == want


def test_paged_attention_gate_fallback(monkeypatch):
    """`0` forces the jnp path; `auto` off-TPU is the jnp path too — all
    three spellings agree numerically."""
    import jax.numpy as jnp

    from mxnet_tpu.ops import pallas_kernels as pk

    q, kp, vp, tbl = _paged_inputs(3, 2, 2, 16, 8, 8, 3, "float32")
    lens = jnp.asarray([5, 17], jnp.int32)
    outs = {}
    for gate in ("0", "auto", "1"):
        monkeypatch.setenv("MXTPU_PALLAS_DECODE", gate)
        outs[gate] = np.asarray(pk.paged_attention(q, kp, vp, tbl, lens))
    assert np.allclose(outs["0"], outs["auto"])
    assert np.max(np.abs(outs["0"] - outs["1"])) < 2e-5


# ---------------------------------------------------------------------------
# sampling ops
# ---------------------------------------------------------------------------

def test_sample_token_greedy_is_argmax():
    logits = mx.nd.array(np.random.RandomState(0).randn(6, 24)
                         .astype(np.float32))
    out = mx.nd.sample_token(logits, temperature=0.0).asnumpy()
    assert np.array_equal(out, np.argmax(logits.asnumpy(), axis=-1))


def test_sample_token_top_k_top_p_masks():
    rng = np.random.RandomState(1)
    logits = mx.nd.array(rng.randn(64, 16).astype(np.float32))
    top3 = np.argsort(logits.asnumpy(), axis=-1)[:, -3:]
    out = mx.nd.sample_token(logits, temperature=1.0, top_k=3).asnumpy()
    for o, allowed in zip(out, top3):
        assert o in allowed, (o, allowed)
    # top_k=1 degenerates to greedy regardless of temperature
    out1 = mx.nd.sample_token(logits, temperature=5.0, top_k=1).asnumpy()
    assert np.array_equal(out1, np.argmax(logits.asnumpy(), axis=-1))
    # a tiny top_p keeps only the argmax too
    outp = mx.nd.sample_token(logits, temperature=5.0,
                              top_p=1e-6).asnumpy()
    assert np.array_equal(outp, np.argmax(logits.asnumpy(), axis=-1))


def test_sample_token_seeded_reproducible_and_symbolic():
    import mxnet_tpu.symbol as sym

    logits = mx.nd.array(np.random.RandomState(2).randn(8, 32)
                         .astype(np.float32))
    mx.random.seed(11)
    a = mx.nd.sample_token(logits, temperature=1.0).asnumpy()
    mx.random.seed(11)
    b = mx.nd.sample_token(logits, temperature=1.0).asnumpy()
    assert np.array_equal(a, b)
    # registered in the symbol namespace too (nd+symbol parity)
    s = sym.sample_token(sym.var("logits"), temperature=0.0)
    ex = s.bind(mx.cpu(), {"logits": logits})
    (out,) = ex.forward()
    assert np.array_equal(out.asnumpy(),
                          np.argmax(logits.asnumpy(), axis=-1))


def test_sample_token_logits_per_row_params():
    """The decode executable's form: per-row temperature/top_k/top_p
    arrays — greedy rows exact, stochastic rows inside their top-k."""
    import jax

    from mxnet_tpu.ops.random_ops import sample_token_logits

    rng = np.random.RandomState(4)
    logits = rng.randn(5, 12).astype(np.float32)
    temps = np.asarray([0.0, 1.0, 0.0, 2.0, 0.0], np.float32)
    ks = np.asarray([0, 2, 0, 4, 0], np.int32)
    ps = np.ones(5, np.float32)
    out = np.asarray(sample_token_logits(
        jax.random.PRNGKey(0), logits, temps, ks, ps))
    greedy = np.argmax(logits, axis=-1)
    for i in (0, 2, 4):
        assert out[i] == greedy[i]
    assert out[1] in np.argsort(logits[1])[-2:]
    assert out[3] in np.argsort(logits[3])[-4:]


def _two_sort_sampler(rng, logits, temperature=1.0, top_k=0, top_p=1.0):
    """The sampler as it stood before it branched on its rows, frozen here
    as the reference: a sort of the vocabulary for top-k's threshold, a
    second for top-p's and the draw, in every call, then the per-row
    choice between argmax and drawn."""
    import jax
    import jax.numpy as jnp

    def top_k_logits(logits, k):
        v = logits.shape[-1]
        kk = jnp.broadcast_to(jnp.asarray(k, jnp.int32), logits.shape[:-1])
        kk = jnp.clip(jnp.where(kk <= 0, v, kk), 1, v)
        desc = jnp.flip(jnp.sort(logits, axis=-1), axis=-1)
        thr = jnp.take_along_axis(desc, (kk - 1)[..., None], axis=-1)
        return jnp.where(logits >= thr, logits, -jnp.inf)

    def top_p_logits(logits, p):
        pp = jnp.broadcast_to(jnp.asarray(p, jnp.float32),
                              logits.shape[:-1])
        pp = jnp.where((pp <= 0.0) | (pp >= 1.0), 1.0, pp)
        desc = jnp.flip(jnp.sort(logits, axis=-1), axis=-1)
        probs = jax.nn.softmax(desc, axis=-1)
        keep = (jnp.cumsum(probs, axis=-1) - probs) < pp[..., None]
        thr = jnp.min(jnp.where(keep, desc, jnp.inf), axis=-1,
                      keepdims=True)
        return jnp.where(logits >= thr, logits, -jnp.inf)

    t = jnp.broadcast_to(jnp.asarray(temperature, jnp.float32),
                         logits.shape[:-1])
    lf = logits.astype(jnp.float32)
    masked = top_p_logits(top_k_logits(lf, top_k), top_p)
    scaled = masked / jnp.maximum(t, 1e-6)[..., None]
    drawn = jax.random.categorical(rng, scaled, axis=-1)
    return jnp.where(t <= 0.0, jnp.argmax(lf, axis=-1),
                     drawn).astype(jnp.int32)


def _sampler_rows(case):
    """(logits, temperature, top_k, top_p) of eight rows over a vocabulary
    of 300; all but `ties` and `bf16` share the logits."""
    b, v = 8, 300
    logits = (3.0 * np.random.RandomState(7).randn(b, v)).astype(np.float32)
    t, k, p = (np.zeros(b, np.float32), np.zeros(b, np.int32),
               np.ones(b, np.float32))
    hot = np.asarray([0.3, 0.7, 1.0, 1.0, 1.3, 2.0, 5.0, 0.05], np.float32)
    some_k = np.asarray([1, 2, 5, 40, 299, 7, 3, 100], np.int32)
    some_p = np.asarray([1e-6, 0.1, 0.5, 0.9, 0.99, 0.3, 0.75, 0.999],
                        np.float32)
    if case == "all_greedy":
        pass
    elif case == "greedy_rows_that_set_filters":
        k, p = some_k, some_p
    elif case == "temperature_only":
        t = hot
    elif case == "top_k_only":
        t, k = hot, some_k
    elif case == "top_p_only":
        t, p = hot, some_p
    elif case == "top_k_and_top_p":
        t, k, p = hot, some_k, some_p[::-1].copy()
    elif case == "ties":
        # a handful of distinct values: the top-k threshold falls inside a
        # run of equal logits in every row, and both forms keep the run
        logits = np.random.RandomState(8).randint(0, 6, (b, v)) \
            .astype(np.float32)
        t, k, p = hot, some_k, some_p
    elif case == "top_k_at_and_over_the_vocabulary":
        t = hot
        k = np.asarray([v, v + 1, 10 * v, v - 1, v, 2 * v, v, v], np.int32)
    elif case == "top_p_0_and_1":
        t = hot
        p = np.asarray([0.0, 1.0, 0.0, 1.0, -1.0, 2.0, 0.0, 1.0],
                       np.float32)
    elif case == "mixed":
        t = np.asarray([0.0, 1.0, 0.0, 0.7, 0.0, 2.0, 1.0, 0.0], np.float32)
        k = np.asarray([0, 0, 5, 5, 0, 0, 40, 0], np.int32)
        p = np.asarray([1.0, 1.0, 0.5, 1.0, 1.0, 0.9, 0.6, 0.2], np.float32)
    elif case == "bf16":
        import jax.numpy as jnp

        logits = jnp.asarray(logits, jnp.bfloat16)   # ties by rounding
        t = np.asarray([0.0, 1.0, 0.0, 0.7, 0.0, 2.0, 1.0, 0.0], np.float32)
        k, p = some_k, some_p
    else:
        raise AssertionError(case)
    return logits, t, k, p


@pytest.mark.parametrize("case", [
    "all_greedy", "greedy_rows_that_set_filters", "temperature_only",
    "top_k_only", "top_p_only", "top_k_and_top_p", "ties",
    "top_k_at_and_over_the_vocabulary", "top_p_0_and_1", "mixed", "bf16"])
def test_sampler_returns_what_the_two_sort_sampler_did(case):
    """Same key, same logits, same per-row parameters: the same token in
    every row, as one executable that branches on the device (the engine's
    form) and with host values, where the branch is taken while tracing."""
    import jax

    from mxnet_tpu.ops.random_ops import sample_token_logits

    logits, t, k, p = _sampler_rows(case)
    old, new = jax.jit(_two_sort_sampler), jax.jit(sample_token_logits)
    for seed in range(6):
        key = jax.random.PRNGKey(seed)
        want = np.asarray(old(key, logits, t, k, p))
        assert want.dtype == np.int32
        got = np.asarray(new(key, logits, t, k, p))
        assert np.array_equal(got, want), (seed, got, want)
        host = np.asarray(sample_token_logits(key, logits, t, k, p))
        assert np.array_equal(host, want), (seed, host, want)
    greedy = np.argmax(np.asarray(logits, np.float32), axis=-1)
    assert np.array_equal(want[t <= 0], greedy[t <= 0])
    if case in ("top_k_only", "ties"):
        assert not np.array_equal(want, greedy)     # it did draw


def _primitives(jaxpr):
    """Every primitive name in a jaxpr, sub-jaxprs included."""
    import jax

    names = []
    for eqn in jaxpr.eqns:
        names.append(eqn.primitive.name)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            names.extend(_primitives(sub))
    return names


@pytest.mark.parametrize("form,conds,sorts", [
    ("arrays", 2, 1),               # the engine's: decided on the device
    ("scalars_greedy", 0, 0),       # sample_token(temperature=0.0)
    ("scalars_temperature", 0, 0),
    ("scalars_top_k", 0, 1),
    ("scalars_top_p", 0, 1),
])
def test_sampler_graph_holds_only_what_was_asked(form, conds, sorts):
    import jax

    from mxnet_tpu.ops import random_ops

    key = jax.random.PRNGKey(0)
    logits, t, k, p = _sampler_rows("mixed")
    if form == "arrays":
        jaxpr = jax.make_jaxpr(random_ops.sample_token_logits)(
            key, logits, t, k, p)
    else:
        attrs = {"scalars_greedy": dict(temperature=0.0, top_k=5, top_p=0.5),
                 "scalars_temperature": dict(temperature=0.8),
                 "scalars_top_k": dict(temperature=0.8, top_k=5),
                 "scalars_top_p": dict(temperature=0.8, top_p=0.5)}[form]
        jaxpr = jax.make_jaxpr(
            lambda key, x: random_ops.sample_token(key, x, **attrs))(
                key, logits)
    names = _primitives(jaxpr.jaxpr)
    assert names.count("cond") == conds, names
    assert names.count("sort") == sorts, names
    if form == "scalars_greedy":
        assert not {"random_bits", "div", "cumsum"} & set(names), names


# ---------------------------------------------------------------------------
# scheduler semantics on a stub engine (no jax compiles: fast, exact)
# ---------------------------------------------------------------------------

class StubEngine:
    """Deterministic no-model engine: prefill answers (sum(prompt)+1)
    mod vocab, decode answers last+1 mod vocab. Records each decode
    step's live-row count so tests can assert batch composition."""

    def __init__(self, vocab=64, buckets=(1, 2, 4), page_size=2,
                 num_pages=12, max_prompt=4, max_new_tokens=8,
                 eos_id=None, step_sleep=0.0, prefill_gate=None):
        self.vocab_size = vocab
        self.buckets = list(buckets)
        self.page_size = page_size
        self.num_pages = num_pages
        self.max_prompt = max_prompt
        self.max_new_tokens = max_new_tokens
        self.max_pages_per_seq = -(-(max_prompt + max_new_tokens)
                                   // page_size)
        self.eos_id = eos_id
        self.step_sleep = step_sleep
        self.prefill_gate = prefill_gate   # Event: hold prefill (tests)
        self.step_counts = []

    def warm(self):
        return 0.0

    def prefill(self, tokens, page_row, sampling, key):
        if self.prefill_gate is not None:
            self.prefill_gate.wait(5.0)
        return (sum(tokens) + 1) % self.vocab_size

    def decode_step(self, tokens, positions, dest_pages, dest_slots,
                    tables, lengths, temps, top_ks, top_ps, key):
        if self.step_sleep:
            time.sleep(self.step_sleep)
        self.step_counts.append(int((np.asarray(lengths) > 0).sum()))
        return ((np.asarray(tokens) + 1) % self.vocab_size).astype(np.int32)

    def geometry(self):
        return {"num_pages": self.num_pages, "page_size": self.page_size}


def _stub_expected(prompt, n, vocab=64):
    first = (sum(prompt) + 1) % vocab
    out = [first]
    for _ in range(n - 1):
        out.append((out[-1] + 1) % vocab)
    return out


def test_scheduler_stub_continuous_batching_join_leave():
    eng = StubEngine(step_sleep=0.01)
    sched = GenerateScheduler(eng, name="stub/1", queue_depth=8)
    try:
        ra = sched.submit([1, 2], max_new_tokens=8)
        time.sleep(0.05)                       # A is decoding alone
        rb = sched.submit([3], max_new_tokens=3)   # late joiner
        a = ra.wait(10)
        b = rb.wait(10)
        assert a == _stub_expected([1, 2], 8)
        assert b == _stub_expected([3], 3)
        # the batch really changed size at step granularity: A ran alone,
        # then A+B together, then A alone again after B finished
        assert 2 in eng.step_counts and 1 in eng.step_counts
        assert eng.step_counts.index(2) > 0    # A started solo
        assert sched.allocator.used_pages == 0
    finally:
        sched.close(drain=False, timeout=0)


def test_scheduler_stub_eos_and_validation():
    eng = StubEngine(eos_id=7)
    sched = GenerateScheduler(eng, name="stub/2", queue_depth=8)
    try:
        # (sum=4)+1=5, then 6, then 7=eos: stops early with reason "eos"
        r = sched.submit([4], max_new_tokens=8)
        out = r.wait(10)
        assert out[-1] == 7 and len(out) == 3
        assert r.finish_reason == "eos"
        with pytest.raises(MXNetError):
            sched.submit([], max_new_tokens=2)
        with pytest.raises(MXNetError):
            sched.submit([1] * 99, max_new_tokens=2)   # prompt too long
        with pytest.raises(MXNetError):
            sched.submit([1], max_new_tokens=0)
        with pytest.raises(MXNetError):
            sched.submit([999], max_new_tokens=2)      # token out of range
    finally:
        sched.close(drain=False, timeout=0)


def test_scheduler_stub_deadline_and_queue_full():
    gate = threading.Event()
    eng = StubEngine(prefill_gate=gate)
    sched = GenerateScheduler(eng, name="stub/3", queue_depth=1)
    try:
        r1 = sched.submit([1], max_new_tokens=2)   # worker parks in prefill
        time.sleep(0.05)
        r2 = sched.submit([2], max_new_tokens=2)   # fills the queue
        with pytest.raises(QueueFullError):
            sched.submit([3], max_new_tokens=2)
        gate.set()
        assert r1.wait(10) == _stub_expected([1], 2)
        assert r2.wait(10) == _stub_expected([2], 2)
        # expired-in-queue: deadline already past at admission
        gate.clear()
        r4 = sched.submit([1], max_new_tokens=2,
                          deadline=time.monotonic() - 0.001)
        gate.set()
        with pytest.raises(DeadlineExceededError):
            r4.wait(10)
        assert sched.allocator.used_pages == 0
    finally:
        sched.close(drain=False, timeout=0)


def test_scheduler_stub_page_pressure_serializes():
    """Worst-case page reservation: two sequences that each need the
    whole pool run one after the other — pressure queues admissions,
    never deadlocks or evicts a running sequence."""
    eng = StubEngine(page_size=2, num_pages=6, max_prompt=4,
                     max_new_tokens=8)
    assert eng.max_pages_per_seq == 6          # one seq = the whole pool
    sched = GenerateScheduler(eng, name="stub/4", queue_depth=8)
    try:
        r1 = sched.submit([1, 2, 3, 4], max_new_tokens=8)
        r2 = sched.submit([2, 2, 2, 2], max_new_tokens=8)
        assert r1.wait(10) == _stub_expected([1, 2, 3, 4], 8)
        assert r2.wait(10) == _stub_expected([2, 2, 2, 2], 8)
        # never more than one resident batch: every step ran solo
        assert set(eng.step_counts) == {1}
        assert sched.allocator.used_pages == 0
    finally:
        sched.close(drain=False, timeout=0)


def test_scheduler_abort_reclaims_pages():
    eng = StubEngine(step_sleep=0.02)
    sched = GenerateScheduler(eng, name="stub/5", queue_depth=8)
    try:
        r = sched.submit([1], max_new_tokens=8)
        time.sleep(0.05)                       # mid-decode
        n = sched.abort_pending()
        assert n >= 1
        with pytest.raises(Exception):
            r.wait(5)
        deadline = time.monotonic() + 5
        while sched.allocator.used_pages and time.monotonic() < deadline:
            time.sleep(0.01)                   # worker lap reclaims
        assert sched.allocator.used_pages == 0
    finally:
        sched.close(drain=False, timeout=0)


@pytest.mark.parametrize("temperature,path,other", [
    (0.0, "greedy", "sampled"), (0.8, "sampled", "greedy")])
def test_scheduler_lap_records_what_the_sampler_was_asked(temperature, path,
                                                          other):
    """Every lap that stepped says in the accountant's ring how many of its
    rows had a temperature, and the step counts under that path."""
    from mxnet_tpu.telemetry import goodput

    name = "stub/sampler-%s" % path

    def steps(p):
        series = telemetry.snapshot().get(
            'mxtpu_serve_sampler_steps_total{model="%s",path="%s"}'
            % (name, p))
        return 0 if series is None else series["value"]

    sched = GenerateScheduler(StubEngine(), name=name, queue_depth=8)
    try:
        for r in [sched.submit([1 + i], max_new_tokens=4,
                               temperature=temperature) for i in range(3)]:
            r.wait(10)
    finally:
        sched.close(drain=False, timeout=0)
    laps = [r for r in goodput.window("serve")
            if r["model"] == name and r["n"]]
    assert laps
    for lap in laps:
        assert lap["sampled"] == (lap["n"] if temperature > 0 else 0)
    assert steps(path) == len(laps) and steps(other) == 0


# ---------------------------------------------------------------------------
# the real engine: greedy parity vs the gluon full-sequence oracle
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_lm():
    lm = lm_mini(vocab_size=96)
    lm.initialize(mx.init.Xavier())
    return lm


def _gluon_greedy(lm, prompt, n):
    toks = list(prompt)
    out = []
    for _ in range(n):
        logits = lm(mx.nd.array([toks], dtype="int32")).asnumpy()[0, -1]
        t = int(np.argmax(logits))
        out.append(t)
        toks.append(t)
    return out


@pytest.fixture(scope="module")
def lm_scheduler(tiny_lm):
    eng = TransformerLMEngine(lm=tiny_lm, num_pages=32, page_size=4,
                              max_prompt=8, max_new_tokens=12, max_batch=4)
    sched = GenerateScheduler(eng, name="lm/1", queue_depth=16)
    yield sched
    sched.close(drain=False, timeout=0)


def test_engine_greedy_matches_gluon_oracle(lm_scheduler, tiny_lm):
    """THE correctness core: incremental paged-KV decode computes the
    same function as the gluon block's full causal forward — greedy
    token sequences match exactly, and batching requests together
    changes nothing (batch invariance)."""
    prompts = [[3, 5, 7], [2], [9, 4, 6, 1, 8], [1, 2, 3, 4]]
    budgets = [5, 9, 3, 7]
    oracles = [_gluon_greedy(tiny_lm, p, n)
               for p, n in zip(prompts, budgets)]
    misses = telemetry.get_registry().counter("mxtpu_jit_cache_miss_total")
    base = misses.value
    reqs = [lm_scheduler.submit(p, max_new_tokens=n)
            for p, n in zip(prompts, budgets)]
    outs = [r.wait(60) for r in reqs]
    assert outs == oracles
    # zero-compile steady state: every bucket was covered by warm
    assert misses.value - base == 0
    assert lm_scheduler.allocator.used_pages == 0


def _pool_bytes(engine):
    import jax

    return [np.asarray(a).tobytes()
            for a in jax.tree_util.tree_leaves(engine._kv)]


@pytest.mark.parametrize("gate", ["1", "0"], ids=["kernel", "jnp"])
def test_engine_pool_in_place_interleaved_pages(tiny_lm, monkeypatch, gate):
    """The engine driven by hand on its per-layer token-major pool, pages
    of two sequences interleaved: greedy tokens of a prefill and 32 decode
    steps equal the dense forward's; a sequence retired mid-way hands its
    pages to a new one while the other keeps decoding; padding rows' writes
    drop and leave every page byte-identical. Once through the Pallas
    kernel (interpret), once through the jnp path."""
    import jax

    monkeypatch.setenv("MXTPU_PALLAS_DECODE", gate)
    ps, maxp, nump = 8, 6, 20 + int(gate)        # a geometry per gate: the
    eng = TransformerLMEngine(                   # gate is read at trace time
        lm=tiny_lm, num_pages=nump, page_size=ps, max_prompt=8,
        max_new_tokens=40, decode_buckets=[2, 4], prefill_buckets=[8])
    leaves = jax.tree_util.tree_leaves(eng._kv)
    assert len(leaves) == 2 * eng.num_layers
    assert all(a.shape == (nump, ps, 128) for a in leaves)   # 32 -> 128 once
    assert eng.kv_bytes() == len(leaves) * nump * ps * 128 * 4
    greedy, key = (0.0, 0, 1.0), mx.random.next_key()
    rows = {"a": [1, 3, 5, 7, 9, 11], "b": [2, 4, 6, 8, 10, 12]}
    prompts = {"a": [3, 5, 7, 2, 9], "b": [9, 4, 6, 1, 8, 2, 7],
               "c": [1, 2, 3]}
    def dense_greedy(prompt, n, pad_to=48):
        # the block is causal, so one padded shape serves every length
        toks = list(prompt)
        for _ in range(n):
            ids = mx.nd.array([toks + [0] * (pad_to - len(toks))],
                              dtype="int32")
            toks.append(int(np.argmax(
                tiny_lm(ids).asnumpy()[0, len(toks) - 1])))
        return toks[len(prompt):]

    want = {n: dense_greedy(p, 33) for n, p in prompts.items()}

    before = _pool_bytes(eng)
    seqs = {}
    for n in "ab":
        tok = eng.prefill(prompts[n], rows[n], greedy, key)
        seqs[n] = {"row": rows[n], "pos": len(prompts[n]), "out": [tok]}
    after = _pool_bytes(eng)
    # a 5- and a 7-token prompt in an 8-bucket: only the first page of each
    # table was written, the bucket's padding rows dropped
    for old, new in zip(before, after):
        width = ps * 128 * 4
        changed = {i for i in range(nump)
                   if old[i * width:(i + 1) * width]
                   != new[i * width:(i + 1) * width]}
        assert changed == {1, 2}

    def step(names, bucket):
        b = bucket
        tokens, positions = np.zeros(b, np.int32), np.zeros(b, np.int32)
        dest_pages = np.full(b, nump, np.int32)       # dropped by default
        dest_slots, lengths = np.zeros(b, np.int32), np.zeros(b, np.int32)
        tables = np.zeros((b, maxp), np.int32)
        for i, n in enumerate(names):
            q = seqs[n]
            tokens[i], positions[i] = q["out"][-1], q["pos"]
            dest_pages[i] = q["row"][q["pos"] // ps]
            dest_slots[i] = q["pos"] % ps
            tables[i], lengths[i] = q["row"], q["pos"] + 1
        out = eng.decode_step(tokens, positions, dest_pages, dest_slots,
                              tables, lengths, np.zeros(b, np.float32),
                              np.zeros(b, np.int32), np.ones(b, np.float32),
                              key)
        for i, n in enumerate(names):
            seqs[n]["out"].append(int(out[i]))
            seqs[n]["pos"] += 1

    for _ in range(12):
        step("ab", 2)
    assert seqs["b"]["out"] == want["b"][:13]
    # b retires; c takes over b's pages (stale K/V in them) in a wider
    # bucket whose two spare rows are inert padding
    tok = eng.prefill(prompts["c"], rows["b"], greedy, key)
    seqs["c"] = {"row": rows["b"], "pos": len(prompts["c"]), "out": [tok]}
    for _ in range(20):
        step("ca", 4)
    assert seqs["a"]["out"] == want["a"]                 # 1 + 32 tokens
    assert seqs["c"]["out"] == want["c"][:21]

    # a step of padding rows only: every write drops, nothing moves
    before = _pool_bytes(eng)
    step("", 2)
    assert _pool_bytes(eng) == before


def test_engine_sampled_tokens_stay_in_vocab(lm_scheduler):
    r = lm_scheduler.submit([5, 6], max_new_tokens=6, temperature=0.8,
                            top_k=4)
    out = r.wait(60)
    assert len(out) == 6
    assert all(0 <= t < 96 for t in out)
    assert lm_scheduler.allocator.used_pages == 0


# ---------------------------------------------------------------------------
# artifact roundtrip + HTTP surface (in-process ServedLM)
# ---------------------------------------------------------------------------

def test_save_load_lm_roundtrip(tiny_lm, tmp_path):
    prefix = save_lm(tiny_lm, str(tmp_path / "lm"))
    lm2 = load_lm(prefix)
    ids = mx.nd.array(np.random.RandomState(0).randint(0, 96, (2, 5)),
                      dtype="int32")
    assert np.array_equal(tiny_lm(ids).asnumpy(), lm2(ids).asnumpy())
    with pytest.raises(MXNetError):
        load_lm(str(tmp_path / "nope"))


def test_http_generate_e2e(tiny_lm, tmp_path):
    prefix = save_lm(tiny_lm, str(tmp_path / "lm"))
    repo = ModelRepository()
    model = repo.load("lm", prefix, generate=True,
                      generate_opts=dict(num_pages=32, page_size=4,
                                         max_prompt=8, max_new_tokens=12,
                                         max_batch=4))
    srv = ServingServer(repo, port=0, addr="127.0.0.1").start()
    url = "http://127.0.0.1:%d/v1/models/lm:generate" % srv.port
    try:
        oracle = _gluon_greedy(tiny_lm, [3, 1, 4], 6)
        body = json.dumps({"tokens": [3, 1, 4], "max_new_tokens": 6,
                           "timeout_ms": 60000}).encode()
        req = urllib.request.Request(
            url, data=body, headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=90) as r:
            resp = json.loads(r.read())
        assert resp["tokens"] == oracle
        assert resp["num_generated"] == 6
        assert resp["finish_reason"] == "length"
        # repository listing carries the generate geometry + kv state
        desc = repo.describe()["models"][0]
        assert desc["kind"] == "generate"
        assert desc["kv"]["pages_used"] == 0
        # malformed bodies are the client's fault: 400, not 500
        for bad in ({"tokens": "abc"}, {"tokens": []},
                    {"tokens": [1], "max_new_tokens": 0},
                    {"tokens": [1], "max_new_tokens": "abc"},
                    {"tokens": [1], "temperature": []}, {}):
            breq = urllib.request.Request(
                url, data=json.dumps(bad).encode(),
                headers={"Content-Type": "application/json"})
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(breq, timeout=30)
            ei.value.read()
            assert ei.value.code == 400, bad
    finally:
        srv.shutdown()
        repo.unload("lm", timeout=1.0)


_STARTUP_DRIVE = r"""
import sys, threading, time
import mxnet_tpu as mx
from mxnet_tpu.gluon.model_zoo.transformer import lm_mini
from mxnet_tpu.serving import ModelRepository, save_lm
from mxnet_tpu.telemetry import goodput

lm = lm_mini(vocab_size=96)
lm.initialize(mx.init.Xavier())
prefix = save_lm(lm, sys.argv[1])
repo = ModelRepository()
model = repo.load("lm", prefix, generate=True, generate_opts=dict(
    num_pages=32, page_size=4, max_prompt=8, max_new_tokens=12, max_batch=4))
recs = sorted(goodput.window("startup"), key=lambda r: r["t0"])
top = [r for r in recs if r["parent"] is None]
names = [r["name"] for r in top if r["name"] in (
    "import", "artifact_write", "artifact_read", "engine_build",
    "first_run", "ready")]
buckets = ["lm_prefill:l8", "lm_decode:b1", "lm_decode:b2", "lm_decode:b4"]
assert names == ["import", "artifact_write", "artifact_read", "engine_build",
                 "first_run", "first_run", "first_run", "first_run",
                 "ready"], names
first = [r for r in recs if r["name"] == "first_run"]
assert [r["label"] for r in first] == buckets, first
for run in first:                   # one program span a bucket, inside it
    held = [r for r in recs if r["name"] == "program"
            and r["parent"] == run["id"]]
    assert [r["label"] for r in held] == [run["label"]], (run, held)
    assert held[0]["tier"] == "memory_miss"
by_name = {r["name"]: r for r in top}
assert by_name["artifact_read"]["bytes"] == by_name["artifact_write"]["bytes"]
assert by_name["artifact_read"]["bytes"] > 0
assert by_name["artifact_read"]["arrays"] == len(lm.collect_params())
assert by_name["engine_build"]["pool_bytes"] == model.generate_info["kv_bytes"]
# pages of 4 rows are no float32 sublane tile: the jnp path, and both say so
assert by_name["engine_build"]["kernel_form"] == \
    model.generate_info["kernel_form"] == {
        "kernel": None, "pages_per_step": 5, "lanes_on_mxu": False}
assert by_name["ready"]["model"] == "lm/1"
assert not any(r["after_ready"] for r in recs)
stages = [r for r in recs if r["name"] in ("trace", "lower",
                                            "backend_compile")]
assert stages and recs[-1]["name"] == "ready"
# traffic: no span is written by a lap after the mark
n = len(goodput.window("startup"))
laps0 = len(goodput.window("serve"))
def client(seed):
    for i in range(10):
        model.generate([1 + seed, 2, 3 + i], max_new_tokens=8,
                       timeout_ms=60000)
threads = [threading.Thread(target=client, args=(k,)) for k in range(3)]
[t.start() for t in threads]
[t.join() for t in threads]
laps = len(goodput.window("serve")) - laps0
assert laps >= 50, laps
after = goodput.window("startup")[n:]
assert after == [], after
repo.unload("lm", timeout=1.0)
print("ok", laps)
"""


def test_load_generate_leaves_the_startup_account_and_traffic_adds_nothing(
        tmp_path):
    """ISSUE 46: a mini TransformerLM through `ModelRepository.load(
    generate=True)` leaves import, artifact, engine_build, one program and
    one first_run a bucket and one ready, in that order, and 50 laps of
    traffic write no span (a process of its own: the package's import span
    and the registry's misses are a fresh process's)."""
    import subprocess
    import sys

    out = subprocess.run(
        [sys.executable, "-c", _STARTUP_DRIVE, str(tmp_path / "lm")],
        capture_output=True, text=True, timeout=300,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.stdout.strip().startswith("ok"), \
        (out.stdout[-2000:], out.stderr[-4000:])


def test_generate_on_predict_model_is_400(tmp_path):
    """:generate against a predict model answers a clear 400."""
    from mxnet_tpu import gluon

    net = gluon.nn.HybridSequential(prefix="p_")
    with net.name_scope():
        net.add(gluon.nn.Dense(4))
    net.initialize(mx.init.Xavier(), ctx=mx.cpu())
    net.hybridize()
    net(mx.nd.array(np.zeros((1, 3), np.float32)))
    prefix = str(tmp_path / "model")
    net.export(prefix, epoch=0)
    repo = ModelRepository()
    repo.load("p", prefix, input_shapes={"data": (3,)}, max_batch=2)
    srv = ServingServer(repo, port=0, addr="127.0.0.1").start()
    try:
        req = urllib.request.Request(
            "http://127.0.0.1:%d/v1/models/p:generate" % srv.port,
            data=json.dumps({"tokens": [1]}).encode())
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req, timeout=30)
        ei.value.read()
        assert ei.value.code == 400
    finally:
        srv.shutdown()
        repo.unload("p", timeout=1.0)


# ---------------------------------------------------------------------------
# THE acceptance e2e (ISSUE 13): 2-replica pooled LM, >=8 concurrent
# generations with unequal budgets, late joiner mid-decode, zero
# post-warm compiles, KV pages fully reclaimed at drain
# ---------------------------------------------------------------------------

def test_pooled_lm_generate_e2e(tmp_path):
    lm = lm_mini(vocab_size=96)
    lm.initialize(mx.init.Xavier())
    prefix = save_lm(lm, str(tmp_path / "lm"))
    repo = ModelRepository()
    model = repo.load(
        "lm", prefix, generate=True, replicas=2,
        generate_opts=dict(num_pages=32, page_size=4, max_prompt=8,
                           max_new_tokens=16, max_batch=4),
        heartbeat_ms=500, backoff_ms=50, teardown_grace=1.0)
    srv = ServingServer(repo, port=0, addr="127.0.0.1").start()
    url = "http://127.0.0.1:%d/v1/models/lm:generate" % srv.port
    try:
        assert model.pool.describe()["mode"] == "generate"
        prompts = [[3, 5, 7], [2], [9, 4, 6, 1, 8], [1, 2, 3, 4],
                   [8, 8], [5], [7, 6, 5, 4, 3], [1]]
        budgets = [5, 9, 3, 7, 4, 8, 6, 2]   # unequal: sequences leave
        #                                      the running batch early
        oracles = [_gluon_greedy(lm, p, n)
                   for p, n in zip(prompts, budgets)]

        results = [None] * len(prompts)

        def client(i, delay=0.0):
            if delay:
                time.sleep(delay)
            body = json.dumps({"tokens": prompts[i],
                               "max_new_tokens": budgets[i],
                               "timeout_ms": 90000}).encode()
            req = urllib.request.Request(
                url, data=body,
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=120) as r:
                results[i] = json.loads(r.read())

        # 6 immediate clients + 2 LATE JOINERS landing mid-decode: they
        # must be admitted into the running batches without restarting
        # anyone (every output still matches the one-request oracle)
        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(6)]
        threads += [threading.Thread(target=client, args=(i, 0.15))
                    for i in (6, 7)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(180)
        assert not any(t.is_alive() for t in threads)
        for i in range(len(prompts)):
            assert results[i] is not None, i
            assert results[i]["tokens"] == oracles[i], \
                (i, results[i]["tokens"], oracles[i])
            assert results[i]["finish_reason"] == "length"
        # worker-side acceptance counters via the stats round trip:
        # ZERO jit_compile events after warm on every replica, and the
        # KV used-gauge back to 0 at drain
        for rid in (0, 1):
            s = model.pool.replica_stats(rid)
            assert s is not None, rid
            assert s["jit_after_warm"] == 0, s
            assert s["kv_pages_used"] == 0, s
            assert s["pending"] == 0, s
    finally:
        srv.shutdown()
        model.close(drain=False, timeout=0)
