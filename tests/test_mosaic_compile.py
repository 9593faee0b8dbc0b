"""AOT-compile the Pallas kernels of the chip_smoke.py phases for a
*described* TPU v5e (`/opt/skills/guides/on-chip-measurement` §2,
rehearsal 3): the chip's compiler is installed in the CPU sandbox, so what
Mosaic refuses (a misaligned slice, too much scoped VMEM, a dot it cannot
parse) is caught here at no chip time. Interpret-mode parity tests
(test_pallas.py, test_generate.py) cannot see any of that.

The public kernel entry points pick interpret mode from
`pallas_kernels._use_interpret()`; each case patches that one function to
False so the real wrappers (padding, custom-vjp, gating geometry) are what
gets lowered. Nothing runs: a compile that passes says nothing about
numerics or speed — chip_smoke.py checks those on the chip.
"""
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import pytest

from mxnet_tpu.ops import pallas_kernels as pk


@pytest.fixture(scope="module")
def v5e():
    """One described v5e device, with jax's persistent compile cache off
    around the module (a described-device executable is written to it but
    cannot be read back without a chip — every later run would warn)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu on this box
        pytest.skip("cannot describe a v5e topology here: %s" % e)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield jax.sharding.SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _largest_lstm_h(b, itemsize):
    """Largest lane-aligned H the LSTM gate admits at batch `b`."""
    h = 128
    while pk.lstm_layer_fits(b, h + 128, itemsize):
        h += 128
    return h


def _flash(shape, dtype, causal):
    def loss(q, k, v):
        return jnp.sum(pk.flash_attention(q, k, v, causal=causal)
                       .astype(jnp.float32))

    return jax.value_and_grad(loss, argnums=(0, 1, 2)), [(shape, dtype)] * 3


def _lstm(t, b, h, dtype):
    def loss(gx, wh, h0, c0):
        ys, _, ct = pk.lstm_layer(gx, wh, h0, c0)
        return jnp.sum(ys.astype(jnp.float32)) + jnp.sum(
            ct.astype(jnp.float32))

    return (jax.value_and_grad(loss, argnums=(0, 1, 2, 3)),
            [((t, b, 4 * h), dtype), ((4 * h, h), dtype),
             ((b, h), dtype), ((b, h), dtype)])


def _paged(b, h, d, n_pages, maxp, ps, dtype):
    """The public entry point on a token-major pool (pages, page_size,
    Cp): it must take the kernel for these shapes, not the jnp path."""
    cp = -(-h * d // 128) * 128
    assert pk._paged_kernel_takes(d, ps, cp, dtype)
    return pk.paged_attention, [
        ((b, h, d), dtype), ((n_pages, ps, cp), dtype),
        ((n_pages, ps, cp), dtype), ((b, maxp), jnp.int32),
        ((b,), jnp.int32)]


def _paged_gqa(b, h, kv, d, n_pages, maxp, ps, dtype):
    """Grouped-query: the pool's row is kv*d lanes, h // kv query heads
    share each KV head."""
    cp = -(-kv * d // 128) * 128
    assert pk._paged_kernel_takes(d, ps, cp, dtype, h // kv)

    def fn(q, kp, vp, tables, lengths):
        return pk.paged_attention(q, kp, vp, tables, lengths, kv_heads=kv)

    return fn, [((b, h, d), dtype), ((n_pages, ps, cp), dtype),
                ((n_pages, ps, cp), dtype), ((b, maxp), jnp.int32),
                ((b,), jnp.int32)]


def _paged_ring(b, h, kv, d, n_pages, ring, ps, dtype):
    """A window layer's call: the table is a ring of ``ring`` pages and each
    sequence names its first live key."""
    cp = -(-kv * d // 128) * 128
    assert pk._paged_kernel_takes(d, ps, cp, dtype, h // kv, b, ring)

    def fn(q, kp, vp, tables, lengths, starts):
        return pk.paged_attention(q, kp, vp, tables, lengths, kv_heads=kv,
                                  starts=starts)

    return fn, [((b, h, d), dtype), ((n_pages, ps, cp), dtype),
                ((n_pages, ps, cp), dtype), ((b, ring), jnp.int32),
                ((b,), jnp.int32), ((b,), jnp.int32)]


def _prompt(l, h, kv, d, window, dtype):
    """A whole prompt's attention through jax's splash-attention kernel:
    the triangle of a full layer, or a window layer's band."""
    assert pk._prompt_kernel_takes(l, d)

    def fn(q, k, v):
        return pk.prompt_attention(q, k, v, d ** -0.5, window)

    return fn, [((l, h, d), dtype), ((l, kv, d), dtype), ((l, kv, d), dtype)]


def _latent(b, h, rank, rot, n_pages, maxp, ps, dtype):
    """The latent (MLA) decode kernel on a pool of compressed rows: (pages,
    page_size, Cp) with Cp = rank + rot rounded up to the lane tile."""
    cp = -(-(rank + rot) // 128) * 128
    assert pk._latent_kernel_takes(ps, cp, dtype)

    def fn(q, pages, tables, lengths):
        return pk.paged_latent_attention(q, pages, tables, lengths, 0.14468,
                                         rank)

    return fn, [((b, h, rank + rot), dtype), ((n_pages, ps, cp), dtype),
                ((b, maxp), jnp.int32), ((b,), jnp.int32)]


def _moe(n, c, f, e, k, dtype, held=None, groups=1, kept=1, **router):
    """The drop-free expert layer (routing in XLA, the grouped product a
    Mosaic kernel) over n rows."""
    from mxnet_tpu.ops.contrib import moe_tile_rows, sigmoid_topk_moe

    held = held or e
    assert pk._moe_kernel_takes(moe_tile_rows(n * k, held), c, f, dtype)

    def fn(x, wg, bias, w1, w3, w2, valid):
        return sigmoid_topk_moe(x, wg, bias, w1, w3, w2, k=k, valid=valid,
                                n_group=groups, topk_group=kept, **router)

    return fn, [((n, c), dtype), ((e, c), dtype), ((e,), dtype),
                ((held, f, c), dtype), ((held, f, c), dtype),
                ((held, f, c), dtype), ((n,), jnp.bool_)]


bf16, f32 = jnp.bfloat16, jnp.float32

CASES = {
    # BERT-base, batch 8: (B*heads, L, Dh)
    "flash-bert-bf16": lambda: _flash((96, 512, 64), bf16, False),
    "flash-bert-bf16-causal": lambda: _flash((96, 512, 64), bf16, True),
    # word-LM 2x650, bptt 35, batch 32
    "lstm-wordlm-bf16": lambda: _lstm(35, 32, 650, bf16),
    "lstm-wordlm-f32": lambda: _lstm(35, 32, 650, f32),
    # the gate's accept boundary (12 MB budget vs Mosaic's 16 MB scoped
    # VMEM limit): the largest H admitted at a small and a large batch
    "lstm-gate-edge-bf16-b32":
        lambda: _lstm(4, 32, _largest_lstm_h(32, 2), bf16),
    "lstm-gate-edge-bf16-b256":
        lambda: _lstm(4, 256, _largest_lstm_h(256, 2), bf16),
    "lstm-gate-edge-f32-b32":
        lambda: _lstm(4, 32, _largest_lstm_h(32, 4), f32),
    "lstm-gate-edge-f32-b256":
        lambda: _lstm(4, 256, _largest_lstm_h(256, 4), f32),
    # serve_decode geometry (GPT-2-small heads, f32 KV as the engine
    # defaults): a page is (16, 768), whole (8, 128) tiles
    "paged-gpt2small-f32": lambda: _paged(8, 12, 64, 512, 32, 16, f32),
    "paged-gpt2small-bf16": lambda: _paged(8, 12, 64, 512, 32, 16, bf16),
    # a head is a whole lane tile
    "paged-aligned-bf16": lambda: _paged(32, 16, 128, 1024, 64, 16, bf16),
    # a tiny model: 2 heads of 32 in one padded 128-lane row
    "paged-tiny-f32": lambda: _paged(4, 2, 32, 64, 8, 8, f32),
    # a head wider than a lane tile: its segment matrix is (256, 256) ones
    "paged-head256-f32": lambda: _paged(8, 4, 256, 256, 16, 16, f32),
    # the two GPT-2 cells' top decode buckets at their tables' lengths:
    # gpt2s_chat_open 64 x 44 pages, gpt2s_docs_closed 32 x 61
    "paged-gpt2s-chat-f32": lambda: _paged(64, 12, 64, 3072, 44, 16, f32),
    "paged-gpt2s-docs-f32": lambda: _paged(32, 12, 64, 3072, 61, 16, f32),
    # chipbench/configs/lfm2_24b_a2b.json: 32 query heads on 8 KV heads of
    # 64, a bfloat16 pool row of 512 lanes, the cell's top decode bucket
    "paged-gqa-lfm2-bf16":
        lambda: _paged_gqa(128, 32, 8, 64, 8192, 64, 16, bf16),
    "paged-gqa-tiny-f32": lambda: _paged_gqa(4, 4, 2, 32, 64, 8, 8, f32),
    # the same configuration's expert layer: 64 experts of 1536, 4 a token;
    # a decode batch of 128 (tiles of 16 rows) and a prompt bucket of 512
    # (tiles of 64)
    "moe-lfm2-decode128-bf16": lambda: _moe(128, 2048, 1536, 64, 4, bf16),
    "moe-lfm2-prefill512-bf16": lambda: _moe(512, 2048, 1536, 64, 4, bf16),
    "moe-small-f32": lambda: _moe(32, 256, 256, 8, 2, f32),
    # GigaChat3.1-702B-A36B's published widths: 64 heads over one cached row
    # of 512 + 64 lanes (640 in the pool), pages of 128 tokens, 38 a sequence
    # (4096 + 768 positions), a decode batch of 128; and a tiny float32 pool
    "latent-gigachat3-bf16":
        lambda: _latent(128, 64, 512, 64, 4096, 38, 128, bf16),
    "latent-tiny-f32": lambda: _latent(4, 4, 32, 8, 64, 3, 8, f32),
    # the same model's expert layer: 16 held of 256 experts of 2048
    # at width 7168, 8 a token in 4 of 8 groups; a decode batch of 128 and
    # the 4096 prompt bucket
    "moe-gigachat3-decode128-bf16":
        lambda: _moe(128, 7168, 2048, 256, 8, bf16, 16, 8, 4),
    "moe-gigachat3-prefill4096-bf16":
        lambda: _moe(4096, 7168, 2048, 256, 8, bf16, 16, 8, 4),
    # chipbench/configs/smallthinker_21b_a3b.json: 28 query heads on 4 KV
    # heads of 128 (7 a KV head), pages of 64 tokens, the cell's top decode
    # bucket; a full layer's table of 204 pages and a window layer's ring of
    # 65 with its first live keys
    "paged-gqa-smallthinker-full-bf16":
        lambda: _paged_gqa(64, 28, 4, 128, 9216, 204, 64, bf16),
    "paged-ring-smallthinker-bf16":
        lambda: _paged_ring(64, 28, 4, 128, 3840, 65, 64, bf16),
    "paged-ring-tiny-f32": lambda: _paged_ring(4, 4, 2, 32, 64, 3, 8, f32),
    # the same configuration's expert layer: 64 ReGLU experts of 768 at
    # width 2560, 6 a token by a softmax over the selected; a decode batch
    # of 64 and the 8192 prompt bucket
    # and its prompts' attention at the 8192 bucket: 28 / 4 heads of 128
    "prompt-smallthinker-full-bf16":
        lambda: _prompt(8192, 28, 4, 128, None, bf16),
    "prompt-smallthinker-window-bf16":
        lambda: _prompt(8192, 28, 4, 128, 4096, bf16),
    "moe-smallthinker-decode64-bf16":
        lambda: _moe(64, 2560, 768, 64, 6, bf16, scores="softmax_selected",
                     activation="relu"),
    "moe-smallthinker-prefill8192-bf16":
        lambda: _moe(8192, 2560, 768, 64, 6, bf16, scores="softmax_selected",
                     activation="relu"),
}


KERNEL_NAMES = {
    "flash": ("flash_attention_fwd", "flash_attention_bwd_dq",
              "flash_attention_bwd_dkv"),
    "lstm": ("lstm_layer_fwd", "lstm_layer_bwd"),
    "paged": ("paged_attention_decode",),
    "latent": ("paged_latent_attention_decode",),
    "moe": ("moe_grouped_ffn",),
    "prompt": ("splash_mqa_fwd_no_residuals",),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(case, v5e, monkeypatch):
    monkeypatch.setattr(pk, "_use_interpret", lambda: False)
    fn, arg_shapes = CASES[case]()
    args = [jax.ShapeDtypeStruct(s, d, sharding=v5e) for s, d in arg_shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, \
        "no Mosaic kernel in the compiled program — the jnp path was taken"
    # every kernel carries a fixed name into the program (and so into the
    # device trace), whatever Python function its body happens to be
    # (an instruction reads `%paged_attention_decode.3`, or, under
    # autodiff, `%transpose_jvp_lstm_layer_bwd__.1`)
    for name in KERNEL_NAMES[case.split("-")[0]]:
        assert re.search(r"%%\w*%s_*\.\d+ = " % name, text), name


# ---------------------------------------------------------------------------
# the paged decode kernel's body: what a program's set-up pays for it is the
# body's size (traced, lowered and compiled once a decode bucket), so the
# body may not grow with the pages a block holds. No clock in here:
# tools/paged_lower_cost.py has the seconds
# ---------------------------------------------------------------------------

def _lower_cost_tool():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "paged_lower_cost", os.path.join(os.path.dirname(__file__), "..",
                                         "tools", "paged_lower_cost.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    return tool


# equations of the kernel this one replaced (PR 46's tree: a page a grid
# step, the lanes summed by a butterfly of rotations or, grouped-query, by
# one MXU product a page), counted by the same function at the same shapes
PARENT_EQUATIONS = {"gpt2s_chat_open": 486, "gpt2s_docs_closed": 486,
                    "lfm2_reason_closed": 586}


@pytest.mark.parametrize("cell", sorted(PARENT_EQUATIONS))
def test_paged_kernel_body_is_no_larger_than_the_one_it_replaced(
        cell, monkeypatch):
    monkeypatch.setattr(pk, "_use_interpret", lambda: False)
    monkeypatch.setenv("MXTPU_PALLAS_DECODE", "1")
    tool = _lower_cost_tool()
    kv, bucket = tool.CELLS[cell][1], tool.CELLS[cell][-1][-1]
    count = tool.kernel_equations(tool.stacked(pk, kv),
                                  tool.shapes(cell, bucket, 1))
    assert 0 < count <= 1.5 * PARENT_EQUATIONS[cell], count


def test_paged_kernel_body_does_not_grow_with_the_block(monkeypatch):
    """Blocks of 4, 8 and 16 pages (a short table; pages of 16; pages of 8)
    trace to bodies that differ by a few equations a page at most: the
    block's rows are one array whatever their count, not a body a page."""
    monkeypatch.setattr(pk, "_use_interpret", lambda: False)
    monkeypatch.setenv("MXTPU_PALLAS_DECODE", "1")
    tool = _lower_cost_tool()
    counts = {}
    for ps, maxp in ((16, 4), (16, 44), (8, 44)):
        per_step = pk.paged_pages_per_step(ps, maxp)
        args = [jax.ShapeDtypeStruct(s, d) for s, d in (
            ((16, 12, 64), f32), ((512, ps, 768), f32), ((512, ps, 768), f32),
            ((16, maxp), jnp.int32), ((16,), jnp.int32))]
        counts[per_step] = tool.kernel_equations(pk.paged_attention, args)
    assert sorted(counts) == [4, 8, 16], counts
    for a in counts:
        for b in counts:
            assert abs(counts[a] - counts[b]) <= 4 * abs(a - b), counts


# ---------------------------------------------------------------------------
# a training batch norm has one lowering, XLA's own (ops/nn.py `_bn_act`):
# no Mosaic kernel and no lane padding of the activation around one
# ---------------------------------------------------------------------------

def _conv_bn_grad(form):
    """value_and_grad of loss(x, w, gamma, beta, res) of a 1x1 conv ->
    training BN(+add)(+ReLU), channels last: the convolution in front, as
    in a net, so that XLA has neighbours to fuse the batch norm into. A
    new function a call: jax caches traces by the function."""
    from mxnet_tpu.ops import nn as N

    def loss(x, w, gamma, beta, res):
        y = jax.lax.conv_general_dilated(
            x, w, (1, 1), "VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        c = y.shape[-1]
        out, mm, mv = N._bn_act(
            y, res if form == "bn_add_relu" else None, gamma, beta,
            jnp.zeros((c,), f32), jnp.ones((c,), f32), 1e-5, 0.9, False,
            False, -1, None if form == "bn" else "relu", True)
        return jnp.sum(out.astype(f32)) + jnp.sum(mm) + jnp.sum(mv)

    return jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4))


def _conv_bn_args(shape, cin, dtype, sharding=None):
    c = shape[-1]
    return [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in (
        (shape[:-1] + (cin,), dtype), ((1, 1, cin, c), dtype),
        ((c,), f32), ((c,), f32), (shape, dtype))]


# the six activations the conv-epilogue kernel's compile cases used
# (ResNet-50 NHWC stages at batch 32, and the widest channel counts the
# kernel's VMEM rule admitted), with the width of the 1x1 conv in front
BN_CASES = {
    "56x56x256-bf16-bn_add_relu": ((32, 56, 56, 256), 64, bf16),
    "112x112x64-bf16-bn_relu": ((32, 112, 112, 64), 16, bf16),
    "7x7x2048-bf16-bn_add_relu": ((32, 7, 7, 2048), 512, bf16),
    "56x56x64-f32-bn_relu": ((32, 56, 56, 64), 256, f32),
    "1x1x16256-bf16-bn": ((64, 1, 1, 16256), 128, bf16),
    "1x1x768-f32-bn_add_relu": ((64, 1, 1, 768), 128, f32),
}


def _channel_widening_pads(text, c):
    """Instructions of a compiled program that pad a `c`-channel array
    to more channels (HLO shapes and `padding=` are in logical order:
    the channel is the last dimension)."""
    found = []
    for line in text.splitlines():
        m = re.search(r" = \w+\[([0-9,]*)\][^ ]* pad\(.*padding=([0-9_x-]+)",
                      line)
        if not m:
            continue
        dims = [int(d) for d in m.group(1).split(",") if d]
        last = [int(v) for v in m.group(2).split("x")[-1].split("_")[:2]]
        if dims and dims[-1] > c and dims[-1] - sum(last) == c:
            found.append(line.strip()[:160])
    return found


@pytest.mark.parametrize("case", sorted(BN_CASES))
def test_training_batch_norm_compiles_to_xla_alone(case, v5e):
    shape, cin, dtype = BN_CASES[case]
    form = case.rsplit("-", 1)[1]
    args = _conv_bn_args(shape, cin, dtype, v5e)
    text = jax.jit(_conv_bn_grad(form)).lower(*args).compile().as_text()
    assert "tpu_custom_call" not in text
    assert "convolution" in text
    assert not _channel_widening_pads(text, shape[-1])


def _primitives(jaxpr, out=None):
    """Multiset of primitive names of a jaxpr, sub-jaxprs included."""
    out = {} if out is None else out
    for eqn in jaxpr.eqns:
        out[eqn.primitive.name] = out.get(eqn.primitive.name, 0) + 1
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _primitives(sub, out)
    return out


@pytest.mark.parametrize("form", ["bn", "bn_relu", "bn_add_relu"])
def test_batch_norm_lowering_reads_no_device_count_or_backend(
        form, v5e, monkeypatch):
    """One configuration, one program: the training batch norm traced
    where jax reports one TPU device holds the primitives of the one
    traced on the tests' 8-device CPU host, and lowered for the
    described one-device v5e it holds no custom call."""
    shape, cin, dtype = (8, 14, 14, 256), 64, bf16

    def traced():
        return _primitives(jax.make_jaxpr(_conv_bn_grad(form))(
            *_conv_bn_args(shape, cin, dtype)).jaxpr)

    here = traced()
    assert jax.device_count() > 1 and jax.default_backend() == "cpu"
    monkeypatch.setattr(jax, "device_count", lambda *a: 1)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(pk, "_use_interpret", lambda: False)
    assert traced() == here
    assert "pallas_call" not in here
    lowered = jax.jit(_conv_bn_grad(form)).lower(
        *_conv_bn_args(shape, cin, dtype, v5e))
    assert "custom_call" not in lowered.as_text()


# ---------------------------------------------------------------------------
# the decode engine's own programs at the benchmark's geometry: the KV pool
# is donated, updated in place and read where it lies
# ---------------------------------------------------------------------------

def _gpt2s_engine(traffic):
    """A `TransformerLMEngine` at chipbench/configs/gpt2_small.json's sizes
    with a cell's buckets (weights of zeros: only shapes are lowered)."""
    import json
    import os

    import numpy as np

    from mxnet_tpu.serving import TransformerLMEngine

    root = os.path.join(os.path.dirname(__file__), "..", "chipbench")
    with open(os.path.join(root, "configs", "gpt2_small.json")) as f:
        config = json.load(f)
    with open(os.path.join(root, "traffic", traffic + ".json")) as f:
        cell = json.load(f)["engine"]
    sizes = config["sizes"]
    u, hid = sizes["units"], sizes["hidden_size"]

    def z(*shape):
        return np.zeros(shape, np.float32)

    def dense(o, i):
        return {"w": z(o, i), "b": z(o)}

    def norm():
        return {"g": z(u), "b": z(u)}

    params = {"word": z(sizes["vocab_size"], u),
              "pos": z(sizes["max_length"], u), "embed_norm": norm(),
              "layers": [{"q": dense(u, u), "k": dense(u, u),
                          "v": dense(u, u), "o": dense(u, u),
                          "attn_norm": norm(), "ffn1": dense(hid, u),
                          "ffn2": dense(u, hid), "ffn_norm": norm()}
                         for _ in range(sizes["num_layers"])]}
    return TransformerLMEngine(params=params, config=sizes,
                               **dict(config["engine"], **cell))


def _engine_program(engine, kind, bucket):
    """(jitted program, example arguments) of one engine executable."""
    import numpy as np

    from mxnet_tpu import random as mxrandom

    def i32(*shape):
        return np.zeros(shape, np.int32)

    def f32s(*shape):
        return np.zeros(shape, np.float32)

    maxp, key = engine.max_pages_per_seq, mxrandom.next_key()
    if kind == "lm_decode":
        b = bucket
        return engine._build_decode(b)(), (
            engine._params, engine._kv, i32(b), i32(b), i32(b), i32(b),
            i32(b, maxp), i32(b), f32s(b), i32(b), f32s(b), key)
    return engine._build_prefill(bucket)(), (
        engine._params, engine._kv, i32(bucket), np.int32(0), i32(maxp),
        f32s(1), i32(1), f32s(1), key)


ENGINE_PROGRAMS = pytest.mark.parametrize("traffic,kind,bucket", [
    ("gpt2s_chat_open", "lm_decode", 64),
    ("gpt2s_docs_closed", "lm_prefill", 1024),
], ids=["lm_decode-b64", "lm_prefill-l1024"])
_COMPILED = {}      # one compile (half a minute) a program, for every test


def _compiled_engine_program(traffic, kind, bucket, v5e, monkeypatch):
    """(the engine's pool bytes, pages and layers; the executable compiled
    for the described v5e). The engine itself, 4 GB of zeros, is let go."""
    if (traffic, kind, bucket) not in _COMPILED:
        monkeypatch.setattr(pk, "_use_interpret", lambda: False)
        engine = _gpt2s_engine(traffic)
        fn, args = _engine_program(engine, kind, bucket)
        shapes = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(
                jnp.shape(a), jnp.asarray(a).dtype
                if not hasattr(a, "dtype") else a.dtype, sharding=v5e),
            args)
        _COMPILED[traffic, kind, bucket] = (
            (engine.kv_bytes(), engine.num_pages, engine.num_layers),
            fn.lower(*shapes).compile())
    return _COMPILED[traffic, kind, bucket]


@ENGINE_PROGRAMS
def test_engine_program_keeps_the_pool_in_place(traffic, kind, bucket, v5e,
                                                monkeypatch):
    (pool, num_pages, num_layers), compiled = _compiled_engine_program(
        traffic, kind, bucket, v5e, monkeypatch)
    assert pool == 2 * 12 * 3072 * 16 * 768 * 4
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 0.05 * pool, mem.temp_size_in_bytes
    assert mem.alias_size_in_bytes >= pool, mem.alias_size_in_bytes
    text = compiled.as_text()
    if kind == "lm_decode":
        assert re.search(r"%\w*paged_attention_decode_*\.\d+ = ", text)
    # no instruction moves an array that holds the page count: the 24
    # leaves are scattered into and streamed from, nothing else
    pages = str(num_pages)
    moved = [line.strip()[:120] for line in text.splitlines()
             if re.search(r" (copy|pad|slice|transpose|dynamic-slice)\(",
                          line)
             and pages in re.findall(
                 r"\d+", line.split(" = ", 1)[1].split("(", 1)[0])]
    assert not moved, moved
    assert len(re.findall(r" scatter\(", text)) == 2 * num_layers


@ENGINE_PROGRAMS
def test_engine_program_sorts_only_where_a_row_filters(traffic, kind, bucket,
                                                       v5e, monkeypatch):
    """The sampler branches on the device inside the bucket's one
    executable: the entry computation holds a `conditional` and no `sort`;
    the one sort of the vocabulary there is lies in a branch computation,
    which a batch of greedy rows never enters."""
    _, compiled = _compiled_engine_program(traffic, kind, bucket, v5e,
                                           monkeypatch)
    text = compiled.as_text()
    bodies, entry = {}, None
    for line in text.splitlines():
        head = re.match(r"(ENTRY )?%?([\w.-]+) \(.*\{$", line)
        if head:
            name = head.group(2)
            bodies[name] = []
            entry = name if head.group(1) else entry
        elif line.startswith(" "):
            bodies[name].append(line)
    sorts = {name: sum(" sort(" in line for line in body)
             for name, body in bodies.items()}
    assert sorts[entry] == 0
    assert any(" conditional(" in line for line in bodies[entry])
    assert sum(sorts.values()) <= 1, sorts
    branches = set(re.findall(r"%([\w.-]+)", " ".join(
        re.findall(r"branch_computations=\{([^}]*)\}", text))))
    assert {name for name, n in sorts.items() if n} <= branches
