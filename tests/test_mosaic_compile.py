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


def _largest_epilogue_c(itemsize):
    c = 128
    while pk.conv_epilogue_fits(c + 128, itemsize):
        c += 128
    return c


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


def _epilogue(shape, dtype):
    c = shape[-1]

    def loss(x, gamma, beta, res):
        out, _, _ = pk.conv_epilogue(x, gamma, beta, residual=res,
                                     relu=True)
        return jnp.sum(out.astype(jnp.float32))

    return (jax.value_and_grad(loss, argnums=(0, 1, 2, 3)),
            [(shape, dtype), ((c,), jnp.float32), ((c,), jnp.float32),
             (shape, dtype)])


def _paged(b, h, d, n_pages, maxp, ps, dtype):
    def fwd(q, k_pages, v_pages, tables, lengths):
        run = pk._paged_compiled((b, h, d, n_pages, maxp, ps,
                                  str(jnp.dtype(dtype)), d ** -0.5, False))
        return run(q, k_pages, v_pages, tables, lengths)

    return fwd, [((b, h, d), dtype), ((n_pages, h, ps, d), dtype),
                 ((n_pages, h, ps, d), dtype), ((b, maxp), jnp.int32),
                 ((b,), jnp.int32)]


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
    # ResNet-50 NHWC stages at batch 32
    "epilogue-56x56x256-bf16": lambda: _epilogue((32, 56, 56, 256), bf16),
    "epilogue-112x112x64-bf16": lambda: _epilogue((32, 112, 112, 64), bf16),
    "epilogue-7x7x2048-bf16": lambda: _epilogue((32, 7, 7, 2048), bf16),
    "epilogue-56x56x64-f32": lambda: _epilogue((32, 56, 56, 64), f32),
    "epilogue-gate-edge-bf16":
        lambda: _epilogue((64, _largest_epilogue_c(2)), bf16),
    "epilogue-gate-edge-f32":
        lambda: _epilogue((64, _largest_epilogue_c(4)), f32),
    # serve_decode geometry (GPT-2-small heads, f32 KV as the engine
    # defaults): 12 x 64 is off the (8, 128) grid -> padded-copy branch
    "paged-gpt2small-f32": lambda: _paged(8, 12, 64, 512, 32, 16, f32),
    "paged-gpt2small-bf16": lambda: _paged(8, 12, 64, 512, 32, 16, bf16),
    # aligned geometry: the page pool feeds the kernel with no copy
    "paged-aligned-bf16": lambda: _paged(32, 16, 128, 1024, 64, 16, bf16),
}


KERNEL_NAMES = {
    "flash": ("flash_attention_fwd", "flash_attention_bwd_dq",
              "flash_attention_bwd_dkv"),
    "lstm": ("lstm_layer_fwd", "lstm_layer_bwd"),
    "epilogue": ("conv_epilogue_stats", "conv_epilogue_apply",
                 "conv_epilogue_bwd_reduce", "conv_epilogue_bwd_dx"),
    "paged": ("paged_attention_decode",),
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
    # autodiff, `%transpose_jvp_conv_epilogue_bwd_dx__.1`)
    for name in KERNEL_NAMES[case.split("-")[0]]:
        assert re.search(r"%%\w*%s_*\.\d+ = " % name, text), name
