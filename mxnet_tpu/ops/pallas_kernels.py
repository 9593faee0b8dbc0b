"""Pallas TPU kernels.

The reference ships hand-written CUDA where library kernels fall short
(src/operator/contrib/transformer.cu, fused RNN rnn-inl.h); the TPU-native
equivalent is Pallas. This module holds the kernels where XLA fusion alone
is insufficient — flash attention first: XLA materializes the (Lq, Lk)
score matrix in HBM, while the flash kernel streams K/V blocks through VMEM
with an online softmax, keeping the working set on-chip (HBM traffic
O(L·D) instead of O(L²)).

On non-TPU backends the same kernels run in interpret mode, so tests and
CPU development use one code path (the strategy SURVEY §4 prescribes for
cross-backend consistency).

Backward: Pallas kernels too (flash-attention backward): the forward saves
only O and the per-row logsumexp; backward recomputes P blockwise in VMEM —
one kernel accumulating dQ over k-blocks, one accumulating dK/dV over
q-blocks — so the backward pass has the same O(L·D) HBM traffic as forward
instead of materializing the (Lq, Lk) probability matrix.
"""
from __future__ import annotations

import functools

import numpy as _np

__all__ = ["flash_attention", "lstm_layer", "paged_attention",
           "paged_attention_reference", "paged_latent_attention",
           "paged_latent_attention_reference", "moe_grouped_ffn",
           "moe_grouped_ffn_reference"]

_NEG_INF = -1e30


def _use_interpret():
    import jax

    return jax.default_backend() != "tpu"


def _decode_kernels_on():
    """The decode kernels' gate, MXTPU_PALLAS_DECODE: `auto` = the kernels
    on a TPU and the jnp paths elsewhere, `1` the kernels everywhere
    (interpret mode off the chip: the parity tests), `0` the jnp paths."""
    from .. import env as _env

    gate = (_env.raw("MXTPU_PALLAS_DECODE") or "auto").strip().lower()
    return gate != "0" and not (gate == "auto" and _use_interpret())


def _attention_reference(q, k, v, causal, sm_scale):
    """Plain jnp attention (the vjp source for backward; also the numerics
    oracle in tests)."""
    import jax.numpy as jnp

    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * sm_scale
    if causal:
        lq, lk = s.shape[-2], s.shape[-1]
        row = jnp.arange(lq)[:, None]
        col = jnp.arange(lk)[None, :]
        s = jnp.where(col <= row, s, _NEG_INF)
    p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    p = p / jnp.sum(p, axis=-1, keepdims=True)
    return jnp.einsum("bqk,bkd->bqd", p, v.astype(jnp.float32)).astype(q.dtype)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, sm_scale, causal,
                lq, lk, block_q, block_k, n_kblocks):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    iq = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * sm_scale          # (block_q, D)
    d = q.shape[-1]

    row_ids = iq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)

    def body(i, carry):
        m, l, acc = carry
        k = k_ref[0, pl.ds(i * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[0, pl.ds(i * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        col_ids = i * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = col_ids < lk
        if causal:
            mask = jnp.logical_and(mask, col_ids <= row_ids)
        s = jnp.where(mask, s, _NEG_INF)
        new_m = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m - new_m)
        p = jnp.exp(s - new_m)
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return new_m, l, acc

    m0 = jnp.full((block_q, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    acc0 = jnp.zeros((block_q, d), jnp.float32)
    # causal: k-blocks strictly above this q-block's diagonal contribute
    # nothing — skip them (dynamic fori bound lowers to while_loop)
    hi = n_kblocks if not causal else jnp.minimum(
        n_kblocks, ((iq + 1) * block_q + block_k - 1) // block_k)
    m, l, acc = jax.lax.fori_loop(0, hi, body, (m0, l0, acc0))
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
    # logsumexp per q row — the only softmax state backward needs. Stored
    # (bh, 8, lq): TPU blocks need sublane-dim multiples of 8, so the row
    # vector is broadcast across 8 sublanes rather than stored (bh, lq).
    lse = (m + jnp.log(jnp.maximum(l, 1e-30)))[:, 0]
    lse_ref[0] = jnp.broadcast_to(lse[None, :], (8, lse.shape[0]))


@functools.lru_cache(maxsize=256)
def _fwd_compiled(shape_key):
    (bh, lq, lk, d, dtype, causal, sm_scale, interpret) = shape_key
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    block_q = min(128, lq)
    block_k = min(128, lk)
    n_q = -(-lq // block_q)
    n_k = -(-lk // block_k)
    lq_pad, lk_pad = n_q * block_q, n_k * block_k

    kernel = functools.partial(_fwd_kernel, sm_scale=sm_scale, causal=causal,
                               lq=lq, lk=lk, block_q=block_q, block_k=block_k,
                               n_kblocks=n_k)

    call = pl.pallas_call(
        kernel,
        name="flash_attention_fwd",
        out_shape=(jax.ShapeDtypeStruct((bh, lq_pad, d), _np.dtype(dtype)),
                   jax.ShapeDtypeStruct((bh, 8, lq_pad), _np.float32)),
        grid=(bh, n_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, lk_pad, d), lambda b, i: (b, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, lk_pad, d), lambda b, i: (b, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=(pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0),
                                memory_space=pltpu.VMEM),
                   pl.BlockSpec((1, 8, block_q), lambda b, i: (b, 0, i),
                                memory_space=pltpu.VMEM)),
        interpret=interpret,
    )

    def run(q, k, v):
        qp = jnp.pad(q, ((0, 0), (0, lq_pad - lq), (0, 0)))
        kp = jnp.pad(k, ((0, 0), (0, lk_pad - lk), (0, 0)))
        vp = jnp.pad(v, ((0, 0), (0, lk_pad - lk), (0, 0)))
        o, lse = call(qp, kp, vp)
        return o[:, :lq, :], lse[:, 0, :lq]

    return run


def _flash_fwd(q, k, v, causal, sm_scale):
    bh, lq, d = q.shape
    lk = k.shape[1]
    run = _fwd_compiled((bh, lq, lk, d, str(q.dtype), bool(causal),
                         float(sm_scale), _use_interpret()))
    return run(q, k, v)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, *,
                   sm_scale, causal, lk, block_q, block_k, n_kblocks):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    iq = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32)                     # (bq, d)
    do = do_ref[0].astype(jnp.float32)                   # (bq, d)
    lse = lse_ref[0, 0][:, None]                         # (bq, 1)
    delta = delta_ref[0, 0][:, None]                     # (bq, 1)
    d = q.shape[-1]
    row_ids = iq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)

    def body(i, acc):
        k = k_ref[0, pl.ds(i * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[0, pl.ds(i * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        col_ids = i * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = col_ids < lk
        if causal:
            mask = jnp.logical_and(mask, col_ids <= row_ids)
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * sm_scale
        return acc + jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)

    acc0 = jnp.zeros((block_q, d), jnp.float32)
    hi = n_kblocks if not causal else jnp.minimum(
        n_kblocks, ((iq + 1) * block_q + block_k - 1) // block_k)
    dq_ref[0] = jax.lax.fori_loop(0, hi, body, acc0).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, *, sm_scale, causal, lq, lk, block_q,
                    block_k, n_qblocks):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    ik = pl.program_id(1)
    k = k_ref[0].astype(jnp.float32)                     # (bk, d)
    v = v_ref[0].astype(jnp.float32)                     # (bk, d)
    d = k.shape[-1]
    col_ids = ik * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)

    def body(i, carry):
        dk, dv = carry
        q = q_ref[0, pl.ds(i * block_q, block_q), :].astype(jnp.float32)
        do = do_ref[0, pl.ds(i * block_q, block_q), :].astype(jnp.float32)
        lse = lse_ref[0, 0, pl.ds(i * block_q, block_q)][:, None]
        delta = delta_ref[0, 0, pl.ds(i * block_q, block_q)][:, None]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        row_ids = i * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        # mask padded q rows too: their lse is garbage and exp could
        # overflow — dO=0 alone doesn't save p itself
        mask = jnp.logical_and(col_ids < lk, row_ids < lq)
        if causal:
            mask = jnp.logical_and(mask, col_ids <= row_ids)
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)
        dv = dv + jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * sm_scale
        dk = dk + jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
        return dk, dv

    z = jnp.zeros((block_k, d), jnp.float32)
    lo = 0 if not causal else (ik * block_k) // block_q
    dk, dv = jax.lax.fori_loop(lo, n_qblocks, body, (z, z))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


@functools.lru_cache(maxsize=256)
def _bwd_compiled(shape_key):
    (bh, lq, lk, d, dtype, causal, sm_scale, interpret) = shape_key
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    block_q = min(128, lq)
    block_k = min(128, lk)
    n_q = -(-lq // block_q)
    n_k = -(-lk // block_k)
    lq_pad, lk_pad = n_q * block_q, n_k * block_k

    dq_call = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, sm_scale=sm_scale, causal=causal,
                          lk=lk, block_q=block_q, block_k=block_k,
                          n_kblocks=n_k),
        name="flash_attention_bwd_dq",
        out_shape=jax.ShapeDtypeStruct((bh, lq_pad, d), _np.dtype(dtype)),
        grid=(bh, n_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0),
                         memory_space=pltpu.VMEM),     # q
            pl.BlockSpec((1, lk_pad, d), lambda b, i: (b, 0, 0),
                         memory_space=pltpu.VMEM),     # k
            pl.BlockSpec((1, lk_pad, d), lambda b, i: (b, 0, 0),
                         memory_space=pltpu.VMEM),     # v
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0),
                         memory_space=pltpu.VMEM),     # do
            pl.BlockSpec((1, 8, block_q), lambda b, i: (b, 0, i),
                         memory_space=pltpu.VMEM),     # lse
            pl.BlockSpec((1, 8, block_q), lambda b, i: (b, 0, i),
                         memory_space=pltpu.VMEM),     # delta
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0),
                               memory_space=pltpu.VMEM),
        interpret=interpret,
    )

    dkv_call = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, sm_scale=sm_scale, causal=causal,
                          lq=lq, lk=lk, block_q=block_q, block_k=block_k,
                          n_qblocks=n_q),
        name="flash_attention_bwd_dkv",
        out_shape=(jax.ShapeDtypeStruct((bh, lk_pad, d), _np.dtype(dtype)),
                   jax.ShapeDtypeStruct((bh, lk_pad, d), _np.dtype(dtype))),
        grid=(bh, n_k),
        in_specs=[
            pl.BlockSpec((1, lq_pad, d), lambda b, i: (b, 0, 0),
                         memory_space=pltpu.VMEM),     # q
            pl.BlockSpec((1, block_k, d), lambda b, i: (b, i, 0),
                         memory_space=pltpu.VMEM),     # k
            pl.BlockSpec((1, block_k, d), lambda b, i: (b, i, 0),
                         memory_space=pltpu.VMEM),     # v
            pl.BlockSpec((1, lq_pad, d), lambda b, i: (b, 0, 0),
                         memory_space=pltpu.VMEM),     # do
            pl.BlockSpec((1, 8, lq_pad), lambda b, i: (b, 0, 0),
                         memory_space=pltpu.VMEM),     # lse
            pl.BlockSpec((1, 8, lq_pad), lambda b, i: (b, 0, 0),
                         memory_space=pltpu.VMEM),     # delta
        ],
        out_specs=(pl.BlockSpec((1, block_k, d), lambda b, i: (b, i, 0),
                                memory_space=pltpu.VMEM),
                   pl.BlockSpec((1, block_k, d), lambda b, i: (b, i, 0),
                                memory_space=pltpu.VMEM)),
        interpret=interpret,
    )

    def run(q, k, v, o, lse, do):
        delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                        axis=-1)                        # (bh, lq)
        qp = jnp.pad(q, ((0, 0), (0, lq_pad - lq), (0, 0)))
        kp = jnp.pad(k, ((0, 0), (0, lk_pad - lk), (0, 0)))
        vp = jnp.pad(v, ((0, 0), (0, lk_pad - lk), (0, 0)))
        dop = jnp.pad(do, ((0, 0), (0, lq_pad - lq), (0, 0)))
        lsep = jnp.broadcast_to(
            jnp.pad(lse, ((0, 0), (0, lq_pad - lq)))[:, None, :],
            (bh, 8, lq_pad))
        deltap = jnp.broadcast_to(
            jnp.pad(delta, ((0, 0), (0, lq_pad - lq)))[:, None, :],
            (bh, 8, lq_pad))
        dq = dq_call(qp, kp, vp, dop, lsep, deltap)
        dk, dv = dkv_call(qp, kp, vp, dop, lsep, deltap)
        return (dq[:, :lq, :], dk[:, :lk, :], dv[:, :lk, :])

    return run


def flash_attention(q, k, v, causal=False, sm_scale=None):
    """Flash attention over (..., L, D) tensors (leading dims are batched).

    TPU-native replacement for attention assembled from the reference's
    primitive ops (batch_dot + softmax + batch_dot, e.g.
    src/operator/contrib/transformer.cc usage); same math, O(L·D) HBM
    traffic. Differentiable via recompute-vjp.
    """
    import jax
    import jax.numpy as jnp

    if sm_scale is None:
        sm_scale = 1.0 / float(_np.sqrt(q.shape[-1]))
    sm_scale = float(sm_scale)

    lead = q.shape[:-2]
    lq, d = q.shape[-2:]
    lk = k.shape[-2]
    qf = q.reshape((-1, lq, d))
    kf = k.reshape((-1, lk, d))
    vf = v.reshape((-1, lk, d))

    @jax.custom_vjp
    def attn(qf, kf, vf):
        return _flash_fwd(qf, kf, vf, causal, sm_scale)[0]

    def fwd(qf, kf, vf):
        o, lse = _flash_fwd(qf, kf, vf, causal, sm_scale)
        return o, (qf, kf, vf, o, lse)

    def bwd(res, g):
        qf, kf, vf, o, lse = res
        bh, lq_, d_ = qf.shape
        lk_ = kf.shape[1]
        run = _bwd_compiled((bh, lq_, lk_, d_, str(qf.dtype), bool(causal),
                             float(sm_scale), _use_interpret()))
        return run(qf, kf, vf, o, lse, g.astype(qf.dtype))

    attn.defvjp(fwd, bwd)
    return attn(qf, kf, vf).reshape(lead + (lq, d))


# ---------------------------------------------------------------------------
# Fused LSTM layer: the whole time loop in ONE kernel, recurrent weights
# resident in VMEM.
#
# TPU-native replacement for the reference's fused cuDNN RNN kernel
# (src/operator/rnn-inl.h:162, cudnn_rnn-inl.h). A lax.scan LSTM issues one
# tiny h2h matmul per timestep; at word-LM shapes (B=32, H=650) each step
# re-reads the 3.4 MB recurrent weight from HBM and leaves the MXU mostly
# idle. Here the grid is the time
# axis (sequential on TPU), w_hh stays in VMEM across all steps, and the
# h/c carries live in f32 VMEM scratch — per-step HBM traffic drops to the
# gx slice in + (y, c, gates) slices out.
#
# Backward is a second Pallas kernel running the time grid in reverse,
# producing per-step pre-activation gate grads (dgx); the weight gradient
# dW_hh = h_prevᵀ·dgx then falls out as ONE large MXU matmul outside the
# kernel instead of T tiny accumulations.
# ---------------------------------------------------------------------------


def lstm_layer_fits(b, h, itemsize):
    """Conservative VMEM budget check for the fused LSTM kernels: w_hhᵀ must
    stay resident plus double-buffered per-step blocks and the f32 carries.
    Budgets against max(forward, backward) per-step traffic — training runs
    BOTH kernels, and for bf16 the backward's per-step blocks are slightly
    larger (dy + gates + c_t + c_prev in, dgx out), so a forward-only check
    could admit a shape that then fails to compile in the backward pass.
    Callers fall back to the lax.scan path when this returns False (large-H
    models that fit fine under scan must not start failing to compile)."""
    hp = -(-h // 128) * 128
    bp = -(-b // 16) * 16
    resident = hp * 4 * hp * itemsize          # w_hhᵀ
    resident += 2 * bp * hp * 4                # f32 h/c (dh/dc) scratch
    fwd_step = bp * 4 * hp * itemsize * 2      # gx in + gates out
    fwd_step += bp * hp * (2 * itemsize + 4)   # ys out + c_all out (f32)
    bwd_step = bp * 4 * hp * itemsize * 2      # gates in + dgx out
    bwd_step += bp * hp * itemsize             # dy in
    bwd_step += 2 * bp * hp * 4                # c_t + c_{t-1} in (f32)
    per_step = max(fwd_step, bwd_step)
    return resident + 2 * per_step < 12 * 1024 * 1024


def _pad_gate_cols(a, h, hp, gates=4):
    """Pad each of the `gates` H-sized blocks along the last axis to Hp."""
    import jax.numpy as jnp

    if h == hp:
        return a
    pads = [(0, 0)] * (a.ndim - 1) + [(0, hp - h)]
    return jnp.concatenate(
        [jnp.pad(p, pads) for p in jnp.split(a, gates, axis=-1)], axis=-1)


def _lstm_fwd_kernel(gx_ref, wht_ref, h0_ref, c0_ref,
                     ys_ref, c_ref, gates_ref, h_scr, c_scr, *, hp):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    t = pl.program_id(0)

    @pl.when(t == 0)
    def _():
        h_scr[...] = h0_ref[...].astype(jnp.float32)
        c_scr[...] = c0_ref[...].astype(jnp.float32)

    h = h_scr[...]
    c = c_scr[...]
    # recurrent matmul in the input dtype (bf16 hits the MXU fast path);
    # carries stay f32 for accumulation accuracy
    g = gx_ref[0].astype(jnp.float32) + jax.lax.dot_general(
        h.astype(gx_ref.dtype), wht_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    i = jax.nn.sigmoid(g[:, :hp])
    f = jax.nn.sigmoid(g[:, hp:2 * hp])
    gg = jnp.tanh(g[:, 2 * hp:3 * hp])
    o = jax.nn.sigmoid(g[:, 3 * hp:])
    c_new = f * c + i * gg
    h_new = o * jnp.tanh(c_new)
    ys_ref[0] = h_new.astype(ys_ref.dtype)
    c_ref[0] = c_new
    gates_ref[0] = jnp.concatenate([i, f, gg, o], axis=-1).astype(
        gates_ref.dtype)
    h_scr[...] = h_new
    c_scr[...] = c_new


def _lstm_bwd_kernel(dy_ref, gates_ref, c_ref, cprev_ref, c0_ref, dct_ref,
                     wht_ref, dgx_ref, dh0_ref, dc0_ref, dh_scr, dc_scr,
                     *, nt, hp):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    rt = pl.program_id(0)          # reverse step: t = nt - 1 - rt

    @pl.when(rt == 0)
    def _():
        dh_scr[...] = jnp.zeros_like(dh_scr)
        dc_scr[...] = dct_ref[...].astype(jnp.float32)

    ga = gates_ref[0].astype(jnp.float32)
    i, f = ga[:, :hp], ga[:, hp:2 * hp]
    gg, o = ga[:, 2 * hp:3 * hp], ga[:, 3 * hp:]
    c_t = c_ref[0]
    c_prev = jnp.where(rt == nt - 1, c0_ref[...].astype(jnp.float32),
                       cprev_ref[0])
    dh = dy_ref[0].astype(jnp.float32) + dh_scr[...]
    tc = jnp.tanh(c_t)
    do = dh * tc
    dc = dc_scr[...] + dh * o * (1.0 - tc * tc)
    dgates = jnp.concatenate([
        (dc * gg) * i * (1.0 - i),           # d(pre-i)
        (dc * c_prev) * f * (1.0 - f),       # d(pre-f)
        (dc * i) * (1.0 - gg * gg),          # d(pre-g)
        do * o * (1.0 - o),                  # d(pre-o)
    ], axis=-1).astype(dgx_ref.dtype)
    dgx_ref[0] = dgates
    dh_new = jax.lax.dot_general(
        dgates, wht_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    dc_new = dc * f
    dh_scr[...] = dh_new
    dc_scr[...] = dc_new
    # constant-indexed output block: every step overwrites, the final grid
    # step (t == 0) leaves the real dh0/dc0
    dh0_ref[...] = dh_new.astype(dh0_ref.dtype)
    dc0_ref[...] = dc_new.astype(dc0_ref.dtype)


def _lstm_infer_kernel(gx_ref, wht_ref, h0_ref, c0_ref, ys_ref, ct_ref,
                       h_scr, c_scr, *, hp):
    """Residual-free forward (inference): only ys and the final c leave the
    kernel — no gates/c_all saves, so the primal path pays no training-
    residual HBM writes."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    t = pl.program_id(0)

    @pl.when(t == 0)
    def _():
        h_scr[...] = h0_ref[...].astype(jnp.float32)
        c_scr[...] = c0_ref[...].astype(jnp.float32)

    h = h_scr[...]
    c = c_scr[...]
    g = gx_ref[0].astype(jnp.float32) + jax.lax.dot_general(
        h.astype(gx_ref.dtype), wht_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    i = jax.nn.sigmoid(g[:, :hp])
    f = jax.nn.sigmoid(g[:, hp:2 * hp])
    gg = jnp.tanh(g[:, 2 * hp:3 * hp])
    o = jax.nn.sigmoid(g[:, 3 * hp:])
    c_new = f * c + i * gg
    h_new = o * jnp.tanh(c_new)
    ys_ref[0] = h_new.astype(ys_ref.dtype)
    h_scr[...] = h_new
    c_scr[...] = c_new
    # constant-indexed: last grid step leaves cT
    ct_ref[...] = c_new.astype(ct_ref.dtype)


@functools.lru_cache(maxsize=64)
def _lstm_infer_compiled(key):
    nt, bp, hp, dtype, interpret = key
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    return pl.pallas_call(
        functools.partial(_lstm_infer_kernel, hp=hp),
        name="lstm_layer_infer",
        grid=(nt,),
        in_specs=[
            pl.BlockSpec((1, bp, 4 * hp), lambda t: (t, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((hp, 4 * hp), lambda t: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bp, hp), lambda t: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bp, hp), lambda t: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=(
            jax.ShapeDtypeStruct((nt, bp, hp), _np.dtype(dtype)),
            jax.ShapeDtypeStruct((bp, hp), _np.dtype(dtype)),
        ),
        out_specs=(
            pl.BlockSpec((1, bp, hp), lambda t: (t, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bp, hp), lambda t: (0, 0),
                         memory_space=pltpu.VMEM),
        ),
        scratch_shapes=[pltpu.VMEM((bp, hp), jnp.float32),
                        pltpu.VMEM((bp, hp), jnp.float32)],
        interpret=interpret,
    )


@functools.lru_cache(maxsize=64)
def _lstm_fwd_compiled(key):
    nt, bp, hp, dtype, interpret = key
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    return pl.pallas_call(
        functools.partial(_lstm_fwd_kernel, hp=hp),
        name="lstm_layer_fwd",
        grid=(nt,),
        in_specs=[
            pl.BlockSpec((1, bp, 4 * hp), lambda t: (t, 0, 0),
                         memory_space=pltpu.VMEM),          # gx
            pl.BlockSpec((hp, 4 * hp), lambda t: (0, 0),
                         memory_space=pltpu.VMEM),          # w_hhᵀ (resident)
            pl.BlockSpec((bp, hp), lambda t: (0, 0),
                         memory_space=pltpu.VMEM),          # h0
            pl.BlockSpec((bp, hp), lambda t: (0, 0),
                         memory_space=pltpu.VMEM),          # c0
        ],
        out_shape=(
            jax.ShapeDtypeStruct((nt, bp, hp), _np.dtype(dtype)),    # ys
            jax.ShapeDtypeStruct((nt, bp, hp), _np.float32),         # c_t
            jax.ShapeDtypeStruct((nt, bp, 4 * hp), _np.dtype(dtype)),  # gates
        ),
        out_specs=(
            pl.BlockSpec((1, bp, hp), lambda t: (t, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bp, hp), lambda t: (t, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bp, 4 * hp), lambda t: (t, 0, 0),
                         memory_space=pltpu.VMEM),
        ),
        scratch_shapes=[pltpu.VMEM((bp, hp), jnp.float32),
                        pltpu.VMEM((bp, hp), jnp.float32)],
        interpret=interpret,
    )


@functools.lru_cache(maxsize=64)
def _lstm_bwd_compiled(key):
    nt, bp, hp, dtype, interpret = key
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rev = lambda rt: (nt - 1 - rt, 0, 0)
    return pl.pallas_call(
        functools.partial(_lstm_bwd_kernel, nt=nt, hp=hp),
        name="lstm_layer_bwd",
        grid=(nt,),
        in_specs=[
            pl.BlockSpec((1, bp, hp), rev, memory_space=pltpu.VMEM),    # dy
            pl.BlockSpec((1, bp, 4 * hp), rev,
                         memory_space=pltpu.VMEM),                      # gates
            pl.BlockSpec((1, bp, hp), rev, memory_space=pltpu.VMEM),    # c_t
            pl.BlockSpec((1, bp, hp),
                         lambda rt: (jnp.maximum(nt - 2 - rt, 0), 0, 0),
                         memory_space=pltpu.VMEM),                      # c_{t-1}
            pl.BlockSpec((bp, hp), lambda rt: (0, 0),
                         memory_space=pltpu.VMEM),                      # c0
            pl.BlockSpec((bp, hp), lambda rt: (0, 0),
                         memory_space=pltpu.VMEM),                      # dcT
            pl.BlockSpec((hp, 4 * hp), lambda rt: (0, 0),
                         memory_space=pltpu.VMEM),                      # w_hhᵀ
        ],
        out_shape=(
            jax.ShapeDtypeStruct((nt, bp, 4 * hp), _np.dtype(dtype)),  # dgx
            jax.ShapeDtypeStruct((bp, hp), _np.dtype(dtype)),          # dh0
            jax.ShapeDtypeStruct((bp, hp), _np.dtype(dtype)),          # dc0
        ),
        out_specs=(
            pl.BlockSpec((1, bp, 4 * hp), rev, memory_space=pltpu.VMEM),
            pl.BlockSpec((bp, hp), lambda rt: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bp, hp), lambda rt: (0, 0),
                         memory_space=pltpu.VMEM),
        ),
        scratch_shapes=[pltpu.VMEM((bp, hp), jnp.float32),
                        pltpu.VMEM((bp, hp), jnp.float32)],
        interpret=interpret,
    )


def lstm_layer(gx, wh, h0, c0):
    """One LSTM layer over a precomputed input projection.

    gx: (T, B, 4H) = x·w_ihᵀ + b_ih + b_hh (both biases folded — they are
    additive in the LSTM cell). wh: (4H, H) recurrent weight in the
    reference's flat layout (gate order i, f, g, o — rnn-inl.h). h0/c0:
    (B, H). Returns (ys (T,B,H), hT, cT). Differentiable via a Pallas
    backward kernel; dW_hh reduces to one large matmul outside the kernel.
    """
    import jax
    import jax.numpy as jnp

    nt, b, gh = gx.shape
    h = gh // 4
    hp = -(-h // 128) * 128
    bp = -(-b // 16) * 16
    dtype = gx.dtype
    interpret = _use_interpret()

    # w_hhᵀ padded to (Hp, 4Hp): pad the H rows, then each gate col block
    wht = _pad_gate_cols(jnp.pad(wh.T, ((0, hp - h), (0, 0))), h, hp)
    gx_p = _pad_gate_cols(
        jnp.pad(gx, ((0, 0), (0, bp - b), (0, 0))), h, hp)
    h0_p = jnp.pad(h0, ((0, bp - b), (0, hp - h)))
    c0_p = jnp.pad(c0, ((0, bp - b), (0, hp - h)))

    @jax.custom_vjp
    def scan_p(gx_p, wht, h0_p, c0_p):
        # primal (not being differentiated): residual-free kernel
        return _lstm_infer_compiled(
            (nt, bp, hp, str(dtype), interpret))(gx_p, wht, h0_p, c0_p)

    def fwd(gx_p, wht, h0_p, c0_p):
        ys_p, c_all, gates = _lstm_fwd_compiled(
            (nt, bp, hp, str(dtype), interpret))(gx_p, wht, h0_p, c0_p)
        return (ys_p, c_all[-1].astype(dtype)), \
            (wht, gates, c_all, h0_p, c0_p, ys_p)

    def bwd(res, cts):
        wht, gates, c_all, h0_p, c0_p, ys_p = res
        dys_p, dct_p = cts
        dgx_p, dh0_p, dc0_p = _lstm_bwd_compiled(
            (nt, bp, hp, str(dtype), interpret))(
            dys_p.astype(dtype), gates, c_all, c_all, c0_p,
            dct_p.astype(dtype), wht)
        # dW_hhᵀ = Σ_t h_{t-1}ᵀ · dgates_t — one large MXU matmul
        h_prev = jnp.concatenate([h0_p[None], ys_p[:-1]], axis=0)
        dwht = jax.lax.dot_general(
            h_prev.reshape(-1, hp), dgx_p.reshape(-1, 4 * hp),
            (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(wht.dtype)
        return dgx_p, dwht, dh0_p, dc0_p

    scan_p.defvjp(fwd, bwd)
    ys_p, ct_p = scan_p(gx_p, wht, h0_p, c0_p)
    ys = ys_p[:, :b, :h]
    return ys, ys[-1], ct_p[:b, :h]


# ---------------------------------------------------------------------------
# Paged decode attention (flash-decode): one query token per sequence
# against a block-allocated paged KV cache (serving/generate.py).
#
# Autoregressive decode is the q_len=1 degenerate case of attention, and
# its memory layout is dictated by the KV-cache allocator: each sequence's
# keys/values live scattered across fixed-size pages named by a per-
# sequence page table, not in one contiguous (L, D) slab. A dense gather
# (k_pages[page_tables] -> (B, max_pages, ...)) materializes a batch-wide
# padded COPY of every sequence's history in HBM per step; the Pallas
# kernel instead leaves both pools in HBM and copies the live pages, and
# only those, into two VMEM buffers of a BLOCK of `paged_pages_per_step`
# pages each, the next block's pages streaming in under this block's
# arithmetic. Nothing is ever copied out of the pool in HBM.
#
# The walk is a WORK LIST of the batch's live blocks, (sequence, block) in
# order, which the kernel's scalar core writes to SMEM from `lengths` before
# the first page moves (a few microseconds a call): one invocation, one loop
# over the list, no grid. A grid over (sequence, block) pays for the TABLE,
# not for the lengths: each grid step costs the pipeline 0.1-0.2 us an
# operand whether it fetches or not, and with a page a BlockSpec operand a
# step of 16 operands read 1.4 us (PR 45's chip runs, PERF.md section 6);
# the list pays for what is live. A page's copy is issued from a loop over
# the block's live pages, so the body holds one copy and one wait, not one
# a page, and a block's rows arrive already stacked by rows in the buffer:
# the body does not grow with the block.
#
# The pool is TOKEN-MAJOR: (pages, page_size, Cp), a page being page_size
# rows of all heads' values side by side (head h owns lanes [h*D, (h+1)*D);
# Cp is H*D rounded up to the 128-lane tile ONCE, by whoever allocates the
# pool). A (page_size, Cp) page is whole (8, 128) tiles whatever H and D
# are, so the kernel reads the pool where it lies: no slice, no pad. The
# per-head dot product is a sum over D adjacent lanes, and the MXU takes it:
# the block's q*k products (float32, on the VPU), every lane tile's and
# every query head's of a group, are stacked by rows and multiplied once a
# block by the 0/1 matrix of the heads' lane segments, which leaves every
# head's score replicated over its own D lanes, so the online softmax (m, l,
# acc) lives as (., Cp) rows with no per-head reshape anywhere and is updated
# once a block. The block is what makes the MXU worth it: a page of 16 rows
# alone is a product of 96 rows, bound by the load of the segment matrix (the
# lane butterfly this form replaced, twelve rotations a vreg a page on the
# XLU, was half the old kernel's time); 8 pages are 768 rows a load. A head
# wider than a lane tile takes the same product with a (D, D) matrix of
# ones: one body for every head size.
#
# Precision: a float32 product goes through the MXU as two bfloat16 terms
# (16 bits of mantissa: scores good to 1e-5); p*v and the accumulators are
# float32 on the VPU.
#
# Grouped-query (fewer KV heads than query heads): the `group` query heads
# that share a KV head arrive as `group` rows of Cp lanes and each keeps a
# row of the softmax state; a block's tile is read from VMEM once for all of
# them, and their products are further rows of the block's one product.
#
# Gate: MXTPU_PALLAS_DECODE (`_decode_kernels_on`). Shapes the kernel cannot
# take (`_paged_kernel_takes`) go to the jnp path whatever the gate says.
# ---------------------------------------------------------------------------


def paged_attention_reference(q, k_pages, v_pages, page_tables, lengths,
                              sm_scale, kv_heads=None, starts=None):
    """Dense-gather oracle (and fallback): q (B, H, D); k_pages / v_pages
    (P, page_size, Cp) token-major with Cp >= KV*D (lanes past KV*D are the
    allocation's padding and are ignored), KV = ``kv_heads`` heads of keys
    and values (None: H, one K and V a query head); H a multiple of KV,
    query head i reading KV head i // (H // KV) (grouped-query); page_tables (B,
    max_pages) int32; lengths (B,) int32 — tokens [0, lengths[b]) of
    sequence b are live, laid out page_tables[b, t // page_size] slot
    t % page_size. A row with length 0 returns zeros-ish garbage that
    callers mask out (its scores are uniformly _NEG_INF, which is finite by
    design — no NaNs). With ``starts`` (B,) int32 the table is a RING: token
    t lies in ``page_tables[b, (t // page_size) % max_pages]`` and tokens
    [starts[b], lengths[b]) are live, at most max_pages * page_size of them
    (a slot holds the newest token that maps to it). Both contractions run
    at HIGHEST precision: an oracle whose f32 scores the MXU rounded to bf16
    could not tell a right kernel from a wrong one on the chip."""
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST
    b, h, d = q.shape
    ps = k_pages.shape[1]
    maxp = page_tables.shape[1]
    kv = h if kv_heads is None else int(kv_heads)
    g = h // kv
    # (B, maxp, ps, Cp) -> (B, L, KV, D)
    k = k_pages[page_tables][..., :kv * d].reshape(b, maxp * ps, kv, d)
    v = v_pages[page_tables][..., :kv * d].reshape(b, maxp * ps, kv, d)
    qg = q.astype(jnp.float32).reshape(b, kv, g, d)
    s = jnp.einsum("bkgd,blkd->bkgl", qg, k.astype(jnp.float32),
                   precision=hi) * sm_scale
    ids = jnp.arange(maxp * ps)[None, None, None, :]
    if starts is None:
        live = ids < lengths[:, None, None, None]
    else:
        # the newest token below the length that maps to each slot
        last = lengths[:, None, None, None] - 1
        token = ids + (last - ids) // (maxp * ps) * (maxp * ps)
        live = (ids <= last) & (token >= starts[:, None, None, None])
    s = jnp.where(live, s, _NEG_INF)
    p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    p = p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    o = jnp.einsum("bkgl,blkd->bkgd", p, v.astype(jnp.float32), precision=hi)
    return o.reshape(b, h, d).astype(q.dtype)


def _paged_kernel(tbl_ref, len_ref, *refs, sm_scale, ps, d, per_step, maxp,
                  group, terms, ring):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    # ``ring``: the table is a ring of maxp pages and a sequence's live keys
    # start at ``start_ref[b]`` (a window layer); else every key below the
    # length is live and the table holds them all
    start_ref = refs[0] if ring else None
    (q_ref, seg_ref, k_hbm, v_hbm, o_ref, k_buf, v_buf, sem, seq_ref, blk_ref,
     m_scr, l_scr, acc_scr) = refs[1:] if ring else refs
    rows = per_step * ps               # a block's tokens
    cp = q_ref.shape[-1]
    w = max(128, d)                    # a lane tile, or one head if wider
    tiles = list(range(0, cp, w))

    def length_of(b):
        if ring:
            return jnp.maximum(len_ref[b], 0)
        return jnp.clip(len_ref[b], 0, maxp * ps)

    def start_of(b):
        # never more live keys than the ring holds: the work list is sized
        # by it
        length = length_of(b)
        return jnp.clip(start_ref[b], jnp.maximum(length - maxp * ps, 0),
                        length)

    def first_block(b):
        return start_of(b) // rows if ring else 0

    # the work list: every live block of every sequence, in order, written
    # to SMEM by the scalar core before the first page moves
    def list_blocks(b, n):
        blocks = (length_of(b) + rows - 1) // rows
        if ring:                       # from the first live key's block on
            j0 = first_block(b)
            blocks = blocks - j0

        def one(j, carry):
            seq_ref[n + j] = b
            blk_ref[n + j] = j0 + j if ring else j
            return carry

        jax.lax.fori_loop(0, blocks, one, 0)
        return n + blocks

    n = jax.lax.fori_loop(0, o_ref.shape[0], list_blocks, 0)

    def copies(page, slot, g):
        return [pltpu.make_async_copy(
            pool.at[page], buf.at[slot, pl.ds(pl.multiple_of(g * ps, ps), ps)],
            sem.at[slot]) for pool, buf in ((k_hbm, k_buf), (v_hbm, v_buf))]

    def live_pages(i):
        # work item i's sequence, its block's first page, and which of the
        # block's pages hold live tokens: [skip, count) (every listed block
        # holds one)
        b, first = seq_ref[i], blk_ref[i] * per_step
        skip = jnp.maximum(start_of(b) // ps - first, 0) if ring else 0
        return b, first, skip, jnp.minimum(
            (length_of(b) + ps - 1) // ps - first, per_step)

    def fetch(i, slot):
        b, first, skip, count = live_pages(i)

        def one(g, carry):
            at = b * maxp + (first + g) % maxp if ring \
                else b * maxp + first + g
            for copy in copies(tbl_ref[at], slot, g):
                copy.start()
            return carry

        jax.lax.fori_loop(skip, count, one, 0)

    def arrive(i, slot):
        def one(g, carry):
            for copy in copies(0, slot, 0):    # a page's bytes, whichever
                copy.wait()
            return carry

        _, _, skip, count = live_pages(i)
        jax.lax.fori_loop(skip, count, one, 0)

    # a sequence of length 0 is on no list: zeros, as its empty sum reads.
    # Rows of a buffer that no page has reached yet are masked by position,
    # so they only have to be finite
    o_ref[...] = jnp.zeros_like(o_ref)
    k_buf[...] = jnp.zeros_like(k_buf)
    v_buf[...] = jnp.zeros_like(v_buf)

    @pl.when(n > 0)
    def _():
        fetch(0, 0)

    def block(i, carry):
        slot = i % 2
        b, j = seq_ref[i], blk_ref[i]
        length = length_of(b)

        @pl.when(i + 1 < n)            # the next block streams under this one
        def _():
            fetch(i + 1, 1 - slot)

        @pl.when(j == first_block(b))
        def _():
            m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
            l_scr[...] = jnp.zeros_like(l_scr)
            acc_scr[...] = jnp.zeros_like(acc_scr)

        arrive(i, slot)
        at = j * rows + jax.lax.broadcasted_iota(jnp.int32, (rows, w), 0)
        live = at < length
        if ring:
            live = live & (at >= start_of(b))
        # every (lane tile, query head of the group)'s q*k over the block's
        # rows stacked by rows, summed inside each head's D lanes by ONE
        # product with the 0/1 matrix of the heads' segments (the same for
        # every tile), which leaves each head's score on all its lanes; a
        # float32 product goes through the MXU as `terms` bfloat16 terms of
        # 8 bits of mantissa each. 2048 rows a product at most
        chunk = max(1, 2048 // (group * rows))
        for t0 in range(0, len(tiles), chunk):
            cs = tiles[t0:t0 + chunk]
            prod = jnp.concatenate(
                [q_ref[b, g:g + 1, c:c + w].astype(jnp.float32) * sm_scale
                 * k_buf[slot, :, c:c + w].astype(jnp.float32)
                 for c in cs for g in range(group)], axis=0)
            s_all = None
            for t in range(terms):
                term = prod.astype(jnp.bfloat16)
                part = jnp.dot(term, seg_ref[...],
                               preferred_element_type=jnp.float32)
                s_all = part if s_all is None else s_all + part
                if t + 1 < terms:
                    prod = prod - term.astype(jnp.float32)
            for at, c in enumerate(cs):
                v = v_buf[slot, :, c:c + w].astype(jnp.float32)  # (rows, w)
                for g in range(group):
                    # row g of q, of the state and of the output: the g-th
                    # of the query heads that share each KV head
                    lo = (at * group + g) * rows
                    s = jnp.where(live, s_all[lo:lo + rows], _NEG_INF)
                    m = m_scr[g:g + 1, c:c + w]
                    new_m = jnp.maximum(m, jnp.max(s, axis=0, keepdims=True))
                    alpha = jnp.exp(m - new_m)
                    p = jnp.exp(s - new_m)                       # (rows, w)
                    m_scr[g:g + 1, c:c + w] = new_m
                    l_scr[g:g + 1, c:c + w] = l_scr[g:g + 1, c:c + w] \
                        * alpha + jnp.sum(p, axis=0, keepdims=True)
                    acc_scr[g:g + 1, c:c + w] = acc_scr[g:g + 1, c:c + w] \
                        * alpha + jnp.sum(p * v, axis=0, keepdims=True)

        @pl.when((j + 1) * rows >= length)     # the sequence's last block
        def _():
            o_ref[b] = (acc_scr[0:group, :]
                        / jnp.maximum(l_scr[0:group, :], 1e-30)
                        ).astype(o_ref.dtype)

        return carry

    jax.lax.fori_loop(0, n, block, 0)


def paged_pages_per_step(ps, maxp):
    """Pages a block of the paged decode kernel holds: about 128 tokens'
    worth, at most a sequence's pages."""
    return max(1, min(128 // ps, maxp))


# bfloat16 terms a q*k product goes through the MXU as: two keep 16 bits of
# mantissa (scores good to 1e-5), which is all a bfloat16 pool's products have
_PAGED_TERMS = 2


def _paged_vmem_bytes(b, cp, ps, maxp, itemsize, group):
    """VMEM the kernel's call holds: two blocks of K and of V, every
    sequence's query and output rows (a group padded to a sublane tile), the
    stacked products and the softmax state."""
    rows = paged_pages_per_step(ps, maxp) * ps
    sublanes = 32 // itemsize
    return (4 * rows * cp * itemsize
            + 2 * b * -(-group // sublanes) * sublanes * cp * itemsize
            + 6 * min(2048, group * rows * cp // 128) * 128 * 4
            + 3 * 8 * cp * 4)


_PAGED_VMEM_LIMIT = 96 << 20     # of a v5e core's 128 MiB


def _paged_kernel_takes(d, ps, cp, pool_dtype, group=1, b=1, maxp=8):
    """Whether the Pallas kernel can read a pool of this form: a head's
    lanes must tile the 128 lanes of the segment product or be whole lane
    tiles (a power-of-two head size), a page must be whole sublane tiles of
    the pool's dtype (8 rows of 32 bits, 16 of 16, 32 of 8), its rows whole
    128-lane tiles, the query heads that share a KV head one row each of
    the (8, Cp) softmax state, and the batch's query and output rows must
    fit VMEM beside the blocks."""
    itemsize = _np.dtype(pool_dtype).itemsize
    return (d & (d - 1) == 0 and ps % (32 // itemsize) == 0
            and cp % 128 == 0 and 1 <= group <= 8
            and _paged_vmem_bytes(b, cp, ps, maxp, itemsize, group)
            <= _PAGED_VMEM_LIMIT)


@functools.lru_cache(maxsize=128)
def _paged_compiled(key):
    (b, d, cp, maxp, ps, dtype, pool_dtype, sm_scale, interpret, group,
     ring) = key
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    per_step = paged_pages_per_step(ps, maxp)
    itemsize = _np.dtype(pool_dtype).itemsize
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    pool = pl.BlockSpec(memory_space=pl.ANY)      # stays in HBM, never copied
    # the longest work list; a ring's live keys may begin inside a block,
    # which is one block more a sequence
    items = b * (-(-maxp // per_step) + int(ring))
    return pl.pallas_call(
        functools.partial(
            _paged_kernel, sm_scale=sm_scale, ps=ps, d=d, per_step=per_step,
            maxp=maxp, group=group, terms=_PAGED_TERMS, ring=ring),
        name="paged_attention_decode",
        out_shape=jax.ShapeDtypeStruct((b, group, cp), _np.dtype(dtype)),
        # page table, lengths (and a ring's first live keys); the queries,
        # the heads' segments; the pools
        in_specs=[smem] * (3 if ring else 2) + [vmem] * 2 + [pool] * 2,
        out_specs=vmem,
        scratch_shapes=[
            pltpu.VMEM((2, per_step * ps, cp), _np.dtype(pool_dtype)),  # K
            pltpu.VMEM((2, per_step * ps, cp), _np.dtype(pool_dtype)),  # V
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((items,), jnp.int32),      # work list: sequence,
            pltpu.SMEM((items,), jnp.int32),      # block
            pltpu.VMEM((8, cp), jnp.float32),     # m
            pltpu.VMEM((8, cp), jnp.float32),     # l
            pltpu.VMEM((8, cp), jnp.float32)],    # acc
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=int(max(
            32 << 20, _paged_vmem_bytes(b, cp, ps, maxp, itemsize, group)
            + (8 << 20)))),
        interpret=interpret,
    )


def paged_attention(q, k_pages, v_pages, page_tables, lengths,
                    sm_scale=None, kv_heads=None, starts=None):
    """Flash-decode attention: one query token per sequence against a
    paged KV cache (docs/serving.md §Generation).

    q: (B, H, D) — the current token's per-head queries. k_pages /
    v_pages: (P, page_size, Cp) token-major block-allocated cache, KV head h
    in lanes [h*D, (h+1)*D), Cp = KV*D rounded up to a multiple of 128 by
    the allocation. KV = ``kv_heads`` (None: H, one K and V a query head);
    with fewer KV heads than query heads, query head i reads KV head
    i // (H // KV): the page is streamed once and each of its tiles serves
    the H // KV heads that share it (the kernel never pads, slices or
    repeats the pool).
    page_tables: (B, max_pages) int32 — sequence b's token t lives in page
    ``page_tables[b, t // page_size]`` row ``t % page_size``; entries
    past the sequence's used pages must still be VALID page indices
    (they are masked by ``lengths``, never dereferenced out of bounds).
    lengths: (B,) int32 live-token counts (0 disables a padding row, whose
    output is zeros).
    starts: None, or (B,) int32 first live keys of a WINDOW layer, whose
    table is a ring: token t lies in ``page_tables[b, (t // page_size) %
    max_pages]``, tokens [starts[b], lengths[b]) are live (at most max_pages
    * page_size of them; an earlier start is raised to that) and the work
    list visits only the blocks that hold one.

    A head size that is no power of two, a page that is not whole sublane
    tiles of the pool's dtype, a Cp off the lane tile, more than 8 query
    heads a KV head or a batch whose rows do not fit VMEM goes to
    `paged_attention_reference`: decided from the shapes alone.
    """
    import jax.numpy as jnp

    if sm_scale is None:
        sm_scale = 1.0 / float(_np.sqrt(q.shape[-1]))
    sm_scale = float(sm_scale)
    b, h, d = q.shape
    _, ps, cp = k_pages.shape
    maxp = page_tables.shape[1]
    kv = h if kv_heads is None else int(kv_heads)
    if h % kv or kv * d > cp:
        raise ValueError("%d query heads cannot share %d KV heads of %d in "
                         "a pool row of %d lanes" % (h, kv, d, cp))
    group = h // kv
    if not (_decode_kernels_on() and _paged_kernel_takes(
            d, ps, cp, k_pages.dtype, group, b, maxp)):
        return paged_attention_reference(q, k_pages, v_pages, page_tables,
                                         lengths, sm_scale, kv, starts)
    call = _paged_compiled((b, d, cp, maxp, ps, str(q.dtype),
                            str(k_pages.dtype), sm_scale, _use_interpret(),
                            group, starts is not None))
    # row g holds, for every KV head, the g-th of the query heads that
    # share it, in that KV head's lanes
    rows = q.reshape(b, kv, group, d).transpose(0, 2, 1, 3) \
        .reshape(b, group, kv * d)
    rows = jnp.pad(rows, ((0, 0), (0, 0), (0, cp - kv * d)))
    # lanes of one head: a 0/1 block on the diagonal (one block of all ones
    # where a head is wider than a lane tile)
    lanes = jnp.arange(max(128, d)) // d
    seg = (lanes[:, None] == lanes[None, :]).astype(jnp.bfloat16)
    bounds = (lengths,) if starts is None else (lengths, starts)
    out = call(page_tables.astype(jnp.int32).reshape(-1),
               *(a.astype(jnp.int32) for a in bounds), rows, seg, k_pages,
               v_pages)
    return out[:, :, :kv * d].reshape(b, group, kv, d) \
        .transpose(0, 2, 1, 3).reshape(b, h, d)


# ---------------------------------------------------------------------------
# Attention of a whole long prompt (a prefill): jax's own splash-attention
# kernel (`jax.experimental.pallas.ops.tpu.splash_attention`), a flash
# attention over a block-sparse mask. The blockwise jnp form
# (`ops.nn.causal_attention(block=, window=)`) writes every block's float32
# scores to HBM and reads them back two or three times: 28 heads x 8192 x
# 4600 keys of a window layer are 4 GB a layer, and the prompt's attention
# was most of a prefill's 0.73 s at 8192 tokens (PERF.md section 6, PR 48).
# The kernel keeps a (block, block) tile of scores in VMEM and visits only
# the key blocks that a query block's mask reaches: the triangle of a full
# layer, the band of a window layer. Grouped-query: one multi-query call a
# KV head (vmapped), its query heads sharing the streamed K and V.
#
# Gate and fallback as for the decode kernels: MXTPU_PALLAS_DECODE, and
# shapes the kernel does not take (a head off the lane tile, a prompt that
# is no whole number of blocks) go to the jnp form.
# ---------------------------------------------------------------------------

_PROMPT_BLOCK = 512     # rows and keys of a tile of the prompt's scores


def _prompt_kernel_takes(l, d):
    """A head of whole lane tiles, a prompt of whole blocks."""
    return d % 128 == 0 and l % _PROMPT_BLOCK == 0


@functools.lru_cache(maxsize=64)
def _prompt_kernel(l, group, window, block, interpret):
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as splash, splash_attention_mask as masks)

    # keys (i - window, i]: window - 1 to the left of the query, none to
    # its right
    mask = masks.LocalMask((l, l), (window - 1, 0), 0) if window \
        else masks.CausalMask((l, l))
    return splash.make_splash_mqa_single_device(
        mask=masks.MultiHeadMask([mask] * group),
        block_sizes=splash.BlockSizes(block_q=block, block_kv=block,
                                      block_kv_compute=block),
        interpret=interpret)


def prompt_attention(q, k, v, sm_scale, window=None):
    """Causal attention of one prompt, grouped-query: q (L, H, D); k, v (L,
    KV, D); query head i reads KV head i // (H // KV); with ``window``
    query i sees keys (i - window, i]. The same function as
    `ops.nn.causal_attention(q, k, v, sm_scale, window=window)`, through the
    splash-attention kernel where the shapes allow (the scale is folded
    into q, in q's dtype; scores, softmax and accumulation in float32)."""
    import jax.numpy as jnp

    l, h, d = q.shape
    kv = k.shape[1]
    if not (_decode_kernels_on() and _prompt_kernel_takes(l, d)):
        from .nn import causal_attention

        return causal_attention(q, k, v, sm_scale, block=_PROMPT_BLOCK,
                                window=window)
    kernel = _prompt_kernel(l, h // kv, window, _PROMPT_BLOCK,
                            _use_interpret())
    heads = (q * sm_scale).astype(q.dtype).reshape(l, kv, h // kv, d) \
        .transpose(1, 2, 0, 3)                           # (KV, G, L, D)
    # one call a KV head (a vmapped call loses the kernel's name in the
    # program, and so in the device trace)
    out = jnp.stack([kernel(heads[i], k[:, i], v[:, i]) for i in range(kv)])
    return out.transpose(2, 0, 1, 3).reshape(l, h, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# Latent (MLA) decode attention over a paged pool of compressed rows.
#
# A latent layer caches ONE row a token for all heads: the compressed KV
# (`rank` lanes) and the shared rotary key beside it, padded to whole lane
# tiles once at allocation: (pages, page_size, Cp). Decode absorbs the
# up-projection into the query and the output, so every head's key is the
# whole row and every head's value its first `rank` lanes: a page is read
# ONCE from HBM and serves two MXU products, (H, Cp) x (Cp, page) for the
# scores of all heads and (H, page) x (page, Cp) for their outputs (the lanes
# past `rank` of that product are dropped by the caller: slicing the page in
# VMEM would need `rank` on a lane tile).
#
# Grid (B, blocks of `per_step` pages): a grid step costs 0.23 us whatever
# it does (PERF.md section 5: a page a step was that cost), so a step
# takes several pages, each through a BlockSpec of its own on the one pool,
# and a block past a sequence's length names the sequence's last live page
# again: nothing is fetched for it and its arithmetic is skipped.
# ---------------------------------------------------------------------------


def paged_latent_attention_reference(q, pages, page_tables, lengths,
                                     sm_scale, rank):
    """Dense-gather oracle (and fallback) of `paged_latent_attention`: q (B,
    H, R) with R <= Cp the row's live lanes (compressed KV then rotary key);
    pages (P, page_size, Cp); every head's key is the cached row's first R
    lanes, its value the first ``rank``. Returns (B, H, rank). Rows with
    length 0 return zeros. Both contractions at HIGHEST precision."""
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST
    b, h, r = q.shape
    ps = pages.shape[1]
    maxp = page_tables.shape[1]
    rows = pages[page_tables].reshape(b, maxp * ps, -1).astype(jnp.float32)
    s = jnp.einsum("bhr,blr->bhl", q.astype(jnp.float32), rows[..., :r],
                   precision=hi) * sm_scale
    live = jnp.arange(maxp * ps)[None, None, :] < lengths[:, None, None]
    s = jnp.where(live, s, _NEG_INF)
    p = jnp.where(live, jnp.exp(s - jnp.max(s, axis=-1, keepdims=True)), 0.0)
    p = p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    o = jnp.einsum("bhl,blr->bhr", p, rows[..., :rank], precision=hi)
    return o.astype(q.dtype)


def _latent_kernel(tbl_ref, len_ref, q_ref, *rest, sm_scale, ps, per_step,
                   n_blocks):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    page_refs = rest[:per_step]
    o_ref, m_scr, l_scr, acc_scr = rest[per_step:]
    b = pl.program_id(0)
    j = pl.program_id(1)
    length = len_ref[b]

    @pl.when(j == 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    for g in range(per_step):
        first = (j * per_step + g) * ps

        @pl.when(first < length)       # a page past the length adds nothing
        def _(g=g, first=first):
            q = q_ref[0]                                         # (H, Cp)
            page = page_refs[g][0]                               # (ps, Cp)
            s = jax.lax.dot_general(
                q, page, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale   # (H, ps)
            col = first + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(col < length, s, _NEG_INF)
            m = m_scr[:, 0:1]
            new_m = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m - new_m)
            p = jnp.exp(s - new_m)
            l = l_scr[:, 0:1] * alpha + jnp.sum(p, axis=1, keepdims=True)
            acc_scr[...] = acc_scr[...] * alpha + jnp.dot(
                p.astype(page.dtype), page,
                preferred_element_type=jnp.float32)              # (H, Cp)
            m_scr[...] = jnp.broadcast_to(new_m, m_scr.shape)
            l_scr[...] = jnp.broadcast_to(l, l_scr.shape)

    @pl.when(j == n_blocks - 1)
    def _():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[:, 0:1], 1e-30)
                    ).astype(o_ref.dtype)


def latent_pages_per_step(ps, maxp):
    """Pages a grid step of the latent kernel takes: about 256 tokens'
    worth, at most 8 and at most a sequence's pages."""
    return max(1, min(8, 256 // ps, maxp))


def _latent_kernel_takes(ps, cp, pool_dtype):
    """A page must be whole sublane tiles of the pool's dtype and its rows
    whole 128-lane tiles."""
    sublanes = 32 // _np.dtype(pool_dtype).itemsize
    return ps % sublanes == 0 and cp % 128 == 0


@functools.lru_cache(maxsize=64)
def _latent_compiled(key):
    (b, h, cp, maxp, ps, dtype, sm_scale, interpret, per_step) = key
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n_blocks = -(-maxp // per_step)

    def page(g):
        def index(bb, j, tbl, lens):
            # past the sequence's last live page: that page again, so the
            # pipeline fetches nothing new
            last = jnp.maximum((lens[bb] + ps - 1) // ps - 1, 0)
            return (tbl[bb, jnp.minimum(j * per_step + g, last)], 0, 0)

        return index

    def row(bb, j, tbl, lens):
        return (bb, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,          # page_tables, lengths (SMEM)
        grid=(b, n_blocks),
        in_specs=[pl.BlockSpec((1, h, cp), row, memory_space=pltpu.VMEM)]
        + [pl.BlockSpec((1, ps, cp), page(g), memory_space=pltpu.VMEM)
           for g in range(per_step)],
        out_specs=pl.BlockSpec((1, h, cp), row, memory_space=pltpu.VMEM),
        scratch_shapes=[pltpu.VMEM((h, 128), jnp.float32),     # m
                        pltpu.VMEM((h, 128), jnp.float32),     # l
                        pltpu.VMEM((h, cp), jnp.float32)],     # acc
    )
    return pl.pallas_call(
        functools.partial(_latent_kernel, sm_scale=sm_scale, ps=ps,
                          per_step=per_step, n_blocks=n_blocks),
        name="paged_latent_attention_decode",
        out_shape=jax.ShapeDtypeStruct((b, h, cp), _np.dtype(dtype)),
        grid_spec=grid_spec,
        interpret=interpret,
    )


def paged_latent_attention(q, pages, page_tables, lengths, sm_scale, rank):
    """Decode attention of a latent (MLA) layer in its absorbed form: one
    query token a sequence against the paged pool of compressed rows
    (docs/serving.md section Generation).

    q (B, H, R): every head's query against the cached row, the key
    up-projection already folded in (R = rank + rotary lanes). pages (P,
    page_size, Cp): row t of a sequence at ``pages[page_tables[b, t //
    page_size], t % page_size]``, lanes [0, rank) the compressed KV, [rank,
    R) the rotary key, the rest the allocation's padding (zeros). Returns
    (B, H, rank): softmax(q . row * sm_scale) over the live rows times their
    first ``rank`` lanes, which the caller takes through the value
    up-projection. ``page_tables`` entries past a sequence's pages must be
    valid indices; ``lengths`` 0 disables a padding row (zeros out).

    A page that is not whole sublane tiles of the pool's dtype, or rows off
    the lane tile, go to `paged_latent_attention_reference`; the gate is
    MXTPU_PALLAS_DECODE, as for `paged_attention`."""
    import jax.numpy as jnp

    sm_scale = float(sm_scale)
    b, h, r = q.shape
    _, ps, cp = pages.shape
    if not rank <= r <= cp:
        raise ValueError("a query of %d lanes over rows of %d with %d "
                         "compressed lanes" % (r, cp, rank))
    if not (_decode_kernels_on()
            and _latent_kernel_takes(ps, cp, pages.dtype)):
        return paged_latent_attention_reference(q, pages, page_tables,
                                                lengths, sm_scale, rank)
    maxp = page_tables.shape[1]
    per_step = latent_pages_per_step(ps, maxp)
    call = _latent_compiled((b, h, cp, maxp, ps, str(q.dtype), sm_scale,
                             _use_interpret(), per_step))
    rows = jnp.pad(q.astype(pages.dtype), ((0, 0), (0, 0), (0, cp - r)))
    out = call(page_tables.astype(jnp.int32), lengths.astype(jnp.int32),
               rows, *([pages] * per_step))
    return out[:, :, :rank].astype(q.dtype)


def decode_attention_form(latent, d, ps, cp, pool_dtype, group, batch, maxp):
    """What an engine's decode attention runs at these shapes, for the
    engine to publish (`TransformerLMEngine.geometry`, the `engine_build`
    span): the kernel's name, the pages it takes at a time and whether a
    head's lanes are summed on the MXU; the jnp path's where the gate or the
    shapes send the call there. `paged_attention` and
    `paged_latent_attention` decide by the same functions."""
    if latent:
        kernel = _latent_kernel_takes(ps, cp, pool_dtype)
        name, per_step = "paged_latent_attention_decode", \
            latent_pages_per_step(ps, maxp)
    else:
        kernel = _paged_kernel_takes(d, ps, cp, pool_dtype, group, batch, maxp)
        name, per_step = "paged_attention_decode", \
            paged_pages_per_step(ps, maxp)
    if not (kernel and _decode_kernels_on()):
        return {"kernel": None, "pages_per_step": maxp, "lanes_on_mxu": False}
    return {"kernel": name, "pages_per_step": per_step, "lanes_on_mxu": True}


# ---------------------------------------------------------------------------
# Grouped expert feed-forward: rows sorted by expert into tiles of `tm`, one
# expert a tile; tile t computes w2_e (act(w1_e x) * w3_e x) for its rows
# with the weights of expert `tile_expert[t]`, which ride scalar prefetch so
# that the BlockSpec index maps stream exactly the experts that were hit, once
# a tile, in blocks of `_moe_tf` of the expert's width. Tiles past `n_tiles` (the
# static grid covers the worst routing) keep the last live tile's block
# indices, so nothing is fetched for them, and write zeros.
#
# w1, w3, w2 are all (E, F, C): a block (1, tf, C) is contiguous in each.
# Gate as for paged attention: MXTPU_PALLAS_DECODE `auto` = kernel on TPU, the
# jnp path elsewhere; `1` forces the kernel (interpret mode off the chip).
# ---------------------------------------------------------------------------

_MOE_BLOCK_BYTES = 40 << 20     # the three weight blocks, double-buffered


_MOE_ACTIVATIONS = ("silu", "relu")     # SwiGLU, ReGLU


def moe_grouped_ffn_reference(xs, tile_expert, n_tiles, w1, w3, w2, tm,
                              activation="silu"):
    """Oracle and fallback of `moe_grouped_ffn`: every tile's weights
    gathered whole, float32 accumulation, zeros past ``n_tiles``."""
    import jax
    import jax.numpy as jnp

    t = tile_expert.shape[0]
    x = xs.reshape(t, tm, xs.shape[-1])
    a = jnp.einsum("tmc,tfc->tmf", x, w1[tile_expert],
                   preferred_element_type=jnp.float32)
    b = jnp.einsum("tmc,tfc->tmf", x, w3[tile_expert],
                   preferred_element_type=jnp.float32)
    gate = jax.nn.relu(a) if activation == "relu" else jax.nn.silu(a)
    h = (gate * b).astype(xs.dtype)
    y = jnp.einsum("tmf,tfc->tmc", h, w2[tile_expert],
                   preferred_element_type=jnp.float32)
    live = jnp.arange(t)[:, None, None] < n_tiles
    return jnp.where(live, y, 0.0).reshape(t * tm, xs.shape[-1])


def _moe_kernel(te_ref, nt_ref, x_ref, w1_ref, w3_ref, w2_ref, o_ref,
                acc_ref, *, nf, activation):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    t = pl.program_id(0)
    j = pl.program_id(1)
    live = t < nt_ref[0]

    @pl.when(j == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(live)
    def _():
        x = x_ref[...]
        nt = (((1,), (1,)), ((), ()))
        a = jax.lax.dot_general(x, w1_ref[0], nt,
                                preferred_element_type=jnp.float32)
        b = jax.lax.dot_general(x, w3_ref[0], nt,
                                preferred_element_type=jnp.float32)
        gate = jnp.maximum(a, 0.0) if activation == "relu" \
            else a * jax.nn.sigmoid(a)
        h = (gate * b).astype(x.dtype)
        acc_ref[...] += jnp.dot(h, w2_ref[0],
                                preferred_element_type=jnp.float32)

    @pl.when(j == nf - 1)
    def _():
        o_ref[...] = acc_ref[...]


def _moe_tf(f, c, itemsize):
    """The expert-width block of a grid step: the widest split of the
    expert's width into whole lane tiles whose three weight blocks fit VMEM
    twice over (1536 = the whole expert at LFM2's sizes in bfloat16, which
    read 85% of the HBM's peak on the chip where blocks of 512 read 81%)."""
    for parts in range(1, f // 128 + 1):
        tf = f // parts
        if f % parts == 0 and tf % 128 == 0 \
                and 6 * tf * c * itemsize <= _MOE_BLOCK_BYTES:
            return tf
    return 128 if f % 128 == 0 else f


def _moe_kernel_takes(tm, c, f, dtype):
    """Whole tiles only: rows a sublane tile of the dtype, the model width
    whole lanes, the expert width split into blocks of whole lanes."""
    itemsize = _np.dtype(dtype).itemsize
    return (tm % (32 // itemsize) == 0 and c % 128 == 0
            and _moe_tf(f, c, itemsize) % 128 == 0 and f % 128 == 0)


@functools.lru_cache(maxsize=64)
def _moe_compiled(key):
    (tiles, tm, c, f, dtype, interpret, activation) = key
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    itemsize = _np.dtype(dtype).itemsize
    tf = _moe_tf(f, c, itemsize)
    nf = f // tf

    def weights(t, j, te, nt):
        live = t < nt[0]
        return (te[t], jnp.where(live, j, nf - 1), 0)

    def rows(t, j, te, nt):
        return (jnp.where(t < nt[0], t, jnp.maximum(nt[0] - 1, 0)), 0)

    def out(t, j, te, nt):
        return (t, 0)

    need = 2 * (3 * tf * c * itemsize + tm * c * (itemsize + 4)) \
        + tm * c * 4 + 3 * tm * tf * 4
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,          # tile_expert, n_tiles (SMEM)
        grid=(tiles, nf),
        in_specs=[
            pl.BlockSpec((tm, c), rows),
            pl.BlockSpec((1, tf, c), weights),
            pl.BlockSpec((1, tf, c), weights),
            pl.BlockSpec((1, tf, c), weights),
        ],
        out_specs=pl.BlockSpec((tm, c), out),
        scratch_shapes=[pltpu.VMEM((tm, c), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_moe_kernel, nf=nf, activation=activation),
        name="moe_grouped_ffn",
        out_shape=jax.ShapeDtypeStruct((tiles * tm, c), jnp.float32),
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=int(max(32 << 20, need + (8 << 20)))),
        interpret=interpret,
    )


def moe_grouped_ffn(xs, tile_expert, n_tiles, w1, w3, w2, tm,
                    activation="silu"):
    """Grouped gated feed-forward over the experts that were hit: w2_e
    (act(w1_e x) * w3_e x), ``activation`` (static) ``silu`` (SwiGLU) or
    ``relu`` (ReGLU).

    xs (T*tm, C): the routed rows laid out by `ops.contrib.sigmoid_topk_moe`,
    tile t holding rows of expert ``tile_expert[t]`` only (rows past an
    expert's count are zeros); ``n_tiles`` (1,) int32 live tiles; w1, w3, w2
    (E, F, C). Returns (T*tm, C) float32, zeros in the tiles past
    ``n_tiles``. Shapes the kernel cannot take go to
    `moe_grouped_ffn_reference`, decided from the shapes alone."""
    import jax.numpy as jnp

    if activation not in _MOE_ACTIVATIONS:
        raise ValueError("an expert's activation is one of %s, not %r"
                         % (_MOE_ACTIVATIONS, activation))
    tiles = tile_expert.shape[0]
    c, f = xs.shape[-1], w1.shape[1]
    if not (_decode_kernels_on() and _moe_kernel_takes(tm, c, f, xs.dtype)):
        return moe_grouped_ffn_reference(xs, tile_expert, n_tiles, w1, w3,
                                         w2, tm, activation)
    call = _moe_compiled((tiles, tm, c, f, str(xs.dtype), _use_interpret(),
                          activation))
    return call(tile_expert.astype(jnp.int32), n_tiles.astype(jnp.int32),
                xs, w1, w3, w2)
