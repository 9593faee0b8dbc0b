"""Neural-net ops.

TPU-native equivalents of the reference's `src/operator/nn/` family
(fully_connected.cc, convolution.cc, deconvolution.cc, pooling.cc,
batch_norm.cc, layer_norm.cc, activation.cc, softmax.cc, dropout.cc, lrn.cc,
upsampling.cc, softmax_output.cc, l2_normalization.cc — SURVEY §2.1 N8).

Design notes (TPU-first):
- Convs/matmuls lower to `lax.conv_general_dilated` / `jnp.dot` → MXU. Layout
  stays NCHW at the API (reference layout); XLA relayouts internally for TPU.
- There are no cuDNN-vs-native variants: one jax definition; XLA fuses the
  elementwise pre/post ops (bias, activation, BN-inference) into the conv.
- Stateful bits (BatchNorm moving stats) are functional: the op *returns* the
  updated stats as aux outputs and the dispatch layer writes them back
  (OpDef.num_visible_outputs; see ndarray/ndarray.py) — mutation become
  functional outputs, the jit-friendly form of the reference's aux states.
- Ops whose behavior depends on train/predict mode (`BatchNorm`, `Dropout`)
  take an `is_train` attr injected by the dispatch layer from the autograd
  mode (reference: Imperative::is_training / OpContext.is_train).
"""
from __future__ import annotations

import builtins
import functools
import itertools
import math

import numpy as _np

from . import register
from ..base import MXNetError

import jax
import jax.numpy as jnp
from jax import lax


# --------------------------------------------------------------------------
# FullyConnected (reference: src/operator/nn/fully_connected.cc)
# --------------------------------------------------------------------------

@register("FullyConnected")
def fully_connected(data, weight, bias=None, num_hidden=None, no_bias=False, flatten=True):
    if data.ndim < 1:
        raise MXNetError("FullyConnected: data must have at least 1 "
                         "dimension, got shape %s" % (data.shape,))
    if flatten:
        x = data.reshape((data.shape[0], -1))
    else:
        x = data
    out = jnp.dot(x, weight.T)
    if bias is not None and not no_bias:
        out = out + bias
    return out


# --------------------------------------------------------------------------
# Convolution / Deconvolution (reference: convolution.cc, deconvolution.cc)
# --------------------------------------------------------------------------

def _norm_layout(ndim, layout):
    """Resolve a conv/pool layout attr to its string form. None/empty means
    the reference default (channels-first). Supported channels-last forms
    mirror the reference's layout enum (convolution.cc:102 NHWC/NDHWC/NWC —
    reference gates them to GPU; here they lower to XLA dnums directly,
    and on TPU channels-last is the MXU-preferred layout)."""
    spatial = "DHW"[3 - (ndim - 2):]
    if not layout:
        return "NC" + spatial
    layout = str(layout)
    if len(layout) != ndim or set(layout) != set("NC" + spatial):
        raise MXNetError("unsupported layout %r for %dd input" % (layout, ndim))
    return layout


def _channels_last(layout):
    return layout is not None and str(layout).endswith("C") and len(str(layout)) > 2


def _to_ncfirst_perm(ndim):
    """(N, *spatial, C) -> (N, C, *spatial)"""
    return (0, ndim - 1) + tuple(range(1, ndim - 1))


def _to_chlast_perm(ndim):
    """(N, C, *spatial) -> (N, *spatial, C)"""
    return (0,) + tuple(range(2, ndim)) + (1,)


def _pool_window(kernel, stride, pads, ch_last):
    """reduce_window (window, strides, padding) tuples for either layout."""
    if ch_last:
        return ((1,) + tuple(kernel) + (1,),
                (1,) + tuple(stride) + (1,),
                ((0, 0),) + tuple(pads) + ((0, 0),))
    return ((1, 1) + tuple(kernel),
            (1, 1) + tuple(stride),
            ((0, 0), (0, 0)) + tuple(pads))


def _conv_dnums(ndim, layout=None):
    lhs = _norm_layout(ndim, layout)
    if lhs[1] == "C":
        kspec = "OI" + lhs[2:]          # weight (O, I, *k)
    else:
        kspec = "O" + lhs[1:-1] + "I"   # weight (O, *k, I) — reference
        # ConvertLayout(OIHW -> NHWC) convention (convolution.cc:158)
    return lax.conv_dimension_numbers(
        (1,) * ndim, (1,) * ndim, (lhs, kspec, lhs))


def _tup(v, n):
    if v is None or v == ():
        return (1,) * n if n else ()
    if isinstance(v, int):
        return (v,) * n
    return tuple(v)


@register("Convolution")
def convolution(data, weight, bias=None, kernel=(), stride=(), dilate=(), pad=(),
                num_filter=0, num_group=1, no_bias=False, cudnn_tune=None,
                cudnn_off=False, workspace=1024, layout=None):
    nsp = data.ndim - 2
    ch_last = _channels_last(layout)
    w_spatial = tuple(weight.shape[1:-1] if ch_last else weight.shape[2:])
    # the kernel attr is redundant with the weight's spatial dims; a
    # mismatch is a user error the reference's shape inference rejects
    # (conv shape check, src/operator/nn/convolution.cc InferShape).
    # Validate only when the attr is a clean int sequence — scalar or
    # string forms (foreign-JSON attrs) skip the check rather than crash.
    try:
        kt = tuple(int(k) for k in kernel) if kernel else ()
    except (TypeError, ValueError):
        kt = ()
    if kt and kt != w_spatial:
        raise MXNetError("Convolution: kernel attr %s != weight spatial "
                         "shape %s" % (kt, w_spatial))
    stride = _tup(stride, nsp)
    dilate = _tup(dilate, nsp)
    pad = _tup(pad if pad != () else 0, nsp)
    dn = _conv_dnums(data.ndim, layout)
    out = lax.conv_general_dilated(
        data, weight,
        window_strides=stride,
        padding=[(p, p) for p in pad],
        rhs_dilation=dilate,
        dimension_numbers=dn,
        feature_group_count=num_group,
        # NOTE: no preferred_element_type here — an fp32-widened primal makes
        # the conv transpose rule pair an fp32 cotangent with bf16 operands and
        # throw under grad. TPU's MXU accumulates bf16 convs in fp32 natively,
        # so bf16-in/bf16-out loses nothing.
    )
    if bias is not None and not no_bias:
        out = out + (bias if ch_last else bias.reshape((1, -1) + (1,) * nsp))
    return out


@register("Deconvolution")
def deconvolution(data, weight, bias=None, kernel=(), stride=(), dilate=(), pad=(),
                  adj=(), target_shape=(), num_filter=0, num_group=1, no_bias=True,
                  cudnn_tune=None, cudnn_off=False, workspace=1024, layout=None):
    """Transposed conv. weight layout (in_c, out_c/g, *k) — same as the
    reference (deconvolution-inl.h); implemented as a fractionally-strided
    conv (lhs_dilation) so XLA lowers it onto the MXU like a regular conv."""
    nsp = data.ndim - 2
    if _channels_last(layout):
        # correctness path only (deconv is off the perf-critical layouts):
        # run the channels-first math and let XLA fold the transposes
        perm_in = _to_ncfirst_perm(data.ndim)
        perm_out = _to_chlast_perm(data.ndim)
        out = deconvolution(
            jnp.transpose(data, perm_in), jnp.transpose(weight, perm_in), bias,
            kernel=kernel, stride=stride, dilate=dilate, pad=pad, adj=adj,
            target_shape=target_shape, num_filter=num_filter,
            num_group=num_group, no_bias=no_bias)
        return jnp.transpose(out, perm_out)
    stride = _tup(stride, nsp)
    dilate = _tup(dilate, nsp)
    pad = _tup(pad if pad != () else 0, nsp)
    adj = _tup(adj if adj != () else 0, nsp)
    if target_shape:
        k = weight.shape[2:]
        adj = tuple(
            target_shape[i] - ((data.shape[2 + i] - 1) * stride[i] - 2 * pad[i]
                               + (dilate[i] * (k[i] - 1) + 1))
            for i in range(nsp))
    in_c = weight.shape[0]
    g = num_group
    # (in_c, oc_g, *k) -> (g, in_c/g, oc_g, *k) -> (g, oc_g, in_c/g, *k) -> (out_c, in_c/g, *k)
    w = weight.reshape((g, in_c // g) + weight.shape[1:])
    w = jnp.swapaxes(w, 1, 2)
    w = w.reshape((g * weight.shape[1], in_c // g) + weight.shape[2:])
    w = jnp.flip(w, axis=tuple(range(2, 2 + nsp)))
    k_eff = [dilate[i] * (weight.shape[2 + i] - 1) + 1 for i in range(nsp)]
    padding = [(k_eff[i] - 1 - pad[i], k_eff[i] - 1 - pad[i] + adj[i]) for i in range(nsp)]
    dn = _conv_dnums(data.ndim)
    out = lax.conv_general_dilated(
        data, w,
        window_strides=(1,) * nsp,
        padding=padding,
        lhs_dilation=stride,
        rhs_dilation=dilate,
        dimension_numbers=dn,
        feature_group_count=g,
    ).astype(data.dtype)
    if bias is not None and not no_bias:
        out = out + bias.reshape((1, -1) + (1,) * nsp)
    return out


# --------------------------------------------------------------------------
# Pooling (reference: src/operator/nn/pooling.cc)
# --------------------------------------------------------------------------

def _extract_patches(x, kernel, stride, pads, pad_value):
    """Channels-first window unfold: (N, C, prod(k), *out_spatial). Shared
    by _patches_max and the large-kernel maxpool backward fallback so the
    dimension_numbers/reshape layout stays in lockstep. Pad value must be
    finite when the result feeds arithmetic: conv_general_dilated_patches
    gathers through a one-hot conv, and 0 * -inf = NaN would poison every
    border window."""
    n, c = x.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (0, 0)) + tuple(pads),
                     constant_values=pad_value)
    patches = lax.conv_general_dilated_patches(
        padded, filter_shape=kernel, window_strides=stride,
        padding=[(0, 0)] * len(kernel),
        dimension_numbers=_conv_dnums(x.ndim))
    return patches.reshape(
        (n, c, int(_np.prod(kernel))) + patches.shape[2:])


def _patches_max(x, kernel, stride, pads):
    """Max pool via patch extraction — differentiable formulation used only
    inside the backward rule of `_float_max_pool`."""
    neg = jnp.asarray(jnp.finfo(x.dtype).min, x.dtype)
    return _extract_patches(x, kernel, stride, pads, neg).max(axis=2)


def _max_pool_taps_bwd(x, y, g, kernel, stride, pads, ch_last):
    """Maxpool input-grad in the caller's layout, at the OUTPUT's size.

    dx[p] = sum over windows w containing p of [x[p] == y[w]] * g[w].
    Padded position q = w*s + a (tap offset a) belongs to stride phase
    q mod s, and within a phase tap a reads y and g unstretched, shifted by
    a // s windows. So x is viewed as (.., m, s, ..) an axis (a reshape, no
    copy), each of the prod(stride) phases sums its taps against static
    slices of y / g (padded once, at the output's size, so that a shift
    past either edge reads a zero cotangent), and the phases are stacked
    back and reshaped into dx once. 3x3/s2 pad 1: the phases take 4, 2, 2
    and 1 taps, nine compare/selects on output-sized arrays. Stride 1 is one
    phase; stride > kernel leaves whole phases zero. Accumulates in the
    cotangent's dtype, taps in row-major order, as before.

    Measured on the chip at PR 40 (TPU v5e, forward + backward of the
    ResNet-50 stem pool alone, bf16[256,112,112,64], device ms a call;
    PERF.md section 6): this form 6.56; the form it replaced, which
    zero-stuffed y and g onto the padded input grid once a tap (18
    `lax.pad`s with interior 1, each written and read back at the input's
    size, where its docstring promised one fused kernel), 36.71
    channels-first with transposes around it and 36.65 channels-last (XLA
    lays 64 channels out batch-minor either way, so the detour itself was
    free); `select_and_scatter` 1.74. As bf16[256,64,112,112]: 9.62
    against 41.83 and 4.18. ResNet-50's step fell from 138.3 to 107.1 ms.

    Tie semantics: every in-window position equal to the max receives the
    full window cotangent (reference CPU pooling backward behavior,
    src/operator/nn/pool.h max path). After a ReLU, and in bfloat16, ties
    are common, which is why reduce_window's own gradient (one position a
    window) is a yardstick here and not a candidate."""
    nsp = len(kernel)
    sp0 = 1 if ch_last else 2  # first spatial axis
    xs = x.shape[sp0:sp0 + nsp]
    osz = y.shape[sp0:sp0 + nsp]
    # phases an axis, each m[i] positions long (x zero-extended to m*s)
    m = [-(-xs[i] // stride[i]) for i in range(nsp)]
    # taps[i][c]: for the phase of unpadded positions p = j*s + c, the
    # window index that position j = 0 reads through each of its taps
    taps = []
    for i in range(nsp):
        s, lo = stride[i], pads[i][0]
        taps.append([[(c + lo) // s - a // s
                      for a in range((c + lo) % s, kernel[i], s)]
                     for c in range(s)])
    # y and g padded once, so that every tap is a static slice of them and
    # a window index outside the output reads a zero cotangent
    ylo = [max([0] + [-w for ws in taps[i] for w in ws]) for i in range(nsp)]
    cfg = [(0, 0, 0)] * x.ndim
    for i in range(nsp):
        hi = max([0] + [w + m[i] - osz[i] for ws in taps[i] for w in ws])
        cfg[sp0 + i] = (ylo[i], hi, 0)
    zero = jnp.zeros((), g.dtype)
    yp = lax.pad(y, jnp.asarray(-jnp.inf, y.dtype), cfg)
    gp = lax.pad(g, zero, cfg)
    # x viewed as (.., m_i, s_i, ..): a phase is an index into the s axes
    ext = [(0, 0, 0)] * x.ndim
    view = list(x.shape[:sp0])
    for i in range(nsp):
        ext[sp0 + i] = (0, m[i] * stride[i] - xs[i], 0)
        view += [m[i], stride[i]]
    view += list(x.shape[sp0 + nsp:])
    xv = lax.pad(x, jnp.zeros((), x.dtype), ext).reshape(view)

    def phase(cls):
        idx = [slice(None)] * len(view)
        for i in range(nsp):
            idx[sp0 + 2 * i + 1] = cls[i]
        xc = xv[tuple(idx)]
        acc = jnp.zeros(xc.shape, g.dtype)
        for w0 in itertools.product(*[taps[i][cls[i]] for i in range(nsp)]):
            sl = [slice(None)] * x.ndim
            for i in range(nsp):
                sl[sp0 + i] = slice(w0[i] + ylo[i], w0[i] + ylo[i] + m[i])
            acc = acc + jnp.where(xc == yp[tuple(sl)], gp[tuple(sl)], zero)
        return acc

    def interleaved(axis, cls):
        if axis == nsp:
            return phase(cls)
        # deeper axes are already (m, s) pairs, shallower ones still m
        return jnp.stack([interleaved(axis + 1, cls + (c,))
                          for c in range(stride[axis])], axis=sp0 + axis + 1)

    dx = interleaved(0, ()).reshape(
        [d + e[1] for d, e in zip(x.shape, ext)])
    return dx[tuple(slice(0, d) for d in x.shape)]


@functools.lru_cache(maxsize=None)
def _float_max_pool(kernel, stride, pads, ch_last=False):
    """Float max pooling: cheap `lax.reduce_window` forward, custom
    backward in the layout it is called in. reduce_window(max)'s own grad
    (TPU SelectAndScatter, 1.74 ms at the ResNet-50 stem's shape where the
    tap form takes 6.56) credits one position a window where the reference
    credits every tie, so it cannot ship; `_max_pool_taps_bwd` has what
    each form measured on the chip."""
    window, strides, padding = _pool_window(kernel, stride, pads, ch_last)

    nsp = len(kernel)
    sp0 = 1 if ch_last else 2

    @jax.custom_vjp
    def mp(x):
        return lax.reduce_window(x, _np.asarray(-_np.inf, x.dtype), lax.max,
                                 window, strides, padding)

    def fwd(x):
        y = mp(x)
        return y, (x, y)

    def bwd(res, g):
        x, y = res
        covers = all(
            kernel[i] >= x.shape[sp0 + i] + pads[i][0] + pads[i][1]
            for i in range(nsp))
        if covers:
            # single window COVERING the padded input (global pool): one
            # broadcast compare. The coverage check matters: a 2x2/s2
            # window on a 3x3 input also has 1x1 output but never reads
            # the last row/col, which must not receive gradient.
            dx = jnp.where(x == y, g, jnp.zeros((), g.dtype))
        elif int(_np.prod(kernel)) <= 32:
            dx = _max_pool_taps_bwd(x, y, g, kernel, stride, pads, ch_last)
        else:
            # large overlapping kernels (rare): patches-based fallback,
            # with the same full-credit tie semantics as the taps path
            # (explicit equality mask instead of jnp.max's even-split vjp;
            # the patch extraction itself is linear, so only it is vjp'd).
            # _extract_patches is channels-first; no cell runs this branch,
            # so a channels-last caller keeps its transposes here.
            if ch_last:
                x, y, g = (jnp.transpose(t, _to_ncfirst_perm(nsp + 2))
                           for t in (x, y, g))
            patches, pull = jax.vjp(
                lambda t: _extract_patches(t, kernel, stride, pads, 0), x)
            mask = patches == y[:, :, None]
            dx = pull(jnp.where(mask, g[:, :, None],
                                jnp.zeros((), g.dtype)))[0]
            if ch_last:
                dx = jnp.transpose(dx, _to_chlast_perm(nsp + 2))
        return (dx,)

    mp.defvjp(fwd, bwd)
    return mp


@register("Pooling")
def pooling(data, kernel=(), pool_type="max", global_pool=False, stride=(), pad=(),
            pooling_convention="valid", count_include_pad=True, p_value=2,
            cudnn_off=False, layout=None):
    nsp = data.ndim - 2
    ch_last = _channels_last(layout)
    sp_off = 1 if ch_last else 2  # first spatial axis position
    if global_pool:
        kernel = data.shape[sp_off:sp_off + nsp]
        stride = (1,) * nsp
        pad = (0,) * nsp
    kernel = _tup(kernel, nsp)
    stride = _tup(stride if stride != () else 1, nsp)
    pad = _tup(pad if pad != () else 0, nsp)
    pads = []
    for i in range(nsp):
        lo = hi = pad[i]
        if pooling_convention == "full" and not global_pool:
            size = data.shape[sp_off + i] + 2 * pad[i] - kernel[i]
            out_d = int(math.ceil(size / stride[i])) + 1
            need = (out_d - 1) * stride[i] + kernel[i] - (data.shape[sp_off + i] + 2 * pad[i])
            hi += builtins.max(need, 0)
        pads.append((lo, hi))
    window, strides, padding = _pool_window(kernel, stride, tuple(pads), ch_last)

    if pool_type == "max":
        if not jnp.issubdtype(data.dtype, jnp.floating):
            init = jnp.iinfo(data.dtype).min
            return lax.reduce_window(data, _np.asarray(init, data.dtype), lax.max,
                                     window, strides, padding)
        return _float_max_pool(kernel, stride, tuple(pads), ch_last)(data)
    if pool_type == "lp":
        powed = jnp.power(jnp.abs(data), p_value)
        s = lax.reduce_window(powed, _np.zeros((), data.dtype), lax.add, window, strides, padding)
        return jnp.power(s, 1.0 / p_value)
    s = lax.reduce_window(data, _np.zeros((), data.dtype), lax.add, window, strides, padding)
    if pool_type == "sum":
        return s
    # avg
    if count_include_pad:
        denom = float(_np.prod(kernel))
        return s / jnp.asarray(denom, data.dtype)
    ones = jnp.ones(data.shape, data.dtype)
    cnt = lax.reduce_window(ones, _np.zeros((), data.dtype), lax.add, window, strides, padding)
    return s / cnt


# --------------------------------------------------------------------------
# Normalization (batch_norm.cc, layer_norm.cc, instance_norm.cc, l2_norm...)
# --------------------------------------------------------------------------

def _bn_axes(ndim, ax):
    red = tuple(i for i in range(ndim) if i != ax)
    bshape_fn = lambda shape: tuple(  # noqa: E731
        shape[ax] if i == ax else 1 for i in range(ndim))
    return red, bshape_fn


def _bn_stats(data, red):
    """Per-channel batch mean/var in ONE fused HBM pass over `data`.

    Both reductions consume the same read (XLA multi-output-fuses them;
    jnp.var's mean-subtracted two-pass re-reads the activation — GBs per BN
    layer at train bs>=256). Raw E[x^2]-E[x]^2 cancels catastrophically for
    large-mean/small-spread channels, so shift by a per-channel proxy of
    the batch mean first: the mean over ONE slice of the leading reduced
    dim (an O(1/N) read), within ~std/sqrt(HW) of the true channel mean
    for any input. The f32 cast of `data` here is consumed ONLY inside the
    fused reductions, so no f32 copy of the activation is materialized —
    keeping it out of the normalize path is what lets every conv output
    stay a single bf16 tensor (round-4 profile: the old shared x32 cast
    made XLA emit (f32, bf16) pairs out of every conv fusion, 3x the
    write bytes)."""
    lead = red[0]  # first reduced dim (batch unless axis==0)
    proxy = jnp.mean(
        lax.slice_in_dim(data, 0, 1, axis=lead).astype(jnp.float32),
        axis=red, keepdims=True)
    d = data.astype(jnp.float32) - proxy
    s1 = jnp.mean(d, axis=red)
    s2 = jnp.mean(jnp.square(d), axis=red)
    mean = proxy.reshape(s1.shape) + s1
    var = jnp.maximum(s2 - jnp.square(s1), 0.0)
    return mean, var


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _bn_train(data, gamma, beta, ax, eps, fix_gamma):
    return _bn_train_fwd(data, gamma, beta, ax, eps, fix_gamma)[0]


def _bn_train_fwd(data, gamma, beta, ax, eps, fix_gamma):
    red, bshape_fn = _bn_axes(data.ndim, ax)
    bshape = bshape_fn(data.shape)
    mean, var = _bn_stats(data, red)
    inv = lax.rsqrt(var + eps)
    g = jnp.ones_like(gamma) if fix_gamma else gamma
    scale = g.astype(jnp.float32) * inv
    shift = beta.astype(jnp.float32) - mean * scale
    dt = data.dtype
    # the big-tensor math is ONE fused FMA in the input dtype; per-channel
    # scale/shift are computed in f32 (cheap, accurate) then rounded once
    out = (data * scale.astype(dt).reshape(bshape)
           + shift.astype(dt).reshape(bshape))
    return (out, mean, var), (data, gamma, beta, mean, inv)


def _bn_train_bwd(ax, eps, fix_gamma, res, cts):
    """Hand-written BN train backward, bandwidth-lean (round-4 MFU work):
    all full-tensor math stays in the input dtype; dgamma/dbeta accumulate
    in f32 inside fused convert-reduces; the correction terms ride C-sized
    f32 vectors. Cotangents for the mean/var outputs are ignored: they feed
    the moving-stat buffers (never differentiated); differentiating through
    output_mean_var stats is unsupported (documented divergence)."""
    data, gamma, beta, mean, inv = res
    ct = cts[0]
    red, bshape_fn = _bn_axes(data.ndim, ax)
    bshape = bshape_fn(data.shape)
    n = 1
    for i in red:
        n *= data.shape[i]
    dt = data.dtype
    xhat = ((data - mean.astype(dt).reshape(bshape))
            * inv.astype(dt).reshape(bshape))
    dbeta = jnp.sum(ct, axis=red, dtype=jnp.float32)
    dgamma = jnp.sum(ct * xhat, axis=red, dtype=jnp.float32)
    g32 = (jnp.ones_like(inv) if fix_gamma
           else gamma.astype(jnp.float32))
    coef = (g32 * inv).astype(dt).reshape(bshape)
    c_b = (dbeta / n).astype(dt).reshape(bshape)
    c_g = (dgamma / n).astype(dt).reshape(bshape)
    dx = coef * (ct - c_b - xhat * c_g)
    dgamma_out = (jnp.zeros_like(gamma) if fix_gamma
                  else dgamma.astype(gamma.dtype))
    return dx, dgamma_out, dbeta.astype(beta.dtype)


_bn_train.defvjp(_bn_train_fwd, _bn_train_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _bn_frozen(data, gamma, beta, mean, var, ax, eps, fix_gamma):
    return _bn_frozen_fwd(data, gamma, beta, mean, var, ax, eps, fix_gamma)[0]


def _bn_frozen_fwd(data, gamma, beta, mean, var, ax, eps, fix_gamma):
    red, bshape_fn = _bn_axes(data.ndim, ax)
    bshape = bshape_fn(data.shape)
    inv = lax.rsqrt(var.astype(jnp.float32) + eps)
    g = jnp.ones_like(gamma) if fix_gamma else gamma
    scale = g.astype(jnp.float32) * inv
    shift = beta.astype(jnp.float32) - mean.astype(jnp.float32) * scale
    dt = data.dtype
    out = (data * scale.astype(dt).reshape(bshape)
           + shift.astype(dt).reshape(bshape))
    return out, (data, gamma, beta, mean, var)


def _bn_frozen_bwd(ax, eps, fix_gamma, res, ct):
    data, gamma, beta, mean, var = res
    red, bshape_fn = _bn_axes(data.ndim, ax)
    bshape = bshape_fn(data.shape)
    dt = data.dtype
    inv = lax.rsqrt(var.astype(jnp.float32) + eps)
    g32 = jnp.ones_like(inv) if fix_gamma else gamma.astype(jnp.float32)
    dx = ct * (g32 * inv).astype(dt).reshape(bshape)
    dbeta = jnp.sum(ct, axis=red, dtype=jnp.float32)
    if fix_gamma:
        dgamma = jnp.zeros_like(gamma)
    else:
        xhat = ((data - mean.astype(dt).reshape(bshape))
                * inv.astype(dt).reshape(bshape))
        dgamma = jnp.sum(ct * xhat, axis=red,
                         dtype=jnp.float32).astype(gamma.dtype)
    return (dx, dgamma, dbeta.astype(beta.dtype),
            jnp.zeros_like(mean), jnp.zeros_like(var))


_bn_frozen.defvjp(_bn_frozen_fwd, _bn_frozen_bwd)


def _bn_act(data, addend, gamma, beta, moving_mean, moving_var, eps, momentum,
            fix_gamma, use_global_stats, axis, act, is_train):
    """Shared BatchNorm(+add)(+ReLU) core behind BatchNorm /
    BatchNormRelu / BatchNormAddRelu.

    One training lowering for every layout, backend and device count: the
    custom-vjp `_bn_train`, then the add and the ReLU as plain ops, which
    XLA fuses into their neighbours (the normalize, add and ReLU into the
    consumer's input, the backward's reductions into the convolutions
    beside them) and partitions under pjit. A Pallas kernel here is a
    fusion barrier: measured on the chip with the convolution in front,
    one loses to this path at every ResNet-50 shape (PERF.md §6, PR 38)."""
    ax = axis % data.ndim
    eps = float(eps)
    fix_gamma = bool(fix_gamma)
    relu = act == "relu"
    if is_train and not use_global_stats:
        out, mean, var = _bn_train(data, gamma, beta, ax, eps, fix_gamma)
        if addend is not None:
            out = out + addend
        if relu:
            out = jax.nn.relu(out)
        new_mm = (moving_mean * momentum
                  + mean.astype(moving_mean.dtype) * (1 - momentum))
        new_mv = (moving_var * momentum
                  + var.astype(moving_var.dtype) * (1 - momentum))
        return out, new_mm, new_mv
    out = _bn_frozen(data, gamma, beta, moving_mean, moving_var, ax,
                     eps, fix_gamma)
    if addend is not None:
        out = out + addend
    if relu:
        out = jax.nn.relu(out)
    return out, moving_mean, moving_var


@register("BatchNorm", num_outputs=3, num_visible_outputs=1)
def batch_norm(data, gamma, beta, moving_mean, moving_var, eps=1e-3, momentum=0.9,
               fix_gamma=True, use_global_stats=False, output_mean_var=False,
               axis=1, cudnn_off=False, is_train=False):
    """Returns (out, new_moving_mean, new_moving_var); the dispatch layer
    writes outputs 1..2 back into the aux-state arrays (reference mutates aux
    in place, src/operator/nn/batch_norm.cc).

    Both paths use a hand-written custom_vjp (see _bn_train/_bn_frozen):
    full-tensor math runs in the input dtype end to end (bf16 under AMP),
    per-channel vectors and reduction accumulators in f32. Under pjit with
    a sharded batch axis the stats reductions psum across replicas
    automatically (the reference's SyncBatchNorm, sync_batch_norm.cc, falls
    out of GSPMD)."""
    return _bn_act(data, None, gamma, beta, moving_mean, moving_var, eps,
                   momentum, fix_gamma, use_global_stats, axis, None,
                   is_train)


@register("BatchNormRelu", aliases=("_contrib_BatchNormRelu",),
          num_outputs=3, num_visible_outputs=1)
def batch_norm_relu(data, gamma, beta, moving_mean, moving_var, eps=1e-3,
                    momentum=0.9, fix_gamma=True, use_global_stats=False,
                    output_mean_var=False, axis=1, act_type="relu",
                    cudnn_off=False, is_train=False):
    """BatchNorm + activation as ONE op (the reference's cuDNN-fused
    BNActivation analogue). Lowered as `_bn_act`: XLA fuses the ReLU into
    the normalize."""
    if act_type not in ("relu",):
        raise MXNetError("BatchNormRelu: unsupported act_type %r" % act_type)
    return _bn_act(data, None, gamma, beta, moving_mean, moving_var, eps,
                   momentum, fix_gamma, use_global_stats, axis, act_type,
                   is_train)


@register("BatchNormAddRelu", aliases=("_contrib_BatchNormAddRelu",),
          num_outputs=3, num_visible_outputs=1)
def batch_norm_add_relu(data, addend, gamma, beta, moving_mean, moving_var,
                        eps=1e-3, momentum=0.9, fix_gamma=True,
                        use_global_stats=False, output_mean_var=False,
                        axis=1, act_type="relu", cudnn_off=False,
                        is_train=False):
    """BatchNorm + residual add + ReLU as ONE op — the ResNet block tail
    (reference: the cuDNN BNAddRelu fusion, contrib BatchNormAddRelu).
    `addend` joins after normalization, before the activation:
    out = relu(bn(data) + addend)."""
    if act_type not in ("relu",):
        raise MXNetError("BatchNormAddRelu: unsupported act_type %r"
                         % act_type)
    return _bn_act(data, addend, gamma, beta, moving_mean, moving_var, eps,
                   momentum, fix_gamma, use_global_stats, axis, act_type,
                   is_train)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _ln_core(data, gamma, beta, ax, eps):
    return _ln_core_fwd(data, gamma, beta, ax, eps)[0]


def _ln_core_fwd(data, gamma, beta, ax, eps):
    """Row-stat LayerNorm, same bandwidth discipline as _bn_train: the f32
    cast lives only inside the fused row reductions (no f32 copy of the
    activation materializes); the normalize is input-dtype math with the
    per-row mean/inv rounded once. One fused read computes both moments,
    shifted by a per-row proxy (the row's first element) so the
    E[d^2]-E[d]^2 form cannot cancel catastrophically for
    large-mean/small-spread rows."""
    proxy = lax.slice_in_dim(data, 0, 1, axis=ax).astype(jnp.float32)
    d = data.astype(jnp.float32) - proxy
    s1 = jnp.mean(d, axis=ax, keepdims=True)
    s2 = jnp.mean(jnp.square(d), axis=ax, keepdims=True)
    mean = proxy + s1
    var = jnp.maximum(s2 - jnp.square(s1), 0.0)
    inv = lax.rsqrt(var + eps)
    dt = data.dtype
    bshape = tuple(data.shape[ax] if i == ax else 1
                   for i in range(data.ndim))
    xhat = (data - mean.astype(dt)) * inv.astype(dt)
    out = (xhat * gamma.astype(dt).reshape(bshape)
           + beta.astype(dt).reshape(bshape))
    return out, (data, gamma, beta, mean, inv)


def _ln_core_bwd(ax, eps, res, ct):
    data, gamma, beta, mean, inv = res
    dt = data.dtype
    ndim = data.ndim
    bshape = tuple(data.shape[ax] if i == ax else 1 for i in range(ndim))
    red = tuple(i for i in range(ndim) if i != ax)
    xhat = (data - mean.astype(dt)) * inv.astype(dt)
    dgamma = jnp.sum(ct * xhat, axis=red, dtype=jnp.float32)
    dbeta = jnp.sum(ct, axis=red, dtype=jnp.float32)
    g = ct * gamma.astype(dt).reshape(bshape)
    # row-wise corrections in f32 (per-row vectors are cheap)
    m1 = jnp.mean(g.astype(jnp.float32), axis=ax, keepdims=True)
    m2 = jnp.mean((g * xhat).astype(jnp.float32), axis=ax, keepdims=True)
    dx = inv.astype(dt) * (g - m1.astype(dt) - xhat * m2.astype(dt))
    return (dx, dgamma.astype(gamma.dtype), dbeta.astype(beta.dtype))


_ln_core.defvjp(_ln_core_fwd, _ln_core_bwd)


@register("LayerNorm")
def layer_norm(data, gamma, beta, axis=-1, eps=1e-5, output_mean_var=False):
    ax = axis % data.ndim
    return _ln_core(data, gamma, beta, ax, float(eps))


@register("InstanceNorm")
def instance_norm(data, gamma, beta, eps=1e-3):
    red = tuple(range(2, data.ndim))
    mean = jnp.mean(data, axis=red, keepdims=True)
    var = jnp.var(data, axis=red, keepdims=True)
    out = (data - mean) * lax.rsqrt(var + eps)
    bshape = (1, data.shape[1]) + (1,) * (data.ndim - 2)
    return out * gamma.reshape(bshape) + beta.reshape(bshape)


@register("L2Normalization")
def l2_normalization(data, eps=1e-10, mode="instance"):
    if mode == "instance":
        red = tuple(range(1, data.ndim))
        n = jnp.sqrt(jnp.sum(jnp.square(data), axis=red, keepdims=True) + eps)
    elif mode == "channel":
        n = jnp.sqrt(jnp.sum(jnp.square(data), axis=1, keepdims=True) + eps)
    else:  # spatial
        red = tuple(range(2, data.ndim))
        n = jnp.sqrt(jnp.sum(jnp.square(data), axis=red, keepdims=True) + eps)
    return data / n


@register("LRN")
def lrn(data, nsize=5, alpha=1e-4, beta=0.75, knorm=2.0):
    sq = jnp.square(data)
    half = nsize // 2
    window = (1, nsize, 1, 1)
    s = lax.reduce_window(sq, _np.zeros((), data.dtype), lax.add, window,
                          (1, 1, 1, 1), [(0, 0), (half, half), (0, 0), (0, 0)])
    return data / jnp.power(knorm + (alpha / nsize) * s, beta)


# --------------------------------------------------------------------------
# Activations (activation.cc, leaky_relu.cc)
# --------------------------------------------------------------------------

@register("Activation")
def activation(data, act_type="relu"):
    if act_type == "relu":
        return jax.nn.relu(data)
    if act_type == "sigmoid":
        return jax.nn.sigmoid(data)
    if act_type == "tanh":
        return jnp.tanh(data)
    if act_type == "softrelu":
        return jax.nn.softplus(data)
    if act_type == "softsign":
        return jax.nn.soft_sign(data)
    raise ValueError("unknown act_type %s" % act_type)


@register("LeakyReLU")
def leaky_relu(data, gamma=None, act_type="leaky", slope=0.25, lower_bound=0.125,
               upper_bound=0.334):
    if act_type == "leaky":
        return jnp.where(data >= 0, data, slope * data)
    if act_type == "prelu":
        bshape = (1, -1) + (1,) * (data.ndim - 2) if data.ndim > 1 else (-1,)
        return jnp.where(data >= 0, data, gamma.reshape(bshape) * data)
    if act_type == "elu":
        return jnp.where(data >= 0, data, slope * jnp.expm1(data))
    if act_type == "selu":
        alpha, scale = 1.6732632423543772, 1.0507009873554805
        return scale * jnp.where(data >= 0, data, alpha * jnp.expm1(data))
    if act_type == "gelu":
        return jax.nn.gelu(data, approximate=False)
    if act_type == "rrelu":
        mid = (lower_bound + upper_bound) / 2.0
        return jnp.where(data >= 0, data, mid * data)
    raise ValueError("unknown act_type %s" % act_type)


@register("im2col")
def im2col(data, kernel=(), stride=(), dilate=(), pad=()):
    """Sliding-window patch extraction (reference: src/operator/nn/im2col.cc
    — the building block DeformableConvolution/custom convs use). data
    (N, C, H, W) -> (N, C*prod(kernel), L) column matrix."""
    kh, kw = kernel
    sh, sw = stride if stride else (1, 1)
    dh, dw = dilate if dilate else (1, 1)
    ph, pw = pad if pad else (0, 0)
    n, c = data.shape[0], data.shape[1]
    patches = lax.conv_general_dilated_patches(
        data, (kh, kw), (sh, sw), [(ph, ph), (pw, pw)],
        rhs_dilation=(dh, dw))               # (N, C*kh*kw, OH, OW)
    oh, ow = patches.shape[2], patches.shape[3]
    return patches.reshape(n, c * kh * kw, oh * ow)


@register("col2im")
def col2im(data, output_size=(), kernel=(), stride=(), dilate=(), pad=()):
    """Scatter-add columns back into an image — im2col's exact transpose
    (reference: im2col.cc col2im). Implemented as the vjp of im2col, which
    XLA lowers to one scatter-add."""
    h, w = output_size
    n = data.shape[0]
    kh, kw = kernel
    c = data.shape[1] // (kh * kw)

    def f(img):
        sh, sw = stride if stride else (1, 1)
        dh, dw = dilate if dilate else (1, 1)
        ph, pw = pad if pad else (0, 0)
        patches = lax.conv_general_dilated_patches(
            img, (kh, kw), (sh, sw), [(ph, ph), (pw, pw)],
            rhs_dilation=(dh, dw))
        return patches.reshape(n, c * kh * kw, -1)

    _, pull = jax.vjp(f, jnp.zeros((n, c, h, w), data.dtype))
    return pull(data)[0]


# --------------------------------------------------------------------------
# Softmax family (softmax.cc, softmax_output.cc, loss_binary_op.cc)
# --------------------------------------------------------------------------

@register("softmax")
def softmax(data, axis=-1, temperature=None, length=None):
    x = data if temperature in (None, 1.0) else data / temperature
    if length is not None:
        steps = jnp.arange(data.shape[axis])
        bshape = [1] * data.ndim
        bshape[axis] = data.shape[axis]
        mask = steps.reshape(bshape) < length.reshape((-1,) + (1,) * (data.ndim - 1))
        x = jnp.where(mask, x, -jnp.inf)
    return jax.nn.softmax(x, axis=axis)


@register("log_softmax")
def log_softmax(data, axis=-1, temperature=None):
    x = data if temperature in (None, 1.0) else data / temperature
    return jax.nn.log_softmax(x, axis=axis)


@register("softmax_cross_entropy")
def softmax_cross_entropy(data, label):
    logp = jax.nn.log_softmax(data, axis=-1)
    nll = -jnp.take_along_axis(logp, label.astype(jnp.int32)[:, None], axis=-1)
    return jnp.sum(nll)


@register("SoftmaxOutput", aliases=("Softmax",))
def softmax_output(data, label, grad_scale=1.0, ignore_label=-1.0, multi_output=False,
                   use_ignore=False, preserve_shape=False, normalization="null",
                   out_grad=False, smooth_alpha=0.0):
    """Fused softmax + cross-entropy gradient: forward is softmax, backward is
    (p - onehot(label)) — the reference computes this in SoftmaxOutput's
    backward (src/operator/softmax_output-inl.h)."""
    axis = 1 if multi_output else -1

    @jax.custom_vjp
    def f(d, l):
        return jax.nn.softmax(d, axis=axis)

    def fwd(d, l):
        out = jax.nn.softmax(d, axis=axis)
        return out, (out, l)

    def bwd(res, g):
        out, lab = res
        depth = out.shape[axis]
        li = lab.astype(jnp.int32)
        onehot = jax.nn.one_hot(li, depth, axis=axis, dtype=out.dtype)
        if smooth_alpha:
            onehot = onehot * (1 - smooth_alpha) + smooth_alpha / (depth - 1) * (1 - onehot)
        grad = out - onehot
        valid = None
        if use_ignore:
            keep = (li != int(ignore_label)).astype(out.dtype)
            grad = grad * jnp.expand_dims(keep, axis if axis != -1 else li.ndim)
            valid = jnp.sum(keep)
        if normalization == "batch":
            grad = grad / out.shape[0]
        elif normalization == "valid":
            grad = grad / (jnp.maximum(valid, 1.0) if valid is not None else out.shape[0])
        return grad * grad_scale, jnp.zeros_like(lab)

    f.defvjp(fwd, bwd)
    return f(data, label)


@register("LinearRegressionOutput")
def linear_regression_output(data, label, grad_scale=1.0):
    @jax.custom_vjp
    def f(d, l):
        return d

    def fwd(d, l):
        return d, (d, l)

    def bwd(res, g):
        d, l = res
        return (d - l.reshape(d.shape)) * grad_scale, jnp.zeros_like(l)

    f.defvjp(fwd, bwd)
    return f(data, label)


@register("LogisticRegressionOutput")
def logistic_regression_output(data, label, grad_scale=1.0):
    @jax.custom_vjp
    def f(d, l):
        return jax.nn.sigmoid(d)

    def fwd(d, l):
        out = jax.nn.sigmoid(d)
        return out, (out, l)

    def bwd(res, g):
        out, l = res
        return ((out - l.reshape(out.shape)) * grad_scale, jnp.zeros_like(l))

    f.defvjp(fwd, bwd)
    return f(data, label)


@register("MAERegressionOutput")
def mae_regression_output(data, label, grad_scale=1.0):
    @jax.custom_vjp
    def f(d, l):
        return d

    def fwd(d, l):
        return d, (d, l)

    def bwd(res, g):
        d, l = res
        return (jnp.sign(d - l.reshape(d.shape)) * grad_scale, jnp.zeros_like(l))

    f.defvjp(fwd, bwd)
    return f(data, label)


@register("MakeLoss", aliases=("make_loss",))
def make_loss(data, grad_scale=1.0, valid_thresh=0.0, normalization="null"):
    @jax.custom_vjp
    def f(d):
        return d

    def fwd(d):
        return d, d.shape

    def bwd(shape, g):
        scale = grad_scale
        if normalization == "batch":
            scale = scale / shape[0]
        return (jnp.full(shape, scale, dtype=jnp.float32),)

    f.defvjp(fwd, bwd)
    return f(data)


# --------------------------------------------------------------------------
# Dropout (dropout.cc) — rng-consuming op
# --------------------------------------------------------------------------

@register("Dropout", needs_rng=True)
def dropout(rng, data, p=0.5, mode="training", axes=(), cudnn_off=False, is_train=False):
    if (not is_train and mode != "always") or p <= 0.0:
        return data
    shape = list(data.shape)
    for a in axes:
        shape[a] = 1
    keep = 1.0 - p
    mask = jax.random.bernoulli(rng, keep, tuple(shape)).astype(data.dtype)
    return data * mask / keep


# --------------------------------------------------------------------------
# UpSampling / resize (upsampling.cc, bilinear via jax.image)
# --------------------------------------------------------------------------

@register("UpSampling")
def upsampling(*args, scale=1, sample_type="nearest", num_args=1, num_filter=0,
               multi_input_mode="concat", workspace=512):
    data = args[0]
    if sample_type == "nearest":
        outs = []
        for d in args:
            s = scale if outs == [] else data.shape[2] * scale // d.shape[2]
            outs.append(jnp.repeat(jnp.repeat(d, s, axis=2), s, axis=3))
        if len(outs) == 1:
            return outs[0]
        if multi_input_mode == "sum":
            return sum(outs)
        return jnp.concatenate(outs, axis=1)
    # bilinear: args = (data, weight) in reference; we resize directly
    n, c, h, w = data.shape
    return jax.image.resize(data, (n, c, h * scale, w * scale), method="bilinear")


# --------------------------------------------------------------------------
# misc
# --------------------------------------------------------------------------

@register("Correlation")
def correlation(data1, data2, kernel_size=1, max_displacement=1, stride1=1,
                stride2=1, pad_size=0, is_multiply=True):
    """FlowNet-style correlation (reference:
    src/operator/correlation-inl.h / correlation.cc). For each displacement
    (dy, dx) on the stride2 grid within ±max_displacement, correlates a
    kernel_size² patch of data1 with the displaced patch of data2, averaged
    over channels and patch. Output channel order is dy-major, matching the
    reference's neighborhood-grid layout. Implemented as a static Python
    loop over the (small) displacement grid of shifted elementwise products
    + one reduce_window box filter each — everything fuses under XLA."""
    import numpy as _onp

    b, c, h, w = data1.shape
    kr = (kernel_size - 1) // 2
    border = max_displacement + kr
    pad2 = [(0, 0), (0, 0), (pad_size, pad_size), (pad_size, pad_size)]
    p1 = jnp.pad(data1, pad2)
    p2 = jnp.pad(data2, pad2)
    ph, pw = h + 2 * pad_size, w + 2 * pad_size
    out_h = int(_onp.ceil((ph - 2 * border) / stride1))
    out_w = int(_onp.ceil((pw - 2 * border) / stride1))
    rad = max_displacement // stride2
    # extra pad so every displaced slice of p2 is in-bounds
    p2x = jnp.pad(p2, [(0, 0), (0, 0),
                       (max_displacement, max_displacement),
                       (max_displacement, max_displacement)])
    norm = float(c * kernel_size * kernel_size)
    chans = []
    for dy in range(-rad, rad + 1):
        for dx in range(-rad, rad + 1):
            oy, ox = dy * stride2, dx * stride2
            shifted = lax.dynamic_slice(
                p2x, (0, 0, max_displacement + oy, max_displacement + ox),
                (b, c, ph, pw))
            prod = p1 * shifted if is_multiply else jnp.abs(p1 - shifted)
            box = lax.reduce_window(
                prod, 0.0, lax.add,
                window_dimensions=(1, c, kernel_size, kernel_size),
                window_strides=(1, c, 1, 1), padding="VALID")
            # box[y'] sums the window STARTING at y'; a window centered at
            # y starts at y - kr
            sl = lax.slice(
                box, (0, 0, border - kr, border - kr),
                (b, 1, border - kr + (out_h - 1) * stride1 + 1,
                 border - kr + (out_w - 1) * stride1 + 1),
                (1, 1, stride1, stride1))
            chans.append(sl / norm)
    return jnp.concatenate(chans, axis=1)


@register("IdentityAttachKLSparseReg")
def identity_attach_kl_sparse_reg(data, sparseness_target=0.1, penalty=0.001, momentum=0.9):
    return data


# ---------------------------------------------------------------------------
# Decoder-LM blocks beyond the 2019 transformer: RMS norm, rotary positions,
# a gated feed-forward, and the gated short convolution of the LFM2 family.
# Pure functions of arrays: the zoo blocks (gluon/model_zoo/lfm2.py) and the
# generation engine (serving/generate.py) call the same ones. Statistics are
# float32 whatever the data's dtype; matrix products accumulate in float32.
# ---------------------------------------------------------------------------

@register("RMSNorm", aliases=("rms_norm",))
def rms_norm(data, gamma, axis=-1, eps=1e-5):
    """data * rsqrt(mean(data^2) + eps) * gamma over ``axis``, in float32,
    returned in data's dtype."""
    x = data.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=axis, keepdims=True)
    return (x * lax.rsqrt(var + eps)
            * gamma.astype(jnp.float32)).astype(data.dtype)


def yarn_mscale(factor, mscale):
    """YaRN's attention temperature: 0.1 * mscale * ln(factor) + 1 past a
    factor of 1. A latent-attention model multiplies its softmax scale by
    the square of this (at ``mscale_all_dim``)."""
    if factor <= 1 or not mscale:
        return 1.0
    return 0.1 * float(mscale) * math.log(float(factor)) + 1.0


def yarn_args(scaling):
    """`rope`'s ``yarn`` attribute from a ``rope_scaling`` group (factor,
    original_max_position_embeddings, beta_fast, beta_slow); None where the
    positions are not scaled."""
    if not scaling or scaling["factor"] <= 1:
        return None
    return (scaling["factor"], scaling["original_max_position_embeddings"],
            scaling["beta_fast"], scaling["beta_slow"])


def latent_softmax_scale(nope, rope_lanes, scaling):
    """A latent-attention model's softmax scale: (nope + rope)^-0.5 times
    YaRN's mscale (at ``mscale_all_dim``) squared."""
    m = yarn_mscale(scaling["factor"], scaling["mscale_all_dim"]) \
        if scaling else 1.0
    return (nope + rope_lanes) ** -0.5 * m ** 2


def yarn_inv_freq(dim, theta, factor, original, beta_fast=32, beta_slow=1):
    """The ``dim // 2`` rotary frequencies under YaRN scaling (a numpy
    float32 array): pair i's theta^(-2i/dim) is kept where the pair turns
    more than ``beta_fast`` times over ``original`` positions, divided by
    ``factor`` where it turns fewer than ``beta_slow`` times, and ramps
    linearly in i between the two correction dims."""
    half = dim // 2
    inv = float(theta) ** (-_np.arange(half, dtype=_np.float64) * 2.0 / dim)

    def correction_dim(rotations):
        return dim * math.log(original / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = _np.clip((_np.arange(half) - low) / (high - low), 0.0, 1.0)
    return (inv / factor * ramp + inv * (1 - ramp)).astype(_np.float32)


@register("_contrib_rope", aliases=("rope",))
def rope(data, positions, theta=10000.0, start=0, yarn=None):
    """Rotary position embedding, rotate-half form: data (..., H, D);
    positions broadcastable to data.shape[:-2]. The lanes from ``start`` on
    are rotated (0: the whole head) and the ones before pass through; with R
    lanes rotated, lane i of their first half pairs with lane i + R/2, and
    the angle of pair i at position p is p * theta^(-2i/R), or p times the
    YaRN frequency (`yarn_inv_freq`) when ``yarn`` = (factor, original
    length, beta_fast, beta_slow) is given. Computed in float32."""
    d = data.shape[-1] - start
    half = d // 2
    if yarn:
        inv = jnp.asarray(yarn_inv_freq(d, float(theta), *yarn))
    else:
        inv = jnp.asarray(float(theta), jnp.float32) ** (
            -jnp.arange(half, dtype=jnp.float32) * 2.0 / d)
    ang = jnp.asarray(positions, jnp.float32)[..., None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x = data.astype(jnp.float32)
    x1, x2 = x[..., start:start + half], x[..., start + half:]
    return jnp.concatenate(
        ([x[..., :start]] if start else [])
        + [x1 * cos - x2 * sin, x2 * cos + x1 * sin],
        axis=-1).astype(data.dtype)


def matmul_nt(x, w, dtype=None):
    """x (..., in) times w (out, in) transposed, accumulated in float32 and
    returned in ``dtype`` (x's when None): the one projection of the LM
    blocks."""
    return lax.dot_general(
        x, w, (((x.ndim - 1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32).astype(dtype or x.dtype)


@register("_contrib_swiglu_ffn", aliases=("swiglu_ffn",))
def swiglu_ffn(data, w1, w3, w2):
    """w2 (silu(w1 x) * w3 x): w1, w3 (F, C); w2 (C, F)."""
    a = matmul_nt(data, w1, jnp.float32)
    b = matmul_nt(data, w3, jnp.float32)
    return matmul_nt((jax.nn.silu(a) * b).astype(data.dtype), w2)


@register("_contrib_short_conv", num_outputs=2, num_visible_outputs=2,
          aliases=("short_conv",))
def short_conv(u, weight, state=None, length=None):
    """Depthwise causal convolution of a few taps along time, with the
    state a decode step needs.

    u (..., T, C); weight (C, K); ``state`` (..., K-1, C) holds u of the K-1
    positions before the first (zeros when None). Returns ``(c, new_state)``:
    c_t = sum_j weight[:, j] * u_{t-(K-1)+j} and new_state = u of the last
    K-1 positions, counted from ``length`` (a traced scalar: the live prefix
    of a padded T) when given, else from T."""
    k = weight.shape[1]
    t = u.shape[-2]
    if state is None:
        state = jnp.zeros(u.shape[:-2] + (k - 1, u.shape[-1]), u.dtype)
    padded = jnp.concatenate([state.astype(u.dtype), u], axis=-2)
    w = weight.astype(jnp.float32)
    c = sum(w[:, j] * lax.slice_in_dim(padded, j, j + t, axis=-2)
            .astype(jnp.float32) for j in range(k))
    if length is None:
        new_state = lax.slice_in_dim(padded, t, t + k - 1, axis=-2)
    else:
        new_state = lax.dynamic_slice_in_dim(padded, length, k - 1, axis=-2)
    return c.astype(u.dtype), new_state


@register("_contrib_gated_short_conv", num_outputs=2, num_visible_outputs=2,
          aliases=("gated_short_conv",))
def gated_short_conv(r, w_in, w_conv, w_out, state=None, length=None):
    """The LFM2 operator: B, C, X = split3(r W_in); c = conv(B * X);
    o = (C * c) W_out. r (..., T, C); returns (o, new conv state)."""
    b, c_gate, x = jnp.split(matmul_nt(r, w_in), 3, axis=-1)
    c, new_state = short_conv(b * x, w_conv, state, length)
    return matmul_nt(c_gate * c, w_out), new_state


@register("_contrib_causal_attention", aliases=("causal_attention",))
def causal_attention(q, k, v, sm_scale=None, block=None, window=None):
    """Causal softmax attention of a whole sequence, grouped-query: q (...,
    L, H, D); k (..., L, KV, D), v (..., L, KV, Dv) with H a multiple of KV;
    query head i reads KV head i // (H // KV). Scores and softmax in
    float32. With ``block`` and more than ``block`` positions the queries
    go ``block`` rows at a time, each against the keys up to its own last
    row: the same numbers, and no (H, L, L) array. With ``window`` query i
    sees keys (i - window, i], ``window`` of them with its own, and a block
    of queries is multiplied with the keys of its band alone."""
    l, h, d = q.shape[-3:]
    kv, dv = k.shape[-2], v.shape[-1]
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    outs = []
    step = l if not block or block >= l else block
    for q0 in range(0, l, step):
        q1 = min(l, q0 + step)
        k0 = max(0, q0 - window + 1) if window else 0
        # the whole sequence in one block is the plain form, unsliced
        qb = q if step == l else q[..., q0:q1, :, :]
        kb, vb = (k, v) if (k0, q1) == (0, l) \
            else (k[..., k0:q1, :, :], v[..., k0:q1, :, :])
        qg = qb.reshape(q.shape[:-3] + (q1 - q0, kv, h // kv, d))
        s = jnp.einsum("...qkgd,...lkd->...kgql", qg, kb,
                       preferred_element_type=jnp.float32) * sm_scale
        causal = jnp.arange(k0, q1)[None, :] <= jnp.arange(q0, q1)[:, None]
        if window:
            causal &= jnp.arange(k0, q1)[None, :] \
                > jnp.arange(q0, q1)[:, None] - window
        p = jax.nn.softmax(jnp.where(causal, s, -1e30), axis=-1)
        o = jnp.einsum("...kgql,...lkd->...qkgd", p.astype(v.dtype), vb,
                       preferred_element_type=jnp.float32)
        outs.append(o.reshape(q.shape[:-3] + (q1 - q0, h, dv)))
    o = outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=-3)
    return o.astype(q.dtype)
