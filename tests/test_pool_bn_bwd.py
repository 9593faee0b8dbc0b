"""Regression tests for the round-4 bandwidth-lean backward rewrites:
maxpool tap-mask backward (3 branches) and the custom-vjp BatchNorm.

Reference semantics anchors: src/operator/nn/pool.h (max pool backward
gives every tied in-window maximum the full window cotangent),
src/operator/nn/batch_norm.cc (train stats + affine, frozen path).
"""
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx  # noqa: F401  (platform setup via conftest)
from mxnet_tpu.ops.nn import _float_max_pool, _patches_max, batch_norm


def _ref_pool(x, kernel, stride, pads, shape, ch_last):
    if ch_last:
        perm = (0, len(shape) - 1) + tuple(range(1, len(shape) - 1))
        x = jnp.transpose(x, perm)
    out = _patches_max(x, kernel, stride, pads)
    if ch_last:
        inv = (0,) + tuple(range(2, len(shape))) + (1,)
        out = jnp.transpose(out, inv)
    return out


def _chlast_shape(shape):
    return (shape[0],) + tuple(shape[2:]) + (shape[1],)


# channels-first shapes; every case also runs as its channels-last twin
_POOL_CASES = [
    ((3, 3), (2, 2), ((1, 1), (1, 1)), (2, 3, 11, 11)),   # stem config, odd
    ((3, 3), (2, 2), ((1, 1), (1, 1)), (2, 3, 12, 12)),   # stem config, even
    ((3, 3), (2, 2), ((1, 2), (1, 2)), (2, 3, 10, 10)),   # full convention
    ((2,), (2,), ((0, 0),), (2, 3, 12)),                  # 1D
    ((2, 2, 2), (2, 2, 2), ((0, 0),) * 3, (1, 2, 6, 6, 6)),  # 3D
    ((7, 7), (3, 3), ((0, 0), (0, 0)), (2, 3, 20, 20)),   # >32 taps
    # 1x1 output whose window does NOT cover the input: the last row/col
    # is never read by forward and must get zero gradient (round-4 review)
    ((2, 2), (2, 2), ((0, 0), (0, 0)), (2, 3, 3, 3)),
    ((3, 3), (1, 1), ((1, 1), (1, 1)), (2, 3, 7, 7)),     # s1: one phase
    # stride over kernel: rows between windows are never read, get zero
    ((2, 2), (3, 3), ((0, 0), (0, 0)), (2, 3, 11, 10)),
    ((3, 3), (2, 2), ((0, 0), (0, 0)), (2, 3, 9, 9)),     # Inception, odd
    ((3, 3), (2, 2), ((0, 0), (0, 0)), (2, 3, 10, 10)),   # Inception, even
    ((3, 2), (2, 3), ((1, 0), (0, 2)), (2, 3, 9, 8)),     # mixed per axis
]


@pytest.mark.parametrize("ch_last", [False, True])
@pytest.mark.parametrize("kernel,stride,pads,shape", _POOL_CASES)
def test_max_pool_bwd_matches_patches(kernel, stride, pads, shape, ch_last):
    if ch_last:
        shape = _chlast_shape(shape)
    rng = np.random.RandomState(0)
    x = jnp.array(rng.randn(*shape).astype(np.float32))
    mp = _float_max_pool(kernel, stride, pads, ch_last)
    y = mp(x)
    ct = jnp.array(rng.randn(*y.shape).astype(np.float32))
    ref = _ref_pool(x, kernel, stride, pads, shape, ch_last)
    assert np.allclose(np.asarray(y), np.asarray(ref), atol=1e-6)
    dx = jax.grad(lambda t: jnp.vdot(mp(t), ct))(x)
    dx_ref = jax.grad(lambda t: jnp.vdot(
        _ref_pool(t, kernel, stride, pads, shape, ch_last), ct))(x)
    assert dx.shape == x.shape
    assert np.abs(np.asarray(dx) - np.asarray(dx_ref)).max() < 1e-6


@pytest.mark.parametrize("ch_last", [False, True])
def test_max_pool_bwd_bf16_matches_f32(ch_last):
    """bfloat16 stays bfloat16 and agrees with the float32 gradient of the
    same (bfloat16-representable, tie-rich) input at bfloat16's tolerance."""
    kernel, stride, pads = (3, 3), (2, 2), ((1, 1), (1, 1))
    shape = (2, 12, 12, 4) if ch_last else (2, 4, 12, 12)
    rng = np.random.RandomState(1)
    # a few levels after a ReLU: most windows hold their maximum twice
    x16 = jnp.maximum(jnp.round(jnp.array(rng.randn(*shape), jnp.bfloat16)), 0)
    mp = _float_max_pool(kernel, stride, pads, ch_last)
    ct16 = jnp.array(rng.randn(*mp(x16).shape), jnp.bfloat16)

    def grad(x, ct):
        return jax.grad(lambda t: jnp.vdot(mp(t), ct))(x)

    dx16 = grad(x16, ct16)
    assert dx16.dtype == jnp.bfloat16
    dx32 = np.asarray(grad(x16.astype(jnp.float32), ct16.astype(jnp.float32)))
    assert np.count_nonzero(dx32) > ct16.size  # more than one a window: ties
    err = np.abs(np.asarray(dx16.astype(jnp.float32)) - dx32).max()
    assert err <= 2 ** -6 * np.abs(dx32).max()


@pytest.mark.parametrize("ch_last", [False, True])
@pytest.mark.parametrize("kernel,stride,shape", [
    ((2, 2), (2, 2), (1, 1, 4, 4)),      # taps branch
    ((7, 7), (7, 7), (1, 1, 14, 14)),    # patches-fallback branch
    ((4, 4), (4, 4), (1, 1, 4, 4)),      # covering/global branch
])
def test_max_pool_tie_semantics_full_credit(kernel, stride, shape, ch_last):
    """Every tied maximum receives the full window cotangent (pool.h),
    identically in all three backward branches and both layouts."""
    pads = ((0, 0), (0, 0))
    if ch_last:
        shape = _chlast_shape(shape)
    x = jnp.ones(shape, jnp.float32)
    mp = _float_max_pool(kernel, stride, pads, ch_last)
    dx = jax.grad(lambda t: mp(t).sum())(x)
    assert np.allclose(np.asarray(dx), 1.0)


@pytest.mark.parametrize("ch_last", [False, True])
def test_max_pool_overlapping_ties_sum_windows(ch_last):
    """3x3/s2 pad 1 on a constant input: a position is credited once by
    every window that holds it (1, 2 or 4 of them), the phases' tap counts."""
    shape = (1, 6, 6, 1) if ch_last else (1, 1, 6, 6)
    mp = _float_max_pool((3, 3), (2, 2), ((1, 1), (1, 1)), ch_last)
    dx = jax.grad(lambda t: mp(t).sum())(jnp.ones(shape, jnp.float32))
    per_axis = np.array([1, 2, 1, 2, 1, 1])  # last row: no window below it
    assert np.array_equal(np.asarray(dx).reshape(6, 6),
                          np.outer(per_axis, per_axis))


@pytest.mark.parametrize("ch_last", [False, True])
def test_max_pool_bwd_lowering_keeps_layout(ch_last):
    """The backward computes in the layout it is given, at the output's
    size: the lowered gradient of the stem pool holds no transpose and no
    interior (zero-stuffing) pad of an activation (ISSUE 40)."""
    shape = (2, 12, 12, 8) if ch_last else (2, 8, 12, 12)
    mp = _float_max_pool((3, 3), (2, 2), ((1, 1), (1, 1)), ch_last)
    text = jax.jit(jax.grad(lambda t: mp(t).astype(jnp.float32).sum())).lower(
        jax.ShapeDtypeStruct(shape, jnp.bfloat16)).as_text()
    assert "transpose" not in text
    pads = re.findall(r"stablehlo\.pad.*", text)
    assert pads  # y and g are padded once each, at the edges only
    for line in pads:
        interior = re.search(r"interior = (?:array<i64: ([^>]*)>|\[([^\]]*)\])",
                             line)
        assert interior, line
        vals = [int(v) for v in (interior.group(1) or interior.group(2)
                                 ).split(",")]
        assert not any(vals), line


def _plain_bn(x, g, b, fix_gamma, axis=1, eps=1e-3):
    ax = axis % x.ndim
    red = tuple(i for i in range(x.ndim) if i != ax)
    bs = tuple(x.shape[ax] if i == ax else 1 for i in range(x.ndim))
    gg = jnp.ones_like(g) if fix_gamma else g
    mean = jnp.mean(x, axis=red)
    var = jnp.var(x, axis=red)
    xh = (x - mean.reshape(bs)) * jax.lax.rsqrt(var.reshape(bs) + eps)
    return gg.reshape(bs) * xh + b.reshape(bs)


@pytest.mark.parametrize("fix_gamma", [True, False])
@pytest.mark.parametrize("axis,shape", [(1, (4, 3, 5, 5)), (3, (4, 5, 5, 3))])
def test_bn_train_grads_match_autodiff(fix_gamma, axis, shape):
    rng = np.random.RandomState(0)
    C = shape[axis]
    x = jnp.array(rng.randn(*shape).astype(np.float32) + 1.5)
    g = jnp.array(rng.rand(C).astype(np.float32) + 0.5)
    b = jnp.array(rng.randn(C).astype(np.float32))
    mm, mv = jnp.zeros(C), jnp.ones(C)
    ct = jnp.array(rng.randn(*shape).astype(np.float32))

    def f_new(x, g, b):
        return jnp.vdot(batch_norm(x, g, b, mm, mv, eps=1e-3,
                                   fix_gamma=fix_gamma, axis=axis,
                                   is_train=True)[0], ct)

    def f_ref(x, g, b):
        return jnp.vdot(_plain_bn(x, g, b, fix_gamma, axis), ct)

    gn = jax.grad(f_new, argnums=(0, 1, 2))(x, g, b)
    gr = jax.grad(f_ref, argnums=(0, 1, 2))(x, g, b)
    for k, (n, r) in enumerate(zip(gn, gr)):
        if fix_gamma and k == 1:
            assert np.abs(np.asarray(n)).max() == 0
            continue
        denom = np.abs(np.asarray(r)).max() + 1e-8
        assert np.abs(np.asarray(n) - np.asarray(r)).max() / denom < 2e-4


def test_bn_frozen_grads_match_autodiff():
    rng = np.random.RandomState(1)
    x = jnp.array(rng.randn(4, 3, 5, 5).astype(np.float32))
    g = jnp.array(rng.rand(3).astype(np.float32) + 0.5)
    b = jnp.array(rng.randn(3).astype(np.float32))
    mm = jnp.array([0.1, -0.2, 0.3], jnp.float32)
    mv = jnp.array([0.5, 1.5, 1.0], jnp.float32)
    ct = jnp.array(rng.randn(4, 3, 5, 5).astype(np.float32))

    def f_new(x, g, b):
        return jnp.vdot(batch_norm(x, g, b, mm, mv, eps=1e-3,
                                   fix_gamma=False, use_global_stats=True,
                                   is_train=True)[0], ct)

    def f_ref(x, g, b):
        bs = (1, 3, 1, 1)
        xh = (x - mm.reshape(bs)) * jax.lax.rsqrt(mv.reshape(bs) + 1e-3)
        return jnp.vdot(g.reshape(bs) * xh + b.reshape(bs), ct)

    gn = jax.grad(f_new, argnums=(0, 1, 2))(x, g, b)
    gr = jax.grad(f_ref, argnums=(0, 1, 2))(x, g, b)
    for n, r in zip(gn, gr):
        denom = np.abs(np.asarray(r)).max() + 1e-8
        assert np.abs(np.asarray(n) - np.asarray(r)).max() / denom < 2e-4


def test_bn_second_order_reverse_over_reverse():
    """create_graph-style grad-of-grad must flow through the custom vjp."""
    rng = np.random.RandomState(2)
    x = jnp.array(rng.randn(4, 3, 5, 5).astype(np.float32))
    g = jnp.array(rng.rand(3).astype(np.float32) + 0.5)
    b = jnp.array(rng.randn(3).astype(np.float32))
    mm, mv = jnp.zeros(3), jnp.ones(3)
    h = jax.grad(lambda t: jnp.sum(jax.grad(lambda y: jnp.sum(
        batch_norm(y, g, b, mm, mv, is_train=True)[0] ** 2))(t) ** 2))(x)
    assert np.isfinite(np.asarray(h)).all()


def test_bn_bf16_keeps_tensor_dtype():
    """The round-4 contract: no f32 materialization of the activation —
    output dtype bf16 in, bf16 out, moving stats in their own dtype."""
    rng = np.random.RandomState(3)
    x = jnp.array(rng.randn(2, 3, 4, 4).astype(np.float32)).astype(jnp.bfloat16)
    g = jnp.ones(3, jnp.bfloat16)
    b = jnp.zeros(3, jnp.bfloat16)
    mm, mv = jnp.zeros(3, jnp.float32), jnp.ones(3, jnp.float32)
    out, nm, nv = batch_norm(x, g, b, mm, mv, is_train=True)
    assert out.dtype == jnp.bfloat16
    assert nm.dtype == jnp.float32 and nv.dtype == jnp.float32
    # and the result is still a faithful normalization
    o32 = np.asarray(out.astype(jnp.float32))
    assert abs(o32.mean()) < 0.1 and abs(o32.std() - 1.0) < 0.15


def _ref_ln(x, g, b, ax, eps=1e-5):
    mean = jnp.mean(x, axis=ax, keepdims=True)
    var = jnp.var(x, axis=ax, keepdims=True)
    nd = x.ndim
    bs = tuple(x.shape[ax % nd] if i == ax % nd else 1 for i in range(nd))
    return (x - mean) * jax.lax.rsqrt(var + eps) * g.reshape(bs) + b.reshape(bs)


@pytest.mark.parametrize("shape,ax", [((4, 7, 16), -1), ((4, 16), -1),
                                      ((3, 16, 5), 1)])
def test_layer_norm_grads_match_autodiff(shape, ax):
    from mxnet_tpu.ops.nn import layer_norm
    rng = np.random.RandomState(0)
    C = shape[ax % len(shape)]
    x = jnp.array((rng.randn(*shape) * 2 + 5).astype(np.float32))
    g = jnp.array(rng.rand(C).astype(np.float32) + 0.5)
    b = jnp.array(rng.randn(C).astype(np.float32))
    out = layer_norm(x, g, b, axis=ax, eps=1e-5)
    assert np.allclose(np.asarray(out),
                       np.asarray(_ref_ln(x, g, b, ax)), atol=2e-4)
    ct = jnp.array(rng.randn(*shape).astype(np.float32))
    gn = jax.grad(lambda *a: jnp.vdot(
        layer_norm(*a, axis=ax, eps=1e-5), ct), argnums=(0, 1, 2))(x, g, b)
    gr = jax.grad(lambda *a: jnp.vdot(
        _ref_ln(*a, ax), ct), argnums=(0, 1, 2))(x, g, b)
    for n, r in zip(gn, gr):
        denom = np.abs(np.asarray(r)).max() + 1e-8
        assert np.abs(np.asarray(n) - np.asarray(r)).max() / denom < 3e-4


def test_layer_norm_bf16_keeps_tensor_dtype():
    from mxnet_tpu.ops.nn import layer_norm
    rng = np.random.RandomState(1)
    x = jnp.array(rng.randn(4, 7, 16).astype(np.float32)).astype(jnp.bfloat16)
    g = jnp.ones(16, jnp.bfloat16)
    b = jnp.zeros(16, jnp.bfloat16)
    o = layer_norm(x, g, b, axis=-1)
    assert o.dtype == jnp.bfloat16
    o32 = np.asarray(o.astype(jnp.float32))
    assert abs(o32.mean()) < 0.05 and abs(o32.std() - 1.0) < 0.1
