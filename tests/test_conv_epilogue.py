"""Conv epilogue (training BatchNorm(+add)(+ReLU), ops/nn.py `_bn_act`) +
space-to-depth stem tests: fwd/bwd parity of the one lowering against an
independent jnp oracle (fp32 and bf16, both layouts), op-level and
model-zoo-level graph equivalence of the fused ops, and the stem
weight-space transform (reference strategy: check_consistency, SURVEY §4)."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon
from mxnet_tpu.ops import pallas_kernels as pk


EPS = 1e-3


def _epi_oracle(x, gamma, beta, res, fix_gamma=False, relu=True):
    """Unfused jnp BN(batch stats)+add+relu — the numerics oracle."""
    import jax
    import jax.numpy as jnp

    red = tuple(range(x.ndim - 1))
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=red)
    var = jnp.var(xf, axis=red)
    inv = jax.lax.rsqrt(var + EPS)
    g = jnp.ones_like(inv) if fix_gamma else gamma.astype(jnp.float32)
    out = (xf - mean) * inv * g + beta.astype(jnp.float32)
    if res is not None:
        out = out + res.astype(jnp.float32)
    if relu:
        out = jnp.maximum(out, 0.0)
    return out.astype(x.dtype), mean, var


def _epi_inputs(shape=(2, 5, 6, 19), seed=0, dtype=np.float32, scale=2.0,
                offset=3.0):
    rng = np.random.RandomState(seed)
    n = int(np.prod(shape))
    x = (rng.randn(*shape) * scale + offset).astype(dtype)
    res = rng.randn(*shape).astype(dtype)
    c = shape[-1]
    gamma = (rng.rand(c) + 0.5).astype(np.float32)
    beta = rng.randn(c).astype(np.float32)
    del n
    return x, gamma, beta, res


FORMS = [(False, True), (True, True), (False, False)]
FORM_IDS = ["bn_relu", "bn_add_relu", "bn"]


def _bn_act_train(x, gamma, beta, res, fix_gamma=False, relu=True, axis=-1):
    """ops/nn.py `_bn_act` in training mode, the one lowering behind
    BatchNorm / BatchNormRelu / BatchNormAddRelu: (out, batch mean, batch
    var), the statistics recovered from the moving-stat outputs."""
    import jax.numpy as jnp

    from mxnet_tpu.ops import nn as N

    c = x.shape[axis]
    out, mm, mv = N._bn_act(x, res, gamma, beta, jnp.zeros((c,), jnp.float32),
                            jnp.zeros((c,), jnp.float32), EPS, 0.0,
                            fix_gamma, False, axis, "relu" if relu else None,
                            True)
    return out, mm, mv


@pytest.mark.parametrize("has_res,relu", FORMS, ids=FORM_IDS)
def test_bn_act_train_forward_matches_oracle(has_res, relu):
    import jax.numpy as jnp

    x, gamma, beta, res = _epi_inputs()
    xa, ga, ba = jnp.asarray(x), jnp.asarray(gamma), jnp.asarray(beta)
    ra = jnp.asarray(res) if has_res else None
    out, mean, var = _bn_act_train(xa, ga, ba, ra, relu=relu)
    ref, mref, vref = _epi_oracle(xa, ga, ba, ra, relu=relu)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(mean), np.asarray(mref),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(var), np.asarray(vref),
                               rtol=2e-5, atol=2e-5)


def test_bn_act_train_fix_gamma():
    import jax.numpy as jnp

    x, gamma, beta, _ = _epi_inputs(seed=1)
    out, _, _ = _bn_act_train(jnp.asarray(x), jnp.asarray(gamma),
                              jnp.asarray(beta), None, fix_gamma=True)
    ref, _, _ = _epi_oracle(jnp.asarray(x), jnp.asarray(gamma),
                            jnp.asarray(beta), None, fix_gamma=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("has_res,relu", FORMS, ids=FORM_IDS)
def test_bn_act_train_gradients_match_oracle(has_res, relu):
    """The hand-written `_bn_train_bwd` (and autodiff of the add and the
    ReLU behind it) against autodiff of the oracle."""
    import jax
    import jax.numpy as jnp

    x, gamma, beta, res = _epi_inputs(seed=2)
    args = [jnp.asarray(x), jnp.asarray(gamma), jnp.asarray(beta)]
    if has_res:
        args.append(jnp.asarray(res))
    nargs = len(args)

    def loss_of(fn):
        def loss(*a):
            out, _, _ = fn(a[0], a[1], a[2], a[3] if has_res else None,
                           relu=relu)
            return jnp.sum(out.astype(jnp.float32) ** 2)
        return loss

    gp = jax.grad(loss_of(_bn_act_train), argnums=tuple(range(nargs)))(*args)
    gr = jax.grad(loss_of(_epi_oracle), argnums=tuple(range(nargs)))(*args)
    for name, a, b in zip("x gamma beta res".split(), gp, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-4, err_msg=name)


def test_bn_act_train_bf16():
    """bf16 in, bf16 out (the AMP step's dtype), float32 statistics."""
    import jax
    import jax.numpy as jnp

    x, gamma, beta, res = _epi_inputs(seed=3)
    xb = jnp.asarray(x, jnp.bfloat16)
    rb = jnp.asarray(res, jnp.bfloat16)
    ga, ba = jnp.asarray(gamma), jnp.asarray(beta)
    out, mean, var = _bn_act_train(xb, ga, ba, rb)
    assert out.dtype == jnp.bfloat16
    assert mean.dtype == jnp.float32 and var.dtype == jnp.float32
    ref, mref, _ = _epi_oracle(xb, ga, ba, rb)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=5e-2, atol=1e-1)
    np.testing.assert_allclose(np.asarray(mean), np.asarray(mref),
                               rtol=1e-4, atol=1e-4)

    def loss(x, g, b, r):
        out, _, _ = _bn_act_train(x, g, b, r)
        return jnp.sum(out.astype(jnp.float32))

    grads = jax.grad(loss, argnums=(0, 1, 2, 3))(xb, ga, ba, rb)
    assert grads[0].dtype == jnp.bfloat16
    for g in grads:
        assert np.isfinite(np.asarray(g, np.float32)).all()


def test_bn_act_train_wide_channel_and_tall():
    """C not a multiple of the 128-lane tile and many rows a channel."""
    import jax.numpy as jnp

    x, gamma, beta, _ = _epi_inputs(shape=(2, 20, 20, 130), seed=4)
    out, mean, var = _bn_act_train(jnp.asarray(x), jnp.asarray(gamma),
                                   jnp.asarray(beta), None)
    ref, mref, vref = _epi_oracle(jnp.asarray(x), jnp.asarray(gamma),
                                  jnp.asarray(beta), None)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(var), np.asarray(vref),
                               rtol=2e-4, atol=2e-4)


def test_bn_act_train_channels_first_matches_channels_last():
    """The one lowering serves both layouts: axis=1 on the transposed
    input gives the transposed output, the same statistics and the same
    gradients."""
    import jax
    import jax.numpy as jnp

    x, gamma, beta, res = _epi_inputs(seed=5)
    xa, ra = jnp.asarray(x), jnp.asarray(res)
    ga, ba = jnp.asarray(gamma), jnp.asarray(beta)

    def to_cf(a):
        return jnp.transpose(a, (0, 3, 1, 2))

    def loss(axis):
        def f(x, g, b, r):
            out, mean, var = _bn_act_train(x, g, b, r, axis=axis)
            return jnp.sum(out ** 2), (out, mean, var)
        return jax.value_and_grad(f, argnums=(0, 1, 2, 3), has_aux=True)

    (_, (o_cl, m_cl, v_cl)), g_cl = loss(-1)(xa, ga, ba, ra)
    (_, (o_cf, m_cf, v_cf)), g_cf = loss(1)(to_cf(xa), ga, ba, to_cf(ra))
    np.testing.assert_allclose(np.asarray(o_cf), np.asarray(to_cf(o_cl)),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(m_cf), np.asarray(m_cl),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(v_cf), np.asarray(v_cl),
                               rtol=2e-5, atol=2e-5)
    for name, a, b in zip("x gamma beta res".split(), g_cf, g_cl):
        b = to_cf(b) if b.ndim == 4 else b
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-4, err_msg=name)


def test_conv_epilogue_option_is_gone():
    """The kernel's option left with it: in no registry, no document and
    no module (a rule that is gone needs no user to set it)."""
    import glob
    import os

    from mxnet_tpu import env

    name = "MXTPU_PALLAS_" + "CONV_EPILOGUE"
    assert name not in env.names()
    root = os.path.join(os.path.dirname(__file__), "..")
    files = [os.path.join(root, "chip_smoke.py")]
    files += glob.glob(os.path.join(root, "docs", "**", "*.md"),
                       recursive=True)
    files += glob.glob(os.path.join(root, "mxnet_tpu", "**", "*.py"),
                       recursive=True)
    assert len(files) > 100
    holds = []
    for path in files:
        with open(path, encoding="utf-8") as f:
            if name in f.read():
                holds.append(os.path.relpath(path, root))
    assert not holds, holds
    assert not hasattr(pk, "conv_epilogue")


def test_lstm_layer_fits_budgets_backward():
    """ADVICE round-5 #2: the check sizes against max(fwd, bwd) per-step
    blocks. The word-LM bench shape must stay fused; a budget that only
    counted forward terms would be strictly looser than one that includes
    the (larger, for bf16) backward terms."""
    assert pk.lstm_layer_fits(32, 650, 2)       # word-LM bench shape
    assert not pk.lstm_layer_fits(32, 4096, 2)  # w_hh alone ~128 MB


def test_fused_bn_ops_inference_parity():
    """nd-level: the fused ops equal the composed unfused graph in
    inference (frozen-stats) mode."""
    np.random.seed(6)
    x = mx.nd.array(np.random.randn(2, 8, 4, 4).astype(np.float32))
    res = mx.nd.array(np.random.randn(2, 8, 4, 4).astype(np.float32))
    g = mx.nd.array(np.random.rand(8).astype(np.float32) + 0.5)
    b = mx.nd.array(np.random.randn(8).astype(np.float32))
    mm = mx.nd.array(np.random.randn(8).astype(np.float32) * 0.1)
    mv = mx.nd.array(np.random.rand(8).astype(np.float32) + 0.5)
    ref = mx.nd.relu(mx.nd.BatchNorm(x, g, b, mm, mv, fix_gamma=False))
    out = mx.nd.BatchNormRelu(x, g, b, mm, mv, fix_gamma=False)
    np.testing.assert_allclose(out.asnumpy(), ref.asnumpy(), rtol=1e-6,
                               atol=1e-6)
    ref2 = mx.nd.relu(mx.nd.BatchNorm(x, g, b, mm, mv, fix_gamma=False) + res)
    out2 = mx.nd.BatchNormAddRelu(x, res, g, b, mm, mv, fix_gamma=False)
    np.testing.assert_allclose(out2.asnumpy(), ref2.asnumpy(), rtol=1e-6,
                               atol=1e-6)


def _copy_params(src, dst):
    for k, v in dst.collect_params().items():
        v.set_data(src.collect_params()[k].data())


def _tiny_resnet(version, block_name, fuse_epilogue, prefix, stem_s2d=False):
    """Tiny 2-stage net through the real zoo classes — every fused block
    type and the real stem, at a CPU-friendly size."""
    from mxnet_tpu.gluon.model_zoo.vision.resnet import (
        ResNetV1, ResNetV2, resnet_block_versions)

    cls = ResNetV1 if version == 1 else ResNetV2
    block = resnet_block_versions[version - 1][block_name]
    return cls(block, [1, 1], [8, 8, 16], classes=10,
               fuse_epilogue=fuse_epilogue, stem_s2d=stem_s2d,
               prefix=prefix)


@pytest.mark.parametrize("version,block_name",
                         [(1, "bottle_neck"), (2, "basic_block")])
def test_resnet_fused_epilogue_graph_equivalence(version, block_name):
    """Zoo-level: the fused-epilogue resnet has IDENTICAL parameter names
    and matches the reference graph in both inference and training
    (forward + a weight gradient)."""
    np.random.seed(7)
    x = mx.nd.array(np.random.randn(2, 3, 32, 32).astype(np.float32))
    pre = "a%d%s_" % (version, block_name[0])
    n1 = _tiny_resnet(version, block_name, False, pre)
    n2 = _tiny_resnet(version, block_name, True, pre)
    n1.initialize()
    n2.initialize()
    n1(x)
    n2(x)
    assert sorted(n1.collect_params()) == sorted(n2.collect_params())
    _copy_params(n1, n2)
    y1 = n1(x)
    y2 = n2(x)
    np.testing.assert_allclose(y1.asnumpy(), y2.asnumpy(), rtol=1e-5,
                               atol=1e-5)
    with autograd.record():
        z1 = n1(x)
        z1.backward()
    with autograd.record():
        z2 = n2(x)
        z2.backward()
    np.testing.assert_allclose(z1.asnumpy(), z2.asnumpy(), rtol=1e-5,
                               atol=1e-5)
    wname = [k for k in n1.collect_params() if k.endswith("weight")][0]
    np.testing.assert_allclose(n1.collect_params()[wname].grad().asnumpy(),
                               n2.collect_params()[wname].grad().asnumpy(),
                               rtol=1e-4, atol=1e-4)


# --- space-to-depth stem ----------------------------------------------------


@pytest.mark.parametrize("layout", ["NCHW", "NHWC"])
def test_stem_weight_transform_exact(layout):
    """stem_weight_to_s2d: s2d + (2,1) pad + 4x4/s1 VALID conv reproduces
    the 7x7/s2/pad3 conv EXACTLY (both layouts, fp32)."""
    import jax.numpy as jnp
    from jax import lax

    from mxnet_tpu.gluon.model_zoo.vision.resnet import stem_weight_to_s2d
    from mxnet_tpu.ops import tensor as T

    rng = np.random.RandomState(8)
    ch_last = layout == "NHWC"
    x = rng.randn(2, 3, 32, 32).astype(np.float32)
    w7 = (rng.randn(8, 3, 7, 7) * 0.1).astype(np.float32)
    if ch_last:
        x = np.transpose(x, (0, 2, 3, 1)).copy()
        w7 = np.transpose(w7, (0, 2, 3, 1)).copy()
        spec = ("NHWC", "OHWI", "NHWC")
        pads = ((0, 0), (2, 1), (2, 1), (0, 0))
    else:
        spec = ("NCHW", "OIHW", "NCHW")
        pads = ((0, 0), (0, 0), (2, 1), (2, 1))
    dn = lax.conv_dimension_numbers(x.shape, w7.shape, spec)
    ref = lax.conv_general_dilated(jnp.asarray(x), jnp.asarray(w7), (2, 2),
                                   [(3, 3), (3, 3)], dimension_numbers=dn)
    z = T.space_to_depth(jnp.asarray(x), block_size=2, layout=layout)
    z = jnp.pad(z, pads)
    w4 = jnp.asarray(stem_weight_to_s2d(w7, layout))
    dn2 = lax.conv_dimension_numbers(z.shape, w4.shape, spec)
    out = lax.conv_general_dilated(z, w4, (1, 1), [(0, 0), (0, 0)],
                                   dimension_numbers=dn2)
    assert out.shape == ref.shape
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_stem_weight_transform_bf16_and_bad_kernel():
    import jax.numpy as jnp

    from mxnet_tpu.base import MXNetError
    from mxnet_tpu.gluon.model_zoo.vision.resnet import stem_weight_to_s2d

    w = np.random.randn(8, 3, 7, 7).astype(np.float32)
    w4 = stem_weight_to_s2d(jnp.asarray(w, jnp.bfloat16))
    assert w4.shape == (8, 12, 4, 4)
    with pytest.raises(MXNetError):
        stem_weight_to_s2d(np.zeros((8, 3, 5, 5), np.float32))


@pytest.mark.parametrize("channels_last", [False, True])
def test_resnet_s2d_stem_checkpoint_convertible(channels_last):
    """Zoo-level: a 7x7-stem checkpoint converted via convert_stem_params
    loads into the s2d-stem model and produces the same outputs."""
    from mxnet_tpu.gluon.model_zoo.vision.resnet import convert_stem_params

    np.random.seed(9)
    x = np.random.randn(2, 3, 32, 32).astype(np.float32)
    if channels_last:
        x = np.transpose(x, (0, 2, 3, 1)).copy()
        layout = "NHWC"
        scope = gluon.nn.layout_scope()
    else:
        layout = "NCHW"
        scope = gluon.nn.layout_scope(channels_last=False)
    xa = mx.nd.array(x)
    with scope:
        n1 = _tiny_resnet(1, "basic_block", False,
                          "s%d_" % channels_last, stem_s2d=False)
        n2 = _tiny_resnet(1, "basic_block", False,
                          "s%d_" % channels_last, stem_s2d=True)
    n1.initialize()
    n2.initialize()
    n1(xa)
    n2(xa)
    params = {k: v.data().asnumpy() for k, v in n1.collect_params().items()}
    conv = convert_stem_params(params, layout=layout)
    for k, v in n2.collect_params().items():
        v.set_data(mx.nd.array(conv[k]))
    y1 = n1(xa)
    y2 = n2(xa)
    np.testing.assert_allclose(y1.asnumpy(), y2.asnumpy(), rtol=2e-4,
                               atol=2e-4)


def test_resnet_s2d_stem_trains():
    """The s2d stem differentiates (the 4x4/s1 VALID conv is the stride-1
    shape class that motivated the rewrite) and its weight gets a finite
    gradient."""
    np.random.seed(10)
    x = mx.nd.array(np.random.randn(2, 3, 32, 32).astype(np.float32))
    net = _tiny_resnet(1, "basic_block", True, "t_", stem_s2d=True)
    net.initialize()
    net(x)
    with autograd.record():
        y = net(x)
        y.backward()
    wname = [k for k in net.collect_params()
             if k.endswith("conv2d0_weight")][0]
    w = net.collect_params()[wname]
    assert w.shape[1] == 12 and w.shape[2:] == (4, 4)
    gw = w.grad().asnumpy()
    assert np.isfinite(gw).all() and np.abs(gw).max() > 0
