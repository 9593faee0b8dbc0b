"""Plain reference: ResNet v1 (He et al. 2015) training steps in jax.numpy.

Float32, training-mode batch norm over the whole batch, softmax cross
entropy, SGD with momentum and weight decay as MXNet applies them
(``mom = momentum*mom - lr*(grad + wd*w); w += mom``, weight decay on every
parameter), every contraction at ``Precision.HIGHEST``.  NHWC, weights
``(out, kh, kw, in)``.  Each residual block is rematerialised, and each stage
around its blocks, so batch 512 at 224x224 fits one 16 GB chip beside nothing
else.

It imports nothing of the program.  Parameter names are the zoo's without the
net's own prefix (``conv2d0_weight``, ``stage1_batchnorm3_gamma``,
``dense0_bias``), so the model factory can hand the same seeded weights to
the program by name.

Departures from the paper, following the code under test: a stage's stride is
in its first 1x1 conv; no bias in any conv; batch-norm eps 1e-5.

``precision`` selects what the contractions see:
  "float32"  the reference proper
  "bfloat16" every stored activation, every cotangent and every weight where
             it is used rounded to bfloat16: what AMP does to the program
  "fp8"      the same in float8_e4m3 with a per-tensor scale, the precision
             below bf16 AMP: the control that `correct` must fail
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

EPS = 1e-5
HIGHEST = lax.Precision.HIGHEST


# --------------------------------------------------------------------------
# structure
# --------------------------------------------------------------------------

def structure(sizes):
    """[(conv name, bn name, shape (O,kh,kw,I), stride, pad)] of the stem and
    of every block, in the zoo's naming order; blocks as
    (stage, [body convs], downsample conv or None)."""
    layers, channels = sizes["layers"], sizes["channels"]
    stem = ("conv2d0", "batchnorm0", (channels[0], 7, 7, 3), 2, 3)
    blocks = []
    cin = channels[0]
    for i, n in enumerate(layers):
        cout, mid = channels[i + 1], channels[i + 1] // 4
        pre, k = "stage%d_" % (i + 1), 0
        for b in range(n):
            stride = 2 if (b == 0 and i > 0) else 1
            body = [(pre + "conv2d%d" % k, pre + "batchnorm%d" % k,
                     (mid, 1, 1, cin), stride, 0),
                    (pre + "conv2d%d" % (k + 1), pre + "batchnorm%d" % (k + 1),
                     (mid, 3, 3, mid), 1, 1),
                    (pre + "conv2d%d" % (k + 2), pre + "batchnorm%d" % (k + 2),
                     (cout, 1, 1, mid), 1, 0)]
            k += 3
            down = None
            if b == 0 and cin != cout:
                down = (pre + "conv2d%d" % k, pre + "batchnorm%d" % k,
                        (cout, 1, 1, cin), stride, 0)
                k += 1
            blocks.append((body, down))
            cin = cout
    return stem, blocks, ("dense0", (sizes["classes"], cin))


def parameter_shapes(sizes):
    """{name: shape} of every trainable parameter and batch-norm buffer."""
    stem, blocks, (dense, dshape) = structure(sizes)
    convs = [stem] + [c for body, down in blocks
                      for c in body + ([down] if down else [])]
    shapes = {}
    for conv, bn, shape, _, _ in convs:
        shapes[conv + "_weight"] = shape
        for leaf in ("gamma", "beta", "running_mean", "running_var"):
            shapes["%s_%s" % (bn, leaf)] = (shape[0],)
    shapes[dense + "_weight"] = dshape
    shapes[dense + "_bias"] = (dshape[0],)
    return shapes


def trainable(name):
    return not name.endswith(("_running_mean", "_running_var"))


# --------------------------------------------------------------------------
# weights and data from the seed, on the device, one jitted call each
# --------------------------------------------------------------------------

def make_weights(seed, sizes):
    """Seeded weights: He-normal convs, a small-normal dense layer, gamma 1
    (0.5 on the batch norm that closes a block, so that 16 blocks do not
    double the signal 16 times), beta 0, running mean 0 and variance 1."""
    shapes = parameter_shapes(sizes)
    _, blocks, _ = structure(sizes)
    closing = {body[-1][1] + "_gamma" for body, _ in blocks}
    names = sorted(shapes)

    @jax.jit
    def make(key):
        out = {}
        for i, name in enumerate(names):
            shape = shapes[name]
            k = jax.random.fold_in(key, i)
            if name.endswith("_weight") and len(shape) == 4:
                fan_in = shape[1] * shape[2] * shape[3]
                out[name] = jax.random.normal(k, shape, jnp.float32) \
                    * (2.0 / fan_in) ** 0.5
            elif name.endswith("_weight"):
                out[name] = jax.random.normal(k, shape, jnp.float32) * 0.01
            elif name.endswith(("_gamma", "_running_var")):
                out[name] = jnp.full(shape, 0.5 if name in closing else 1.0,
                                     jnp.float32)
            else:
                out[name] = jnp.zeros(shape, jnp.float32)
        return out

    return make(jax.random.PRNGKey(seed % (2 ** 31)))


def make_batches(seed, sizes, count):
    """``count`` distinct host batches ``(x, y)``: float32 NHWC images, the
    dtype ImageRecordIter hands train_imagenet.py, and float32 class ids.
    Every row differs; the four quarters of a batch differ in scale and
    offset, so batch-norm statistics taken per shard of a batch split four
    ways could not pass for global ones."""
    import numpy as np

    b, hw, classes = sizes["batch"], sizes["hw"], sizes["classes"]

    @jax.jit
    def make(key):
        kx, ky = jax.random.split(key)
        x = jax.random.normal(kx, (b, hw, hw, 3), jnp.float32)
        g = (jnp.arange(b) * 4 // b).astype(jnp.float32)[:, None, None, None]
        x = x * (0.5 + 0.5 * g) + 0.3 * (g - 1.5)
        y = jax.random.randint(ky, (b,), 0, classes).astype(jnp.float32)
        return x, y

    key = jax.random.PRNGKey((seed + 0x5EED) % (2 ** 31))
    out = []
    for i in range(count):
        x, y = make(jax.random.fold_in(key, i))
        out.append((np.asarray(x), np.asarray(y)))
    return out


# --------------------------------------------------------------------------
# the forward pass and the steps
# --------------------------------------------------------------------------

def _quantize(dtype):
    """Round to ``dtype`` and back; float8 with a per-tensor scale, as fp8
    training recipes do."""
    if dtype == jnp.bfloat16:
        return lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)

    def q8(a):
        scale = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / 448.0
        return (a / scale).astype(dtype).astype(jnp.float32) * scale
    return q8


def _round(precision):
    """``q(a)``: what a tensor looks like once it has been kept in the
    precision, on the way forward and, for its cotangent, on the way back.
    Applied to every activation the network stores and to every weight where
    it is used, which is what mixed precision does to the program (AMP keeps
    activations, batch-norm arithmetic and cotangents in the low type; the
    master weights, the gradients' accumulation and the update stay
    float32)."""
    if precision == "float32":
        return lambda a: a
    dtype = {"bfloat16": jnp.bfloat16, "fp8": jnp.float8_e4m3fn}.get(precision)
    if dtype is None:
        raise ValueError("unknown precision %r" % (precision,))
    rnd = _quantize(dtype)

    @jax.custom_vjp
    def q(a):
        return rnd(a)

    q.defvjp(lambda a: (rnd(a), None), lambda _, ct: (rnd(ct),))
    return q


def _conv(x, w, stride, pad, q):
    return lax.conv_general_dilated(
        q(x), q(w), (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NHWC", "OHWI", "NHWC"), precision=HIGHEST)


def _bn(x, gamma, beta):
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    return (x - mean) * lax.rsqrt(var + EPS) * gamma + beta


def _conv_bn(x, w, conv, q):
    name, bn, _, stride, pad = conv
    return q(_bn(q(_conv(x, w[name + "_weight"], stride, pad, q)),
                 w[bn + "_gamma"], w[bn + "_beta"]))


def _max_pool_3x3_s2(x):
    return lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                             [(0, 0), (1, 1), (1, 1), (0, 0)])


def loss_fn(w, x, y, sizes, precision="float32"):
    """Mean softmax cross entropy of the batch, training-mode batch norm."""
    q = _round(precision)
    stem, blocks, (dense, _) = structure(sizes)

    @jax.checkpoint
    def run_stem(x, ws):
        return _max_pool_3x3_s2(jax.nn.relu(_conv_bn(q(x), ws, stem, q)))

    def block_fn(body, down):
        @jax.checkpoint
        def run(x, ws):
            r = x if down is None else _conv_bn(x, ws, down, q)
            o = jax.nn.relu(_conv_bn(x, ws, body[0], q))
            o = jax.nn.relu(_conv_bn(o, ws, body[1], q))
            return q(jax.nn.relu(_conv_bn(o, ws, body[2], q) + r))
        return run

    def pick(convs):
        return {k: w[k] for c in convs
                for k in (c[0] + "_weight", c[1] + "_gamma", c[1] + "_beta")}

    # blocks are rematerialised one by one, and each stage as a whole around
    # them, so that what is kept is the stages' inputs and, while one stage is
    # differentiated, its own blocks' inputs: batch 512 fits beside nothing
    stages, cout = [], None
    for body, down in blocks:
        if body[-1][2][0] != cout:
            stages.append([])
            cout = body[-1][2][0]
        stages[-1].append((body, down))

    def stage_fn(members):
        @jax.checkpoint
        def run(x, ws):
            for (body, down), wb in zip(members, ws):
                x = block_fn(body, down)(x, wb)
            return x
        return run

    h = run_stem(x, pick([stem]))
    for members in stages:
        h = stage_fn(members)(h, [pick(body + ([down] if down else []))
                                  for body, down in members])
    h = jnp.mean(h, axis=(1, 2))
    z = jnp.dot(q(h), q(w[dense + "_weight"]).T, precision=HIGHEST) \
        + w[dense + "_bias"]
    lse = jax.nn.logsumexp(z, axis=-1)
    picked = jnp.take_along_axis(z, y.astype(jnp.int32)[:, None], axis=1)[:, 0]
    return jnp.mean(lse - picked)


def _leaf_norms(tree, names):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(tree[n]))) for n in names])


@functools.lru_cache(maxsize=None)
def _step_fn(sizes_key, precision, lr, momentum, wd):
    sizes = dict(sizes_key)
    sizes["layers"], sizes["channels"] = list(sizes["layers"]), \
        list(sizes["channels"])

    def step(w, mom, x, y):
        train = {k: v for k, v in w.items() if trainable(k)}
        loss, g = jax.value_and_grad(
            lambda t: loss_fn(t, x, y, sizes, precision))(train)
        names = sorted(train)
        gnorm = _leaf_norms(g, names)
        new_mom = {k: momentum * mom[k] - lr * (g[k] + wd * train[k])
                   for k in names}
        new_w = dict(w)
        for k in names:
            new_w[k] = train[k] + new_mom[k]
        return loss, gnorm, new_w, new_mom

    return jax.jit(step, donate_argnums=(0, 1))


def _freeze(sizes):
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in sizes.items()
                        if k in ("layers", "channels", "classes", "hw",
                                 "batch")))


def follow(seed, sizes, batches, optimizer, steps=3, precision="float32"):
    """Take ``steps`` SGD steps from the seeded weights on ``batches`` and
    return what `correct` compares: each step's loss, the per-leaf norm of
    the first gradient, and the per-leaf norm of the parameters' change
    after the last step, leaves in sorted-name order."""
    import numpy as np

    w0 = make_weights(seed, sizes)
    names = sorted(k for k in w0 if trainable(k))
    w = {k: jnp.array(v, copy=True) for k, v in w0.items()}
    mom = {k: jnp.zeros_like(w0[k]) for k in names}
    step = _step_fn(_freeze(sizes), precision,
                    float(optimizer["learning_rate"]),
                    float(optimizer["momentum"]), float(optimizer["wd"]))
    losses, gnorm1 = [], None
    for i in range(steps):
        x, y = batches[i]
        loss, gnorm, w, mom = step(w, mom, jnp.asarray(x), jnp.asarray(y))
        losses.append(loss)
        if i == 0:
            gnorm1 = gnorm
    dw = jax.jit(lambda a, b: jnp.stack(
        [jnp.sqrt(jnp.sum(jnp.square(a[n] - b[n]))) for n in names]))(w, w0)
    return {"names": names,
            "losses": [float(v) for v in losses],
            "grad_norms": np.asarray(gnorm1, np.float64),
            "dw_norms": np.asarray(dw, np.float64)}
