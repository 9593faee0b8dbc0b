"""Random sampling ops.

Reference: src/operator/random/sample_op.cc (uniform/normal/gamma/exponential/
poisson/negative_binomial/randint), multisample_op.cc, shuffle_op.cc,
sample_multinomial_op.cc. TPU-native: every op consumes one threefry subkey
from the global chain (mxnet_tpu/random.py) — stateless, reproducible, and
traceable (the key is a runtime input under jit, SURVEY §7.8(e))."""
from __future__ import annotations

from . import register

import jax
import jax.numpy as jnp
import numpy as np

from ..base import np_dtype, device_int_dtype as _device_int_dtype


@register("_random_uniform", needs_rng=True, aliases=("uniform", "random_uniform"))
def random_uniform(rng, low=0.0, high=1.0, shape=(), dtype="float32"):
    return jax.random.uniform(rng, shape, np_dtype(dtype), low, high)


@register("_random_normal", needs_rng=True, aliases=("normal", "random_normal"))
def random_normal(rng, loc=0.0, scale=1.0, shape=(), dtype="float32"):
    return jax.random.normal(rng, shape, np_dtype(dtype)) * scale + loc


@register("_random_gamma", needs_rng=True, aliases=("gamma_sample",))
def random_gamma(rng, alpha=1.0, beta=1.0, shape=(), dtype="float32"):
    return jax.random.gamma(rng, alpha, shape, np_dtype(dtype)) * beta


@register("_random_exponential", needs_rng=True)
def random_exponential(rng, lam=1.0, shape=(), dtype="float32"):
    return jax.random.exponential(rng, shape, np_dtype(dtype)) / lam


@register("_random_poisson", needs_rng=True)
def random_poisson(rng, lam=1.0, shape=(), dtype="float32"):
    return jax.random.poisson(rng, lam, shape).astype(np_dtype(dtype))


@register("_random_negative_binomial", needs_rng=True)
def random_negative_binomial(rng, k=1, p=1.0, shape=(), dtype="float32"):
    k1, k2 = jax.random.split(rng)
    lam = jax.random.gamma(k1, k, shape) * ((1 - p) / p)
    return jax.random.poisson(k2, lam, shape).astype(np_dtype(dtype))


@register("_random_generalized_negative_binomial", needs_rng=True)
def random_gen_negative_binomial(rng, mu=1.0, alpha=1.0, shape=(), dtype="float32"):
    k1, k2 = jax.random.split(rng)
    lam = jax.random.gamma(k1, 1.0 / alpha, shape) * (alpha * mu)
    return jax.random.poisson(k2, lam, shape).astype(np_dtype(dtype))


@register("_random_randint", needs_rng=True, aliases=("randint",))
def random_randint(rng, low=0, high=1, shape=(), dtype="int32"):
    return jax.random.randint(rng, shape, low, high, np_dtype(dtype))


@register("_sample_unique_zipfian", needs_rng=True,
          size_attrs=("range_max",))
def sample_unique_zipfian(rng, range_max=1, shape=()):
    """Unique draws per row from the zipfian (log-uniform) class
    distribution p(k) ∝ log((k+2)/(k+1)) — reference:
    src/operator/random/unique_sample_op.cc (draws until unique). The
    TPU-native version samples WITHOUT replacement in one shot via the
    Gumbel-top-k trick, which is both compile-friendly (static shapes, no
    rejection loop) and exactly equivalent in distribution."""
    rows, k = (shape[0], shape[1]) if len(shape) == 2 else (1, int(shape[0]))
    if rows * range_max <= (1 << 24):
        # exact sampling without replacement: Gumbel-top-k over the class
        # log-probs (equivalent in distribution to draw-until-unique).
        # Covers every case where k is comparable to range_max.
        classes = jnp.arange(range_max)
        logp = jnp.log(jnp.log((classes + 2.0) / (classes + 1.0)))
        g = jax.random.gumbel(rng, (rows, range_max))
        _, idx = jax.lax.top_k(logp[None, :] + g, k)
        return idx.reshape(shape).astype(_device_int_dtype())
    # Huge vocab (sampled-softmax scale, k << range_max): materializing
    # (rows, range_max) would be GBs. Oversample m = 4k+32 i.i.d. zipfian
    # draws via the inverse CDF, deduplicate per row (uniques compacted
    # first), and take the first k uniques. Fewer than k uniques would need
    # >3k+32 collisions among m draws over a range of millions — vanishing
    # probability; in that tail the row keeps duplicates rather than
    # fabricating out-of-distribution fillers (documented divergence from
    # the reference's unbounded draw-until-unique loop).
    m = 4 * k + 32
    u = jax.random.uniform(rng, (rows, m))
    draws = (jnp.exp(u * jnp.log(float(range_max + 1))) - 1.0).astype(_device_int_dtype())
    draws = jnp.clip(draws, 0, range_max - 1)
    s = jnp.sort(draws, axis=1)
    dup = jnp.concatenate(
        [jnp.zeros((rows, 1), bool), s[:, 1:] == s[:, :-1]], axis=1)
    order = jnp.argsort(dup, axis=1, stable=True)
    return jnp.take_along_axis(s, order, axis=1)[:, :k] \
        .reshape(shape).astype(_device_int_dtype())


@register("_sample_multinomial", needs_rng=True, aliases=("sample_multinomial", "multinomial"))
def sample_multinomial(rng, data, shape=(), get_prob=False, dtype="int32"):
    """data: (..., k) probabilities; draws `shape` samples per distribution
    (reference: sample_multinomial_op.cc)."""
    n = 1
    for s in shape if isinstance(shape, tuple) else (shape,):
        n *= s
    logits = jnp.log(jnp.maximum(data, 1e-30))
    samp_shape = (shape,) if isinstance(shape, int) else tuple(shape)
    if data.ndim == 1:
        out = jax.random.categorical(rng, logits, shape=samp_shape or None)
    else:
        out = jax.random.categorical(rng, logits[..., None, :].repeat(max(n, 1), axis=-2), axis=-1)
        out = out.reshape(data.shape[:-1] + samp_shape) if samp_shape else out.reshape(data.shape[:-1])
    return out.astype(np_dtype(dtype))


@register("_shuffle", needs_rng=True, aliases=("shuffle",))
def shuffle(rng, data):
    return jax.random.permutation(rng, data, axis=0)


# --------------------------------------------------------------------------
# token sampling (serving.generate decode loop; SOSP'23 vLLM-style
# sampling surface). One op covers the whole family — greedy is
# temperature<=0, top-k/top-p are nucleus filters on the logits — so a
# mixed decode batch with per-row parameters stays ONE executable
# (`sample_token_logits` takes arrays; the registered op takes the attr
# spelling for nd/symbol callers). The executable branches on what its rows
# ask for: a batch of greedy rows never sorts the vocabulary.
# --------------------------------------------------------------------------

def _filter_logits(lf, top_k, top_p):
    """Mask float32 logits outside each row's top-k (k<=0 disables) and
    then outside its nucleus: the smallest prefix of descending-probability
    tokens whose mass reaches p (always at least the argmax; p<=0 or p>=1
    disables). Scalar or per-row k and p. One descending sort serves both
    thresholds: the top-k-masked row in sorted order is the sorted row with
    its tail masked (ties at the threshold are kept either way)."""
    v = lf.shape[-1]
    kk = jnp.broadcast_to(jnp.asarray(top_k, jnp.int32), lf.shape[:-1])
    kk = jnp.clip(jnp.where(kk <= 0, v, kk), 1, v)
    pp = jnp.broadcast_to(jnp.asarray(top_p, jnp.float32), lf.shape[:-1])
    pp = jnp.where((pp <= 0.0) | (pp >= 1.0), 1.0, pp)
    desc = jnp.flip(jnp.sort(lf, axis=-1), axis=-1)
    thr_k = jnp.take_along_axis(desc, (kk - 1)[..., None], axis=-1)
    desc = jnp.where(desc >= thr_k, desc, -jnp.inf)
    probs = jax.nn.softmax(desc, axis=-1)
    keep = (jnp.cumsum(probs, axis=-1) - probs) < pp[..., None]
    thr_p = jnp.min(jnp.where(keep, desc, jnp.inf), axis=-1, keepdims=True)
    masked = jnp.where(lf >= thr_k, lf, -jnp.inf)
    return jnp.where(masked >= thr_p, masked, -jnp.inf)


def _if_any(x, on_true, on_false):
    """``on_true()`` if any element of ``x`` holds, else ``on_false()``:
    chosen while tracing for Python and numpy values, by a `lax.cond` in the
    program for jax arrays and tracers."""
    if isinstance(x, jax.Array):
        return jax.lax.cond(jnp.any(x), on_true, on_false)
    return on_true() if np.any(x) else on_false()


def sample_token_logits(rng, logits, temperature=1.0, top_k=0, top_p=1.0):
    """Sample one token id per row of ``logits`` (..., V): greedy argmax
    where temperature<=0, else temperature-scaled categorical over the
    top-k/top-p-filtered distribution. Parameters may be scalars or
    per-row arrays (the decode scheduler batches requests with different
    sampling knobs into one executable). Returns int32 (...).

    The work follows the rows. With array parameters the program holds two
    `lax.cond`s on them: a batch whose rows are all greedy computes the
    argmax and nothing else; a batch with sampled rows draws, and sorts the
    vocabulary (once) only if a sampled row sets a top-k or a top-p. Every
    row gets what it asked for whatever the rest of the batch asks. With
    Python scalars the same choices are made while tracing and no `cond`
    is in the graph. (Under `vmap` a `cond` becomes a select and both
    branches run.)"""
    t = jnp.broadcast_to(jnp.asarray(temperature, jnp.float32),
                         logits.shape[:-1])
    lf = logits.astype(jnp.float32)

    def greedy():
        return jnp.argmax(lf, axis=-1).astype(jnp.int32)

    def draw(masked):
        scaled = masked / jnp.maximum(t, 1e-6)[..., None]
        drawn = jax.random.categorical(rng, scaled, axis=-1)
        return jnp.where(t <= 0.0, greedy(), drawn).astype(jnp.int32)

    def sampled():
        filters = (top_k > 0) | ((top_p > 0.0) & (top_p < 1.0))
        return _if_any((temperature > 0.0) & filters,
                       lambda: draw(_filter_logits(lf, top_k, top_p)),
                       lambda: draw(lf))

    return _if_any(temperature > 0.0, sampled, greedy)


@register("_sample_token", needs_rng=True, aliases=("sample_token",))
def sample_token(rng, data, temperature=1.0, top_k=0, top_p=1.0,
                 dtype="int32"):
    """data: (..., V) logits -> (...) sampled token ids (greedy /
    temperature / top-k / top-p per the attrs; one threefry subkey per
    call, ops/random_ops.py convention). The attrs are Python scalars, so
    the graph holds only what they ask for: the argmax alone at
    temperature<=0, no sort without a top-k or a top-p."""
    out = sample_token_logits(rng, data, temperature=float(temperature),
                              top_k=int(top_k), top_p=float(top_p))
    return out.astype(np_dtype(dtype))


@register("GridGenerator")
def grid_generator(data, transform_type="affine", target_shape=()):
    h, w = target_shape
    ys = jnp.linspace(-1.0, 1.0, h)
    xs = jnp.linspace(-1.0, 1.0, w)
    gy, gx = jnp.meshgrid(ys, xs, indexing="ij")
    if transform_type == "affine":
        base = jnp.stack([gx.ravel(), gy.ravel(), jnp.ones(h * w)], axis=0)
        theta = data.reshape((-1, 2, 3))
        out = jnp.einsum("bij,jk->bik", theta, base)
        return out.reshape((-1, 2, h, w))
    # warp: data is (b, 2, h, w) flow
    grid = jnp.stack([gx, gy], axis=0)[None]
    norm = jnp.asarray([(w - 1) / 2.0, (h - 1) / 2.0]).reshape((1, 2, 1, 1))
    return grid + data / norm


@register("BilinearSampler")
def bilinear_sampler(data, grid, cudnn_off=False):
    """reference: src/operator/bilinear_sampler.cc — sample `data` (NCHW) at
    normalized grid coords (N,2,H',W') in [-1,1]."""
    n, c, h, w = data.shape
    gx = (grid[:, 0] + 1.0) * (w - 1) / 2.0
    gy = (grid[:, 1] + 1.0) * (h - 1) / 2.0

    def sample_one(img, x, y):
        # img: (C,H,W); x,y: (H',W')
        x0 = jnp.floor(x)
        y0 = jnp.floor(y)
        wx = x - x0
        wy = y - y0

        def gather(yy, xx):
            yi = jnp.clip(yy.astype(jnp.int32), 0, h - 1)
            xi = jnp.clip(xx.astype(jnp.int32), 0, w - 1)
            valid = ((yy >= 0) & (yy <= h - 1) & (xx >= 0) & (xx <= w - 1)).astype(img.dtype)
            return img[:, yi, xi] * valid[None]

        out = (gather(y0, x0) * ((1 - wx) * (1 - wy))[None]
               + gather(y0, x0 + 1) * (wx * (1 - wy))[None]
               + gather(y0 + 1, x0) * ((1 - wx) * wy)[None]
               + gather(y0 + 1, x0 + 1) * (wx * wy)[None])
        return out

    return jax.vmap(sample_one)(data, gx, gy)


@register("SpatialTransformer")
def spatial_transformer(data, loc, target_shape=(), transform_type="affine",
                        sampler_type="bilinear", cudnn_off=False):
    grid = grid_generator(loc, "affine", target_shape)
    return bilinear_sampler(data, grid)


# --------------------------------------------------------------------------
# vectorized per-distribution sampling (reference: multisample_op.cc —
# `sample_uniform` et al: one distribution per input element, `shape` draws
# from each; output shape = param.shape + shape)
# --------------------------------------------------------------------------

def _multisample(rng, params, shape, draw, dtype):
    shape = tuple(shape) if isinstance(shape, (tuple, list)) else \
        ((int(shape),) if shape else ())
    lead = params[0].shape
    flat = [jnp.reshape(p, (-1,)) for p in params]
    keys = jax.random.split(rng, flat[0].shape[0])
    out = jax.vmap(lambda k, *ps: draw(k, shape, *ps))(keys, *flat)
    return out.reshape(lead + shape).astype(np_dtype(dtype))


@register("_sample_uniform", needs_rng=True, aliases=("sample_uniform",))
def sample_uniform(rng, low, high, shape=(), dtype="float32"):
    return _multisample(
        rng, [low, high], shape,
        lambda k, s, lo, hi: jax.random.uniform(k, s) * (hi - lo) + lo, dtype)


@register("_sample_normal", needs_rng=True, aliases=("sample_normal",))
def sample_normal(rng, mu, sigma, shape=(), dtype="float32"):
    return _multisample(
        rng, [mu, sigma], shape,
        lambda k, s, m, sd: jax.random.normal(k, s) * sd + m, dtype)


@register("_sample_gamma", needs_rng=True, aliases=("sample_gamma",))
def sample_gamma(rng, alpha, beta, shape=(), dtype="float32"):
    return _multisample(
        rng, [alpha, beta], shape,
        lambda k, s, a, b: jax.random.gamma(k, a, s) * b, dtype)


@register("_sample_exponential", needs_rng=True,
          aliases=("sample_exponential",))
def sample_exponential(rng, lam, shape=(), dtype="float32"):
    return _multisample(
        rng, [lam], shape,
        lambda k, s, l: jax.random.exponential(k, s) / l, dtype)


@register("_sample_poisson", needs_rng=True, aliases=("sample_poisson",))
def sample_poisson(rng, lam, shape=(), dtype="float32"):
    return _multisample(
        rng, [lam], shape,
        lambda k, s, l: jax.random.poisson(k, l, s).astype(jnp.float32),
        dtype)


@register("_sample_negative_binomial", needs_rng=True,
          aliases=("sample_negative_binomial",))
def sample_negative_binomial(rng, k, p, shape=(), dtype="float32"):
    def draw(key, s, kk, pp):
        k1, k2 = jax.random.split(key)
        lam = jax.random.gamma(k1, kk, s) * ((1 - pp) / pp)
        return jax.random.poisson(k2, lam, s).astype(jnp.float32)

    return _multisample(rng, [k, p], shape, draw, dtype)


@register("_sample_generalized_negative_binomial", needs_rng=True,
          aliases=("sample_generalized_negative_binomial", "sample_gnb"))
def sample_generalized_negative_binomial(rng, mu, alpha, shape=(),
                                         dtype="float32"):
    def draw(key, s, m, a):
        k1, k2 = jax.random.split(key)
        lam = jax.random.gamma(k1, 1.0 / a, s) * (a * m)
        return jax.random.poisson(k2, lam, s).astype(jnp.float32)

    return _multisample(rng, [mu, alpha], shape, draw, dtype)
