"""Plain reference: a post-LN decoder-only transformer LM with a tied head,
one full forward pass in jax.numpy, float32, no cache, no batching, every
contraction at ``Precision.HIGHEST``.

It follows the code under test (``model_zoo.transformer.TransformerLM``), not
GPT-2: token + learned position embedding, LayerNorm, then per layer
``x = LN(x + attn(x))``, ``x = LN(x + ffn(x))`` with exact (erf) GELU, biases
on every projection, logits ``x @ word.T``, LayerNorm eps 1e-5.  GPT-2 proper
is pre-LN with a final LayerNorm; the sizes are GPT-2 small's, the block
order is the program's, and the configuration file says so.

It imports nothing of the program and makes its own weights from the seed.

``precision``:
  "float32"   the reference proper
  "bfloat16"  weights, activations and every intermediate in bfloat16, the
              precision below the configuration's float32: the control that
              `correct` must fail
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

EPS = 1e-5
HIGHEST = lax.Precision.HIGHEST


def parameter_shapes(sizes):
    c, f = sizes["units"], sizes["hidden_size"]
    shapes = {"word": (sizes["vocab_size"], c), "pos": (sizes["max_length"], c),
              "embed_norm.g": (c,), "embed_norm.b": (c,)}
    for i in range(sizes["num_layers"]):
        p = "layer%d." % i
        for d, (o, n) in (("q", (c, c)), ("k", (c, c)), ("v", (c, c)),
                          ("o", (c, c)), ("ffn1", (f, c)), ("ffn2", (c, f))):
            shapes[p + d + ".w"] = (o, n)
            shapes[p + d + ".b"] = (o,)
        for ln in ("attn_norm", "ffn_norm"):
            shapes[p + ln + ".g"] = (c,)
            shapes[p + ln + ".b"] = (c,)
    return shapes


def make_weights(seed, sizes):
    """Seeded float32 weights on the device, one jitted call: normal(0, 0.02)
    matrices, word embeddings and biases, LayerNorm gains near 1, and
    position embeddings at normal(0, 0.06).  With positions at 0.02 the tied
    head makes every position's best token the input token itself by a margin
    of 2.4 logits, and no arithmetic error could ever show in a served token;
    at 0.06 the input token is the best a third of the time and a tenth of
    the positions have their two best logits within 0.02 (CPU, float32,
    PERF.md section 2)."""
    shapes = parameter_shapes(sizes)
    names = sorted(shapes)

    @jax.jit
    def make(key):
        out = {}
        for i, name in enumerate(names):
            k = jax.random.fold_in(key, i)
            r = jax.random.normal(k, shapes[name], jnp.float32)
            if name.endswith(".g"):
                out[name] = 1.0 + 0.02 * r
            elif name == "pos":
                out[name] = 0.06 * r
            else:
                out[name] = 0.02 * r
        return out

    return make(jax.random.PRNGKey(seed % (2 ** 31)))


def _ln(x, g, b):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * lax.rsqrt(var + EPS) * g + b


def _dense(x, w, b):
    return jnp.dot(x, w.T, precision=HIGHEST) + b


def forward(w, tokens, sizes):
    """tokens (L,) int32 -> logits (L, V); position t sees tokens [0, t].
    Runs in the dtype of ``w``."""
    heads = sizes["num_heads"]
    c = sizes["units"]
    dh = c // heads
    n = tokens.shape[0]
    x = w["word"][tokens] + w["pos"][jnp.arange(n)]
    x = _ln(x, w["embed_norm.g"], w["embed_norm.b"])
    causal = jnp.arange(n)[None, :] <= jnp.arange(n)[:, None]
    neg = jnp.asarray(-1e30, jnp.float32).astype(x.dtype)
    for i in range(sizes["num_layers"]):
        p = "layer%d." % i
        q = _dense(x, w[p + "q.w"], w[p + "q.b"]).reshape(n, heads, dh)
        k = _dense(x, w[p + "k.w"], w[p + "k.b"]).reshape(n, heads, dh)
        v = _dense(x, w[p + "v.w"], w[p + "v.b"]).reshape(n, heads, dh)
        s = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) \
            / math.sqrt(dh)
        s = jnp.where(causal[None], s, neg)
        a = jax.nn.softmax(s, axis=-1)
        att = jnp.einsum("hqk,khd->qhd", a, v, precision=HIGHEST) \
            .reshape(n, c)
        x = _ln(x + _dense(att, w[p + "o.w"], w[p + "o.b"]),
                w[p + "attn_norm.g"], w[p + "attn_norm.b"])
        h = jax.nn.gelu(_dense(x, w[p + "ffn1.w"], w[p + "ffn1.b"]),
                        approximate=False)
        x = _ln(x + _dense(h, w[p + "ffn2.w"], w[p + "ffn2.b"]),
                w[p + "ffn_norm.g"], w[p + "ffn_norm.b"])
    return jnp.dot(x, w["word"].T, precision=HIGHEST)


@functools.lru_cache(maxsize=None)
def _gap_fn(sizes_key, length):
    sizes = dict(sizes_key)

    @jax.jit
    def gaps(w, w_low, tokens, first, count):
        """For the served tokens at positions first .. first+count-1 of
        ``tokens``: how far each one's reference logit lies below the
        reference's best at its position; and the same for the token a
        lower-precision copy of the weights puts first there."""
        logits = forward(w, tokens, sizes).astype(jnp.float32)
        pos = jnp.arange(length)
        # logits at p predict token p+1
        served = jnp.roll(tokens, -1)
        best = jnp.max(logits, axis=-1)
        at_served = jnp.take_along_axis(logits, served[:, None], axis=1)[:, 0]
        mask = (pos + 1 >= first) & (pos + 1 < first + count)
        gap = jnp.where(mask, best - at_served, 0.0)
        low = forward(w_low, tokens, sizes)
        pick = jnp.argmax(low.astype(jnp.float32), axis=-1)
        at_pick = jnp.take_along_axis(logits, pick[:, None], axis=1)[:, 0]
        gap_low = jnp.where(mask, best - at_pick, 0.0)
        return (jnp.max(gap), jnp.max(gap_low), jnp.sum(gap > 0),
                jnp.sum(gap_low > 0), jnp.sum(gap), jnp.sum(gap_low))

    return gaps


def _freeze(sizes):
    return tuple(sorted((k, v) for k, v in sizes.items()
                        if isinstance(v, int)))


def served_gaps(seed, sizes, requests, pad_to, control=None):
    """``requests`` are ``(prompt tokens, served tokens)``.  One reference
    pass over each prompt with its served tokens, padded to ``pad_to``;
    returns the widest gap by which a served token's reference logit lies
    below the reference's best and the sum of those gaps per 1000 served
    tokens, and, with ``control`` (a precision below the configuration's),
    the same two for the token that the reference computed in that precision
    puts first.  The widest gap is read where a handful of near-ties flip and
    swings from seed to seed; the sum grows with the square of the arithmetic
    error (more flips, each deeper) and is the number `correct` is held to."""
    import numpy as np

    w = make_weights(seed, sizes)
    if control in (None, "float32"):
        w_low = w
    elif control == "bfloat16":
        w_low = {k: v.astype(jnp.bfloat16) for k, v in w.items()}
    else:
        raise ValueError("unknown control precision %r" % (control,))
    fn = _gap_fn(_freeze(sizes), pad_to)
    worst, worst_low, n_tokens, off, off_low = 0.0, 0.0, 0, 0, 0
    total, total_low = 0.0, 0.0
    for prompt, served in requests:
        seq = list(prompt) + list(served)
        if len(seq) > pad_to:
            raise ValueError("sequence of %d tokens over pad_to %d"
                             % (len(seq), pad_to))
        tokens = np.zeros(pad_to, np.int32)
        tokens[:len(seq)] = seq
        g, gl, n, nl, t, tl = fn(w, w_low, jnp.asarray(tokens), len(prompt),
                                 len(served))
        worst, worst_low = max(worst, float(g)), max(worst_low, float(gl))
        total, total_low = total + float(t), total_low + float(tl)
        off, off_low = off + int(n), off_low + int(nl)
        n_tokens += len(served)
    return {"served_gap": worst, "control_gap": worst_low,
            "served_gap_per_1k": 1e3 * total / n_tokens,
            "control_gap_per_1k": 1e3 * total_low / n_tokens,
            "tokens": n_tokens, "requests": len(requests),
            "not_best": off, "control_not_best": off_low}
