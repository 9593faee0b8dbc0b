"""Plain reference: the LFM2-MoE decoder LM (LiquidAI `lfm2_moe`), one full
forward pass in jax.numpy, float32, no cache, no batching, no kernels, every
contraction at ``Precision.HIGHEST``.

``h`` is the residual stream; eps 1e-5 in every RMS norm; no bias anywhere::

    h0 = E[token]                                  # no position table
    for each layer l:
      r = rms_norm(h; operator_norm_l)
      full_attention:  q = r Wq -> (H, D); k, v = r Wk, r Wv -> (KV, D)
                       q, k = rms_norm over D with q_norm_l, k_norm_l
                       q, k = rope(theta, rotate-half, the whole head)
                       a = causal softmax(q k^T / sqrt(D)) v, query head i
                           reading KV head i // (H // KV);  o = a Wo
      conv:            B, C, X = split3(r W_in);  u = B * X
                       c_t = sum_j w_l[:, j] * u_{t-(K-1)+j}   (u_{<0} = 0)
                       o = (C * c) W_out
      h = h + o;  r = rms_norm(h; ffn_norm_l)
      leading dense layer:  f = W2 (silu(W1 r) * W3 r)
      else:  s = sigmoid(r Wg);  sel = top-k of (s + expert_bias_l)
             g_e = s_e / (sum_{sel} s + 1e-6) * routed_scaling_factor
             f = sum_{e in sel} g_e * W2_e (silu(W1_e r) * W3_e r)
      h = h + f
    logits = rms_norm(h; embedding_norm) E^T

The experts are a plain loop over all of them with a mask: every expert is
computed for every token and nobody is dropped. It imports nothing of the
program and makes its own weights from the seed. The weights are drawn as
bfloat16 VALUES, so that the program (which keeps them in bfloat16) and this
reference hold the same numbers; the reference upcasts them to float32 where
it uses them, a layer (an expert) at a time, because the whole model in
float32 does not fit one chip.

``control``:
  None      the reference proper
  "fp8"     weights and the inputs of every matrix product rounded to
            float8 e4m3, the precision below the configuration's bfloat16:
            the control that `correct` must fail
"""
from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
F32 = jnp.float32


def _dims(sizes):
    c = sizes["hidden_size"]
    h, kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    return c, h, kv, c // h


def parameter_shapes(sizes):
    c, h, kv, d = _dims(sizes)
    e, f = sizes["num_experts"], sizes["moe_intermediate_size"]
    shapes = {"embed": (sizes["vocab_size"], c), "embedding_norm": (c,)}
    for i, kind in enumerate(sizes["layer_types"]):
        p = "layer%d." % i
        shapes[p + "operator_norm"] = (c,)
        shapes[p + "ffn_norm"] = (c,)
        if kind == "full_attention":
            shapes.update({p + "q": (h * d, c), p + "k": (kv * d, c),
                           p + "v": (kv * d, c), p + "o": (c, h * d),
                           p + "q_norm": (d,), p + "k_norm": (d,)})
        else:
            shapes.update({p + "in_proj": (3 * c, c),
                           p + "conv": (c, sizes["conv_L_cache"]),
                           p + "out_proj": (c, c)})
        if i < sizes["num_dense_layers"]:
            fd = sizes["intermediate_size"]
            shapes.update({p + "w1": (fd, c), p + "w3": (fd, c),
                           p + "w2": (c, fd)})
        else:
            # the three expert matrices are all (E, F, C): f = h W2_e with
            # h (.., F), so W2_e is stored as the program keeps it
            shapes.update({p + "router": (e, c), p + "expert_bias": (e,),
                           p + "experts.w1": (e, f, c),
                           p + "experts.w3": (e, f, c),
                           p + "experts.w2": (e, f, c)})
    return shapes


def _scale(name, shape):
    """(mean, std) of a leaf's draw. A matrix is normal(0, 0.9 / sqrt(its
    input width)), which is 0.02 at the published width of 2048: a unit-RMS
    input gives outputs of RMS 0.9 at any width, so every operator and
    feed-forward moves the residual stream and greedy decoding is not the
    identity through the tied head (at a fixed 0.02 it is, at the small
    widths of the CPU tests: the gated convolution multiplies three small
    things). The embedding is normal(0, 0.02); the convolution's taps are
    normal(0, 1/sqrt(taps)) so that they keep u's scale; norm gains are near
    1; the router's selection bias is normal(0, 0.05): selecting with it and
    weighing without it then differ, and routing is not perfectly even."""
    if name.endswith("norm"):
        return 1.0, 0.02
    if name.endswith(".conv"):
        return 0.0, 1.0 / math.sqrt(shape[1])
    if name.endswith("expert_bias"):
        return 0.0, 0.05
    if name == "embed":
        return 0.0, 0.02
    # an expert's w2 is kept (F, C): its input width is F
    fan_in = shape[1] if name.endswith("experts.w2") else shape[-1]
    return 0.0, 0.9 / math.sqrt(fan_in)


@functools.lru_cache(maxsize=None)
def _draw(shape, mean, std):
    @jax.jit
    def draw(key):
        return (mean + std * jax.random.normal(key, shape, F32)) \
            .astype(jnp.bfloat16)

    return draw


def make_weights(seed, sizes):
    """Seeded weights on the device, bfloat16 values, one jitted draw a
    leaf (the float32 normals of a leaf exist only inside its draw)."""
    shapes = parameter_shapes(sizes)
    key = jax.random.PRNGKey(seed % (2 ** 31))
    out = {}
    for i, name in enumerate(sorted(shapes)):
        mean, std = _scale(name, shapes[name])
        out[name] = _draw(tuple(shapes[name]), mean, std)(
            jax.random.fold_in(key, i))
    return out


def _q(x, low):
    """Round to float8 e4m3 and back (the control), or nothing."""
    if not low:
        return x
    return x.astype(jnp.float8_e4m3fn).astype(F32)


def _up(w, low):
    return _q(w.astype(F32), low)


def _rms(x, g, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * lax.rsqrt(var + eps) * g.astype(F32)


def _mm(x, w, low):
    """x (.., in) times w (out, in) transposed."""
    return jnp.einsum("...i,oi->...o", _q(x, low), _up(w, low),
                      precision=HIGHEST)


def _rope(x, theta):
    n, _, d = x.shape
    half = d // 2
    inv = jnp.asarray(theta, F32) ** (-jnp.arange(half, dtype=F32) * 2.0 / d)
    ang = jnp.arange(n, dtype=F32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def forward(w, tokens, sizes, control=None):
    """tokens (L,) int32 -> logits (L, V) float32; position t sees tokens
    [0, t]."""
    low = control == "fp8"
    c, h, kv, d = _dims(sizes)
    eps, n = sizes["norm_eps"], tokens.shape[0]
    k_sel = sizes["num_experts_per_tok"]
    x = w["embed"][tokens].astype(F32)
    causal = jnp.arange(n)[None, :] <= jnp.arange(n)[:, None]
    for i, kind in enumerate(sizes["layer_types"]):
        p = "layer%d." % i
        r = _rms(x, w[p + "operator_norm"], eps)
        if kind == "full_attention":
            q = _rms(_mm(r, w[p + "q"], low).reshape(n, h, d),
                     w[p + "q_norm"], eps)
            k = _rms(_mm(r, w[p + "k"], low).reshape(n, kv, d),
                     w[p + "k_norm"], eps)
            v = _mm(r, w[p + "v"], low).reshape(n, kv, d)
            q, k = _rope(q, sizes["rope_theta"]), _rope(k, sizes["rope_theta"])
            # query head i reads KV head i // (h // kv)
            k = jnp.repeat(k, h // kv, axis=1)
            v = jnp.repeat(v, h // kv, axis=1)
            s = jnp.einsum("qhd,khd->hqk", _q(q, low), _q(k, low),
                           precision=HIGHEST) / math.sqrt(d)
            a = jax.nn.softmax(jnp.where(causal[None], s, -1e30), axis=-1)
            att = jnp.einsum("hqk,khd->qhd", _q(a, low), _q(v, low),
                             precision=HIGHEST).reshape(n, h * d)
            o = _mm(att, w[p + "o"], low)
        else:
            taps = sizes["conv_L_cache"]
            b, cg, xg = jnp.split(_mm(r, w[p + "in_proj"], low), 3, axis=-1)
            u = jnp.concatenate([jnp.zeros((taps - 1, c), F32), b * xg])
            wc = w[p + "conv"].astype(F32)
            conv = sum(wc[:, j] * u[j:j + n] for j in range(taps))
            o = _mm(cg * conv, w[p + "out_proj"], low)
        x = x + o
        r = _rms(x, w[p + "ffn_norm"], eps)
        if i < sizes["num_dense_layers"]:
            hid = jax.nn.silu(_mm(r, w[p + "w1"], low)) \
                * _mm(r, w[p + "w3"], low)
            f = _mm(hid, w[p + "w2"], low)
        else:
            s = jax.nn.sigmoid(_mm(r, w[p + "router"], low))      # (n, E)
            _, sel = lax.top_k(s + w[p + "expert_bias"].astype(F32), k_sel)
            chosen = jnp.sum(jax.nn.one_hot(sel, s.shape[1], dtype=F32),
                             axis=1)                               # 0/1 mask
            gate = s * chosen
            gate = gate / (jnp.sum(gate, axis=1, keepdims=True) + 1e-6) \
                * sizes["routed_scaling_factor"]

            def expert(acc, e):
                w1, w3, w2, g = e
                hid = jax.nn.silu(_mm(r, w1, low)) * _mm(r, w3, low)
                out = jnp.einsum("nf,fc->nc", _q(hid, low), _up(w2, low),
                                 precision=HIGHEST)
                return acc + g[:, None] * out, None

            f, _ = lax.scan(expert, jnp.zeros_like(x),
                            (w[p + "experts.w1"], w[p + "experts.w3"],
                             w[p + "experts.w2"], gate.T))
        x = x + f
    return _mm(_rms(x, w["embedding_norm"], eps), w["embed"], low)


@functools.lru_cache(maxsize=None)
def _gap_fn(sizes_key, length, control):
    sizes = json.loads(sizes_key)

    @jax.jit
    def gaps(w, tokens, first, count):
        """For the served tokens at positions first .. first+count-1 of
        ``tokens``: how far each one's reference logit lies below the
        reference's best at its position; and, with a control, the same for
        the token the control's forward puts first there."""
        logits = forward(w, tokens, sizes)
        pos = jnp.arange(length)
        served = jnp.roll(tokens, -1)            # logits at p predict p+1
        best = jnp.max(logits, axis=-1)
        at_served = jnp.take_along_axis(logits, served[:, None], axis=1)[:, 0]
        mask = (pos + 1 >= first) & (pos + 1 < first + count)
        gap = jnp.where(mask, best - at_served, 0.0)
        if control is None:
            gap_low = jnp.zeros_like(gap)
        else:
            pick = jnp.argmax(forward(w, tokens, sizes, control), axis=-1)
            at_pick = jnp.take_along_axis(logits, pick[:, None], axis=1)[:, 0]
            gap_low = jnp.where(mask, best - at_pick, 0.0)
        return (jnp.max(gap), jnp.max(gap_low), jnp.sum(gap > 0),
                jnp.sum(gap_low > 0), jnp.sum(gap), jnp.sum(gap_low))

    return gaps


def served_gaps(seed, sizes, requests, pad_to, control=None):
    """``requests`` are ``(prompt tokens, served tokens)``. One reference
    pass over each prompt with its served tokens, padded to ``pad_to``;
    returns the widest gap by which a served token's reference logit lies
    below the reference's best and the sum of those gaps per 1000 served
    tokens (the number `correct` is held to), and, with ``control``, the
    same two for the token that the reference computed in that precision
    puts first."""
    import numpy as np

    if control not in (None, "fp8"):
        raise ValueError("unknown control precision %r" % (control,))
    w = make_weights(seed, sizes)
    fn = _gap_fn(json.dumps(sizes, sort_keys=True), pad_to, control)
    worst, worst_low, n_tokens, off, off_low = 0.0, 0.0, 0, 0, 0
    total, total_low = 0.0, 0.0
    for prompt, served in requests:
        seq = list(prompt) + list(served)
        if len(seq) > pad_to:
            raise ValueError("sequence of %d tokens over pad_to %d"
                             % (len(seq), pad_to))
        tokens = np.zeros(pad_to, np.int32)
        tokens[:len(seq)] = seq
        g, gl, n, nl, t, tl = fn(w, jnp.asarray(tokens), len(prompt),
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
