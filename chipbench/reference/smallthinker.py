"""Plain reference: the SmallThinker decoder LM (PowerInfer `smallthinker`),
one full forward pass in jax.numpy, float32, no cache, no batching, no
kernels, every contraction at ``Precision.HIGHEST``.

``x`` is the residual stream; eps 1e-6 in every RMS norm; no bias anywhere::

    x = E[token]                                   # no position table
    for each layer l:
      r = rms_norm(x; input_norm_l)
      z = r Wr^T                                   # router logits, (n, E):
                                                   # from the PRE-attention rows
      q = r Wq^T -> (H, D);  k, v = r Wk^T, r Wv^T -> (KV, D)
      rope_layout[l] = 1:  q, k = rope(theta, rotate-half, the whole head)
      rope_layout[l] = 0:  nothing (NoPE)
      s_ij = q_i . k_j / sqrt(D)  for j <= i and, where
             sliding_window_layout[l] = 1,  j > i - window
      a = softmax_j(s) v, query head h reading KV head h // (H // KV)
      x = x + a Wo^T
      u = rms_norm(x; post_attention_norm_l)
      (t, sel) = top-k of z;  g = softmax(t) over the k selected
      f = sum_{e in sel} g_e * W2_e (relu(W1_e u) * W3_e u)
      x = x + f
    logits = rms_norm(x; final_norm) Wh^T          # the head untied

Attention goes ``Q_BLOCK`` queries at a time against all the keys, masked:
the (H, L, L) scores of 13 312 positions would be 20 GB. The experts are a
plain loop over all of them with a mask: every expert is computed for every
token and nobody is dropped. It imports nothing of the program and makes its
own weights from the seed. The weights are drawn as bfloat16 VALUES, so that
the program (which keeps them in bfloat16) and this reference hold the same
numbers; the reference upcasts them to float32 where it uses them, a layer
(an expert) at a time, because the whole model in float32 does not fit one
chip.

``control``:
  None        the reference proper
  "fp8"       weights and the inputs of every matrix product rounded to
              float8 e4m3, the precision below the configuration's bfloat16:
              the control that `correct` must fail
  "nowindow"  the window layers attend over the whole context: what an
              engine that ignored the window would compute, which `correct`
              must fail too (else the drawn weights make the window
              invisible)
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
Q_BLOCK = 512           # queries a block of the reference's attention
PAD = 2048              # a checked request is padded to a multiple of this
CONTROLS = (None, "fp8", "nowindow")
QK_STD = 1.75           # std of q's and k's entries: scores of std 3


def parameter_shapes(sizes):
    c, d = sizes["hidden_size"], sizes["head_dim"]
    h, kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    e, f = sizes["moe_num_primary_experts"], sizes["moe_ffn_hidden_size"]
    held = sizes.get("num_experts_held") or e
    shapes = {"embed": (sizes["vocab_size"], c), "final_norm": (c,),
              "head": (sizes["vocab_size"], c)}
    for i in range(len(sizes["rope_layout"])):
        p = "layer%d." % i
        shapes.update({
            p + "input_norm": (c,), p + "post_attention_norm": (c,),
            p + "q": (h * d, c), p + "k": (kv * d, c), p + "v": (kv * d, c),
            p + "o": (c, h * d), p + "router": (e, c),
            # the three expert matrices are all (E, F, C): f = h W2_e with
            # h (.., F), so W2_e is stored as the program keeps it
            p + "experts.w1": (held, f, c), p + "experts.w3": (held, f, c),
            p + "experts.w2": (held, f, c)})
    return shapes


def _scale(name, shape):
    """(mean, std) of a leaf's draw. A matrix is normal(0, 0.9 / sqrt(its
    input width)): a unit-RMS input gives outputs of RMS 0.9 at any width,
    so attention and experts both move the residual stream. W_q and W_k are
    drawn wider, ``QK_STD`` / sqrt(width) each (the configuration's
    ``assumed.qk_scale``): q and k then have entries of std ``QK_STD`` and
    the scores q.k / sqrt(D) a standard deviation of ``QK_STD``^2, about 3
    at 1.75. At 0.9 it is 0.8, the softmax over 8000 random keys is near
    uniform, attention's output is 1% of the residual and a wrong window is
    invisible to the comparison. The embedding is normal(0, 0.02), norm
    gains are near 1."""
    if name.endswith("norm"):
        return 1.0, 0.02
    if name == "embed":
        return 0.0, 0.02
    if name.endswith(".q") or name.endswith(".k"):
        return 0.0, QK_STD / math.sqrt(shape[-1])
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


def _attention(q, k, v, window, low):
    """q (n, H, D), k and v (n, KV, D) -> (n, H*D): ``Q_BLOCK`` queries at
    a time against every key, masked to j <= i and, with ``window``,
    j > i - window."""
    n, h, d = q.shape
    kv = k.shape[1]
    block = min(Q_BLOCK, n)
    pad = -n % block
    # query head i reads KV head i // (h // kv)
    qg = jnp.pad(q, ((0, pad), (0, 0), (0, 0))) \
        .reshape(-1, block, kv, h // kv, d)
    keys = jnp.arange(n)

    def one(args):
        qb, i0 = args
        rows = i0 + jnp.arange(block)
        s = jnp.einsum("qkgd,lkd->kgql", _q(qb, low), _q(k, low),
                       precision=HIGHEST) / math.sqrt(d)
        live = keys[None, :] <= rows[:, None]
        if window:
            live &= keys[None, :] > rows[:, None] - window
        a = jax.nn.softmax(jnp.where(live, s, -1e30), axis=-1)
        return jnp.einsum("kgql,lkd->qkgd", _q(a, low), _q(v, low),
                          precision=HIGHEST)

    out = lax.map(one, (qg, jnp.arange(qg.shape[0]) * block))
    return out.reshape(-1, h * d)[:n]


def hidden(w, tokens, sizes, control=None, breaks=()):
    """tokens (L,) int32 -> the residual stream after the last layer, (L,
    C) float32; position t sees tokens [0, t]. ``breaks`` (tests only)
    names departures a broken program would make: ``rope_everywhere``
    rotates the NoPE layers too, ``router_post`` feeds the router the
    post-attention rows."""
    low = control == "fp8"
    d = sizes["head_dim"]
    h, kv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    eps, n = sizes["rms_norm_eps"], tokens.shape[0]
    k_sel = sizes["moe_num_active_primary_experts"]
    first = sizes.get("expert_offset", 0)
    x = w["embed"][tokens].astype(F32)
    for i, (windowed, rotary) in enumerate(zip(sizes["sliding_window_layout"],
                                               sizes["rope_layout"])):
        p = "layer%d." % i
        r = _rms(x, w[p + "input_norm"], eps)
        z = _mm(r, w[p + "router"], low)                           # (n, E)
        q = _mm(r, w[p + "q"], low).reshape(n, h, d)
        k = _mm(r, w[p + "k"], low).reshape(n, kv, d)
        v = _mm(r, w[p + "v"], low).reshape(n, kv, d)
        if rotary or "rope_everywhere" in breaks:
            q, k = _rope(q, sizes["rope_theta"]), _rope(k, sizes["rope_theta"])
        window = sizes["sliding_window_size"] \
            if windowed and control != "nowindow" else None
        x = x + _mm(_attention(q, k, v, window, low), w[p + "o"], low)
        u = _rms(x, w[p + "post_attention_norm"], eps)
        if "router_post" in breaks:
            z = _mm(u, w[p + "router"], low)
        top, sel = lax.top_k(z, k_sel)
        gates = jax.nn.softmax(top, axis=-1)                       # (n, k)
        gate = jnp.sum(jax.nn.one_hot(sel, z.shape[1], dtype=F32)
                       * gates[:, :, None], axis=1)                # (n, E)
        held = w[p + "experts.w1"].shape[0]

        def expert(acc, e):
            w1, w3, w2, g = e
            hid = jax.nn.relu(_mm(u, w1, low)) * _mm(u, w3, low)
            out = jnp.einsum("nf,fc->nc", _q(hid, low), _up(w2, low),
                             precision=HIGHEST)
            return acc + g[:, None] * out, None

        f, _ = lax.scan(expert, jnp.zeros_like(x),
                        (w[p + "experts.w1"], w[p + "experts.w3"],
                         w[p + "experts.w2"], gate.T[first:first + held]))
        x = x + f
    return x


def head(w, x, sizes, control=None):
    """Rows of the residual stream -> logits (.., V) float32."""
    return _mm(_rms(x, w["final_norm"], sizes["rms_norm_eps"]), w["head"],
               control == "fp8")


def forward(w, tokens, sizes, control=None, breaks=()):
    """tokens (L,) int32 -> logits (L, V) float32."""
    return head(w, hidden(w, tokens, sizes, control, breaks), sizes, control)


@functools.lru_cache(maxsize=None)
def _gap_fn(sizes_key, length, most, control):
    sizes = json.loads(sizes_key)

    @jax.jit
    def gaps(w, tokens, first, count):
        """For the served tokens at positions first .. first+count-1 of
        ``tokens`` (``count`` <= ``most``): how far each one's reference
        logit lies below the reference's best at its position; and, with a
        control, the same for the token the control's forward puts first
        there. Logits are taken at the served positions alone: (L, V) in
        float32 is 8 GB at 13 312 positions."""
        at = jnp.minimum(first - 1 + jnp.arange(most), length - 1)
        mask = jnp.arange(most) < count          # logits at p predict p+1
        served = tokens[jnp.minimum(at + 1, length - 1)]
        logits = head(w, hidden(w, tokens, sizes)[at], sizes)
        best = jnp.max(logits, axis=-1)
        at_served = jnp.take_along_axis(logits, served[:, None], axis=1)[:, 0]
        gap = jnp.where(mask, best - at_served, 0.0)
        if control is None:
            gap_low = jnp.zeros_like(gap)
        else:
            pick = jnp.argmax(head(w, hidden(w, tokens, sizes, control)[at],
                                   sizes, control), axis=-1)
            at_pick = jnp.take_along_axis(logits, pick[:, None], axis=1)[:, 0]
            gap_low = jnp.where(mask, best - at_pick, 0.0)
        return (jnp.max(gap), jnp.max(gap_low), jnp.sum(gap > 0),
                jnp.sum(gap_low > 0), jnp.sum(gap), jnp.sum(gap_low))

    return gaps


def served_gaps(seed, sizes, requests, pad_to, control=None):
    """``requests`` are ``(prompt tokens, served tokens)``. One reference
    pass over each prompt with its served tokens, padded to the next
    multiple of ``PAD`` (at most ``pad_to``: a short request does not pay
    for the longest); returns the widest gap by which a served token's
    reference logit lies below the reference's best and the sum of those
    gaps per 1000 served tokens (the number `correct` is held to), and, with
    ``control``, the same two for the token that the reference computed
    under that control puts first."""
    import numpy as np

    if control not in CONTROLS:
        raise ValueError("unknown control %r" % (control,))
    w = make_weights(seed, sizes)
    key = json.dumps(sizes, sort_keys=True)
    worst, worst_low, n_tokens, off, off_low = 0.0, 0.0, 0, 0, 0
    total, total_low = 0.0, 0.0
    for prompt, served in requests:
        seq = list(prompt) + list(served)
        if len(seq) > pad_to:
            raise ValueError("sequence of %d tokens over pad_to %d"
                             % (len(seq), pad_to))
        length = min(pad_to, -(-len(seq) // PAD) * PAD)
        tokens = np.zeros(length, np.int32)
        tokens[:len(seq)] = seq
        most = min(length, -(-len(served) // 256) * 256)
        g, gl, n, nl, t, tl = _gap_fn(key, length, most, control)(
            w, jnp.asarray(tokens), len(prompt), len(served))
        worst, worst_low = max(worst, float(g)), max(worst_low, float(gl))
        total, total_low = total + float(t), total_low + float(tl)
        off, off_low = off + int(n), off_low + int(nl)
        n_tokens += len(served)
    return {"served_gap": worst, "control_gap": worst_low,
            "served_gap_per_1k": 1e3 * total / n_tokens,
            "control_gap_per_1k": 1e3 * total_low / n_tokens,
            "tokens": n_tokens, "requests": len(requests),
            "not_best": off, "control_not_best": off_low}
