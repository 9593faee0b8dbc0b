"""Plain reference: the GigaChat3 decoder LM (ai-sage, ``model_type:
deepseek_v3``), one full forward pass in jax.numpy, float32, un-absorbed
latent attention, no cache, no batching, no kernels, every contraction at
``Precision.HIGHEST``.

``h`` is the residual stream; eps 1e-6 in every RMS norm; no bias anywhere;
``d`` the model width, ``H`` heads of ``nope`` + ``rot`` query/key lanes and
``v`` value lanes::

    h = E[token]
    for each layer l:
      r = rms(h; input_norm_l)
      c_q = rms(r W_qa; q_a_norm);  q = c_q W_qb -> (H, nope + rot)
      [c_kv | k_rot] = r W_kva      (kv_lora_rank + rot: ONE k_rot for all heads)
      c_kv = rms(c_kv; kv_a_norm);  q_rot, k_rot = yarn_rope(., position)
      [k_nope | v] = c_kv W_kvb -> (H, nope + v)
      a = causal softmax(([q_nope|q_rot] . [k_nope|k_rot]) * scale) v
      scale = (nope + rot)^-0.5 * m^2,  m = 0.1 * mscale_all_dim * ln(factor) + 1
      h = h + a W_o;  r = rms(h; post_attention_norm_l)
      l < first_k_dense_replace:  f = W_down (silu(W_gate r) * W_up r)
      else:  s = sigmoid(r W_g) (all routed experts);  s' = s + b_l
             group score = sum of the 2 largest s' in each of n_group groups of
             consecutive experts; the topk_group best groups are kept, s' of
             the others := 0;  sel = top-k of that
             g_e = s_e / (sum_{sel} s + 1e-20) * routed_scaling_factor
             f = sum_{e in sel, e held here} g_e Expert_e(r) + Shared(r)
      h = h + f
    logits = rms(h; norm) W_head                    # the head is its own matrix

    yarn_rope (rotate-half over the rot lanes): inv_freq_i = theta^(-2i/rot);
      ramp over i between the correction dims of beta_fast and beta_slow
      (original length); inv_freq = inv_freq/factor * ramp + inv_freq * (1 -
      ramp); cos and sin are scaled by mscale / mscale_all_dim's ratio, 1 here.

    multi-token prediction (``num_nextn_predict_layers`` 1, `forward_mtp`):
      x_i = W_eh [rms(h_i; hnorm) ; rms(E[t_{i+1}]; enorm)]  (2d -> d), one
      expert layer as above over positions i, then rms(.; shared_head_norm)
      W_head: the logits of t_{i+2}.

The experts held here (``num_experts_held`` from ``expert_offset``) are a
plain loop with a mask: every held expert is computed for every token and
nobody is dropped; what the absent experts would add is left out, as in the
program. It imports nothing of the program and makes its own weights from the
seed, drawn as bfloat16 VALUES so that the program (which keeps them in
bfloat16) and this reference hold the same numbers; the reference upcasts
them to float32 where it uses them, a layer (an expert) at a time. Attention
runs in query blocks, so that no (H, L, L) array exists at 5120 positions.

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
Q_BLOCK = 512           # query rows of one attention block


def _held(sizes):
    return sizes.get("num_experts_held") or sizes["n_routed_experts"]


def _layer_shapes(p, sizes, experts):
    c, h = sizes["hidden_size"], sizes["num_attention_heads"]
    nope, rot = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"]
    rq, rkv, dv = sizes["q_lora_rank"], sizes["kv_lora_rank"], \
        sizes["v_head_dim"]
    shapes = {p + "input_norm": (c,), p + "post_attention_norm": (c,),
              p + "q_a": (rq, c), p + "q_a_norm": (rq,),
              p + "q_b": (h * (nope + rot), rq),
              p + "kv_a": (rkv + rot, c), p + "kv_a_norm": (rkv,),
              p + "kv_b": (h * (nope + dv), rkv), p + "o": (c, h * dv)}
    if experts:
        e, f = sizes["n_routed_experts"], sizes["moe_intermediate_size"]
        fs = f * sizes["n_shared_experts"]
        held = _held(sizes)
        # the three matrices of the held experts are all (held, F, C), as
        # the program keeps them: f = hid W_down_e with hid (.., F)
        shapes.update({p + "router": (e, c), p + "expert_bias": (e,),
                       p + "experts.w_gate": (held, f, c),
                       p + "experts.w_up": (held, f, c),
                       p + "experts.w_down": (held, f, c),
                       p + "shared.w_gate": (fs, c),
                       p + "shared.w_up": (fs, c),
                       p + "shared.w_down": (c, fs)})
    else:
        fd = sizes["intermediate_size"]
        shapes.update({p + "w_gate": (fd, c), p + "w_up": (fd, c),
                       p + "w_down": (c, fd)})
    return shapes


def parameter_shapes(sizes):
    c, v = sizes["hidden_size"], sizes["vocab_size"]
    shapes = {"embed": (v, c), "head": (v, c), "norm": (c,)}
    for i in range(sizes["num_hidden_layers"]):
        shapes.update(_layer_shapes("layer%d." % i, sizes,
                                    i >= sizes["first_k_dense_replace"]))
    if sizes.get("num_nextn_predict_layers"):
        shapes.update({"mtp.hnorm": (c,), "mtp.enorm": (c,),
                       "mtp.eh_proj": (c, 2 * c),
                       "mtp.shared_head_norm": (c,)})
        shapes.update(_layer_shapes("mtp.layer.", sizes, True))
    return shapes


def _scale(name, shape):
    """(mean, std) of a leaf's draw, as chipbench/reference/lfm2_moe.py: a
    matrix is normal(0, 0.9 / sqrt(its input width)), so that every operator
    and feed-forward moves the residual stream at any width; the embedding
    normal(0, 0.02); norm gains near 1; the router's selection bias normal(0,
    0.05), so that selecting with it and weighing without it differ."""
    if name.endswith("norm"):
        return 1.0, 0.02
    if name.endswith("expert_bias"):
        return 0.0, 0.05
    if name == "embed":
        return 0.0, 0.02
    # the held experts' w_down is kept (F, C): its input width is F
    fan_in = shape[1] if name.endswith("experts.w_down") else shape[-1]
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


def yarn_inv_freq(dim, theta, scaling):
    """The ``dim // 2`` rotary frequencies under YaRN: pairs that turn more
    than ``beta_fast`` times over the original length keep their frequency,
    pairs that turn less than ``beta_slow`` times have it divided by
    ``factor``, a linear ramp between."""
    half = dim // 2
    inv = [theta ** (-2.0 * i / dim) for i in range(half)]
    if not scaling or scaling.get("factor", 1) == 1:
        return jnp.asarray(inv, F32)
    orig = scaling["original_max_position_embeddings"]

    def correction_dim(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction_dim(scaling["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(scaling["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    out = []
    for i in range(half):
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        out.append(inv[i] / scaling["factor"] * ramp + inv[i] * (1 - ramp))
    return jnp.asarray(out, F32)


def _mscale(factor, m):
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def softmax_scale(sizes):
    d = sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"]
    sc = sizes.get("rope_scaling") or {}
    return d ** -0.5 * _mscale(sc.get("factor", 1),
                               sc.get("mscale_all_dim", 0)) ** 2


def _rope(x, positions, sizes):
    """Rotate-half over the whole last axis of x (n, heads, rot)."""
    sc = sizes.get("rope_scaling") or {}
    half = x.shape[-1] // 2
    inv = yarn_inv_freq(x.shape[-1], float(sizes["rope_theta"]), sc)
    ang = positions.astype(F32)[:, None, None] * inv
    m = _mscale(sc.get("factor", 1), sc.get("mscale", 1)) \
        / _mscale(sc.get("factor", 1), sc.get("mscale_all_dim", 0))
    cos, sin = jnp.cos(ang) * m, jnp.sin(ang) * m
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(w, p, r, positions, sizes, low):
    """Latent attention, un-absorbed: K and V of every head expanded from
    the compressed row, causal softmax in blocks of query rows."""
    n, h = r.shape[0], sizes["num_attention_heads"]
    nope, rot = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"]
    rkv, dv, eps = sizes["kv_lora_rank"], sizes["v_head_dim"], \
        sizes["rms_norm_eps"]
    c_q = _rms(_mm(r, w[p + "q_a"], low), w[p + "q_a_norm"], eps)
    q = _mm(c_q, w[p + "q_b"], low).reshape(n, h, nope + rot)
    ckv = _mm(r, w[p + "kv_a"], low)
    c_kv = _rms(ckv[:, :rkv], w[p + "kv_a_norm"], eps)
    k_rot = _rope(ckv[:, None, rkv:], positions, sizes)          # (n, 1, rot)
    q = jnp.concatenate([q[..., :nope],
                         _rope(q[..., nope:], positions, sizes)], -1)
    kv = _mm(c_kv, w[p + "kv_b"], low).reshape(n, h, nope + dv)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_rot, (n, h, rot))], -1)
    v = kv[..., nope:]
    scale = softmax_scale(sizes)
    outs = []
    for q0 in range(0, n, Q_BLOCK):
        q1 = min(n, q0 + Q_BLOCK)
        s = jnp.einsum("qhd,khd->hqk", _q(q[q0:q1], low), _q(k[:q1], low),
                       precision=HIGHEST) * scale
        causal = jnp.arange(q1)[None, :] <= jnp.arange(q0, q1)[:, None]
        a = jax.nn.softmax(jnp.where(causal[None], s, -1e30), axis=-1)
        outs.append(jnp.einsum("hqk,khd->qhd", _q(a, low), _q(v[:q1], low),
                               precision=HIGHEST))
    att = jnp.concatenate(outs).reshape(n, h * dv)
    return _mm(att, w[p + "o"], low)


def route(scores, bias, sizes):
    """(n, E) sigmoid scores and the (E,) selection bias -> (n, E) gates:
    zero off the selection, the selected scores over their sum (plus 1e-20)
    times the scaling factor on it. Group-limited: the experts lie in
    ``n_group`` groups of consecutive indices; a group scores the sum of its
    two largest biased scores; the ``topk_group`` best groups are kept and
    the biased scores of the others are set to 0 before the top-k."""
    n, e = scores.shape
    groups = sizes.get("n_group") or 1
    biased = scores + bias.astype(F32)
    if groups > 1:
        per = biased.reshape(n, groups, e // groups)
        top2, _ = lax.top_k(per, 2)
        _, keep = lax.top_k(jnp.sum(top2, axis=-1), sizes["topk_group"])
        kept = jnp.sum(jax.nn.one_hot(keep, groups, dtype=F32), axis=1)
        biased = jnp.where(jnp.repeat(kept, e // groups, axis=1) > 0,
                           biased, 0.0)
    _, sel = lax.top_k(biased, sizes["num_experts_per_tok"])
    chosen = jnp.sum(jax.nn.one_hot(sel, e, dtype=F32), axis=1)   # 0/1
    gate = scores * chosen
    if sizes.get("norm_topk_prob", True):
        gate = gate / (jnp.sum(gate, axis=1, keepdims=True) + 1e-20)
    return gate * sizes["routed_scaling_factor"]


def _swiglu(r, w_gate, w_up, w_down, low):
    hid = jax.nn.silu(_mm(r, w_gate, low)) * _mm(r, w_up, low)
    return _mm(hid, w_down, low)


def _experts(w, p, r, sizes, low):
    s = jax.nn.sigmoid(_mm(r, w[p + "router"], low))              # (n, E)
    gate = route(s, w[p + "expert_bias"], sizes)
    off = sizes.get("expert_offset", 0)
    here = gate[:, off:off + _held(sizes)]

    def expert(acc, e):
        w_gate, w_up, w_down, g = e
        hid = jax.nn.silu(_mm(r, w_gate, low)) * _mm(r, w_up, low)
        out = jnp.einsum("nf,fc->nc", _q(hid, low), _up(w_down, low),
                         precision=HIGHEST)
        return acc + g[:, None] * out, None

    f, _ = lax.scan(expert, jnp.zeros_like(r),
                    (w[p + "experts.w_gate"], w[p + "experts.w_up"],
                     w[p + "experts.w_down"], here.T))
    return f + _swiglu(r, w[p + "shared.w_gate"], w[p + "shared.w_up"],
                       w[p + "shared.w_down"], low)


def _layer(w, p, x, positions, sizes, experts, low):
    eps = sizes["rms_norm_eps"]
    x = x + _attention(w, p, _rms(x, w[p + "input_norm"], eps), positions,
                       sizes, low)
    r = _rms(x, w[p + "post_attention_norm"], eps)
    if experts:
        return x + _experts(w, p, r, sizes, low)
    return x + _swiglu(r, w[p + "w_gate"], w[p + "w_up"], w[p + "w_down"],
                       low)


def hidden(w, tokens, sizes, control=None):
    """tokens (L,) int32 -> the residual stream after the last layer,
    (L, d) float32, before the final norm."""
    low = control == "fp8"
    positions = jnp.arange(tokens.shape[0])
    x = w["embed"][tokens].astype(F32)
    for i in range(sizes["num_hidden_layers"]):
        x = _layer(w, "layer%d." % i, x, positions, sizes,
                   i >= sizes["first_k_dense_replace"], low)
    return x


def forward(w, tokens, sizes, control=None):
    """tokens (L,) int32 -> logits (L, V) float32; position t sees tokens
    [0, t]."""
    x = hidden(w, tokens, sizes, control)
    return _mm(_rms(x, w["norm"], sizes["rms_norm_eps"]), w["head"],
               control == "fp8")


def forward_mtp(w, tokens, sizes, control=None):
    """The multi-token-prediction module: tokens (L,) -> logits (L - 1, V),
    row i the prediction of token i + 2 from the main model's stream at i
    and the embedding of token i + 1."""
    low, eps = control == "fp8", sizes["rms_norm_eps"]
    n = tokens.shape[0] - 1
    h = hidden(w, tokens, sizes, control)[:n]
    e = w["embed"][tokens[1:]].astype(F32)
    x = _mm(jnp.concatenate([_rms(h, w["mtp.hnorm"], eps),
                             _rms(e, w["mtp.enorm"], eps)], -1),
            w["mtp.eh_proj"], low)
    x = _layer(w, "mtp.layer.", x, jnp.arange(n), sizes, True, low)
    return _mm(_rms(x, w["mtp.shared_head_norm"], eps), w["head"], low)


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
