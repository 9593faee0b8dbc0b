"""Operations and bytes of a decoder whose layers mix sliding-window and full
attention over drop-free ReGLU experts (the `smallthinker` block of
``chipbench/configs/smallthinker_21b_a3b.json``), from the configuration's
sizes alone: what the algorithm needs, not what a program happens to do. A
multiply-add is 2 operations; weights and cached rows are counted in the
configuration's dtype (bfloat16, 2 bytes). A window layer's attention is
counted over its BAND: a query's last ``sliding_window_size`` keys, not the
whole context.

The three kernels' own work (`paged_attention_decode_work`,
`moe_grouped_ffn_work`, `prompt_attention_work`) is kept here for the per-kernel roofline shares that
the builder reads from a device trace (PERF.md section 5); the per-layer
metrics `step_mfu.swa_moe.serve` and `decode_hbm_roofline.swa_moe.serve`
read the whole step's."""
ITEM = 2            # bytes of a bfloat16


def sizes_of(facts):
    """The run's sizes if they are a window-and-full model's, else None."""
    sizes = (facts.get("config") or {}).get("sizes") or {}
    return sizes if "sliding_window_layout" in sizes else None


def layer_counts(s):
    window = sum(1 for w in s["sliding_window_layout"] if w)
    return {"layers": len(s["sliding_window_layout"]), "window": window,
            "full": len(s["sliding_window_layout"]) - window}


def experts_held(s):
    return s.get("num_experts_held") or s["moe_num_primary_experts"]


def attention_params(s):
    """q, k, v, o of one layer (norms left out)."""
    c, d = s["hidden_size"], s["head_dim"]
    h, kv = s["num_attention_heads"], s["num_key_value_heads"]
    return 2 * c * h * d + 2 * c * kv * d


def expert_params(s):
    return 3 * s["hidden_size"] * s["moe_ffn_hidden_size"]


def layer_params(s):
    """Every parameter of one layer: attention, router, all experts, the
    two norms."""
    c = s["hidden_size"]
    return (attention_params(s) + c * s["moe_num_primary_experts"]
            + s["moe_num_primary_experts"] * expert_params(s) + 2 * c)


def total_params(s):
    """The layers, the embedding, the untied head and the final norm."""
    c = s["hidden_size"]
    return (layer_counts(s)["layers"] * layer_params(s)
            + 2 * c * s["vocab_size"] + c)


def active_layer_params(s):
    """Matrix parameters of one layer that one token passes through: the
    attention projections, the router, its k experts."""
    return (attention_params(s)
            + s["hidden_size"] * s["moe_num_primary_experts"]
            + s["moe_num_active_primary_experts"] * expert_params(s))


def head_flops(s):
    """The head, for a row whose logits are taken (a decode row, a prompt's
    last)."""
    return 2 * s["hidden_size"] * s["vocab_size"]


def causal_pairs(length, window=None):
    """(query, key) pairs of a causal forward over ``length`` positions:
    the triangle, or with ``window`` the band."""
    if not window or length <= window:
        return length * (length + 1) / 2.0
    return window * (window + 1) / 2.0 + (length - window) * window


def attention_flops(s, full_pairs, window_pairs):
    """q.k and p.v of every query head over the pairs attended, a full
    layer's and a window layer's, in every layer of each kind."""
    n = layer_counts(s)
    per_pair = 4 * s["num_attention_heads"] * s["head_dim"]
    return per_pair * (n["full"] * full_pairs + n["window"] * window_pairs)


def lap_flops(s, prefill_tokens, prefills, rows, context_tokens,
              window_tokens):
    """Model FLOPs of one scheduler lap: every prompt token and every
    decode row through the active parameters of every layer, the head for
    the rows that are sampled, a decode row's attention over its cache
    (``context_tokens`` in the full layers, ``window_tokens`` in the window
    layers), a prompt's over its triangle or band (prompts of the mean
    length: the lap holds sums)."""
    tokens = prefill_tokens + rows
    mean_prompt = prefill_tokens / max(1, prefills)
    w = s["sliding_window_size"]
    return (tokens * 2 * layer_counts(s)["layers"] * active_layer_params(s)
            + (rows + prefills) * head_flops(s)
            + attention_flops(
                s, context_tokens + prefills * causal_pairs(mean_prompt),
                window_tokens + prefills * causal_pairs(mean_prompt, w)))


def non_expert_weight_bytes(s):
    """What a decode step reads whatever it routes: every layer's attention,
    router and norms, the final norm and the head (the embedding is a
    gather of a row a token)."""
    c = s["hidden_size"]
    return ITEM * (layer_counts(s)["layers"] * (
        attention_params(s) + c * s["moe_num_primary_experts"] + 2 * c)
        + c * s["vocab_size"] + c)


def kv_bytes_per_token(s):
    """(a full layer's group, a window layer's group): K and V of every
    layer of the group."""
    n = layer_counts(s)
    row = 2 * s["num_key_value_heads"] * s["head_dim"] * ITEM
    return n["full"] * row, n["window"] * row


def decode_step_bytes(s, experts_hit, context_tokens, window_tokens):
    """Bytes a decode step has to read: the weights outside the experts,
    each expert that was hit once (``experts_hit`` summed over the layers),
    the full layers' live KV and the window layers' live KV."""
    full, window = kv_bytes_per_token(s)
    return (non_expert_weight_bytes(s)
            + experts_hit * expert_params(s) * ITEM
            + context_tokens * full + window_tokens * window)


def kv_rows_streamed_share(s, context_tokens, window_tokens):
    """Cached rows a decode step streams over what it would stream were
    every layer a full-attention layer (%): what the window saves."""
    n = layer_counts(s)
    return 100.0 * (n["full"] * context_tokens + n["window"] * window_tokens) \
        / (n["layers"] * context_tokens)


def paged_attention_decode_work(s, rows, live_tokens):
    """(operations, bytes) of ONE call of the grouped-query
    `paged_attention_decode` kernel: one layer's q.k and p.v over the live
    keys (the context in a full layer, the window's share of it in a window
    layer), its live K and V rows, the queries in and the output out."""
    h, kv, d = s["num_attention_heads"], s["num_key_value_heads"], \
        s["head_dim"]
    ops = 4 * h * d * live_tokens
    byts = 2 * kv * d * ITEM * live_tokens + 2 * rows * h * d * ITEM
    return ops, byts


def prompt_attention_work(s, length, window=None):
    """(operations, bytes) of ONE layer's attention over a prompt of
    ``length`` tokens through the splash-attention kernel (one call a KV
    head, `splash_mqa_fwd_no_residuals`): q.k and p.v of every query head
    over the triangle's pairs, or the band's; q in and the output out once,
    K and V streamed once a query block whose mask reaches them (counted
    here once: a floor)."""
    h, kv, d = s["num_attention_heads"], s["num_key_value_heads"], \
        s["head_dim"]
    ops = 4 * h * d * causal_pairs(length, window)
    byts = (2 * h + 2 * kv) * d * ITEM * length
    return ops, byts


def moe_grouped_ffn_work(s, pairs, experts_hit):
    """(operations, bytes) of ONE call of `moe_grouped_ffn`: three products
    a (token, expert) pair, each hit expert's three matrices read once, the
    routed rows in (bfloat16) and out (float32)."""
    c = s["hidden_size"]
    ops = 2 * pairs * expert_params(s)
    byts = experts_hit * expert_params(s) * ITEM + pairs * c * (ITEM + 4)
    return ops, byts
