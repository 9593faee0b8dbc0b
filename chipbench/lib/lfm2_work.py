"""Operations and bytes of the LFM2-MoE serving programs, from the
configuration's sizes alone: what the algorithm needs, not what a program
happens to do. A multiply-add is 2 operations; weights, pages and slots are
counted in the configuration's dtype (bfloat16, 2 bytes).

The two kernels' own work (`paged_attention_decode_work`,
`moe_grouped_ffn_work`) is kept here for the per-kernel roofline shares that
the builder reads from a device trace (PERF.md section 5); the per-layer
metrics `step_mfu.serve` and `decode_hbm_roofline.serve` read the whole
step's."""
ITEM = 2            # bytes of a bfloat16


def sizes_of(facts):
    """The run's LFM2 sizes, or None for another configuration."""
    sizes = (facts.get("config") or {}).get("sizes") or {}
    return sizes if "num_experts" in sizes else None


def _dims(s):
    c = s["hidden_size"]
    h, kv = s["num_attention_heads"], s["num_key_value_heads"]
    return c, h, kv, c // h


def layer_counts(s):
    attn = sum(1 for t in s["layer_types"] if t == "full_attention")
    conv = len(s["layer_types"]) - attn
    dense = s["num_dense_layers"]
    return {"attention": attn, "conv": conv, "dense": dense,
            "experts": len(s["layer_types"]) - dense}


def operator_params(s):
    """(attention, conv) parameters of one operator, norms left out."""
    c, h, kv, d = _dims(s)
    attn = c * h * d * 2 + c * kv * d * 2
    conv = 3 * c * c + c * c + c * s["conv_L_cache"]
    return attn, conv


def expert_params(s):
    return 3 * s["hidden_size"] * s["moe_intermediate_size"]


def experts_held(s):
    return s.get("num_experts_held") or s["num_experts"]


def active_params_per_token(s):
    """Matrix parameters one token passes through: every operator, the dense
    feed-forwards, the router and its k experts in each expert layer, and
    the head (the embedding lookup is a gather, not a product)."""
    n = layer_counts(s)
    attn, conv = operator_params(s)
    c = s["hidden_size"]
    return (n["attention"] * attn + n["conv"] * conv
            + n["dense"] * 3 * c * s["intermediate_size"]
            + n["experts"] * (c * s["num_experts"]
                              + s["num_experts_per_tok"] * expert_params(s))
            + c * s["vocab_size"])


def token_flops(s):
    return 2 * active_params_per_token(s)


def attention_flops(s, context_tokens):
    """q.k and p.v of every query head over ``context_tokens`` cached
    positions (summed over the rows of a step), in every attention layer."""
    c, h, kv, d = _dims(s)
    return layer_counts(s)["attention"] * 4 * h * d * context_tokens


def non_expert_weight_bytes(s):
    """What a decode step reads whatever it routes: every operator, the
    dense feed-forwards, the routers and the head."""
    n = layer_counts(s)
    attn, conv = operator_params(s)
    c = s["hidden_size"]
    return ITEM * (n["attention"] * attn + n["conv"] * conv
                   + n["dense"] * 3 * c * s["intermediate_size"]
                   + n["experts"] * c * s["num_experts"]
                   + c * s["vocab_size"])


def kv_bytes_per_token(s):
    c, h, kv, d = _dims(s)
    return layer_counts(s)["attention"] * 2 * kv * d * ITEM


def slot_bytes_per_sequence(s):
    return layer_counts(s)["conv"] * (s["conv_L_cache"] - 1) \
        * s["hidden_size"] * ITEM


def decode_step_bytes(s, rows, experts_hit, context_tokens):
    """Bytes a decode step of ``rows`` sequences has to read: the weights
    outside the experts, each expert that was hit once (``experts_hit``
    summed over the expert layers), the live KV, and the slots (read and
    written)."""
    return (non_expert_weight_bytes(s)
            + experts_hit * expert_params(s) * ITEM
            + context_tokens * kv_bytes_per_token(s)
            + 2 * rows * slot_bytes_per_sequence(s))


def paged_attention_decode_work(s, rows, context_tokens):
    """(operations, bytes) of ONE call of the grouped-query
    `paged_attention_decode` kernel: one layer's q.k and p.v over the live
    context, its live K and V rows, the queries in and the output out."""
    c, h, kv, d = _dims(s)
    ops = 4 * h * d * context_tokens
    byts = 2 * kv * d * ITEM * context_tokens + 2 * rows * h * d * ITEM
    return ops, byts


def moe_grouped_ffn_work(s, pairs, experts_hit):
    """(operations, bytes) of ONE call of `moe_grouped_ffn`: three products
    a (token, expert) pair, each hit expert's three matrices read once, the
    routed rows in (bfloat16) and out (float32)."""
    c = s["hidden_size"]
    ops = 2 * pairs * expert_params(s)
    byts = experts_hit * expert_params(s) * ITEM + pairs * c * (ITEM + 4)
    return ops, byts
