"""Operations and bytes of a latent-attention (MLA) decoder with a shared
expert and a held share of its routed experts (the `deepseek_v3` block of
``chipbench/configs/gigachat3_702b_a36b.json``), from the configuration's
sizes alone: what the algorithm needs, not what a program happens to do. A
multiply-add is 2 operations; weights and cached rows are counted in the
configuration's dtype (bfloat16, 2 bytes).

The two kernels' own work (`paged_latent_attention_decode_work`,
`moe_grouped_ffn_work`) is kept here for the per-kernel roofline shares that
the builder reads from a device trace (PERF.md section 5); the per-layer
metrics `step_mfu.mla_moe.serve` and `decode_hbm_roofline.mla_moe.serve`
read the whole step's."""
ITEM = 2            # bytes of a bfloat16


def sizes_of(facts):
    """The run's sizes if they are a latent-attention model's, else None."""
    sizes = (facts.get("config") or {}).get("sizes") or {}
    return sizes if "kv_lora_rank" in sizes else None


def layer_counts(s):
    dense = s["first_k_dense_replace"]
    return {"layers": s["num_hidden_layers"], "dense": dense,
            "experts": s["num_hidden_layers"] - dense}


def experts_held(s):
    return s.get("num_experts_held") or s["n_routed_experts"]


def attention_params(s):
    """Matrix parameters of one latent attention operator: q_a, q_b, kv_a,
    kv_b, o (norms left out)."""
    c, h = s["hidden_size"], s["num_attention_heads"]
    nope, rot = s["qk_nope_head_dim"], s["qk_rope_head_dim"]
    rq, rkv, dv = s["q_lora_rank"], s["kv_lora_rank"], s["v_head_dim"]
    return (c * rq + rq * h * (nope + rot) + c * (rkv + rot)
            + rkv * h * (nope + dv) + h * dv * c)


def expert_params(s):
    """One routed expert; a shared expert is as wide."""
    return 3 * s["hidden_size"] * s["moe_intermediate_size"]


def non_expert_params(s):
    """Matrix parameters every token passes through whatever it routes:
    every attention operator, the dense feed-forwards, the routers, the
    shared experts and the head (the embedding lookup is a gather)."""
    n = layer_counts(s)
    c = s["hidden_size"]
    return (n["layers"] * attention_params(s)
            + n["dense"] * 3 * c * s["intermediate_size"]
            + n["experts"] * (c * s["n_routed_experts"]
                              + s["n_shared_experts"] * expert_params(s))
            + c * s["vocab_size"])


def total_params(s):
    """Everything the chip holds: the above, the held routed experts and
    the embedding."""
    return (non_expert_params(s) + s["hidden_size"] * s["vocab_size"]
            + layer_counts(s)["experts"] * experts_held(s) * expert_params(s))


def cached_lanes(s):
    """Live lanes of a token's cached row in one layer."""
    return s["kv_lora_rank"] + s["qk_rope_head_dim"]


def decode_attention_flops_per_row(s):
    """Operations one decode query spends on one cached row in one layer,
    absorbed form: every head's score over the row's live lanes and its
    output over the compressed lanes."""
    return 2 * s["num_attention_heads"] * (cached_lanes(s)
                                           + s["kv_lora_rank"])


def prefill_attention_flops_per_pair(s):
    """Operations of one (query, key) pair in one layer, expanded form:
    every head's score over nope + rope lanes and its output over v lanes."""
    return 2 * s["num_attention_heads"] * (
        s["qk_nope_head_dim"] + s["qk_rope_head_dim"] + s["v_head_dim"])


def lap_flops(s, prefill_tokens, prefills, rows, context_tokens, pairs):
    """Model operations of one scheduler lap: ``prefill_tokens`` prompt
    tokens in ``prefills`` prompts and ``rows`` decode rows through the
    parameters outside the routed experts, ``pairs`` (token, expert) pairs
    really computed here through one expert each, the decode rows'
    attention over ``context_tokens`` cached rows and each prompt's causal
    attention over half its own square."""
    mean_prompt = prefill_tokens / max(1, prefills)
    return (2 * (prefill_tokens + rows) * non_expert_params(s)
            + 2 * pairs * expert_params(s)
            + layer_counts(s)["layers"] * (
                context_tokens * decode_attention_flops_per_row(s)
                + prefill_tokens * mean_prompt / 2.0
                * prefill_attention_flops_per_pair(s)))


def non_expert_weight_bytes(s):
    """What a decode step reads whatever it routes."""
    return ITEM * non_expert_params(s)


def cache_bytes_per_token(s):
    """Live bytes of one token's rows over all layers (the pool's padding
    to the lane tile is not counted: it need not be read)."""
    return layer_counts(s)["layers"] * cached_lanes(s) * ITEM


def decode_step_bytes(s, experts_hit, context_tokens):
    """Bytes a decode step has to read: the weights outside the routed
    experts, each routed expert that was hit once (``experts_hit`` summed
    over the expert layers), and the live cached rows."""
    return (non_expert_weight_bytes(s)
            + experts_hit * expert_params(s) * ITEM
            + context_tokens * cache_bytes_per_token(s))


def paged_latent_attention_decode_work(s, rows, context_tokens):
    """(operations, bytes) of ONE call of `paged_latent_attention_decode`:
    one layer's scores and outputs over the live rows, the rows themselves,
    the queries in and the attended rows out."""
    h = s["num_attention_heads"]
    ops = context_tokens * decode_attention_flops_per_row(s)
    byts = context_tokens * cached_lanes(s) * ITEM \
        + rows * h * (cached_lanes(s) + s["kv_lora_rank"]) * ITEM
    return ops, byts


def moe_grouped_ffn_work(s, pairs, experts_hit):
    """(operations, bytes) of ONE call of `moe_grouped_ffn`: three products
    a (token, expert) pair, each hit expert's three matrices read once, the
    routed rows in (bfloat16) and out (float32)."""
    c = s["hidden_size"]
    ops = 2 * pairs * expert_params(s)
    byts = experts_hit * expert_params(s) * ITEM + pairs * c * (ITEM + 4)
    return ops, byts
