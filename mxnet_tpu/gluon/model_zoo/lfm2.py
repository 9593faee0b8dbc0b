"""LFM2-MoE decoder LM: gated short convolutions beside grouped-query
attention, a drop-free sparse expert feed-forward.

Not in the reference. The architecture is LiquidAI's `lfm2_moe` as its public
config describes it: pre-norm RMS blocks whose operator is either a gated
short convolution (``B, C, X = split3(r W_in); o = (C * conv(B * X)) W_out``,
a depthwise causal convolution of ``conv_L_cache`` taps) or grouped-query
attention with a per-head RMS norm on q and k and rotary positions; the
first ``num_dense_layers`` layers have a dense SwiGLU, the others route every
token to ``num_experts_per_tok`` of ``num_experts`` experts by a sigmoid router
whose bias selects but does not weigh. No bias anywhere, no position table,
the head tied to the embedding.

The eager forward here, the generation engine's prefill and decode programs
(`serving/generate.py`) and nothing else call the same layer functions in
``ops/nn.py`` and ``ops/contrib.py``. An expert layer is told which experts
it holds (``num_experts_held`` from ``expert_offset``): it routes over all of
them and computes its own part, so the parts of all holders add up to the
whole layer.
"""
from __future__ import annotations

from ...base import MXNetError
from ..block import HybridBlock

__all__ = ["Lfm2Layer", "Lfm2LM", "lfm2_mini"]


class Lfm2Layer(HybridBlock):
    """One block: operator (attention | conv) and feed-forward (dense |
    experts), each behind its own RMS norm, each added to the residual."""

    def __init__(self, cfg, index, **kwargs):
        super().__init__(**kwargs)
        self._cfg = cfg
        self.operator = cfg["layer_types"][index]
        self.experts = index >= cfg["num_dense_layers"]
        c, d = cfg["hidden_size"], cfg["head_dim"]
        h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
        dt = cfg["dtype"]

        def get(name, shape, init=None):
            return self.params.get(name, shape=shape, dtype=dt, init=init)

        with self.name_scope():
            self.operator_norm = get("operator_norm", (c,), "ones")
            self.ffn_norm = get("ffn_norm", (c,), "ones")
            if self.operator == "full_attention":
                self.q_weight = get("q_weight", (h * d, c))
                self.k_weight = get("k_weight", (kv * d, c))
                self.v_weight = get("v_weight", (kv * d, c))
                self.o_weight = get("o_weight", (c, h * d))
                self.q_norm = get("q_norm", (d,), "ones")
                self.k_norm = get("k_norm", (d,), "ones")
            elif self.operator == "conv":
                self.in_weight = get("in_weight", (3 * c, c))
                self.conv_weight = get("conv_weight",
                                       (c, cfg["conv_L_cache"]))
                self.out_weight = get("out_weight", (c, c))
            else:
                raise MXNetError("layer type %r is neither full_attention "
                                 "nor conv" % (self.operator,))
            if self.experts:
                e, f = cfg["num_experts"], cfg["moe_intermediate_size"]
                held = cfg["num_experts_held"]
                self.gate_weight = get("gate_weight", (e, c))
                self.expert_bias = get("expert_bias", (e,), "zeros")
                # all three (held, F, C): a block of an expert's width is
                # contiguous in each (ops/pallas_kernels.moe_grouped_ffn)
                self.expert_w1 = get("expert_w1", (held, f, c))
                self.expert_w3 = get("expert_w3", (held, f, c))
                self.expert_w2 = get("expert_w2", (held, f, c))
            else:
                f = cfg["intermediate_size"]
                self.w1 = get("w1", (f, c))
                self.w3 = get("w3", (f, c))
                self.w2 = get("w2", (c, f))

    def hybrid_forward(self, F, x, positions, **p):
        cfg = self._cfg
        eps = cfg["norm_eps"]
        r = F.rms_norm(x, p["operator_norm"], eps=eps)
        if self.operator == "full_attention":
            h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
            d = cfg["head_dim"]

            def proj(w, heads):
                return F.FullyConnected(r, w, None, num_hidden=heads * d,
                                        flatten=False, no_bias=True) \
                    .reshape((0, 0, heads, d))

            q = F.rms_norm(proj(p["q_weight"], h), p["q_norm"], eps=eps)
            k = F.rms_norm(proj(p["k_weight"], kv), p["k_norm"], eps=eps)
            q = F.contrib.rope(q, positions, theta=cfg["rope_theta"])
            k = F.contrib.rope(k, positions, theta=cfg["rope_theta"])
            a = F.contrib.causal_attention(q, k, proj(p["v_weight"], kv))
            o = F.FullyConnected(a.reshape((0, 0, h * d)), p["o_weight"],
                                 None, num_hidden=cfg["hidden_size"],
                                 flatten=False, no_bias=True)
        else:
            o, _ = F.contrib.gated_short_conv(r, p["in_weight"],
                                              p["conv_weight"],
                                              p["out_weight"])
        x = x + o
        r = F.rms_norm(x, p["ffn_norm"], eps=eps)
        if self.experts:
            f, _ = F.contrib.sigmoid_topk_moe(
                r, p["gate_weight"], p["expert_bias"], p["expert_w1"],
                p["expert_w3"], p["expert_w2"],
                k=cfg["num_experts_per_tok"],
                expert_offset=cfg["expert_offset"],
                routed_scaling_factor=cfg["routed_scaling_factor"],
                norm_topk_prob=cfg["norm_topk_prob"])
        else:
            f = F.contrib.swiglu_ffn(r, p["w1"], p["w3"], p["w2"])
        return x + f


class Lfm2LM(HybridBlock):
    """The decoder LM: ``inputs`` (B, L) int token ids -> logits (B, L, V)
    float32; position t sees tokens [0, t]."""

    def __init__(self, vocab_size=65536, hidden_size=2048,
                 intermediate_size=11776, moe_intermediate_size=1536,
                 num_experts=64, num_experts_per_tok=4,
                 num_attention_heads=32, num_key_value_heads=8,
                 layer_types=("conv", "full_attention"), num_dense_layers=1,
                 conv_L_cache=3, norm_eps=1e-5, rope_theta=1000000.0,
                 max_position_embeddings=128000, routed_scaling_factor=1.0,
                 norm_topk_prob=True, num_experts_held=None, expert_offset=0,
                 dtype="bfloat16", **kwargs):
        super().__init__(**kwargs)
        if hidden_size % num_attention_heads \
                or num_attention_heads % num_key_value_heads:
            raise MXNetError("heads must divide the hidden size, and KV "
                             "heads the heads")
        held = num_experts if num_experts_held is None else num_experts_held
        if expert_offset < 0 or expert_offset + held > num_experts:
            raise MXNetError("experts %d..%d are not among %d"
                             % (expert_offset, expert_offset + held,
                                num_experts))
        self._config = dict(
            vocab_size=int(vocab_size), hidden_size=int(hidden_size),
            intermediate_size=int(intermediate_size),
            moe_intermediate_size=int(moe_intermediate_size),
            num_experts=int(num_experts),
            num_experts_per_tok=int(num_experts_per_tok),
            num_attention_heads=int(num_attention_heads),
            num_key_value_heads=int(num_key_value_heads),
            layer_types=[str(t) for t in layer_types],
            num_dense_layers=int(num_dense_layers),
            conv_L_cache=int(conv_L_cache), norm_eps=float(norm_eps),
            rope_theta=float(rope_theta),
            max_position_embeddings=int(max_position_embeddings),
            routed_scaling_factor=float(routed_scaling_factor),
            norm_topk_prob=bool(norm_topk_prob),
            num_experts_held=int(held), expert_offset=int(expert_offset),
            dtype=str(dtype))
        cfg = dict(self._config,
                   head_dim=int(hidden_size) // int(num_attention_heads))
        self._cfg = cfg
        with self.name_scope():
            self.word_weight = self.params.get(
                "word_weight", shape=(vocab_size, hidden_size), dtype=dtype)
            self.embedding_norm = self.params.get(
                "embedding_norm", shape=(hidden_size,), dtype=dtype,
                init="ones")
            self.cells = []
            for i in range(len(cfg["layer_types"])):
                cell = Lfm2Layer(cfg, i, prefix="layer%d_" % i)
                self.register_child(cell)
                self.cells.append(cell)

    @property
    def config(self):
        """Constructor arguments (`serving.generate` artifact header)."""
        return dict(self._config, layer_types=list(
            self._config["layer_types"]))

    def description(self):
        """The per-layer description the generation engine builds its
        prefill and decode programs from (docs/serving.md §Generation)."""
        cfg = self._cfg
        return {
            "arch": "lfm2", "dtype": cfg["dtype"],
            "units": cfg["hidden_size"], "vocab_size": cfg["vocab_size"],
            "max_length": cfg["max_position_embeddings"],
            "norm": "rms", "norm_at": "pre", "norm_eps": cfg["norm_eps"],
            "positions": "rotary", "rope_theta": cfg["rope_theta"],
            "embed_norm": False, "final_norm": True, "head": "tied",
            "heads": cfg["num_attention_heads"],
            "kv_heads": cfg["num_key_value_heads"],
            "head_dim": cfg["head_dim"], "qk_norm": True, "ffn": "swiglu",
            "conv_taps": cfg["conv_L_cache"],
            "experts": {"total": cfg["num_experts"],
                        "held": cfg["num_experts_held"],
                        "offset": cfg["expert_offset"],
                        "per_token": cfg["num_experts_per_tok"],
                        "scaling": cfg["routed_scaling_factor"],
                        "norm_topk": cfg["norm_topk_prob"]},
            "layers": [
                {"operator": "attention" if t == "full_attention" else "conv",
                 "ffn": "experts" if i >= cfg["num_dense_layers"]
                 else "dense"}
                for i, t in enumerate(cfg["layer_types"])]}

    def hybrid_forward(self, F, inputs, word_weight, embedding_norm):
        cfg = self._cfg
        x = F.Embedding(inputs, word_weight, input_dim=cfg["vocab_size"],
                        output_dim=cfg["hidden_size"],
                        dtype=cfg["dtype"])
        positions = F.arange(0, inputs.shape[1], dtype="int32")
        for cell in self.cells:
            x = cell(x, positions)
        x = F.rms_norm(x, embedding_norm, eps=cfg["norm_eps"])
        # tied head: logits = x @ word_weight.T, float32
        return F.FullyConnected(x.astype("float32"),
                                word_weight.astype("float32"), None,
                                num_hidden=cfg["vocab_size"], flatten=False,
                                no_bias=True)

    def decode_params(self):
        """The parameters as the structured dict of device arrays that
        `serving.generate.TransformerLMEngine` consumes (the engine and this
        block compute the same function: tests/test_lfm2.py)."""
        if any(p._data is None for p in self.collect_params().values()):
            from ... import nd

            self(nd.array([[0]], dtype="int32"))

        def arr(p):
            return p.data()._data

        layers = []
        for cell in self.cells:
            layer = {"attn_norm": {"g": arr(cell.operator_norm)},
                     "ffn_norm": {"g": arr(cell.ffn_norm)}}
            if cell.operator == "full_attention":
                layer.update(
                    q={"w": arr(cell.q_weight)}, k={"w": arr(cell.k_weight)},
                    v={"w": arr(cell.v_weight)}, o={"w": arr(cell.o_weight)},
                    q_norm={"g": arr(cell.q_norm)},
                    k_norm={"g": arr(cell.k_norm)})
            else:
                layer.update({"in": {"w": arr(cell.in_weight)},
                              "conv": arr(cell.conv_weight),
                              "out": {"w": arr(cell.out_weight)}})
            if cell.experts:
                layer.update(gate=arr(cell.gate_weight),
                             expert_bias=arr(cell.expert_bias),
                             ew1=arr(cell.expert_w1), ew3=arr(cell.expert_w3),
                             ew2=arr(cell.expert_w2))
            else:
                layer.update(w1=arr(cell.w1), w3=arr(cell.w3),
                             w2=arr(cell.w2))
            layers.append(layer)
        return {"word": arr(self.word_weight),
                "final_norm": {"g": arr(self.embedding_norm)},
                "layers": layers}


def lfm2_mini(vocab_size=128, **kwargs):
    """Tiny LFM2-MoE for tests: conv, attention, conv, conv; one leading
    dense layer, 8 experts of which 2 a token; float32."""
    cfg = dict(hidden_size=128, intermediate_size=256,
               moe_intermediate_size=128, num_experts=8,
               num_experts_per_tok=2, num_attention_heads=4,
               num_key_value_heads=2,
               layer_types=("conv", "full_attention", "conv", "conv"),
               num_dense_layers=1, max_position_embeddings=256,
               dtype="float32")
    cfg.update(kwargs)
    return Lfm2LM(vocab_size=vocab_size, **cfg)
