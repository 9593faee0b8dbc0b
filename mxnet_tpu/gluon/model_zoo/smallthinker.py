"""SmallThinker decoder LM: sliding-window layers beside full-attention
layers without positions, a router that reads the rows BEFORE attention,
sparse ReGLU experts.

Not in the reference. The architecture is PowerInfer's `smallthinker` as its
public config describes it: pre-norm RMS blocks of grouped-query attention
and a drop-free expert feed-forward in every layer, no dense layer, no shared
expert. ``sliding_window_layout[l]`` = 1 makes layer l attend over the last
``sliding_window_size`` keys (its own among them), 0 over the whole context;
``rope_layout[l]`` = 1 rotates q and k over the whole head, 0 applies no
positional encoding at all (NoPE). The router's logits are taken from the
operator's normed INPUT rows, the ``moe_num_active_primary_experts`` largest
select, and the gates are a softmax over the selected logits; an expert is
``W_down (relu(W_gate u) * W_up u)`` over the post-attention normed rows u.
No bias anywhere, a final RMS norm, the head untied.

The eager forward here, the generation engine's prefill and decode programs
(`serving/generate.py`) and nothing else call the same layer functions in
``ops/nn.py`` and ``ops/contrib.py``. An expert layer is told which experts
it holds (``num_experts_held`` from ``expert_offset``): it routes over all
of them and computes its own part, so the parts of all holders add up to the
whole layer.
"""
from __future__ import annotations

from ...base import MXNetError
from ..block import HybridBlock

__all__ = ["SmallThinkerLayer", "SmallThinkerLM", "smallthinker_mini"]


class SmallThinkerLayer(HybridBlock):
    """One block: attention (windowed or full, rotary or NoPE) and the
    expert feed-forward, each behind its own RMS norm, each added to the
    residual; the router reads the first norm's rows."""

    def __init__(self, cfg, index, **kwargs):
        super().__init__(**kwargs)
        self._cfg = cfg
        self.window = cfg["sliding_window_size"] \
            if cfg["sliding_window_layout"][index] else None
        self.rotary = bool(cfg["rope_layout"][index])
        c, d = cfg["hidden_size"], cfg["head_dim"]
        h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
        e, f = cfg["moe_num_primary_experts"], cfg["moe_ffn_hidden_size"]
        held, dt = cfg["num_experts_held"], cfg["dtype"]

        def get(name, shape, init=None):
            return self.params.get(name, shape=shape, dtype=dt, init=init)

        with self.name_scope():
            self.input_norm = get("input_norm", (c,), "ones")
            self.post_attention_norm = get("post_attention_norm", (c,),
                                           "ones")
            self.q_weight = get("q_weight", (h * d, c))
            self.k_weight = get("k_weight", (kv * d, c))
            self.v_weight = get("v_weight", (kv * d, c))
            self.o_weight = get("o_weight", (c, h * d))
            self.gate_weight = get("gate_weight", (e, c))
            # all three (held, F, C): a block of an expert's width is
            # contiguous in each (ops/pallas_kernels.moe_grouped_ffn)
            self.expert_w1 = get("expert_w1", (held, f, c))
            self.expert_w3 = get("expert_w3", (held, f, c))
            self.expert_w2 = get("expert_w2", (held, f, c))

    def hybrid_forward(self, F, x, positions, **p):
        cfg = self._cfg
        eps, d = cfg["rms_norm_eps"], cfg["head_dim"]
        h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
        r = F.rms_norm(x, p["input_norm"], eps=eps)

        def proj(w, heads):
            return F.FullyConnected(r, w, None, num_hidden=heads * d,
                                    flatten=False, no_bias=True) \
                .reshape((0, 0, heads, d))

        q, k = proj(p["q_weight"], h), proj(p["k_weight"], kv)
        if self.rotary:
            q = F.contrib.rope(q, positions, theta=cfg["rope_theta"])
            k = F.contrib.rope(k, positions, theta=cfg["rope_theta"])
        a = F.contrib.causal_attention(q, k, proj(p["v_weight"], kv),
                                       window=self.window)
        x = x + F.FullyConnected(a.reshape((0, 0, h * d)), p["o_weight"],
                                 None, num_hidden=cfg["hidden_size"],
                                 flatten=False, no_bias=True)
        u = F.rms_norm(x, p["post_attention_norm"], eps=eps)
        f, _ = F.contrib.sigmoid_topk_moe(
            u, p["gate_weight"], None, p["expert_w1"], p["expert_w3"],
            p["expert_w2"], r, k=cfg["moe_num_active_primary_experts"],
            expert_offset=cfg["expert_offset"], scores="softmax_selected",
            activation="relu")
        return x + f


class SmallThinkerLM(HybridBlock):
    """The decoder LM: ``inputs`` (B, L) int token ids -> logits (B, L, V)
    float32; position t sees tokens [0, t]."""

    def __init__(self, vocab_size=151936, hidden_size=2560, head_dim=128,
                 num_attention_heads=28, num_key_value_heads=4,
                 moe_ffn_hidden_size=768, moe_num_primary_experts=64,
                 moe_num_active_primary_experts=6,
                 sliding_window_layout=(0, 1, 1, 1), rope_layout=(0, 1, 1, 1),
                 sliding_window_size=4096, rms_norm_eps=1e-6,
                 rope_theta=1500000.0, max_position_embeddings=16384,
                 num_experts_held=None, expert_offset=0, dtype="bfloat16",
                 **kwargs):
        super().__init__(**kwargs)
        if num_attention_heads % num_key_value_heads:
            raise MXNetError("KV heads must divide the heads")
        if len(sliding_window_layout) != len(rope_layout):
            raise MXNetError("the two layouts name the same layers: %d and "
                             "%d entries" % (len(sliding_window_layout),
                                             len(rope_layout)))
        held = moe_num_primary_experts if num_experts_held is None \
            else num_experts_held
        if expert_offset < 0 or expert_offset + held > moe_num_primary_experts:
            raise MXNetError("experts %d..%d are not among %d"
                             % (expert_offset, expert_offset + held,
                                moe_num_primary_experts))
        self._config = dict(
            vocab_size=int(vocab_size), hidden_size=int(hidden_size),
            head_dim=int(head_dim),
            num_attention_heads=int(num_attention_heads),
            num_key_value_heads=int(num_key_value_heads),
            moe_ffn_hidden_size=int(moe_ffn_hidden_size),
            moe_num_primary_experts=int(moe_num_primary_experts),
            moe_num_active_primary_experts=int(
                moe_num_active_primary_experts),
            sliding_window_layout=[int(v) for v in sliding_window_layout],
            rope_layout=[int(v) for v in rope_layout],
            sliding_window_size=int(sliding_window_size),
            rms_norm_eps=float(rms_norm_eps), rope_theta=float(rope_theta),
            max_position_embeddings=int(max_position_embeddings),
            num_experts_held=int(held), expert_offset=int(expert_offset),
            dtype=str(dtype))
        cfg = self._cfg = self.config
        with self.name_scope():
            self.word_weight = self.params.get(
                "word_weight", shape=(vocab_size, hidden_size), dtype=dtype)
            self.final_norm = self.params.get(
                "final_norm", shape=(hidden_size,), dtype=dtype, init="ones")
            self.head_weight = self.params.get(
                "head_weight", shape=(vocab_size, hidden_size), dtype=dtype)
            self.cells = []
            for i in range(len(cfg["rope_layout"])):
                cell = SmallThinkerLayer(cfg, i, prefix="layer%d_" % i)
                self.register_child(cell)
                self.cells.append(cell)

    @property
    def config(self):
        """Constructor arguments (`serving.generate` artifact header)."""
        return dict(self._config,
                    sliding_window_layout=list(
                        self._config["sliding_window_layout"]),
                    rope_layout=list(self._config["rope_layout"]))

    def description(self):
        """The per-layer description the generation engine builds its
        prefill and decode programs from (docs/serving.md §Generation):
        every layer says its ``window`` (None: the whole context) and
        whether it is ``rotary``."""
        cfg = self._cfg
        return {
            "arch": "smallthinker", "dtype": cfg["dtype"],
            "units": cfg["hidden_size"], "vocab_size": cfg["vocab_size"],
            "max_length": cfg["max_position_embeddings"],
            "norm": "rms", "norm_at": "pre", "norm_eps": cfg["rms_norm_eps"],
            "positions": "rotary", "rope_theta": cfg["rope_theta"],
            "embed_norm": False, "final_norm": True, "head": "own",
            "heads": cfg["num_attention_heads"],
            "kv_heads": cfg["num_key_value_heads"],
            "head_dim": cfg["head_dim"], "qk_norm": False, "ffn": "swiglu",
            "experts": {"total": cfg["moe_num_primary_experts"],
                        "held": cfg["num_experts_held"],
                        "offset": cfg["expert_offset"],
                        "per_token": cfg["moe_num_active_primary_experts"],
                        "scaling": 1.0, "norm_topk": False,
                        "scores": "softmax_selected", "activation": "relu",
                        "router_rows": "operator"},
            "layers": [
                {"operator": "attention", "ffn": "experts",
                 "window": cfg["sliding_window_size"] if windowed else None,
                 "rotary": bool(rotary)}
                for windowed, rotary in zip(cfg["sliding_window_layout"],
                                            cfg["rope_layout"])]}

    def hybrid_forward(self, F, inputs, word_weight, final_norm, head_weight):
        cfg = self._cfg
        x = F.Embedding(inputs, word_weight, input_dim=cfg["vocab_size"],
                        output_dim=cfg["hidden_size"], dtype=cfg["dtype"])
        positions = F.arange(0, inputs.shape[1], dtype="int32")
        for cell in self.cells:
            x = cell(x, positions)
        x = F.rms_norm(x, final_norm, eps=cfg["rms_norm_eps"])
        return F.FullyConnected(x.astype("float32"),
                                head_weight.astype("float32"), None,
                                num_hidden=cfg["vocab_size"], flatten=False,
                                no_bias=True)

    def decode_params(self):
        """The parameters as the structured dict of device arrays that
        `serving.generate.TransformerLMEngine` consumes (the engine and this
        block compute the same function: tests/test_smallthinker.py)."""
        if any(p._data is None for p in self.collect_params().values()):
            from ... import nd

            self(nd.array([[0]], dtype="int32"))

        def arr(p):
            return p.data()._data

        return {"word": arr(self.word_weight), "head": arr(self.head_weight),
                "final_norm": {"g": arr(self.final_norm)},
                "layers": [
                    {"attn_norm": {"g": arr(cell.input_norm)},
                     "ffn_norm": {"g": arr(cell.post_attention_norm)},
                     "q": {"w": arr(cell.q_weight)},
                     "k": {"w": arr(cell.k_weight)},
                     "v": {"w": arr(cell.v_weight)},
                     "o": {"w": arr(cell.o_weight)},
                     "gate": arr(cell.gate_weight),
                     "ew1": arr(cell.expert_w1), "ew3": arr(cell.expert_w3),
                     "ew2": arr(cell.expert_w2)} for cell in self.cells]}


def smallthinker_mini(vocab_size=128, **kwargs):
    """Tiny SmallThinker for tests: one period (full NoPE, three window
    layers of 8 keys), width 64, 4 / 2 heads of 16, 8 experts of which 2 a
    token; float32."""
    cfg = dict(hidden_size=64, head_dim=16, num_attention_heads=4,
               num_key_value_heads=2, moe_ffn_hidden_size=32,
               moe_num_primary_experts=8, moe_num_active_primary_experts=2,
               sliding_window_size=8, max_position_embeddings=256,
               dtype="float32")
    cfg.update(kwargs)
    return SmallThinkerLM(vocab_size=vocab_size, **cfg)
