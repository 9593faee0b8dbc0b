"""GigaChat3 decoder LM (``model_type: deepseek_v3``): latent attention, a
group-limited sigmoid router over many experts with a shared expert beside
them, and a multi-token-prediction module.

Not in the reference. The block is ai-sage's GigaChat3.1 as its public
config describes it, computed as the public `deepseek_v3` modelling code
does: pre-norm RMS blocks; **latent attention** — the query through a
low-rank pair (``q_a`` → RMS → ``q_b``), keys and values from ONE compressed
row a token (``kv_a`` → ``kv_lora_rank`` lanes, RMS-normed, beside
``qk_rope_head_dim`` rotary lanes that all heads share), expanded by ``kv_b``
to every head's ``qk_nope_head_dim`` key lanes and ``v_head_dim`` value lanes;
YaRN-scaled rotary positions on the rotary lanes only (rotate-half layout)
and the softmax scale times YaRN's mscale squared; the first
``first_k_dense_replace`` layers a dense SwiGLU, the others
``num_experts_per_tok`` of ``n_routed_experts`` experts chosen by a sigmoid
router whose bias selects but does not weigh, limited to the ``topk_group``
best of ``n_group`` groups, the gates normalised and scaled, plus
``n_shared_experts`` shared expert(s) that every token takes. No bias
anywhere; the head is its own matrix.

The eager forward here and the generation engine's programs
(`serving/generate.py`: prefill expands K and V and attends in query blocks,
decode absorbs ``kv_b`` into the query and the output and attends over the
cached rows) call the same layer functions in ``ops/nn.py`` and
``ops/contrib.py``. An expert layer is told which routed experts it holds
(``num_experts_held`` from ``expert_offset``): it routes over all of them and
computes its own part; the shared expert is whole on every holder.

``num_nextn_predict_layers`` = 1 adds the multi-token-prediction module
(`mtp_logits`): the engine does not draft with it (ROADMAP B10).
"""
from __future__ import annotations

from ...base import MXNetError
from ...ops.nn import latent_softmax_scale, yarn_args
from ..block import HybridBlock

__all__ = ["GigaChat3Layer", "GigaChat3MTP", "GigaChat3LM", "gigachat3_mini"]

_YARN = {"factor": 1.0, "original_max_position_embeddings": 4096,
         "beta_fast": 32, "beta_slow": 1, "mscale": 1.0,
         "mscale_all_dim": 0.0}


def softmax_scale(cfg):
    return latent_softmax_scale(cfg["qk_nope_head_dim"],
                                cfg["qk_rope_head_dim"], cfg["rope_scaling"])


def _fc(F, x, w, n):
    return F.FullyConnected(x, w, None, num_hidden=n, flatten=False,
                            no_bias=True)


class GigaChat3Layer(HybridBlock):
    """One block: latent attention and a feed-forward (dense | experts +
    shared), each behind its own RMS norm, each added to the residual."""

    def __init__(self, cfg, experts, **kwargs):
        super().__init__(**kwargs)
        self._cfg = cfg
        self.experts = bool(experts)
        c, h, dt = cfg["hidden_size"], cfg["num_attention_heads"], \
            cfg["dtype"]
        nope, rot = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
        rq, rkv, dv = cfg["q_lora_rank"], cfg["kv_lora_rank"], \
            cfg["v_head_dim"]

        def get(name, shape, init=None):
            return self.params.get(name, shape=shape, dtype=dt, init=init)

        with self.name_scope():
            self.input_norm = get("input_norm", (c,), "ones")
            self.post_attention_norm = get("post_attention_norm", (c,),
                                           "ones")
            self.q_a_weight = get("q_a_weight", (rq, c))
            self.q_a_norm = get("q_a_norm", (rq,), "ones")
            self.q_b_weight = get("q_b_weight", (h * (nope + rot), rq))
            self.kv_a_weight = get("kv_a_weight", (rkv + rot, c))
            self.kv_a_norm = get("kv_a_norm", (rkv,), "ones")
            self.kv_b_weight = get("kv_b_weight", (h * (nope + dv), rkv))
            self.o_weight = get("o_weight", (c, h * dv))
            if self.experts:
                e, f = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
                held, fs = cfg["num_experts_held"], \
                    f * cfg["n_shared_experts"]
                self.gate_weight = get("gate_weight", (e, c))
                self.expert_bias = get("expert_bias", (e,), "zeros")
                # all three (held, F, C): ops/pallas_kernels.moe_grouped_ffn
                self.expert_w1 = get("expert_w1", (held, f, c))
                self.expert_w3 = get("expert_w3", (held, f, c))
                self.expert_w2 = get("expert_w2", (held, f, c))
                self.shared_w1 = get("shared_w1", (fs, c))
                self.shared_w3 = get("shared_w3", (fs, c))
                self.shared_w2 = get("shared_w2", (c, fs))
            else:
                f = cfg["intermediate_size"]
                self.w1 = get("w1", (f, c))
                self.w3 = get("w3", (f, c))
                self.w2 = get("w2", (c, f))

    def hybrid_forward(self, F, x, positions, **p):
        cfg = self._cfg
        eps, h = cfg["rms_norm_eps"], cfg["num_attention_heads"]
        nope, rot = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
        rkv, dv = cfg["kv_lora_rank"], cfg["v_head_dim"]
        rope = dict(theta=cfg["rope_theta"],
                    yarn=yarn_args(cfg["rope_scaling"]))
        r = F.rms_norm(x, p["input_norm"], eps=eps)
        c_q = F.rms_norm(_fc(F, r, p["q_a_weight"], cfg["q_lora_rank"]),
                         p["q_a_norm"], eps=eps)
        q = _fc(F, c_q, p["q_b_weight"], h * (nope + rot)) \
            .reshape((0, 0, h, nope + rot))
        q = F.contrib.rope(q, positions, start=nope, **rope)
        ckv = _fc(F, r, p["kv_a_weight"], rkv + rot)
        c_kv = F.rms_norm(F.slice_axis(ckv, axis=-1, begin=0, end=rkv),
                          p["kv_a_norm"], eps=eps)
        k_rot = F.contrib.rope(
            F.slice_axis(ckv, axis=-1, begin=rkv, end=rkv + rot)
            .reshape((0, 0, 1, rot)), positions, **rope)
        kv = _fc(F, c_kv, p["kv_b_weight"], h * (nope + dv)) \
            .reshape((0, 0, h, nope + dv))
        k = F.concat(F.slice_axis(kv, axis=-1, begin=0, end=nope),
                     F.broadcast_axis(k_rot, axis=2, size=h), dim=3)
        v = F.slice_axis(kv, axis=-1, begin=nope, end=nope + dv)
        a = F.contrib.causal_attention(q, k, v, sm_scale=softmax_scale(cfg))
        x = x + _fc(F, a.reshape((0, 0, h * dv)), p["o_weight"],
                    cfg["hidden_size"])
        r = F.rms_norm(x, p["post_attention_norm"], eps=eps)
        if self.experts:
            f, _ = F.contrib.sigmoid_topk_moe(
                r, p["gate_weight"], p["expert_bias"], p["expert_w1"],
                p["expert_w3"], p["expert_w2"],
                k=cfg["num_experts_per_tok"],
                expert_offset=cfg["expert_offset"],
                routed_scaling_factor=cfg["routed_scaling_factor"],
                norm_topk_prob=cfg["norm_topk_prob"],
                n_group=cfg["n_group"], topk_group=cfg["topk_group"],
                gate_eps=1e-20)
            f = f + F.contrib.swiglu_ffn(r, p["shared_w1"], p["shared_w3"],
                                         p["shared_w2"])
        else:
            f = F.contrib.swiglu_ffn(r, p["w1"], p["w3"], p["w2"])
        return x + f


class GigaChat3MTP(HybridBlock):
    """The multi-token-prediction module: ``x_i = W_eh [rms(h_i; hnorm) ;
    rms(E[t_{i+1}]; enorm)]``, one expert layer, then the shared head behind
    its own norm: the logits of token i + 2."""

    def __init__(self, cfg, **kwargs):
        super().__init__(**kwargs)
        self._cfg = cfg
        c, dt = cfg["hidden_size"], cfg["dtype"]
        with self.name_scope():
            self.hnorm = self.params.get("hnorm", shape=(c,), dtype=dt,
                                         init="ones")
            self.enorm = self.params.get("enorm", shape=(c,), dtype=dt,
                                         init="ones")
            self.eh_proj_weight = self.params.get(
                "eh_proj_weight", shape=(c, 2 * c), dtype=dt)
            self.shared_head_norm = self.params.get(
                "shared_head_norm", shape=(c,), dtype=dt, init="ones")
            self.layer = GigaChat3Layer(cfg, True, prefix="layer_")
            self.register_child(self.layer)

    def hybrid_forward(self, F, h, embedded, positions, hnorm, enorm,
                       eh_proj_weight, shared_head_norm):
        cfg = self._cfg
        eps = cfg["rms_norm_eps"]
        x = _fc(F, F.concat(F.rms_norm(h, hnorm, eps=eps),
                            F.rms_norm(embedded, enorm, eps=eps), dim=2),
                eh_proj_weight, cfg["hidden_size"])
        return F.rms_norm(self.layer(x, positions), shared_head_norm,
                          eps=eps)


class GigaChat3LM(HybridBlock):
    """The decoder LM: ``inputs`` (B, L) int token ids -> logits (B, L, V)
    float32; position t sees tokens [0, t]."""

    def __init__(self, vocab_size=128256, hidden_size=7168,
                 intermediate_size=18432, moe_intermediate_size=2048,
                 num_hidden_layers=64, first_k_dense_replace=3,
                 n_routed_experts=256, n_shared_experts=1,
                 num_experts_per_tok=8, n_group=8, topk_group=4,
                 routed_scaling_factor=2.5, norm_topk_prob=True,
                 num_attention_heads=64, q_lora_rank=1536, kv_lora_rank=512,
                 qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=192,
                 rms_norm_eps=1e-6, rope_theta=100000.0, rope_scaling=None,
                 max_position_embeddings=262144, num_nextn_predict_layers=0,
                 num_experts_held=None, expert_offset=0, dtype="bfloat16",
                 **kwargs):
        super().__init__(**kwargs)
        held = n_routed_experts if num_experts_held is None \
            else num_experts_held
        if expert_offset < 0 or expert_offset + held > n_routed_experts:
            raise MXNetError("experts %d..%d are not among %d"
                             % (expert_offset, expert_offset + held,
                                n_routed_experts))
        if n_routed_experts % n_group or topk_group > n_group:
            raise MXNetError("%d experts do not lie in %d groups of which "
                             "%d are kept" % (n_routed_experts, n_group,
                                              topk_group))
        if qk_rope_head_dim % 2 or num_nextn_predict_layers not in (0, 1):
            raise MXNetError("the rotary lanes pair up, and there is at "
                             "most one multi-token-prediction module")
        scaling = dict(_YARN)
        scaling.update({k: v for k, v in (rope_scaling or {}).items()
                        if k in _YARN})
        if scaling["factor"] > 1 \
                and scaling["mscale"] != scaling["mscale_all_dim"]:
            raise MXNetError("rope_scaling with mscale != mscale_all_dim "
                             "scales cos and sin, which `ops.nn.rope` does "
                             "not do")
        self._config = dict(
            vocab_size=int(vocab_size), hidden_size=int(hidden_size),
            intermediate_size=int(intermediate_size),
            moe_intermediate_size=int(moe_intermediate_size),
            num_hidden_layers=int(num_hidden_layers),
            first_k_dense_replace=int(first_k_dense_replace),
            n_routed_experts=int(n_routed_experts),
            n_shared_experts=int(n_shared_experts),
            num_experts_per_tok=int(num_experts_per_tok),
            n_group=int(n_group), topk_group=int(topk_group),
            routed_scaling_factor=float(routed_scaling_factor),
            norm_topk_prob=bool(norm_topk_prob),
            num_attention_heads=int(num_attention_heads),
            q_lora_rank=int(q_lora_rank), kv_lora_rank=int(kv_lora_rank),
            qk_nope_head_dim=int(qk_nope_head_dim),
            qk_rope_head_dim=int(qk_rope_head_dim),
            v_head_dim=int(v_head_dim), rms_norm_eps=float(rms_norm_eps),
            rope_theta=float(rope_theta), rope_scaling=scaling,
            max_position_embeddings=int(max_position_embeddings),
            num_nextn_predict_layers=int(num_nextn_predict_layers),
            num_experts_held=int(held), expert_offset=int(expert_offset),
            dtype=str(dtype))
        cfg = self._cfg = self._config
        with self.name_scope():
            self.word_weight = self.params.get(
                "word_weight", shape=(vocab_size, hidden_size), dtype=dtype)
            self.head_weight = self.params.get(
                "head_weight", shape=(vocab_size, hidden_size), dtype=dtype)
            self.norm = self.params.get("norm", shape=(hidden_size,),
                                        dtype=dtype, init="ones")
            self.cells = []
            for i in range(cfg["num_hidden_layers"]):
                cell = GigaChat3Layer(cfg, i >= cfg["first_k_dense_replace"],
                                      prefix="layer%d_" % i)
                self.register_child(cell)
                self.cells.append(cell)
            self.mtp = None
            if cfg["num_nextn_predict_layers"]:
                self.mtp = GigaChat3MTP(cfg, prefix="mtp_")
                self.register_child(self.mtp)

    @property
    def config(self):
        """Constructor arguments (`serving.generate` artifact header)."""
        return dict(self._config,
                    rope_scaling=dict(self._config["rope_scaling"]))

    def description(self):
        """The per-layer description the generation engine builds its
        prefill and decode programs from (docs/serving.md §Generation)."""
        cfg = self._cfg
        dense = cfg["first_k_dense_replace"]
        return {
            "arch": "gigachat3", "dtype": cfg["dtype"],
            "units": cfg["hidden_size"], "vocab_size": cfg["vocab_size"],
            "max_length": cfg["max_position_embeddings"],
            "norm": "rms", "norm_at": "pre", "norm_eps": cfg["rms_norm_eps"],
            "positions": "rotary", "rope_theta": cfg["rope_theta"],
            "rope_scaling": dict(cfg["rope_scaling"]),
            "embed_norm": False, "final_norm": True, "head": "own",
            "heads": cfg["num_attention_heads"], "attention": "latent",
            "latent": {"q_rank": cfg["q_lora_rank"],
                       "kv_rank": cfg["kv_lora_rank"],
                       "nope": cfg["qk_nope_head_dim"],
                       "rope": cfg["qk_rope_head_dim"],
                       "v": cfg["v_head_dim"]},
            "ffn": "swiglu",
            "experts": {"total": cfg["n_routed_experts"],
                        "held": cfg["num_experts_held"],
                        "offset": cfg["expert_offset"],
                        "per_token": cfg["num_experts_per_tok"],
                        "scaling": cfg["routed_scaling_factor"],
                        "norm_topk": cfg["norm_topk_prob"],
                        "groups": cfg["n_group"],
                        "topk_groups": cfg["topk_group"],
                        "gate_eps": 1e-20,
                        "shared": cfg["n_shared_experts"]},
            "layers": [{"operator": "attention",
                        "ffn": "experts" if i >= dense else "dense"}
                       for i in range(cfg["num_hidden_layers"])]}

    def _hidden(self, F, inputs, word_weight):
        cfg = self._cfg
        x = F.Embedding(inputs, word_weight, input_dim=cfg["vocab_size"],
                        output_dim=cfg["hidden_size"], dtype=cfg["dtype"])
        positions = F.arange(0, inputs.shape[1], dtype="int32")
        for cell in self.cells:
            x = cell(x, positions)
        return x

    def _logits(self, F, x, head_weight):
        return _fc(F, x.astype("float32"), head_weight.astype("float32"),
                   self._cfg["vocab_size"])

    def hybrid_forward(self, F, inputs, word_weight, head_weight, norm):
        x = self._hidden(F, inputs, word_weight)
        return self._logits(F, F.rms_norm(x, norm,
                                          eps=self._cfg["rms_norm_eps"]),
                            head_weight)

    def mtp_logits(self, inputs):
        """Eager: ``inputs`` (B, L) -> (B, L - 1, V) float32, row i the
        module's logits of token i + 2 from the stream at i and token
        i + 1."""
        from ... import nd as F

        if self.mtp is None:
            raise MXNetError("this model was built without its "
                             "multi-token-prediction module")
        cfg = self._cfg
        n = inputs.shape[1] - 1
        word = self.word_weight.data()
        h = F.slice_axis(self._hidden(F, inputs, word), axis=1, begin=0,
                         end=n)
        embedded = F.Embedding(
            F.slice_axis(inputs, axis=1, begin=1, end=n + 1), word,
            input_dim=cfg["vocab_size"], output_dim=cfg["hidden_size"],
            dtype=cfg["dtype"])
        x = self.mtp(h, embedded, F.arange(0, n, dtype="int32"))
        return self._logits(F, x, self.head_weight.data())

    def decode_params(self):
        """The parameters as the structured dict of device arrays that
        `serving.generate.TransformerLMEngine` consumes; ``kv_b`` is handed
        over as its key part (H, nope, rank) and its value part (H, v,
        rank), which the absorbed decode path contracts separately. The
        prediction module is not among them: the engine does not draft."""
        if any(p._data is None for p in self.collect_params().values()):
            from ... import nd

            self(nd.array([[0]], dtype="int32"))
        cfg = self._cfg
        h, nope = cfg["num_attention_heads"], cfg["qk_nope_head_dim"]

        def arr(p):
            return p.data()._data

        layers = []
        for cell in self.cells:
            kvb = arr(cell.kv_b_weight).reshape(h, -1, cfg["kv_lora_rank"])
            layer = {"attn_norm": {"g": arr(cell.input_norm)},
                     "ffn_norm": {"g": arr(cell.post_attention_norm)},
                     "q_a": {"w": arr(cell.q_a_weight)},
                     "q_a_norm": {"g": arr(cell.q_a_norm)},
                     "q_b": {"w": arr(cell.q_b_weight)},
                     "kv_a": {"w": arr(cell.kv_a_weight)},
                     "kv_a_norm": {"g": arr(cell.kv_a_norm)},
                     "kvb_k": kvb[:, :nope], "kvb_v": kvb[:, nope:],
                     "o": {"w": arr(cell.o_weight)}}
            if cell.experts:
                layer.update(gate=arr(cell.gate_weight),
                             expert_bias=arr(cell.expert_bias),
                             ew1=arr(cell.expert_w1), ew3=arr(cell.expert_w3),
                             ew2=arr(cell.expert_w2),
                             sw1=arr(cell.shared_w1), sw3=arr(cell.shared_w3),
                             sw2=arr(cell.shared_w2))
            else:
                layer.update(w1=arr(cell.w1), w3=arr(cell.w3),
                             w2=arr(cell.w2))
            layers.append(layer)
        return {"word": arr(self.word_weight), "head": arr(self.head_weight),
                "final_norm": {"g": arr(self.norm)}, "layers": layers}


def gigachat3_mini(vocab_size=128, **kwargs):
    """Tiny GigaChat3 for tests: one dense layer then two expert layers of
    16 experts in 4 groups (2 kept, 4 a token) and a shared expert; 4 heads
    of 16 + 8 rotary lanes over a latent row of 32; YaRN 4 over 32; float32."""
    cfg = dict(hidden_size=64, intermediate_size=128,
               moe_intermediate_size=32, num_hidden_layers=3,
               first_k_dense_replace=1, n_routed_experts=16,
               num_experts_per_tok=4, n_group=4, topk_group=2,
               num_attention_heads=4, q_lora_rank=48, kv_lora_rank=32,
               qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=24,
               rope_scaling={"factor": 4.0, "mscale": 1.0,
                             "mscale_all_dim": 1.0,
                             "original_max_position_embeddings": 32},
               max_position_embeddings=256, dtype="float32")
    cfg.update(kwargs)
    return GigaChat3LM(vocab_size=vocab_size, **cfg)
