"""Model factory: the zoo's ``GigaChat3LM`` holding the benchmark's seeded
weights, saved with ``save_lm`` and loaded through the path a serving user
takes: ``ModelRepository.load(generate=True)`` behind a ``ServingServer``.

The zoo module is imported here, at the top: a tree without it fails at
import, before any weight is made."""
from __future__ import annotations

import os

# the load behind a server is every decoder LM's: nothing of it is the model's
from chipbench.models.transformer_lm import serve  # noqa: F401
from mxnet_tpu.gluon.model_zoo import gigachat3 as zoo

# reference leaf's last part -> suffix of the zoo's parameter name
_PART = {
    "input_norm": "input_norm", "post_attention_norm": "post_attention_norm",
    "q_a": "q_a_weight", "q_a_norm": "q_a_norm", "q_b": "q_b_weight",
    "kv_a": "kv_a_weight", "kv_a_norm": "kv_a_norm", "kv_b": "kv_b_weight",
    "o": "o_weight", "w_gate": "w1", "w_up": "w3", "w_down": "w2",
    "router": "gate_weight", "expert_bias": "expert_bias",
    "experts.w_gate": "expert_w1", "experts.w_up": "expert_w3",
    "experts.w_down": "expert_w2", "shared.w_gate": "shared_w1",
    "shared.w_up": "shared_w3", "shared.w_down": "shared_w2",
}
_TOP = {"embed": "word_weight", "head": "head_weight", "norm": "norm",
        "mtp.hnorm": "mtp_hnorm", "mtp.enorm": "mtp_enorm",
        "mtp.eh_proj": "mtp_eh_proj_weight",
        "mtp.shared_head_norm": "mtp_shared_head_norm"}


def _zoo_name(leaf):
    if leaf in _TOP:
        return _TOP[leaf]
    if leaf.startswith("mtp.layer."):
        return "mtp_layer_" + _PART[leaf[len("mtp.layer."):]]
    layer, part = leaf.split(".", 1)
    return "%s_%s" % (layer, _PART[part])


def build(sizes, weights, dtype=None):
    """The zoo model around ``weights``: each parameter adopts the
    reference's device array as it is (cast to ``dtype`` if given): nothing
    is copied, nothing is initialized."""
    lm = zoo.GigaChat3LM(**dict(sizes, **({"dtype": dtype} if dtype else {})))
    have = {n[len(lm.prefix):]: p for n, p in lm.collect_params().items()}
    want = {_zoo_name(k): v for k, v in weights.items()}
    if set(have) != set(want):
        raise RuntimeError("the zoo's parameters and the reference's differ: "
                           "%s" % sorted(set(have) ^ set(want))[:8])
    for name, p in have.items():
        if tuple(p.shape) != tuple(want[name].shape):
            raise RuntimeError("%s: zoo %s, reference %s"
                               % (name, p.shape, want[name].shape))
        p.adopt(want[name].astype(dtype) if dtype else want[name])
    return lm


def save(config, weights, directory):
    """Build the zoo model and write the serving artifact; returns its
    prefix. The block is collected here, cycles and all: it holds the
    caller's 8.6 GB of device arrays, which the load that follows has to
    find free (one run of 19 died of it: the collector had not yet run)."""
    import gc

    from mxnet_tpu.serving.generate import save_lm

    lm = build(config["sizes"], weights)
    prefix = save_lm(lm, os.path.join(directory, "lm"))
    del lm
    gc.collect()
    return prefix
