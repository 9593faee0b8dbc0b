"""Model factory: the zoo's ``Lfm2LM`` holding the benchmark's seeded
weights, saved with ``save_lm`` and loaded through the path a serving user
takes: ``ModelRepository.load(generate=True)`` behind a ``ServingServer``.

The zoo module is imported here, at the top: a tree without it fails at
import, before any weight is made."""
from __future__ import annotations

import os

from mxnet_tpu.gluon.model_zoo import lfm2 as zoo

# reference leaf's last part -> suffix of the zoo's parameter name
_PART = {
    "operator_norm": "operator_norm", "ffn_norm": "ffn_norm",
    "q": "q_weight", "k": "k_weight", "v": "v_weight", "o": "o_weight",
    "q_norm": "q_norm", "k_norm": "k_norm",
    "in_proj": "in_weight", "conv": "conv_weight", "out_proj": "out_weight",
    "w1": "w1", "w3": "w3", "w2": "w2",
    "router": "gate_weight", "expert_bias": "expert_bias",
    "experts.w1": "expert_w1", "experts.w3": "expert_w3",
    "experts.w2": "expert_w2",
}


def _zoo_name(leaf):
    if leaf == "embed":
        return "word_weight"
    if leaf == "embedding_norm":
        return leaf
    layer, part = leaf.split(".", 1)
    return "%s_%s" % (layer, _PART[part])


def save(config, weights, directory):
    """Build the zoo model around ``weights`` (each parameter adopts the
    reference's device array as it is: nothing is copied, nothing is
    initialized) and write the serving artifact; returns its prefix."""
    from mxnet_tpu.serving.generate import save_lm

    lm = zoo.Lfm2LM(**config["sizes"])
    params = lm.collect_params()
    have = {n[len(lm.prefix):]: p for n, p in params.items()}
    want = {_zoo_name(k): v for k, v in weights.items()}
    if set(have) != set(want):
        raise RuntimeError("the zoo's parameters and the reference's differ: "
                           "%s" % sorted(set(have) ^ set(want))[:8])
    for name, p in have.items():
        if tuple(p.shape) != tuple(want[name].shape):
            raise RuntimeError("%s: zoo %s, reference %s"
                               % (name, p.shape, want[name].shape))
        p.adopt(want[name])
    return save_lm(lm, os.path.join(directory, "lm"))


def serve(config, traffic, prefix):
    """(repository, server, model): the artifact loaded for generation with
    the configuration's geometry and this traffic's buckets, behind an HTTP
    server on 127.0.0.1."""
    from mxnet_tpu.serving import ModelRepository, ServingServer

    geometry = dict(config["engine"])
    geometry.update(traffic["engine"])
    repo = ModelRepository()
    model = repo.load("lm", prefix, generate=True,
                      queue_depth=int(traffic["queue_depth"]),
                      generate_opts=geometry)
    server = ServingServer(repo, port=0, addr="127.0.0.1").start()
    return repo, server, model
