"""Model factory: the zoo's ``SmallThinkerLM`` holding the benchmark's seeded
weights, saved with ``save_lm`` and loaded through the path a serving user
takes: ``ModelRepository.load(generate=True)`` behind a ``ServingServer``.
The configuration's ``engine`` group reaches the engine whole, the window
group's page count (``window_pages``) with it.

The zoo module is imported here, at the top: a tree without it fails at
import, before any weight is made."""
from __future__ import annotations

import os

from mxnet_tpu.gluon.model_zoo import smallthinker as zoo

# reference leaf's last part -> suffix of the zoo's parameter name
_PART = {
    "input_norm": "input_norm", "post_attention_norm": "post_attention_norm",
    "q": "q_weight", "k": "k_weight", "v": "v_weight", "o": "o_weight",
    "router": "gate_weight", "experts.w1": "expert_w1",
    "experts.w3": "expert_w3", "experts.w2": "expert_w2",
}
_TOP = {"embed": "word_weight", "head": "head_weight",
        "final_norm": "final_norm"}


def _zoo_name(leaf):
    if leaf in _TOP:
        return _TOP[leaf]
    layer, part = leaf.split(".", 1)
    return "%s_%s" % (layer, _PART[part])


def save(config, weights, directory):
    """Build the zoo model around ``weights`` (each parameter adopts the
    reference's device array as it is: nothing is copied, nothing is
    initialized) and write the serving artifact; returns its prefix."""
    from mxnet_tpu.serving.generate import save_lm

    lm = zoo.SmallThinkerLM(**config["sizes"])
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
    import gc

    from mxnet_tpu.serving import ModelRepository, ServingServer

    # the block that `save` built is unreachable by now, but a gluon block
    # is a reference cycle and its 7.93 GB of adopted arrays stay on the
    # device until a collection: weights twice and the two page pools do
    # not fit the chip (my chip run, PR 48: the pool's allocation failed
    # with 417 MB free)
    gc.collect()
    geometry = dict(config["engine"])
    geometry.update(traffic["engine"])
    repo = ModelRepository()
    model = repo.load("lm", prefix, generate=True,
                      queue_depth=int(traffic["queue_depth"]),
                      generate_opts=geometry)
    server = ServingServer(repo, port=0, addr="127.0.0.1").start()
    return repo, server, model
