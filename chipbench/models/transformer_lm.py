"""Model factory: the zoo's ``TransformerLM`` holding the benchmark's seeded
weights, saved with ``save_lm`` and loaded through the path a serving user
takes: ``ModelRepository.load(generate=True)`` behind a ``ServingServer``."""
from __future__ import annotations

import os

# reference leaf name -> (suffix of the zoo's parameter name)
_MAP = (
    ("word", "word_weight"), ("pos", "pos_weight"),
    ("embed_norm.g", "layernorm0_gamma"), ("embed_norm.b", "layernorm0_beta"),
)
_LAYER = (
    ("q", "multiheadattention0_query"), ("k", "multiheadattention0_key"),
    ("v", "multiheadattention0_value"), ("o", "multiheadattention0_out"),
    ("ffn1", "positionwiseffn0_ffn1"), ("ffn2", "positionwiseffn0_ffn2"),
)
_LAYER_NORMS = (("attn_norm", "layernorm0"), ("ffn_norm", "layernorm1"))


def _zoo_name(leaf):
    for ref, zoo in _MAP:
        if leaf == ref:
            return zoo
    layer, rest = leaf.split(".", 1)
    part, kind = rest.rsplit(".", 1)
    for ref, zoo in _LAYER:
        if part == ref:
            return "%s_%s_%s" % (layer, zoo, "weight" if kind == "w" else "bias")
    for ref, zoo in _LAYER_NORMS:
        if part == ref:
            return "%s_%s_%s" % (layer, zoo, "gamma" if kind == "g" else "beta")
    raise KeyError(leaf)


def save(config, weights, directory):
    """Build the zoo model with ``weights`` and write the serving artifact;
    returns its prefix."""
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo.transformer import TransformerLM
    from mxnet_tpu.ndarray import NDArray
    from mxnet_tpu.serving.generate import save_lm

    lm = TransformerLM(dropout=0.0, **config["sizes"])
    lm.initialize(mx.init.Zero())
    lm(mx.nd.array([[0]], dtype="int32"))     # deferred shapes
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
        p.set_data(NDArray(want[name]))
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
