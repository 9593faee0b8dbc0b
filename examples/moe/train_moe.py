"""Expert-parallel MoE language-model training over a dp x ep mesh.

Not in the reference (MoE postdates MXNet 1.x) — this is the expert-parallel
extension SURVEY §2.3 plans as a TPU-native goal. A small causal LM whose
transformer FFN is `gluon.contrib.moe.MoEFFN` trains under
`parallel.DistributedTrainer`: the expert tables shard over the `ep` mesh
axis (parallel/sharding.py routes any parameter named "*expert*" there) and
XLA lowers the dispatch/combine einsums to all_to_alls over ICI. Top-1
(Switch) or top-k (GShard/Mixtral) routing per --top-k, with the ST-MoE
router z-loss folded into the objective.

Run: JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
         python examples/moe/train_moe.py [--ep 4] [--top-k 2]
"""
import argparse
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

VOCAB = 64
SEQ = 16


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--ep", type=int, default=0,
                    help="expert-parallel axis size (0 = all devices)")
    ap.add_argument("--experts", type=int, default=4)
    ap.add_argument("--top-k", type=int, default=2)
    ap.add_argument("--steps", type=int, default=40)
    ap.add_argument("--batch", type=int, default=32)
    args = ap.parse_args()

    import jax

    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.gluon.contrib.moe import MoEFFN
    from mxnet_tpu.parallel import DistributedTrainer, make_mesh

    n = len(jax.devices())
    ep = args.ep or min(n, args.experts)
    if n % ep:
        raise SystemExit("device count %d not divisible by ep=%d" % (n, ep))
    mesh = make_mesh([("dp", n // ep), ("ep", ep)])
    print("mesh:", dict(zip(mesh.axis_names, mesh.devices.shape)),
          "on", jax.devices()[0].platform)

    class MoELM(gluon.HybridBlock):
        """embed -> (attention-free) mixer -> MoE FFN -> tied-ish head.
        The point is the routed expert layer, not the mixer."""

        def __init__(self, units=32, **kw):
            super().__init__(**kw)
            with self.name_scope():
                self.embed = nn.Embedding(VOCAB, units)
                self.mix = nn.Dense(units, flatten=False,
                                    activation="relu")
                self.moe = MoEFFN(units=units, hidden_size=2 * units,
                                  num_experts=args.experts,
                                  num_experts_per_token=args.top_k,
                                  z_loss_coef=1e-3, capacity_factor=2.0,
                                  return_aux=True)
                self.head = nn.Dense(VOCAB, flatten=False)

        def hybrid_forward(self, F, tokens):
            h = self.embed(tokens)
            h = h + self.mix(h)
            ffn, aux = self.moe(h)
            return self.head(h + ffn), aux

    net = MoELM()
    net.initialize(mx.init.Xavier())
    net(mx.nd.zeros((2, SEQ)))  # materialize deferred shapes

    sce = gluon.loss.SoftmaxCrossEntropyLoss()

    def lm_loss(out, labels):
        logits, aux = out
        return sce(logits.reshape((-1, VOCAB)),
                   labels.reshape((-1,))) + 0.01 * aux

    trainer = DistributedTrainer(net, "adam", {"learning_rate": 3e-3},
                                 loss=lm_loss, mesh=mesh)

    # synthetic next-token task: tok[t+1] = (3*tok[t] + 7) % VOCAB — fully
    # learnable by embed+head, so perplexity collapses if training works
    rng = np.random.RandomState(0)
    loss = None
    for step in range(args.steps):
        first = rng.randint(0, VOCAB, (args.batch, 1))
        seq = [first]
        for _ in range(SEQ):
            seq.append((3 * seq[-1] + 7) % VOCAB)
        toks = np.concatenate(seq, axis=1).astype(np.float32)
        loss = trainer.step(toks[:, :SEQ], toks[:, 1:SEQ + 1])
        if step % 10 == 0 or step == args.steps - 1:
            print("step %3d  loss %.4f" % (step, float(loss.asnumpy())))
    final = float(loss.asnumpy())
    assert np.isfinite(final), "non-finite loss"
    if args.steps >= 40:
        assert final < 2.0, "did not learn (loss %.3f)" % final
    print("done — %d experts (top-%d) sharded over ep=%d"
          % (args.experts, args.top_k, ep))


if __name__ == "__main__":
    main()
