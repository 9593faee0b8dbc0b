"""Model factory: the zoo's ResNet v1 promoted to the fused sharded step, the
path a training user takes (``gluon.Trainer(sharded=True)`` +
``trainer.prefetch()`` + ``trainer.step_batch``), holding the benchmark's
seeded weights."""
from __future__ import annotations


def build(config, weights, chips):
    """Returns ``(trainer, names)``: the promoted trainer, and for each
    trainable parameter, in the order the fused step keeps them, the name the
    reference knows it by."""
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon.model_zoo import vision
    from mxnet_tpu.ndarray import NDArray

    sizes = config["sizes"]
    ctx = mx.tpu()
    with ctx, gluon.nn.layout_scope():
        if config["model"].get("zoo"):
            net = getattr(vision, config["model"]["zoo"])(
                classes=sizes["classes"])
        else:                  # a rehearsal's tiny net, same blocks
            net = vision.ResNetV1(vision.BottleneckV1, sizes["layers"],
                                  sizes["channels"], classes=sizes["classes"])
        net.initialize(ctx=ctx)
        # deferred shapes need one forward; spatial size is free (global
        # pool), so a thumbnail keeps the op-by-op pass cheap
        net(mx.nd.zeros((2, 32, 32, 3), ctx=ctx))
    params = net.collect_params()
    prefix = net.prefix
    have = {n[len(prefix):]: p for n, p in params.items()}
    if set(have) != set(weights):
        raise RuntimeError("the zoo's parameters and the reference's differ: "
                           "%s" % sorted(set(have) ^ set(weights))[:8])
    for name, p in have.items():
        if tuple(p.shape) != tuple(weights[name].shape):
            raise RuntimeError("%s: zoo %s, reference %s"
                               % (name, p.shape, weights[name].shape))
        p.set_data(NDArray(weights[name], ctx=ctx))
    mesh = None
    if chips > 1:
        from mxnet_tpu.parallel import make_mesh

        mesh = make_mesh([(a, int(n)) for a, n in config["mesh"][str(chips)]],
                         devices=jax.devices()[:chips])
    opt = config["optimizer"]
    trainer = gluon.Trainer(
        params, opt["name"],
        {k: v for k, v in opt.items() if k != "name"}, sharded=True,
        block=net, loss=gluon.loss.SoftmaxCrossEntropyLoss(), mesh=mesh,
        amp_dtype=config["amp_dtype"])
    sharded = trainer.sharded
    names = [sharded._param_names[i][len(prefix):]
             for i in sharded._trainable]
    return trainer, names
