#!/usr/bin/env python3
"""chip_smoke.py — does the system still start, compile and run on the chip?

One process, one TPU chip: the two hot paths the benchmark's first cells
will measure — a trainer taking steps and the generate server answering
requests — driven end to end through the entry points a user calls, at
published widths, with random weights made from ``--seed``:

    device          jax.devices() answers with a TPU whose bf16 peak is known
    train_resnet50  gluon.Trainer(sharded=True) fused step, ResNet-50 NHWC
                    bf16 AMP batch 256 fed through trainer.prefetch()
    train_bert_base BERT-base pretraining step, batch 8 x 512 (flash
                    attention forward AND backward in their Mosaic lowering)
    train_lstm_lm   word-LM 2x650 LSTM step (the fused Pallas LSTM layer)
    serve_decode    GPT-2-small-width TransformerLM -> export ->
                    ModelRepository.load -> ServingServer; 8 HTTP :generate
                    requests, 4 in flight (paged-attention decode kernel)
    compile_cache   the executable-artifact tier stores and reloads a TPU
                    executable; where jax's own cache lives, hits and misses

Every phase prints ONE JSON line (what it checked, seconds, compile
seconds). The LAST line of stdout is the verdict and nothing else:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

``ok`` is true only if every phase ran on a TPU and every check held; the
exit code is 0 exactly then. This script measures nothing: no rate or
utilization is printed.

``--chips 4`` (a four-chip host) runs ONLY the ResNet-50 step on a four-
device mesh — once dp=4, once dp=2 x fsdp=2 — and the one-chip step it is
compared with. ``--rehearse`` shrinks every size for a CPU walk through the
control flow (Pallas kernels in interpret mode); a rehearsal is never a
pass, whatever it runs on.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import tempfile
import threading
import time
import traceback
import urllib.error
import urllib.request

import numpy as np

SIZES = {
    "real": dict(
        resnet=dict(factory="resnet50_v1", classes=1000, hw=224, batch=256,
                    warmup=2, steps=6),
        bert=dict(vocab=30522, seq_len=512, batch=8, warmup=1, steps=3,
                  model=dict(units=768, hidden_size=3072, num_layers=12,
                             num_heads=12, max_length=512)),
        lstm=dict(vocab=10000, embed=650, hidden=650, layers=2, bptt=35,
                  batch=32, warmup=1, steps=3),
        serve=dict(model=dict(vocab_size=50257, units=768, hidden_size=3072,
                              num_layers=12, num_heads=12, max_length=1024),
                   short=(16, 32), long=(200, 256), max_new=32,
                   page_size=16, num_pages=96,
                   prefill_buckets=[32, 256], concurrency=4),
        mesh_steps=3,
    ),
    "rehearse": dict(
        resnet=dict(factory="resnet18_v1", classes=16, hw=32, batch=8,
                    warmup=2, steps=6),
        bert=dict(vocab=128, seq_len=32, batch=2, warmup=1, steps=3,
                  model=dict(units=64, hidden_size=128, num_layers=2,
                             num_heads=2, max_length=32)),
        lstm=dict(vocab=100, embed=64, hidden=64, layers=2, bptt=8,
                  batch=4, warmup=1, steps=3),
        serve=dict(model=dict(vocab_size=128, units=64, hidden_size=128,
                              num_layers=2, num_heads=2, max_length=64),
                   short=(4, 8), long=(20, 32), max_new=8,
                   page_size=4, num_pages=64,
                   prefill_buckets=[8, 32], concurrency=4),
        mesh_steps=3,
    ),
}

# option-gated kernels: on the chip their `auto` defaults decide and this
# script sets nothing; a CPU rehearsal forces them on (interpret mode) so it
# walks the same branches the chip will
_REHEARSAL_KERNEL_GATES = ("MXTPU_PALLAS_LSTM", "MXTPU_PALLAS_DECODE")


# ---------------------------------------------------------------------------
# evidence helpers
# ---------------------------------------------------------------------------

def _counter(name, labels=None):
    from mxnet_tpu import telemetry

    return telemetry.counter(name, labels).value


def _compile_seconds():
    """Cumulative seconds the compile registry spent filling executables
    (trace + lower + compile), from the goodput accountant."""
    from mxnet_tpu.telemetry import goodput

    return goodput.totals()["phases"].get("compile", 0.0)


def _platforms(arrays):
    return sorted({d.platform for a in arrays for d in a.devices()})


def _trainer_buffers(sharded):
    """Every parameter and optimizer-state buffer the fused step owns."""
    import jax

    return list(sharded._arrays) + jax.tree_util.tree_leaves(sharded._states)


def _step_executable(sharded, batch):
    """The fused step's `Compiled` as the compile registry holds it."""
    from mxnet_tpu import compile as mxc

    sig = tuple((tuple(b.shape), str(b.dtype)) for b in batch)
    exe = mxc.compiled(sharded._step_key(sig))
    if exe is None:
        raise RuntimeError("the fused step was not AOT-filled; no program "
                           "to inspect")
    return exe


def _fused_step_compiles():
    """`jit_compile` events of the fused training step so far."""
    from mxnet_tpu.telemetry import recorder

    return sum(1 for e in recorder.events() if e["event"] == "jit_compile"
               and e["fields"].get("op") == "dist_trainer_step")


def _use_interpret():
    from mxnet_tpu.ops import pallas_kernels

    return pallas_kernels._use_interpret()


def _bn_lowering(mosaic_calls):
    """The lowering of a ResNet step's training batch norms, from the
    program's count of Mosaic calls: XLA's own (ops/nn.py `_bn_act`)
    holds none."""
    return ("xla (_bn_train + add + relu)" if mosaic_calls == 0
            else "%d Mosaic calls in a step that should hold none"
            % mosaic_calls)


def _kernel_path(program, kernel, fallback):
    """Which of a gated kernel's two paths the compiled program took, read
    from the program itself: a Mosaic kernel is a `tpu_custom_call`."""
    n = program.count("tpu_custom_call")
    if n:
        return "%s (%d Mosaic calls)" % (kernel, n)
    if _use_interpret():
        return ("interpret mode: %s or %s, the program cannot tell"
                % (kernel, fallback))
    return fallback


@contextlib.contextmanager
def _env_override(name, value):
    old = os.environ.get(name)
    os.environ[name] = value
    try:
        yield
    finally:
        if old is None:
            del os.environ[name]
        else:
            os.environ[name] = old


def _seed(seed):
    import mxnet_tpu as mx

    # initializers draw from NumPy's global RNG, per-step keys from the
    # mx chain: both seeded makes two builds identical
    np.random.seed(seed)
    mx.random.seed(seed)


def _train(net, loss, optimizer, opt_params, batch, warmup, steps,
           mesh=None, after_first_step=None):
    """Promote `net` to the fused sharded step through gluon.Trainer and
    take warmup + steps on one batch fed through trainer.prefetch().
    Returns the evidence every training phase checks;
    `after_first_step(sharded)` may read state between steps 1 and 2."""
    import jax

    from mxnet_tpu import gluon
    from mxnet_tpu.telemetry import memory as tm_memory

    trainer = gluon.Trainer(
        net.collect_params(), optimizer, opt_params, sharded=True,
        block=net, loss=loss, mesh=mesh, amp_dtype="bfloat16")
    n = warmup + steps
    feed = trainer.prefetch(iter([batch] * n))
    c0 = _compile_seconds()
    losses, miss_after_warm, placed = [], None, None
    try:
        for i, (xb, yb) in enumerate(feed):
            if i == warmup:
                miss_after_warm = _counter("mxtpu_jit_cache_miss_total")
            losses.append(trainer.step_batch(xb, yb))
            placed = (xb, yb)
            if i == 0 and after_first_step is not None:
                after_first_step(trainer.sharded)
        jax.block_until_ready(losses[-1]._data)
    finally:
        feed.close()
    losses = [float(v.asnumpy()) for v in losses]
    sharded = trainer.sharded
    exe = _step_executable(sharded, placed)
    program = exe.as_text()
    mem = exe.memory_analysis()
    donation = tm_memory.last_donation_report() or {}
    info = {
        "losses": [round(v, 5) for v in losses],
        "compile_s": round(_compile_seconds() - c0, 2),
        "mosaic_calls": program.count("tpu_custom_call"),
        # per device, from the compiler: arguments + outputs + temporaries
        # less what donation aliases
        "step_device_bytes": None if mem is None else int(
            mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes),
        "buffers_on": _platforms(_trainer_buffers(sharded)),
        "batch_on": _platforms(placed),
        "compiles_after_warmup":
            _counter("mxtpu_jit_cache_miss_total") - miss_after_warm,
        "donation_aliased_fraction": donation.get("aliased_fraction"),
        "interpret": _use_interpret(),
        # what the runtime itself counts on the first device of the mesh
        "memory_stats": {
            k: v for k, v in
            (sharded.mesh.devices.flat[0].memory_stats() or {}).items()
            if k in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit",
                     "bytes_reserved", "peak_bytes_reserved",
                     "largest_alloc_size")},
    }
    checks = {
        "loss_finite": bool(np.all(np.isfinite(losses))),
        "loss_decreased": losses[-1] < losses[0],
        "buffers_on_tpu": info["buffers_on"] == ["tpu"],
        "batch_on_tpu": info["batch_on"] == ["tpu"],
        "no_compile_after_warmup": info["compiles_after_warmup"] == 0,
        "donation_ok": bool(donation.get("ok")),
        "not_interpret": not info["interpret"],
    }
    return trainer, program, info, checks


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def phase_device(cfg):
    import jax

    from mxnet_tpu import runtime
    from mxnet_tpu.lib import native

    dev = jax.devices()[0]
    info = {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()), "jax": jax.__version__,
        # raises for a kind the table does not list
        "peak_bf16_tflops": runtime.chip_peak_tflops(dev)
        if dev.platform != "cpu" else None,
        "native_lib_loaded": bool(native.available()),
        "interpret": _use_interpret(),
    }
    waits, info["block_until_ready"] = _block_until_ready_waits()
    checks = {
        "platform_is_tpu": dev.platform == "tpu",
        "peak_known": info["peak_bf16_tflops"] is not None,
        "device_count": len(jax.devices()) == cfg["chips"],
        "not_interpret": not info["interpret"],
        # every timing in this repo ends in it
        "block_until_ready_waits": waits,
    }
    return info, checks


def _block_until_ready_waits(n=2048, iters=64):
    """Does `block_until_ready` return only once the device has finished?
    A chain of dependent matmuls long enough to notice is dispatched, then
    waited on; if the wait came back early, the host fetch that follows
    would have to sit out the rest of the execution."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def chain(a):
        def body(_, p):
            return (p @ a) * jnp.bfloat16(1.0 / n)
        return jnp.sum(jax.lax.fori_loop(0, iters, body, a),
                       dtype=jnp.float32)

    a = jnp.ones((n, n), jnp.bfloat16)
    float(chain(a))                                   # compile + warm
    t0 = time.perf_counter()
    out = jax.block_until_ready(chain(a))
    t_block = time.perf_counter() - t0
    t0 = time.perf_counter()
    float(out)
    t_fetch = time.perf_counter() - t0
    return t_fetch < 0.25 * t_block, {
        "blocked_ms": round(t_block * 1e3, 2),
        "fetch_after_ms": round(t_fetch * 1e3, 2),
        "result_on": _platforms([out])}


def _build_resnet(cfg, seed, batch=None, **zoo_flags):
    """Zoo ResNet, channels-last, with a synthetic batch. With no flags
    this is the model a user gets from the zoo. `fuse_epilogue=` /
    `stem_s2d=` are the zoo's two opt-in rewrites."""
    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon.model_zoo import vision

    r = dict(cfg["resnet"], batch=batch or cfg["resnet"]["batch"])
    _seed(seed)
    ctx = mx.tpu()
    with ctx, gluon.nn.layout_scope():
        net = getattr(vision, r["factory"])(classes=r["classes"], **zoo_flags)
        net.initialize(ctx=ctx)
        # deferred shapes need one forward; spatial size is free (global
        # pool), so a thumbnail keeps the op-by-op pass cheap
        net(mx.nd.zeros((2, 32, 32, 3), ctx=ctx))
    rng = np.random.RandomState(seed)
    x = rng.uniform(-1, 1, (r["batch"], r["hw"], r["hw"], 3)) \
        .astype(np.float32)
    # four runs of images with different scale and offset: a batch split
    # four ways has shards whose statistics differ grossly, so BatchNorm
    # statistics taken per shard could not pass for global ones
    g = np.repeat(np.arange(4, dtype=np.float32), r["batch"] // 4) \
        .reshape(-1, 1, 1, 1)
    x = x * (0.5 + 0.5 * g) + 0.3 * (g - 1.5)
    y = rng.randint(0, r["classes"], (r["batch"],)).astype(np.float32)
    return net, (x, y)


# SGD settings of every ResNet step here. The rate is low on purpose: the
# same batch is repeated from a random init without warm-up, where 0.1
# overshoots within two steps; the smoke checks that the loss falls, and
# (--chips 4) that meshes agree, not how fast it trains.
_RESNET_SGD = {"learning_rate": 0.01, "momentum": 0.9, "wd": 1e-4}


def _layout_parity(seed):
    """A tiny conv net trained from identical weights in NCHW and NHWC
    must track the same loss on the chip (bf16 rounding differs across
    layouts, hence the tolerance)."""
    import mxnet_tpu as mx
    from mxnet_tpu import gluon

    rng = np.random.RandomState(seed)
    xs = rng.uniform(-1, 1, (16, 32, 32, 3)).astype(np.float32)
    ys = rng.randint(0, 8, (16,)).astype(np.float32)
    ctx = mx.tpu()

    def build(channels_last):
        with ctx, gluon.nn.layout_scope(channels_last):
            net = gluon.nn.HybridSequential()
            net.add(gluon.nn.Conv2D(16, 3, padding=1, use_bias=False),
                    gluon.nn.BatchNorm(), gluon.nn.Activation("relu"),
                    gluon.nn.MaxPool2D(2, 2), gluon.nn.GlobalAvgPool2D(),
                    gluon.nn.Flatten(), gluon.nn.Dense(8))
            net.initialize(mx.init.Xavier(), ctx=ctx)
            x = xs if channels_last else np.transpose(xs, (0, 3, 1, 2))
            net(mx.nd.array(x[:2], ctx=ctx))
        return net, x

    net_cf, x_cf = build(False)
    net_cl, x_cl = build(True)
    for (_, a), (_, b) in zip(sorted(net_cf.collect_params().items()),
                              sorted(net_cl.collect_params().items())):
        w = a.data().asnumpy()
        b.set_data(mx.nd.array(np.transpose(w, (0, 2, 3, 1))
                               if w.ndim == 4 else w, ctx=ctx))
    losses = {}
    for tag, net, x in (("nchw", net_cf, x_cf), ("nhwc", net_cl, x_cl)):
        tr = gluon.Trainer(net.collect_params(), "sgd",
                           {"learning_rate": 0.1, "momentum": 0.9},
                           sharded=True, block=net,
                           loss=gluon.loss.SoftmaxCrossEntropyLoss(),
                           amp_dtype="bfloat16")
        losses[tag] = [float(tr.step_batch(x, ys).asnumpy())
                       for _ in range(4)]
    ok = bool(np.all(np.isfinite(losses["nhwc"]))
              and np.allclose(losses["nhwc"], losses["nchw"],
                              rtol=0.05, atol=0.05))
    return losses, ok


def _int8_conv_chain():
    """quantize_v2 -> quantized conv/act/pool/flatten -> dequantize on the
    MXU against the float graph."""
    import mxnet_tpu as mx
    import mxnet_tpu.contrib.quantization as q

    data = mx.sym.var("data")
    h = mx.sym.Convolution(data=data, kernel=(3, 3), num_filter=16,
                           pad=(1, 1), name="conv1")
    h = mx.sym.Pooling(mx.sym.relu(h), global_pool=True, pool_type="avg",
                       name="gap")
    sym = mx.sym.Flatten(h)
    rng = np.random.RandomState(1)
    data = {"data": rng.uniform(-1, 1, (8, 3, 16, 16)).astype(np.float32)}
    params = {"conv1_weight": rng.normal(0, 0.2, (16, 3, 3, 3))
              .astype(np.float32),
              "conv1_bias": np.zeros(16, np.float32)}
    qsym = q.quantize_graph(sym, calib_ranges=None)
    qparams = {k: v._data if hasattr(v, "_data") else v
               for k, v in q.quantize_params(qsym, params).items()}
    qops = {n.op for n in qsym._topo() if not n.is_var}
    stays_int8 = {"_contrib_quantized_conv", "_contrib_quantized_act",
                  "_contrib_quantized_pooling", "_contrib_quantized_flatten",
                  "_contrib_requantize"} <= qops
    fp = np.asarray(sym.eval_with({**data, **params}))
    qt = np.asarray(qsym.eval_with({**data, **qparams}))
    err, scale = float(np.abs(fp - qt).max()), float(np.abs(fp).max())
    return {"max_err": round(err, 5), "scale": round(scale, 5),
            "chain_stays_int8": stays_int8}, \
        stays_int8 and err < 0.1 * max(scale, 1e-3)


def phase_train_resnet50(cfg):
    from mxnet_tpu import gluon

    r = cfg["resnet"]
    loss = gluon.loss.SoftmaxCrossEntropyLoss()
    net, batch = _build_resnet(cfg, cfg["seed"])
    trainer, program, info, checks = _train(
        net, loss, "sgd", dict(_RESNET_SGD), batch, r["warmup"], r["steps"])
    info.update(model=r["factory"], batch=r["batch"], layout="NHWC",
                amp="bfloat16", graph="zoo default: separate BN/ReLU/add",
                stem="7x7/s2 (space-to-depth is opt-in, off)",
                bn_lowering=_bn_lowering(info["mosaic_calls"]))
    # a training batch norm has one lowering, XLA's own: no Mosaic call
    checks["bn_lowering_in_step"] = info["mosaic_calls"] == 0
    del net, trainer                 # give the chip's memory back

    # The zoo's two opt-in rewrites (fused BN+ReLU(+add) graph, space-to-
    # depth stem), at half the batch.
    half = r["batch"] // 2
    net, batch = _build_resnet(cfg, cfg["seed"], batch=half,
                               fuse_epilogue=True, stem_s2d=True)
    trainer, program, vinfo, vchecks = _train(
        net, loss, "sgd", dict(_RESNET_SGD), batch, 1, 2)
    vinfo.update(batch=half, graph="fuse_epilogue=True", stem="stem_s2d=True",
                 bn_lowering=_bn_lowering(vinfo["mosaic_calls"]))
    info["fused_graph_s2d_stem"] = vinfo
    info["compile_s"] = round(info["compile_s"] + vinfo.pop("compile_s"), 2)
    checks.update({"fused_s2d.%s" % k: v for k, v in vchecks.items()})
    checks["fused_s2d.bn_lowering_in_step"] = vinfo["mosaic_calls"] == 0
    del net, trainer

    info["layout_parity_losses"], checks["nhwc_tracks_nchw"] = \
        _layout_parity(cfg["seed"])
    info["int8_conv"], checks["int8_conv_chain"] = _int8_conv_chain()
    return info, checks


def _flash_vs_reference():
    """Flash-attention forward and both backward kernels against the XLA
    reference at the BERT-base head geometry (max abs error)."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops.pallas_kernels import (_attention_reference,
                                              flash_attention)

    rng = np.random.RandomState(0)
    errs, ok = {}, True
    for name, shape, causal, dt, tol in (
            ("f32_causal", (2, 256, 64), True, jnp.float32, 3e-2),
            ("bf16", (4, 512, 64), False, jnp.bfloat16, 2e-1)):
        q, k, v, g = (jnp.asarray(rng.normal(size=shape), dtype=dt)
                      for _ in range(4))
        scale = 1.0 / np.sqrt(shape[-1])
        o, pull = jax.vjp(
            lambda a, b, c: flash_attention(a, b, c, causal=causal), q, k, v)
        o_r, pull_r = jax.vjp(
            lambda a, b, c: _attention_reference(a, b, c, causal, scale),
            q, k, v)
        err = max(float(jnp.abs(a.astype(jnp.float32)
                                - b.astype(jnp.float32)).max())
                  for a, b in zip((o,) + pull(g), (o_r,) + pull_r(g)))
        errs[name] = round(err, 5)
        ok = ok and err < tol
    return errs, ok


def phase_train_bert_base(cfg):
    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.gluon.block import HybridBlock
    from mxnet_tpu.gluon.model_zoo.transformer import BERTModel

    b = cfg["bert"]

    class BERTPretrain(HybridBlock):
        """The zoo encoder under a masked-LM vocabulary head: BERT's
        pretraining step."""

        def __init__(self, **kwargs):
            super().__init__(**kwargs)
            with self.name_scope():
                self.bert = BERTModel(vocab_size=b["vocab"], dropout=0.0,
                                      **b["model"])
                self.mlm = nn.Dense(b["vocab"], flatten=False,
                                    prefix="mlm_")

        def hybrid_forward(self, F, tokens):
            seq, _ = self.bert(tokens)
            return self.mlm(seq)

    _seed(cfg["seed"])
    ctx = mx.tpu()
    with ctx:
        net = BERTPretrain()
        net.initialize(mx.init.Xavier(), ctx=ctx)
        net(mx.nd.zeros((1, 8), ctx=ctx, dtype="int32"))
    rng = np.random.RandomState(cfg["seed"])
    tokens = rng.randint(0, b["vocab"], (b["batch"], b["seq_len"])) \
        .astype(np.int32)
    labels = rng.randint(0, b["vocab"], (b["batch"], b["seq_len"])) \
        .astype(np.float32)
    _, program, info, checks = _train(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), "adam",
        {"learning_rate": 1e-4}, (tokens, labels), b["warmup"], b["steps"])
    layers = b["model"]["num_layers"]
    info.update(model="bert_%d_%d_%d" % (layers, b["model"]["units"],
                                         b["model"]["num_heads"]),
                batch=b["batch"], seq_len=b["seq_len"],
                attention=_kernel_path(program, "pallas flash_attention",
                                       "no flash kernel in the step"))
    # one forward + two backward kernels per layer
    checks["flash_fwd_bwd_in_step"] = info["mosaic_calls"] >= 3 * layers
    info["flash_max_abs_err"], checks["flash_matches_reference"] = \
        _flash_vs_reference()
    return info, checks


def _lstm_kernel_vs_scan(l):
    """One forward of the LSTM layer dispatcher, Pallas kernel against the
    lax.scan path, same inputs (bf16)."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import rnn as rnn_ops

    t, b, i, h = l["bptt"], l["batch"], l["embed"], l["hidden"]
    rng = np.random.RandomState(0)

    def arr(*shape):
        return jnp.asarray(rng.normal(0, 0.1, shape), jnp.bfloat16)

    args = (arr(t, b, i), arr(4 * h, i), arr(4 * h, h), arr(4 * h),
            arr(4 * h), arr(b, h), arr(b, h))

    def layer():
        # a new function each time: jit caches traces per function, and the
        # gate is read while tracing
        return jax.jit(lambda *a: rnn_ops._run_layer(*a, "lstm")[0])

    with _env_override("MXTPU_PALLAS_LSTM", "0"):
        ys_scan = layer()(*args)
    ys_kernel = layer()(*args)          # the gate's default
    return float(jnp.abs(ys_kernel.astype(jnp.float32)
                         - ys_scan.astype(jnp.float32)).max())


def phase_train_lstm_lm(cfg):
    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon.model_zoo.word_lm import RNNModel
    from mxnet_tpu.ops import pallas_kernels

    l = cfg["lstm"]
    vocab = l["vocab"]

    class SeqCE(gluon.loss.SoftmaxCrossEntropyLoss):
        # the loss in float32: in bf16 a loss near ln(10000) moves in steps
        # of 0.06, and three SGD steps from a uniform softmax move it less
        def hybrid_forward(self, F, pred, label):
            return super().hybrid_forward(
                F, pred.reshape((-1, vocab)).astype("float32"),
                label.reshape((-1,)))

    _seed(cfg["seed"])
    ctx = mx.tpu()
    with ctx:
        net = RNNModel(vocab, l["embed"], l["hidden"], l["layers"],
                       dropout=0.0)
        net.initialize(mx.init.Xavier(), ctx=ctx)
        net(mx.nd.zeros((2, 2), ctx=ctx, dtype="int32"))
    rng = np.random.RandomState(cfg["seed"])
    tok = rng.randint(0, vocab, (l["bptt"], l["batch"])).astype(np.int32)
    lab = rng.randint(0, vocab, (l["bptt"], l["batch"])).astype(np.float32)
    _, program, info, checks = _train(
        net, SeqCE(), "sgd", {"learning_rate": 20.0}, (tok, lab),
        l["warmup"], l["steps"])
    fits = pallas_kernels.lstm_layer_fits(l["batch"], l["hidden"], 2)
    info.update(model="lstm_%dx%d" % (l["layers"], l["hidden"]),
                batch=l["batch"], bptt=l["bptt"], lstm_layer_fits=fits,
                recurrence=_kernel_path(program, "pallas lstm_layer",
                                        "lax.scan"))
    checks["lstm_layer_fits"] = bool(fits)
    # a forward and a backward kernel per layer
    checks["pallas_lstm_in_step"] = info["mosaic_calls"] >= 2 * l["layers"]
    diff = _lstm_kernel_vs_scan(l)
    info["kernel_vs_scan_max_abs_diff"] = round(diff, 5)
    checks["kernel_matches_scan"] = diff < 5e-2     # bf16, |h| <= 1
    return info, checks


def _post_generate(url, tokens, max_new):
    body = json.dumps({"tokens": tokens, "max_new_tokens": max_new,
                       "timeout_ms": 600000}).encode()
    req = urllib.request.Request(
        url, data=body, headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=660) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, {"error": e.read().decode(errors="replace")[:500]}


def _reference_decode(engine, prompt, max_new):
    """Greedy decode of one prompt driven straight through `engine`, whose
    decode step is traced with the dense-gather oracle
    (`paged_attention_reference`) in place of the Pallas kernel."""
    from mxnet_tpu import random as mx_random

    ps, maxp = engine.page_size, engine.max_pages_per_seq
    page_row = np.zeros(maxp, np.int32)
    need = -(-(len(prompt) + max_new) // ps)
    page_row[:need] = np.arange(need)
    greedy = (0.0, 0, 1.0)
    out = [engine.prefill(prompt, page_row, greedy, mx_random.next_key())]
    pos = len(prompt)
    with _env_override("MXTPU_PALLAS_DECODE", "0"):
        while len(out) < max_new:
            nxt = engine.decode_step(
                np.int32([out[-1]]), np.int32([pos]),
                np.int32([page_row[pos // ps]]), np.int32([pos % ps]),
                page_row[None], np.int32([pos + 1]), np.float32([0.0]),
                np.int32([0]), np.float32([1.0]), mx_random.next_key())
            out.append(int(nxt[0]))
            pos += 1
    return out


def _paged_kernel_vs_reference(engine):
    """The decode kernel against the dense oracle on the engine's own KV
    pool (layer 0's K and V leaves as the requests left them), every
    sequence at a different length."""
    import jax.numpy as jnp

    from mxnet_tpu.ops import pallas_kernels as pk

    b, maxp = 4, engine.max_pages_per_seq
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.normal(size=(b, engine.num_heads, engine.head_dim)),
                    engine.kv_dtype)
    tables = jnp.asarray(rng.permutation(engine.num_pages)[:b * maxp]
                         .reshape(b, maxp).astype(np.int32))
    lengths = jnp.asarray(
        np.linspace(1, maxp * engine.page_size, b).astype(np.int32))
    k_pages, v_pages = engine._kv[0]
    scale = engine.head_dim ** -0.5
    with _env_override("MXTPU_PALLAS_DECODE", "1"):
        got = pk.paged_attention(q, k_pages, v_pages, tables, lengths,
                                 sm_scale=scale)
    ref = pk.paged_attention_reference(q, k_pages, v_pages, tables, lengths,
                                       scale)
    return float(jnp.abs(got - ref).max())


def phase_serve_decode(cfg):
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu import compile as mxc
    from mxnet_tpu.gluon.model_zoo.transformer import TransformerLM
    from mxnet_tpu.serving import ModelRepository, ServingServer
    from mxnet_tpu.serving.generate import (TransformerLMEngine, load_lm,
                                            save_lm)

    s = cfg["serve"]
    _seed(cfg["seed"])
    lm = TransformerLM(dropout=0.0, **s["model"])
    lm.initialize(mx.init.Normal(0.02))
    geometry = dict(num_pages=s["num_pages"], page_size=s["page_size"],
                    max_prompt=s["long"][1], max_new_tokens=s["max_new"],
                    prefill_buckets=s["prefill_buckets"])
    rng = np.random.RandomState(cfg["seed"])
    vocab = s["model"]["vocab_size"]
    prompts = [rng.randint(0, vocab, rng.randint(lo, hi + 1)).tolist()
               for lo, hi in [s["short"], s["long"]] * 4]
    heads = s["model"]["num_heads"]
    head_dim = s["model"]["units"] // heads

    c0 = _compile_seconds()
    with tempfile.TemporaryDirectory(prefix="chip_smoke_lm_") as tmp:
        prefix = save_lm(lm, os.path.join(tmp, "lm"))
        del lm
        repo = ModelRepository()
        model = repo.load("lm", prefix, generate=True, generate_opts=dict(
            geometry, decode_buckets=[s["concurrency"]]))
        ref_engine = TransformerLMEngine(
            lm=load_lm(prefix), decode_buckets=[1], **geometry)
    engine = model.scheduler.engine
    srv = ServingServer(repo, port=0, addr="127.0.0.1").start()
    url = "http://127.0.0.1:%d/v1/models/lm:generate" % srv.port
    replies = [None] * len(prompts)
    gate = threading.Semaphore(s["concurrency"])

    def client(i):
        with gate:
            replies[i] = _post_generate(url, prompts[i], s["max_new"])

    miss0 = _counter("mxtpu_jit_cache_miss_total")
    steps0 = _counter("mxtpu_serve_decode_steps_total", {"model": "lm/1"})
    try:
        threads = [threading.Thread(target=client, args=(i,), daemon=True)
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=900)
        hung = [t for t in threads if t.is_alive()]
        compiles_serving = _counter("mxtpu_jit_cache_miss_total") - miss0
        key = engine._key("lm_decode", ("batch", s["concurrency"]))
        program = mxc.compiled(key).as_text()
        reference = _reference_decode(ref_engine, prompts[1], s["max_new"])
        kernel_diff = _paged_kernel_vs_reference(engine)
        pool = jax.tree_util.tree_leaves(engine._kv)
    finally:
        srv.shutdown()
        repo.unload("lm", timeout=5.0)
    served = replies[1][1].get("tokens") if replies[1] else None
    info = {
        "model": "transformer_lm_%dx%d" % (s["model"]["num_layers"],
                                           s["model"]["units"]),
        "heads": "%dx%d" % (heads, head_dim), "vocab": vocab,
        "requests": len(prompts), "in_flight": s["concurrency"],
        "prompt_lengths": [len(p) for p in prompts],
        "status": [r[0] if r else None for r in replies],
        "weights_dtype": str(engine._params["word"].dtype),
        "kv_dtype": engine.kv_dtype,
        "compile_s": round(_compile_seconds() - c0, 2),
        "warm_seconds": round(model.warm_seconds, 2),
        "prefill_buckets": engine.prefill_buckets,
        "decode_buckets": engine.buckets,
        "decode_steps": _counter("mxtpu_serve_decode_steps_total",
                                 {"model": "lm/1"}) - steps0,
        "mosaic_calls": program.count("tpu_custom_call"),
        "decode_attention": _kernel_path(
            program, "pallas paged_attention", "jnp dense gather"),
        "kv_pool": "%d leaves of %s %s" % (
            len(pool), tuple(pool[0].shape), pool[0].dtype),
        "kernel_vs_reference_max_abs_diff": round(kernel_diff, 7),
        "tokens_equal_reference": served == reference,
        "compiles_while_serving": compiles_serving,
        "interpret": _use_interpret(),
        "params_on": _platforms([engine._params["word"]] + pool),
    }
    checks = {
        "all_200": all(r and r[0] == 200 for r in replies) and not hung,
        "all_full_length": all(
            r and len(r[1].get("tokens") or ()) == s["max_new"]
            for r in replies),
        "paged_kernel_in_decode_step":
            info["mosaic_calls"] >= s["model"]["num_layers"],
        "greedy_equals_dense_reference": served == reference,
        "kernel_matches_reference": kernel_diff < 1e-4,
        "no_compile_while_serving": compiles_serving == 0,
        "weights_and_kv_on_tpu": info["params_on"] == ["tpu"],
        "not_interpret": not info["interpret"],
    }
    return info, checks


def phase_compile_cache(cfg):
    """Both persistent tiers: where jax's own cache lives and what it
    counted over this run, and a sharded + donated TPU train step stored
    into the executable-artifact tier, evicted from memory and loaded
    back in the same process."""
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu import compile as mxc
    from mxnet_tpu import gluon

    x = np.random.RandomState(0).uniform(-1, 1, (32, 64)).astype(np.float32)
    y = (np.arange(32) % 8).astype(np.float32)

    def one_step():
        _seed(cfg["seed"])
        ctx = mx.tpu()
        with ctx:
            net = gluon.nn.HybridSequential(prefix="cc_")
            with net.name_scope():
                net.add(gluon.nn.Dense(128, activation="relu"),
                        gluon.nn.Dense(8))
            net.initialize(ctx=ctx)
            net(mx.nd.array(x, ctx=ctx))
        tr = gluon.Trainer(net.collect_params(), "sgd",
                           {"learning_rate": 0.1}, sharded=True, block=net,
                           loss=gluon.loss.SoftmaxCrossEntropyLoss())
        return float(tr.step_batch(x, y).asnumpy())

    names = ("mxtpu_compile_cache_persist_store_total",
             "mxtpu_compile_cache_persist_hit_total")
    with tempfile.TemporaryDirectory(prefix="chip_smoke_cc_") as tmp, \
            _env_override("MXTPU_COMPILE_CACHE", tmp):
        c0 = [_counter(n) for n in names]
        first = one_step()
        c1, fills1 = [_counter(n) for n in names], _fused_step_compiles()
        mxc.reset()                      # evict the memory tier
        second = one_step()
        c2, fills2 = [_counter(n) for n in names], _fused_step_compiles()
        artifacts = len(os.listdir(os.path.join(tmp, "objects"))) \
            if os.path.isdir(os.path.join(tmp, "objects")) else 0
    info = {
        "jax_cache_dir": cfg["jax_cache_dir"],
        "jax_cache_dir_from_env":
            bool(os.environ.get("JAX_COMPILATION_CACHE_DIR")),
        "jax_cache_hits": cfg["jax_cache_events"]["hits"],
        "jax_cache_misses": cfg["jax_cache_events"]["misses"],
        "artifacts_stored": c1[0] - c0[0],
        "artifacts_on_disk": artifacts,
        "artifact_hits_after_evict": c2[1] - c1[1],
        # eager per-op fills recur after the eviction; the fused step's
        # must not
        "fused_step_compiles_after_evict": fills2 - fills1,
        "loss_first": round(first, 6), "loss_reloaded": round(second, 6),
        "backend": jax.default_backend(),
    }
    checks = {
        "executable_stored": info["artifacts_stored"] >= 1,
        "executable_reloaded": info["artifact_hits_after_evict"] >= 1,
        "fused_step_not_recompiled":
            info["fused_step_compiles_after_evict"] == 0,
        "reloaded_step_same_loss": first == second,
    }
    return info, checks


PHASES = [
    ("device", phase_device),
    ("train_resnet50", phase_train_resnet50),
    ("train_bert_base", phase_train_bert_base),
    ("train_lstm_lm", phase_train_lstm_lm),
    ("serve_decode", phase_serve_decode),
    ("compile_cache", phase_compile_cache),
]


# ---------------------------------------------------------------------------
# --chips 4: the sharded ResNet-50 step against the one-chip step
# ---------------------------------------------------------------------------

def _shard_report(arr):
    return [{"device": sh.device.id, "index": str(sh.index),
             "shape": list(sh.data.shape)} for sh in arr.addressable_shards]


def _bn_running_stats(sharded, layers=2):
    """Running mean and variance of the first BatchNorm layers, by suffix
    (the zoo prefixes every build with its own counter)."""
    names = [n for n in sharded._param_names
             if n.endswith(("_running_mean", "_running_var"))][:2 * layers]
    return {n.split("_", 1)[1]:
            np.asarray(sharded._arrays[sharded._param_names.index(n)])
            for n in names}


def phase_train_resnet50_mesh(cfg):
    import jax

    from mxnet_tpu import gluon
    from mxnet_tpu.parallel import make_mesh

    devs = jax.devices()[:4]
    # peak_bytes_in_use is a high-water mark the backend never resets:
    # the four-device meshes run first so devices 1-3 report them, and
    # device 0 ends at the one-chip step's (largest) peak
    meshes = [
        ("dp4", make_mesh([("dp", 4)], devices=devs)),
        ("dp2_fsdp2", make_mesh([("dp", 2), ("fsdp", 2)], devices=devs)),
        ("one_chip", make_mesh([("dp", 1)], devices=devs[:1])),
    ]
    steps = cfg["mesh_steps"]
    info, checks, losses, bn = {"configs": {}}, {}, {}, {}
    for name, mesh in meshes:
        def keep_bn_stats(sharded, name=name):
            bn[name] = _bn_running_stats(sharded)

        net, batch = _build_resnet(cfg, cfg["seed"])
        trainer, program, tinfo, tchecks = _train(
            net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
            dict(_RESNET_SGD), batch, 1, steps - 1, mesh=mesh,
            after_first_step=keep_bn_stats)
        sharded = trainer.sharded
        big = max(range(len(sharded._arrays)),
                  key=lambda i: sharded._arrays[i].size)
        xb = sharded._shard_batch(jax.numpy.asarray(batch[0]))
        n = mesh.devices.size
        tinfo.update(
            mesh=dict(zip(mesh.axis_names, mesh.devices.shape)),
            all_reduce_in_step="all-reduce" in program,
            largest_param=sharded._param_names[big].split("_", 1)[1],
            largest_param_spec=str(sharded._shardings[big].spec),
            largest_param_shards=_shard_report(sharded._arrays[big]),
            batch_shards=_shard_report(xb),
            peak_bytes_in_use={
                d.id: (d.memory_stats() or {}).get("peak_bytes_in_use")
                for d in mesh.devices.flat},
            bn_lowering=_bn_lowering(tinfo["mosaic_calls"]))
        info["configs"][name] = tinfo
        losses[name] = tinfo["losses"]
        for k, v in tchecks.items():
            checks["%s.%s" % (name, k)] = v
        checks["%s.param_on_%d_devices" % (name, n)] = len(
            {sh["device"] for sh in tinfo["largest_param_shards"]}) == n
        checks["%s.batch_on_%d_devices" % (name, n)] = len(
            {sh["device"] for sh in tinfo["batch_shards"]}) == n
        if n > 1:
            checks["%s.all_reduce_in_step" % name] = \
                tinfo["all_reduce_in_step"]
        del trainer, sharded, net

    # Same seed, same global batch. BatchNorm statistics: the running
    # stats after ONE step depend on the forward alone (identical weights),
    # so they must agree up to bf16 rounding if the sharded step takes them
    # over the global batch; per-shard statistics of this batch would be
    # off by tens of percent. The loss after `steps` steps then agrees up
    # to what two updates amplify of that rounding.
    # Measured on a v5e 2x2 (PR 23): 5.1e-3, 2.2e-4 and 1.4e-3 at worst.
    tol = {"bn_stats_rel": 2e-2, "first_loss_rel": 2e-3, "last_loss_rel": 1e-2}
    info["tolerances"] = tol
    ref = losses["one_chip"]
    for name in ("dp4", "dp2_fsdp2"):
        bn_rel = max(
            float(np.abs(v - bn["one_chip"][k]).max()
                  / np.abs(bn["one_chip"][k]).max())
            for k, v in bn[name].items())
        first = abs(losses[name][0] - ref[0]) / abs(ref[0])
        last = abs(losses[name][-1] - ref[-1]) / abs(ref[-1])
        info["%s_vs_one_chip" % name] = {
            "bn_running_stats_rel_diff": round(bn_rel, 6),
            "first_loss_rel_diff": round(first, 6),
            "loss_after_%d_steps_rel_diff" % steps: round(last, 6)}
        checks["%s.bn_stats_are_global" % name] = bn_rel < tol["bn_stats_rel"]
        checks["%s.first_loss_matches_one_chip" % name] = \
            first < tol["first_loss_rel"]
        checks["%s.loss_after_%d_steps_matches_one_chip" % (name, steps)] = \
            last < tol["last_loss_rel"]
    return info, checks


MESH_PHASES = [
    ("device", phase_device),
    ("train_resnet50_mesh", phase_train_resnet50_mesh),
]


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def _run_phase(name, fn, cfg):
    t0 = time.perf_counter()
    rec = {"phase": name}
    try:
        info, checks = fn(cfg)
        rec.update(info)
        rec["checks"] = checks
        rec["ok"] = all(checks.values())
    except Exception as e:  # noqa: BLE001 — recorded, and fails the run
        traceback.print_exc(file=sys.stderr)
        rec["ok"] = False
        rec["error"] = "%s: %s" % (type(e).__name__, str(e)[:2000])
    rec["seconds"] = round(time.perf_counter() - t0, 2)
    print(json.dumps(rec), flush=True)
    return rec


def _verdict(ok):
    """The contract's last line, and nothing else in it."""
    try:
        import jax

        d = jax.devices()[0]
        device = {"platform": d.platform, "kind": d.device_kind,
                  "count": len(jax.devices())}
    except Exception:  # noqa: BLE001 — no backend at all
        device = None
    print(json.dumps({"ok": bool(ok), "device": device}), flush=True)
    return 0 if ok else 1


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes, kernels forced on: a CPU walk through "
                         "the control flow; never a pass")
    args = ap.parse_args(argv)

    import jax

    from mxnet_tpu.base import enable_persistent_compile_cache

    cache_events = {"hits": 0, "misses": 0}

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            cache_events["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            cache_events["misses"] += 1

    jax.monitoring.register_event_listener(on_event)
    try:
        accelerated = jax.devices()[0].platform != "cpu"
    except RuntimeError:        # no backend at all: the device phase says so
        accelerated = False
    cfg = dict(SIZES["rehearse" if args.rehearse else "real"],
               seed=args.seed, chips=args.chips,
               # armed before the first compile, and only for an
               # accelerator: a CPU run leaves nothing in the chip's cache
               jax_cache_dir=enable_persistent_compile_cache()
               if accelerated else None,
               jax_cache_events=cache_events)
    if args.rehearse and args.chips == 1 and not accelerated:
        for gate in _REHEARSAL_KERNEL_GATES:
            os.environ.setdefault(gate, "1")

    ok = not args.rehearse
    for name, fn in (MESH_PHASES if args.chips == 4 else PHASES):
        rec = _run_phase(name, fn, cfg)
        ok = ok and rec["ok"]
        if name == "device" and not rec["ok"] and not args.rehearse:
            break       # no chip: nothing below may run, let alone pass
    return _verdict(ok)


if __name__ == "__main__":
    sys.exit(main())
