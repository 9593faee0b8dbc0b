"""Benchmark: ResNet-50 training throughput + MFU (the reference's headline
number — docs/faq/perf.md:234, `train_imagenet.py` imgs/sec).

Runs the full compiled training step (fwd + CE loss + bwd + SGD-momentum
update as ONE donated-buffer XLA executable, via parallel.DistributedTrainer
on a 1-chip mesh) at batch 32 on synthetic ImageNet-shaped data and prints
one JSON line.

Reported fields beyond the driver's required four:
  dtype          — compute precision of the timed run (bf16 by default —
                   the MXU's native dtype; MXTPU_BENCH_DTYPE=float32 for fp32)
  mfu            — model FLOPs utilization: analytic train FLOPs/img
                   (fwd 2*MACs, train = 3x fwd — the standard accounting)
                   over the chip's peak for the run's precision
  step_ms_*      — per-step wall-time distribution (each step synced),
                   separating steady-state step time from dispatch pipelining
  vs_baseline    — measured imgs/sec over the reference's 298.51 imgs/sec
                   (ResNet-50 train bs=32, V100 fp32, MXNet 1.2 + cuDNN 7,
                   docs/faq/perf.md:234). The V100 number is fp32; when this
                   run is bf16 the comparison crosses precision — that is the
                   point (bf16 is the TPU-native training mode), and `dtype`
                   + `vs_baseline_fp32_ref` make the comparison explicit.

MXTPU_BENCH_MODE=score switches to inference scoring (mirrors the
reference's example/image-classification/benchmark_score.py — forward-only
imgs/sec vs the V100 1076.81 fp32 / 2085.51 fp16 rows, perf.md:176,190).

MXTPU_BENCH_MODE=bert runs a BERT-base (12/768/12) masked-LM-shaped train
step (flash-attention MHA) and reports tokens/sec + MFU. The reference has
no in-tree BERT throughput number (GluonNLP is external — SURVEY §6), so
vs_baseline is measured against BASELINE.json's ≥60%-MFU target instead.

MXTPU_BENCH_MODE=lstm runs the word-LM 2x650 LSTM (reference
example/rnn/word_lm defaults, PTB-shaped synthetic data) and reports
tokens/sec + MFU under the same stance as the bert mode.

MXTPU_BENCH_MODE=goodput runs the goodput-attribution A/B: a tiny
module.fit whose legacy data-wait split must agree with the
telemetry/goodput.py phase accountant within 10% (docs/observability.md
§Goodput) — the `train_goodput` row.

MXTPU_BENCH_MODE=train_sharded runs the hot-path promotion A/B
(docs/sharded_training.md): op-by-op gluon.Trainer loop vs the fused
ShardedTrainer whole-step executable on a dispatch-bound MLP, reporting
the speedup, per-step dispatch-count delta, donation aliased_fraction
and the data-wait/compute split (MXTPU_BENCH_SHARDED_IMPL selects the
headline implementation).

MXTPU_BENCH_MODE=train_input runs the input-pipeline A/B
(docs/data_pipeline.md): the same fused step_batch loop fed by the same
deliberately stalled iterator (MXTPU_BENCH_INPUT_STALL_MS per batch),
synchronously vs wrapped in trainer.prefetch(...) — the
data.DevicePrefetcher double buffer. Reports the data_wait_fraction of
both arms, the imgs/sec speedup, whether the two loss trajectories
match bit-for-bit, post-warm jit_compile counts, and the goodput
attributor's coverage of the prefetched run — the `train_input` row.
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

BASELINE_TRAIN = 298.51   # reference docs/faq/perf.md:234 (V100 fp32, bs=32)
BASELINE_SCORE_FP32 = 1076.81  # perf.md:176 (V100 fp32 inference, bs=32)
BASELINE_SCORE_FP16 = 2085.51  # perf.md:190 (V100 fp16 inference, bs=32)

BATCH = int(os.environ.get("MXTPU_BENCH_BATCH", 32))
WARMUP = int(os.environ.get("MXTPU_BENCH_WARMUP", 5))
ITERS = int(os.environ.get("MXTPU_BENCH_ITERS", 20))
MODE = os.environ.get("MXTPU_BENCH_MODE", "train")
# model under test for train/score modes (validated against the mode's
# net table in main() so a typo still yields a diagnosable JSON line)
NET = os.environ.get("MXTPU_BENCH_NET", "resnet50")
# NCHW (reference layout, default) or NHWC (MXU-preferred channels-last)
LAYOUT = os.environ.get("MXTPU_BENCH_LAYOUT", "NCHW").upper()
# bf16 compute + fp32 master weights is the TPU-native training precision
AMP_DTYPE = os.environ.get("MXTPU_BENCH_DTYPE", "bfloat16")
if AMP_DTYPE in ("float32", "fp32", "none"):
    AMP_DTYPE = None

# Analytic ResNet-50 FLOPs at 224x224: 3.86 GMACs -> 7.72 GF forward
# (2 FLOPs per MAC; conv+fc, exact per-layer count for the v1
# architecture this bench builds — stride-2 on the bottleneck 1x1, NOT
# the 4.09-GMAC v1b/torchvision variant with stride on the 3x3, which
# this constant wrongly used before and inflated reported MFU ~6%).
# Training = fwd + bwd-wrt-input + bwd-wrt-weight ~= 3x forward (the
# standard accounting used by MFU papers). Cross-checked against the
# automatic cost-analysis accounting (telemetry/flops.py): auto/hand =
# 0.96 train, 0.96 fwd on CPU XLA.
RESNET50_FWD_FLOPS_PER_IMG = 2 * 3.858e9
RESNET50_TRAIN_FLOPS_PER_IMG = 3 * RESNET50_FWD_FLOPS_PER_IMG

from mxnet_tpu.runtime import chip_peak_tflops as _chip_peak_tflops  # noqa: E402


def _percentiles(ms):
    ms = sorted(ms)
    n = len(ms)
    return {
        "step_ms_median": round(ms[n // 2], 2),
        "step_ms_p10": round(ms[max(0, int(0.1 * n))], 2),
        "step_ms_p90": round(ms[min(n - 1, int(0.9 * n))], 2),
    }


def _goodput_mark():
    """Snapshot the goodput accountant's cumulative totals — pair with
    _goodput_breakdown() to decompose a timed region into phases."""
    from mxnet_tpu.telemetry import goodput

    t = goodput.totals()
    return dict(t["phases"]), t["wall"]


def _goodput_breakdown(mark):
    """Per-phase seconds + fractions of the step wall accumulated since
    ``mark`` (telemetry/goodput.py attribution — the CPU-side mirror of
    tools/step_profile.py's on-device xplane rollup, so the two rows line
    up). None when the accountant saw no steps (telemetry disabled)."""
    from mxnet_tpu.telemetry import goodput

    ph0, wall0 = mark
    t = goodput.totals()
    wall = t["wall"] - wall0
    if wall <= 0.0:
        return None
    secs, fracs = {}, {}
    for p, v in t["phases"].items():
        if p == "between_steps":  # loop idle — not part of any step's wall
            continue
        d = v - ph0.get(p, 0.0)
        if d > 1e-9:
            secs[p] = round(d, 4)
            fracs[p] = round(d / wall, 4)
    return {"phase_seconds": secs, "phase_fractions": fracs,
            "goodput_fraction": fracs.get("compute", 0.0),
            "step_wall_s": round(wall, 4)}


def _build(ctx, factory="resnet50_v1", hw=224):
    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon.model_zoo import vision

    batch = BATCH
    fac = getattr(vision, factory)
    with ctx:
        if LAYOUT == "NHWC":
            # channels-last build (MXU-preferred): layout_scope flips the
            # default conv/pool layout + BN axis for the whole zoo model
            with gluon.nn.layout_scope():
                net = fac()
            xshape = (batch, hw, hw, 3)
        else:
            net = fac()
            xshape = (batch, 3, hw, hw)
        net.initialize(ctx=ctx)
        rng = np.random.RandomState(0)
        # data lives on-device: a real input pipeline double-buffers batches
        # to HBM; the timed loop must not pay host->device transfer per step
        x = mx.nd.array(rng.uniform(-1, 1, xshape).astype(np.float32), ctx=ctx)
        label = mx.nd.array(rng.randint(0, 1000, (batch,))
                            .astype(np.float32), ctx=ctx)
        net(x)  # finish deferred init
    return net, x, label


# Training nets beyond the headline ResNet-50, mirroring the reference's
# train_imagenet.py rows in BASELINE.md (docs/faq/perf.md:233-236).
# (factory, input hw, train FLOPs/img, V100 fp32 imgs/sec, ref batch).
_TRAIN_NETS = {
    "resnet50": ("resnet50_v1", 224, RESNET50_TRAIN_FLOPS_PER_IMG,
                 BASELINE_TRAIN, 32),
    "inception_v3": ("inception_v3", 299, 3 * 11.46e9, 253.68, 128),
    "alexnet": ("alexnet", 224, 3 * 1.43e9, 2994.32, 256),
}


def bench_train():
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.parallel import DistributedTrainer, make_mesh

    net_key = NET
    factory, hw, flops_per_img, base, base_batch = _TRAIN_NETS[net_key]

    ctx = mx.tpu()
    net, x, label = _build(ctx, factory=factory, hw=hw)
    dev = _require_accelerator()
    if x._data.devices() != {dev}:
        raise RuntimeError("bench batch lives on %s, not on %s"
                           % (x._data.devices(), dev))

    mesh = make_mesh([("dp", 1)], devices=[dev])
    trainer = DistributedTrainer(
        net, "sgd", {"learning_rate": 0.1, "momentum": 0.9, "wd": 1e-4},
        loss=gluon.loss.SoftmaxCrossEntropyLoss(), mesh=mesh,
        amp_dtype=AMP_DTYPE)
    # per-step FLOPs are no longer declared by hand: the jit-cache-fill
    # cost analysis (telemetry/flops.py, MXTPU_TRACE_FLOPS) accounts them
    # and telemetry publishes achieved MFU on its own; the bench keeps its
    # analytic flops_per_img for the headline number and reports both

    def timed_train(xb, yb, batch, split=None):
        """warmup -> drain -> free-running timed loop (async dispatch
        pipelines host & device) -> imgs/sec. `split` (when given)
        receives the data-wait vs dispatch/compute decomposition of the
        timed region — the same two-phase accounting module.fit publishes
        as mxtpu_data_{wait,compute}_seconds_total, here with a pre-staged
        generator standing in for the input pipeline's next()."""
        for _ in range(WARMUP):
            trainer.step(xb, yb)
        trainer.step(xb, yb).asnumpy()  # drain dispatch before timed region
        gp_mark = _goodput_mark() if split is not None else None
        batches = ((xb, yb) for _ in range(ITERS))
        wait = 0.0
        t0 = time.perf_counter()
        while True:
            tw = time.perf_counter()
            try:
                xs, ys = next(batches)
            except StopIteration:
                break
            wait += time.perf_counter() - tw
            loss = trainer.step(xs, ys)
        loss.asnumpy()
        total = time.perf_counter() - t0
        if split is not None:
            split.update(data_wait_s=round(wait, 4),
                         compute_s=round(total - wait, 4),
                         data_wait_fraction=round(wait / total, 4))
            gp = _goodput_breakdown(gp_mark)
            if gp is not None:
                split["goodput"] = gp
        return batch * ITERS / total

    split = {}
    imgs_per_sec = timed_train(x, label, BATCH, split=split)

    if os.environ.get("MXTPU_BENCH_PROFILE"):
        # capture an XLA (xplane) trace of a few steady-state steps next to
        # the JSON artifact — the evidence docs/perf_notes.md's MFU gap
        # analysis is built from
        from mxnet_tpu import profiler as _prof

        trace_dir = os.environ.get("MXTPU_BENCH_PROFILE_DIR",
                                   "bench_trace_%s" % MODE)
        _prof.start_xla_trace(trace_dir)
        for _ in range(3):
            trainer.step(x, label)
        trainer.step(x, label).asnumpy()
        _prof.stop_xla_trace()
        # stderr: stdout carries exactly ONE JSON line (driver contract)
        print("xla trace captured in %s" % trace_dir, file=sys.stderr)

    # step-time distribution: each step synced
    step_ms = []
    for _ in range(ITERS):
        t1 = time.perf_counter()
        trainer.step(x, label).asnumpy()
        step_ms.append((time.perf_counter() - t1) * 1e3)

    peak = _chip_peak_tflops(dev)
    mfu = (imgs_per_sec * flops_per_img / (peak * 1e12)) if peak else None

    # cost-analysis cross-check: the automatically accounted per-step
    # FLOPs (what telemetry MFU is computed from, zero set_step_flops)
    # against the analytic hand count — the two should agree within a few
    # percent or the analytic model is wrong
    auto_step_flops = mx.telemetry.flops.last_step_flops()
    hand_step_flops = flops_per_img * BATCH
    out = {
        "metric": "%s_train_bs%d_imgs_per_sec" % (net_key, BATCH),
        "value": round(imgs_per_sec, 2),
        "unit": "imgs/sec",
        "vs_baseline": round(imgs_per_sec / base, 3),
        "dtype": AMP_DTYPE or "float32",
        "baseline": {"value": base, "dtype": "float32",
                     "hw": "V100", "batch": base_batch},
        "batch": BATCH,
        "device": getattr(dev, "device_kind", str(dev)),
        "flops_per_img": flops_per_img,
        "auto_step_flops": auto_step_flops,
        "auto_vs_hand_flops": round(auto_step_flops / hand_step_flops, 4)
                              if auto_step_flops else None,
        "peak_bf16_tflops": peak,
        "mfu": round(mfu, 4) if mfu is not None else None,
        # auto MFU = auto_step_flops / step_seconds / peak, with
        # step_seconds = BATCH / imgs_per_sec
        "auto_mfu": round(auto_step_flops * imgs_per_sec
                          / (BATCH * peak * 1e12), 4)
                    if peak and auto_step_flops and imgs_per_sec else None,
    }
    out.update(split)
    out.update(_percentiles(step_ms))

    _sweep_segment(out, dev, flops_per_img,
                   lambda sb: timed_train(*_sweep_batch_arrays(ctx, sb, hw), sb))
    # decompose at the chip-bound batch (the sweep size) when the sweep ran:
    # the MFU plan is read against sweep_mfu, so the segments must time the
    # same configuration, not the latency-bound headline batch
    seg_x = x
    if "sweep_batch" in out:
        seg_x = _sweep_batch_arrays(ctx, out["sweep_batch"], hw)[0]
    _mfu_segments(out, dev, net, ctx, seg_x, flops_per_img / 3)
    print(json.dumps(out))


def bench_train_sharded():
    """A/B over the user-facing hot path (MXTPU_BENCH_MODE=train_sharded):
    the op-by-op gluon.Trainer loop (autograd.record -> loss.backward ->
    trainer.step; one host dispatch per op) against the promoted fused
    ShardedTrainer whole-step executable (docs/sharded_training.md). The
    model is a deliberately dispatch-bound MLP: per-op Python/dispatch
    overhead is exactly the cost the fused step removes, so the gap IS the
    measurement. MXTPU_BENCH_SHARDED_IMPL=opbyop emits the op-by-op row
    alone; the default `fused` row times BOTH loops under the same init
    and data and reports the in-row speedup, the per-step dispatch-count
    delta, the donation verifier's aliased_fraction, and the data-wait vs
    compute split of the timed region."""
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon, telemetry
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.telemetry import memory as _tm_memory

    impl = os.environ.get("MXTPU_BENCH_SHARDED_IMPL", "fused")
    ctx = mx.tpu()
    dev = jax.devices()[0]
    in_dim, hidden, classes = 784, 1024, 10
    # fwd FLOPs: 2 MACs per weight element across the three Dense layers
    fwd_flops = 2 * (in_dim * hidden + hidden * hidden + hidden * classes)
    flops_per_img = 3 * fwd_flops  # train = fwd + bwd-input + bwd-weight

    def build(prefix):
        with ctx:
            net = nn.HybridSequential(prefix=prefix)
            with net.name_scope():
                net.add(nn.Dense(hidden, activation="relu", prefix="fc1_"))
                net.add(nn.Dense(hidden, activation="relu", prefix="fc2_"))
                net.add(nn.Dense(classes, prefix="fc3_"))
            net.initialize(ctx=ctx)
        return net

    rng = np.random.RandomState(0)
    x = mx.nd.array(rng.uniform(-1, 1, (BATCH, in_dim))
                    .astype(np.float32), ctx=ctx)
    y = mx.nd.array(rng.randint(0, classes, (BATCH,))
                    .astype(np.float32), ctx=ctx)
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    opt_args = {"learning_rate": 0.05, "momentum": 0.9}

    def dispatches():
        # total op dispatches across categories (imperative/autograd/...)
        return sum(v.get("value", 0) for k, v in
                   telemetry.snapshot().items()
                   if k.startswith("mxtpu_op_dispatch_total"))

    def timed(step, drain):
        for _ in range(WARMUP):
            step()
        drain(step())
        d0 = dispatches()
        gp_mark = _goodput_mark()
        batches = (None for _ in range(ITERS))
        wait = 0.0
        t0 = time.perf_counter()
        while True:
            tw = time.perf_counter()
            try:
                next(batches)
            except StopIteration:
                break
            wait += time.perf_counter() - tw
            out = step()
        drain(out)
        total = time.perf_counter() - t0
        res = {"imgs_per_sec": round(BATCH * ITERS / total, 2),
               "dispatch_per_step": round((dispatches() - d0) / ITERS, 1),
               "data_wait_s": round(wait, 4),
               "compute_s": round(total - wait, 4),
               "data_wait_fraction": round(wait / total, 4)}
        gp = _goodput_breakdown(gp_mark)
        if gp is not None:
            res["goodput"] = gp
        return res

    def run_opbyop():
        net = build("ab_op_")
        net(x)
        tr = gluon.Trainer(net.collect_params(), "sgd", dict(opt_args))

        def step():
            with autograd.record():
                ls = loss_fn(net(x), y)
            ls.backward()
            tr.step(BATCH)
            return ls

        return timed(step, lambda ls: ls.asnumpy())

    def run_fused():
        net = build("ab_fz_")
        net(x)
        tr = gluon.Trainer(net.collect_params(), "sgd", dict(opt_args),
                           sharded=True, block=net, loss=loss_fn)
        res = timed(lambda: tr.step_batch(x, y), lambda ls: ls.asnumpy())
        rep = _tm_memory.last_donation_report() or {}
        res["aliased_fraction"] = rep.get("aliased_fraction")
        return res

    peak = _chip_peak_tflops(dev)
    out = {
        "metric": "mlp_train_sharded_%s_bs%d_imgs_per_sec" % (impl, BATCH),
        "unit": "imgs/sec",
        "batch": BATCH,
        "device": getattr(dev, "device_kind", str(dev)),
        "flops_per_img": flops_per_img,
    }
    if impl == "opbyop":
        a = run_opbyop()
        out.update(value=a["imgs_per_sec"], vs_baseline=None, opbyop=a)
    else:
        a = run_opbyop()
        b = run_fused()
        speedup = b["imgs_per_sec"] / a["imgs_per_sec"] \
            if a["imgs_per_sec"] else None
        out.update(
            value=b["imgs_per_sec"],
            # in-row baseline: the op-by-op loop under identical init/data
            vs_baseline=round(speedup, 3) if speedup else None,
            baseline={"value": a["imgs_per_sec"], "hw": "op-by-op",
                      "batch": BATCH},
            opbyop=a, fused=b,
            speedup_fused_vs_opbyop=round(speedup, 3) if speedup else None,
            dispatch_per_step_opbyop=a["dispatch_per_step"],
            dispatch_per_step_fused=b["dispatch_per_step"],
            aliased_fraction=b.get("aliased_fraction"),
            data_wait_s=b["data_wait_s"], compute_s=b["compute_s"],
            data_wait_fraction=b["data_wait_fraction"])
        if peak:
            out["mfu"] = round(out["value"] * flops_per_img
                               / (peak * 1e12), 4)
    print(json.dumps(out))


def bench_train_goodput():
    """Goodput-attribution A/B over module.fit (MXTPU_BENCH_MODE=goodput):
    run a tiny MLP fit and compare the legacy two-phase split the fit loop
    has always published (mxtpu_data_{wait,compute}_seconds_total{src=fit})
    against the goodput accountant's phase decomposition of the SAME run
    (telemetry/goodput.py). The two account the iterator wait through
    independent code paths, so their data-wait seconds must agree within
    10% — `ab_agree_within_10pct` is the row's self-check. The headline
    value is the attributed goodput fraction (compute ÷ step wall). This
    row prices the attribution machinery, not a device: it is meaningful
    on CPU and is labeled with whatever platform actually ran it."""
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu import telemetry

    rng = np.random.RandomState(0)
    n, in_dim, classes = 4096, 64, 8
    X = rng.uniform(-1, 1, (n, in_dim)).astype(np.float32)
    Y = rng.randint(0, classes, (n,)).astype(np.float32)

    data = mx.sym.var("data")
    sym = mx.sym.FullyConnected(data, num_hidden=64, name="fc1")
    sym = mx.sym.Activation(sym, act_type="relu")
    sym = mx.sym.FullyConnected(sym, num_hidden=classes, name="fc2")
    sym = mx.sym.SoftmaxOutput(sym, name="softmax")

    def fit_split():
        s = telemetry.snapshot()

        def val(name):
            return float((s.get('%s{src="fit"}' % name) or {})
                         .get("value") or 0.0)

        return (val("mxtpu_data_wait_seconds_total"),
                val("mxtpu_data_compute_seconds_total"))

    train = mx.io.NDArrayIter(X, Y, batch_size=BATCH, shuffle=True,
                              label_name="softmax_label")
    mod = mx.mod.Module(sym, context=mx.cpu())
    epochs = max(2, ITERS // 4)
    w0, c0 = fit_split()
    gp_mark = _goodput_mark()
    t0 = time.perf_counter()
    mod.fit(train, num_epoch=epochs, optimizer="sgd",
            optimizer_params={"learning_rate": 0.1})
    total = time.perf_counter() - t0
    w1, c1 = fit_split()
    legacy_wait, legacy_compute = w1 - w0, c1 - c0
    gp = _goodput_breakdown(gp_mark) or {
        "phase_seconds": {}, "phase_fractions": {},
        "goodput_fraction": None, "step_wall_s": 0.0}
    gp_wait = gp["phase_seconds"].get("data_wait", 0.0)
    ratio = (gp_wait / legacy_wait) if legacy_wait > 0 else None
    out = {
        "metric": "train_goodput",
        "value": gp["goodput_fraction"],
        "unit": "fraction",
        "vs_baseline": None,
        "device": getattr(jax.devices()[0], "device_kind",
                          jax.devices()[0].platform),
        "platform": jax.devices()[0].platform,
        "batch": BATCH,
        "epochs": epochs,
        "steps": epochs * (n // BATCH),
        "fit_wall_s": round(total, 4),
        "goodput": gp,
        "legacy_fit_split": {"data_wait_s": round(legacy_wait, 4),
                             "compute_s": round(legacy_compute, 4)},
        "ab_data_wait_ratio": round(ratio, 4) if ratio is not None
        else None,
        "ab_agree_within_10pct": bool(ratio is not None
                                      and 0.9 <= ratio <= 1.1),
    }
    print(json.dumps(out))


def bench_train_input():
    """Input-pipeline A/B (MXTPU_BENCH_MODE=train_input): one fused
    step_batch loop, one deliberately stalled source iterator
    (MXTPU_BENCH_INPUT_STALL_MS of producer work per batch, modeling
    decode/augment/IO), two feeding disciplines:

      sync       — the loop blocks on every next(): the stall lands in
                   the step gap and shows up as data_wait.
      prefetched — the same iterator wrapped in trainer.prefetch(...)
                   (data.DevicePrefetcher): a producer thread absorbs
                   the stall and lands batches on device, already laid
                   out to the step's batch_spec sharding, while the
                   previous step computes.

    Both arms run the identical weight init and batch sequence, so the
    loss trajectories must match — `loss_trajectory_match` is the row's
    self-check, alongside zero post-warm jit_compile events per arm and
    the goodput attributor covering >=0.9 of the prefetched arm's step
    wall. The headline value is the prefetched imgs/sec; the acceptance
    figure is `data_wait_reduction` (sync / prefetched fraction). The
    stall only hides behind compute, so the MLP is sized compute-heavy;
    meaningful on CPU and labeled with whatever platform ran it."""
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu import gluon, random as _mxrandom
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.telemetry import recorder as _rec

    ctx = mx.tpu()
    dev = jax.devices()[0]
    stall_ms = int(os.environ.get("MXTPU_BENCH_INPUT_STALL_MS", 20))
    # compute-heavy on purpose: prefetch can only hide a stall behind
    # compute, so the step must cost more than the stall it absorbs
    in_dim, hidden, classes = 1024, 2048, 10
    fwd_flops = 2 * (in_dim * hidden + hidden * hidden + hidden * classes)
    flops_per_img = 3 * fwd_flops

    rng = np.random.RandomState(0)
    nsteps = WARMUP + ITERS
    X = rng.uniform(-1, 1, (nsteps * BATCH, in_dim)).astype(np.float32)
    Y = rng.randint(0, classes, (nsteps * BATCH,)).astype(np.float32)
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()

    class _StalledIter:
        """NDArrayIter plus a fixed per-batch producer stall — the
        synthetic stand-in for decode/augment/IO cost."""

        def __init__(self):
            self._it = mx.io.NDArrayIter(X, Y, batch_size=BATCH,
                                         shuffle=False,
                                         label_name="softmax_label")
            self.batch_size = BATCH

        def __iter__(self):
            return self

        def __next__(self):
            batch = self._it.next()  # raises StopIteration at the end
            time.sleep(stall_ms / 1e3)
            return batch

        next = __next__

        def reset(self):
            self._it.reset()

    def build_trainer():
        # both seeds: initializers draw from NumPy's global RNG, the
        # per-step keys from the mx chain — identical weights and
        # identical step RNG are what make the A/B trajectories equal
        np.random.seed(1234)
        _mxrandom.seed(1234)
        with ctx:
            net = nn.HybridSequential(prefix="inp_")
            with net.name_scope():
                net.add(nn.Dense(hidden, activation="relu", prefix="fc1_"))
                net.add(nn.Dense(hidden, activation="relu", prefix="fc2_"))
                net.add(nn.Dense(classes, prefix="fc3_"))
            net.initialize(ctx=ctx)
        net(mx.nd.zeros((BATCH, in_dim), ctx=ctx))
        return gluon.Trainer(net.collect_params(), "sgd",
                             {"learning_rate": 0.05, "momentum": 0.9},
                             sharded=True, block=net, loss=loss_fn)

    def jit_compiles():
        return sum(1 for e in _rec.events() if e["event"] == "jit_compile")

    def run_arm(prefetched):
        tr = build_trainer()
        src = _StalledIter()
        it = tr.prefetch(src) if prefetched else src
        losses = []
        # warm: first batches compile the fused step; the timed region
        # below must then run compile-free (jit_compiles_after_warm)
        for _ in range(WARMUP):
            b = next(it)
            losses.append(tr.step_batch(b.data[0], b.label[0]))
        losses[-1].asnumpy()  # drain before opening the timed region
        j0 = jit_compiles()
        gp_mark = _goodput_mark()
        wait = 0.0
        t0 = time.perf_counter()
        for _ in range(ITERS):
            tw = time.perf_counter()
            b = next(it)
            wait += time.perf_counter() - tw
            losses.append(tr.step_batch(b.data[0], b.label[0]))
        losses[-1].asnumpy()
        total = time.perf_counter() - t0
        jits = jit_compiles() - j0
        if prefetched:
            it.close()
        res = {"imgs_per_sec": round(BATCH * ITERS / total, 2),
               "data_wait_s": round(wait, 4),
               "compute_s": round(total - wait, 4),
               "data_wait_fraction": round(wait / total, 4),
               "jit_compiles_after_warm": jits}
        gp = _goodput_breakdown(gp_mark)
        if gp is not None:
            res["goodput"] = gp
            # attributor coverage: share of the step wall landing in a
            # NAMED phase (everything step_end couldn't attribute is
            # "other" — telemetry/goodput.py)
            res["goodput_coverage"] = round(
                1.0 - gp["phase_fractions"].get("other", 0.0), 4)
        return res, np.array([float(v.asnumpy()) for v in losses])

    sync, loss_sync = run_arm(prefetched=False)
    pre, loss_pre = run_arm(prefetched=True)
    reduction = (sync["data_wait_fraction"] / pre["data_wait_fraction"]
                 if pre["data_wait_fraction"] > 0 else None)
    traj_delta = float(np.max(np.abs(loss_sync - loss_pre)))
    speedup = (pre["imgs_per_sec"] / sync["imgs_per_sec"]
               if sync["imgs_per_sec"] else None)
    out = {
        "metric": "mlp_train_input_prefetch_bs%d_imgs_per_sec" % BATCH,
        "value": pre["imgs_per_sec"],
        "unit": "imgs/sec",
        # in-row baseline: the sync loop under identical init and data
        "vs_baseline": round(speedup, 3) if speedup else None,
        "baseline": {"value": sync["imgs_per_sec"], "hw": "sync next()",
                     "batch": BATCH},
        "device": getattr(dev, "device_kind", str(dev)),
        "platform": dev.platform,
        "batch": BATCH,
        "steps": ITERS,
        "stall_ms": stall_ms,
        "flops_per_img": flops_per_img,
        "sync": sync,
        "prefetched": pre,
        "speedup_prefetched_vs_sync": round(speedup, 3) if speedup
        else None,
        "data_wait_fraction_sync": sync["data_wait_fraction"],
        "data_wait_fraction_prefetched": pre["data_wait_fraction"],
        "data_wait_reduction": round(reduction, 2) if reduction is not None
        else None,
        "loss_trajectory_max_delta": traj_delta,
        "loss_trajectory_match": bool(traj_delta == 0.0),
        "jit_compiles_after_warm": (sync["jit_compiles_after_warm"]
                                    + pre["jit_compiles_after_warm"]),
        "goodput_coverage_prefetched": pre.get("goodput_coverage"),
    }
    print(json.dumps(out))


def _mfu_segments(out, dev, net, ctx, x, fwd_flops_per_img, iters=None):
    """Self-diagnosing capture: decompose the train step into its fwd-only
    and fwd+bwd sub-executables (inlined from tools/mfu_probe.py) plus the
    raw bf16 matmul ceiling, so every train artifact localizes its own MFU
    gap without needing a separate probe session. Extra best-effort
    fields; MXTPU_BENCH_SEGMENTS=0 disables. Runs LAST: it casts the net
    to bf16 in place, so nothing may time the trainer after it.

    Timing note: every timed region ends in a host fetch, and the matmul
    chains dependent iterations inside one jit so identical dispatches
    can't be elided."""
    try:
        if os.environ.get("MXTPU_BENCH_SEGMENTS", "1") == "0":
            return
        import jax
        import jax.numpy as jnp

        from __graft_entry__ import _pure_forward

        peak = _chip_peak_tflops(dev)
        batch = x.shape[0]

        def timed(fn, *args, n=max(3, (iters or ITERS) // 2)):
            fn(*args)  # compile
            jax.device_get(jax.tree.leaves(fn(*args))[0])  # drain dispatch
            t0 = time.perf_counter()
            r = None
            for _ in range(n):
                r = fn(*args)
            jax.device_get(jax.tree.leaves(r)[0])
            return (time.perf_counter() - t0) / n

        # raw bf16 matmul ceiling — the calibration anchor the fwd/bwd
        # numbers are read against (sustained on this chip, not datasheet)
        n_mm = int(os.environ.get("MXTPU_BENCH_SEG_MM_N", 8192))
        k_mm = 8
        a = jax.random.normal(jax.random.PRNGKey(0), (n_mm, n_mm),
                              jnp.float32).astype(jnp.bfloat16)
        b = jax.random.normal(jax.random.PRNGKey(1), (n_mm, n_mm),
                              jnp.float32).astype(jnp.bfloat16)

        @jax.jit
        def mm(p, q):
            for _ in range(k_mm):
                p = (p @ q) * jnp.bfloat16(1e-4)
            # reduce to a scalar: the drain fetch must not pull the full
            # n_mm^2 bf16 product (128 MB at 8192) back to the host — that
            # fetch dominated the timed region and under-reported the
            # matmul ceiling ~5x
            return jnp.sum(p, dtype=jnp.float32)

        dt = timed(mm, a, b) / k_mm
        tf_mm = 2 * n_mm ** 3 / dt / 1e12
        # small-matrix contract runs (CPU, SEG_MM_N=128) land far below
        # 0.05 TF/s; one-decimal rounding must not flatten them to 0.0
        out["seg_matmul_tflops"] = round(tf_mm, 1 if tf_mm >= 1 else 6)
        if peak:
            out["seg_matmul_mfu"] = round(tf_mm / peak, 4)

        net.cast("bfloat16")
        fwd = _pure_forward(net, ctx)
        jitted = jax.jit(fwd)
        xb = x._data.astype(jnp.bfloat16)

        dt_f = timed(jitted, xb)
        out["seg_fwd_ms"] = round(dt_f * 1e3, 2)
        if peak:
            mfu_f = batch * fwd_flops_per_img / dt_f / 1e12 / peak
            # as for the matmul ceiling above: a tiny contract run on a
            # loaded CPU must not round to 0.0
            out["seg_fwd_mfu"] = round(mfu_f, 4 if mfu_f >= 1e-3 else 9)

        # grad w.r.t. the INPUT only (weights are closure constants): the
        # executable is fwd + the dgrad chain = ~2x fwd FLOPs. wgrad is the
        # remaining slice: full-step mfu vs this number localizes it.
        grad_fn = jax.jit(jax.grad(
            lambda d: fwd(d).astype(jnp.float32).sum()))
        dt_g = timed(grad_fn, xb)
        out["seg_fwd_dgrad_ms"] = round(dt_g * 1e3, 2)
        if peak:
            out["seg_fwd_dgrad_mfu"] = round(
                batch * 2 * fwd_flops_per_img / dt_g / 1e12 / peak, 4)
        # input-grad forces the STEM's dgrad (input-dilated, MXU-hostile),
        # which the real train step never computes (dx of the first conv is
        # dead and XLA DCEs it) — alexnet's stride-4 11x11 stem makes this
        # segment read 50x slower than its real step. Flag it so artifact
        # readers weigh the number correctly.
        out["seg_fwd_dgrad_note"] = ("includes stem dgrad (DCE'd in real "
                                     "training; dominant for large-stride "
                                     "stems)")
    except Exception as e:  # noqa: BLE001 — segments are best-effort extra
        out["seg_error"] = str(e)[:200]


def _sweep_batch_arrays(ctx, sweep_batch, hw=224):
    """Fresh on-device (data, label) arrays at the sweep batch size."""
    import numpy as _np

    import mxnet_tpu as mx

    rng = _np.random.RandomState(1)
    shape = (sweep_batch, hw, hw, 3) if LAYOUT == "NHWC" \
        else (sweep_batch, 3, hw, hw)
    with ctx:
        xl = mx.nd.array(rng.uniform(-1, 1, shape).astype(_np.float32), ctx=ctx)
        yl = mx.nd.array(rng.randint(
            0, 1000, (sweep_batch,)).astype(_np.float32), ctx=ctx)
    return xl, yl


def _sweep_segment(out, dev, flops_per_img, run):
    """Large-batch segment shared by train and score modes: the bs=32
    headline matches the reference's configuration, but MFU at that batch
    is input-bound; a second timed run at MXTPU_BENCH_SWEEP_BATCH (default
    256) shows how close the compiled step gets to the chip's ceiling
    (BASELINE.json >=60% MFU target). Extra fields only — the driver's
    one-JSON-line headline contract (metric/value/unit/vs_baseline) is
    untouched: everything here is best-effort inside the try, and the
    sweep is skipped entirely on the CPU-fallback path (extra ResNet-50
    steps at bs>=256 on a CPU would stall the artifact for hours). Set
    MXTPU_BENCH_SWEEP_BATCH=0 to disable on TPU too.

    Two points by default: MXTPU_BENCH_SWEEP_BATCH (256; fields sweep_*)
    and the larger MXTPU_BENCH_SWEEP_BATCH2 (512; fields sweep2_* — the
    step is HBM-bound so MFU rises with batch). Either =0 disables that
    point; a failure at one point (e.g. sweep2 OOM) keeps the other's
    fields and records sweep{,2}_error.

    `run(sweep_batch)` -> imgs/sec at that batch."""
    if getattr(dev, "platform", "cpu") == "cpu":
        return
    peak = _chip_peak_tflops(dev)
    seen = {BATCH}
    for prefix, env, default in (("sweep", "MXTPU_BENCH_SWEEP_BATCH", 256),
                                 ("sweep2", "MXTPU_BENCH_SWEEP_BATCH2", 512)):
        try:
            b = int(os.environ.get(env) or default)
            if not b or b in seen:
                continue
            seen.add(b)
            ips = run(b)
            out["%s_batch" % prefix] = b
            out["%s_imgs_per_sec" % prefix] = round(ips, 2)
            if peak:
                out["%s_mfu" % prefix] = round(
                    ips * flops_per_img / (peak * 1e12), 4)
        except Exception as e:  # noqa: BLE001 — sweep is best-effort extra
            out["%s_error" % prefix] = str(e)[:200]


# Scoring nets beyond the headline ResNet-50, mirroring the reference's
# benchmark_score.py model list where BASELINE.md has V100 rows
# (docs/faq/perf.md:176,190). (factory, input hw, fwd FLOPs/img,
# fp32 V100 imgs/sec, fp16 V100 imgs/sec or None).
_SCORE_NETS = {
    "resnet50": ("resnet50_v1", 224, RESNET50_FWD_FLOPS_PER_IMG,
                 BASELINE_SCORE_FP32, BASELINE_SCORE_FP16),
    "resnet152": ("resnet152_v1", 224, 2 * 11.3e9, 451.82, 887.34),
    "inception_v3": ("inception_v3", 299, 2 * 5.73e9, 814.59, None),
}


def bench_score():
    """Inference scoring mode (reference benchmark_score.py analogue).
    MXTPU_BENCH_NET picks the model (resnet50 default / resnet152 /
    inception_v3 — the BASELINE.md V100 scoring rows)."""
    import jax
    import jax.numpy as jnp

    import mxnet_tpu as mx

    net_key = NET
    factory, hw, flops_per_img, base_fp32, base_fp16 = _SCORE_NETS[net_key]

    ctx = mx.tpu()
    net, x, _ = _build(ctx, factory=factory, hw=hw)
    dev = jax.devices()[0]

    dtype = jnp.bfloat16 if AMP_DTYPE else jnp.float32
    if AMP_DTYPE:
        # pure-bf16 inference: params cast too (reference fp16 scoring
        # casts the whole net — benchmark_score.py dtype arg)
        net.cast(AMP_DTYPE)
    from __graft_entry__ import _pure_forward
    fwd = _pure_forward(net, ctx)
    xb = x._data.astype(dtype)

    jitted = jax.jit(fwd)

    def timed_score(xl, batch):
        """compile/warm -> drain -> free-running timed loop -> imgs/sec.
        Drains via device_get: the host fetch bounds the timed region."""
        jax.device_get(jitted(xl))
        for _ in range(WARMUP):
            jitted(xl)
        jax.device_get(jitted(xl))
        t0 = time.perf_counter()
        o = None
        for _ in range(ITERS):
            o = jitted(xl)
        jax.device_get(o)
        return batch * ITERS / (time.perf_counter() - t0)

    imgs_per_sec = timed_score(xb, BATCH)

    # bf16 runs compare against the fp16 V100 row when the reference
    # published one; otherwise against fp32 with the dtype recorded
    if AMP_DTYPE and base_fp16 is not None:
        base, base_dtype = base_fp16, "float16"
    else:
        base, base_dtype = base_fp32, "float32"
    peak = _chip_peak_tflops(dev)
    mfu = (imgs_per_sec * flops_per_img / (peak * 1e12)) if peak else None
    out = {
        "metric": "%s_score_bs%d_imgs_per_sec" % (net_key, BATCH),
        "value": round(imgs_per_sec, 2),
        "unit": "imgs/sec",
        "vs_baseline": round(imgs_per_sec / base, 3),
        "dtype": str(jnp.dtype(dtype)),
        "baseline": {"value": base, "dtype": base_dtype, "hw": "V100"},
        "batch": BATCH,
        "device": getattr(dev, "device_kind", str(dev)),
        "flops_per_img": flops_per_img,
        "peak_bf16_tflops": peak,
        "mfu": round(mfu, 4) if mfu is not None else None,
    }
    def run_score_sweep(sweep_batch):
        rng = np.random.RandomState(1)
        shape = (sweep_batch, hw, hw, 3) if LAYOUT == "NHWC" \
            else (sweep_batch, 3, hw, hw)
        xl = jnp.asarray(rng.uniform(-1, 1, shape).astype(np.float32)
                         ).astype(dtype)
        return timed_score(xl, sweep_batch)

    _sweep_segment(out, dev, flops_per_img, run_score_sweep)
    print(json.dumps(out))


def bench_score_int8():
    """INT8 quantized scoring (MXTPU_BENCH_MODE=score_int8): the
    reference's quantize_model deployment path (contrib/quantization.py:422)
    end-to-end — trace the zoo net to a symbol, calibrate + rewrite to
    quantized ops (int8 MXU dot/conv), and time the quantized Predictor.
    The reference publishes no int8 imgs/sec row, so vs_baseline compares
    against the V100 fp32 scoring row with dtype recorded as int8."""
    import tempfile

    import jax

    import mxnet_tpu as mx
    from mxnet_tpu.contrib import quantization as q
    from mxnet_tpu.io import NDArrayIter
    from mxnet_tpu.model import load_checkpoint
    from mxnet_tpu.predict import Predictor

    factory, hw, flops_per_img, base_fp32, _ = _SCORE_NETS[NET]
    ctx = mx.tpu()
    net, x, _ = _build(ctx, factory=factory, hw=hw)
    dev = jax.devices()[0]

    with tempfile.TemporaryDirectory() as td:
        prefix = os.path.join(td, "m")
        net.hybridize()
        with ctx:
            net(x)
        net.export(prefix)
        sym, arg_params, aux_params = load_checkpoint(prefix, 0)

        xnp = np.asarray(x.asnumpy(), dtype=np.float32)

        # deployment pre-pass: fold BN into convs so conv->relu->pool
        # trunks quantize into contiguous int8 segments (no fp32 islands)
        sym, arg_params, aux_params = q.fold_batch_norm(
            sym, arg_params, aux_params)
        from mxnet_tpu.model import save_checkpoint

        save_checkpoint(prefix + "-folded", 0, sym, arg_params, aux_params)

        # weights quantize OFFLINE (int8 `_quantize` params) — the compiled
        # step binds int8 weights directly; save and bind the returned
        # quantized param dict
        qsym, qargs, qauxs = q.quantize_model(
            sym, arg_params, aux_params, calib_mode="naive",
            calib_data=NDArrayIter(xnp, batch_size=xnp.shape[0]))
        save_checkpoint(prefix + "-quant", 0, qsym, qargs, qauxs)
        pred = Predictor(qsym, prefix + "-quant-0000.params", ctx=ctx,
                         input_shapes={"data": tuple(xnp.shape)})

    def timed_int8(batch):
        pred.forward(data=x)
        jax.device_get(pred.get_output(0)._data)
        for _ in range(WARMUP):
            pred.forward(data=x)
        jax.device_get(pred.get_output(0)._data)
        t0 = time.perf_counter()
        for _ in range(ITERS):
            pred.forward(data=x)
        jax.device_get(pred.get_output(0)._data)
        return batch * ITERS / (time.perf_counter() - t0)

    imgs_per_sec = timed_int8(BATCH)
    peak = _chip_peak_tflops(dev)
    # int8 runs the MXU at >= bf16 peak; reporting MFU against the bf16
    # peak keeps the figure conservative and comparable with other modes
    mfu = (imgs_per_sec * flops_per_img / (peak * 1e12)) if peak else None
    out = {
        "metric": "%s_score_int8_bs%d_imgs_per_sec" % (NET, BATCH),
        "value": round(imgs_per_sec, 2),
        "unit": "imgs/sec",
        "vs_baseline": round(imgs_per_sec / base_fp32, 3),
        "dtype": "int8",
        "baseline": {"value": base_fp32, "dtype": "float32", "hw": "V100"},
        "batch": BATCH,
        "device": getattr(dev, "device_kind", str(dev)),
        "flops_per_img": flops_per_img,
        "peak_bf16_tflops": peak,
        "mfu": round(mfu, 4) if mfu is not None else None,
    }
    print(json.dumps(out))


def bench_bert():
    """BERT-base train-step tokens/sec (BASELINE.json config 'BERT-base
    pretraining'). Synthetic token batches; the step is the full compiled
    fwd (flash-attention encoder) + vocab-head CE + bwd + Adam update."""
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.gluon.block import HybridBlock
    from mxnet_tpu.gluon.model_zoo.transformer import bert_12_768_12
    from mxnet_tpu.parallel import DistributedTrainer, make_mesh

    seq_len = int(os.environ.get("MXTPU_BENCH_SEQLEN", 512))
    batch = int(os.environ.get("MXTPU_BENCH_BATCH", 8))
    vocab = 30522

    class BERTPretrain(HybridBlock):
        def __init__(self, **kwargs):
            super().__init__(**kwargs)
            with self.name_scope():
                # dropout 0: throughput benchmark measures the math, not rng
                self.bert = bert_12_768_12(dropout=0.0)
                self.mlm = nn.Dense(vocab, flatten=False, prefix="mlm_")

        def hybrid_forward(self, F, tokens):
            seq, _ = self.bert(tokens)
            return self.mlm(seq)

    ctx = mx.tpu()
    dev = jax.devices()[0]
    with ctx:
        net = BERTPretrain()
        net.initialize(mx.init.Xavier())
        rng = np.random.RandomState(0)
        tokens = mx.nd.array(rng.randint(0, vocab, (batch, seq_len))
                             .astype(np.int32), ctx=ctx, dtype="int32")
        labels = mx.nd.array(rng.randint(0, vocab, (batch, seq_len))
                             .astype(np.float32), ctx=ctx)
        net(tokens)

    mesh = make_mesh([("dp", 1)], devices=[dev])
    trainer = DistributedTrainer(
        net, "adam", {"learning_rate": 1e-4},
        loss=gluon.loss.SoftmaxCrossEntropyLoss(), mesh=mesh,
        amp_dtype=AMP_DTYPE)

    for _ in range(WARMUP):
        trainer.step(tokens, labels)
    trainer.step(tokens, labels).asnumpy()

    t0 = time.perf_counter()
    for _ in range(ITERS):
        loss = trainer.step(tokens, labels)
    loss.asnumpy()
    dt = time.perf_counter() - t0
    tokens_per_sec = batch * seq_len * ITERS / dt

    step_ms = []
    for _ in range(ITERS):
        t1 = time.perf_counter()
        trainer.step(tokens, labels).asnumpy()
        step_ms.append((time.perf_counter() - t1) * 1e3)

    # standard transformer accounting: 6*N FLOPs per token for fwd+bwd over
    # the non-embedding params, + 12*layers*units*seq for attention scores
    n_params = sum(int(np.prod(p.shape))
                   for n, p in net.collect_params().items())
    # embedding tables don't contribute matmul FLOPs; they are created with
    # the word_/segment_/pos_ prefixes (transformer.py BERTModel)
    n_embed = sum(int(np.prod(p.shape))
                  for n, p in net.collect_params().items()
                  if any(t in n for t in ("word_", "segment_", "pos_")))
    flops_per_token = 6 * (n_params - n_embed) + 12 * 12 * 768 * seq_len
    peak = _chip_peak_tflops(dev)
    mfu = (tokens_per_sec * flops_per_token / (peak * 1e12)) if peak else None

    out = {
        "metric": "bert_base_train_tokens_per_sec",
        "value": round(tokens_per_sec, 2),
        "unit": "tokens/sec",
        "vs_baseline": round(mfu / 0.60, 3) if mfu is not None else None,
        "dtype": AMP_DTYPE or "float32",
        "baseline": {"target_mfu": 0.60,
                     "note": "no in-tree reference BERT number (perf.md has "
                             "CNNs only); ratio is mfu/target"},
        "batch": batch, "seq_len": seq_len,
        "params": n_params, "flops_per_token": flops_per_token,
        "peak_bf16_tflops": peak,
        "mfu": round(mfu, 4) if mfu is not None else None,
    }
    out.update(_percentiles(step_ms))
    print(json.dumps(out))


def bench_lstm():
    """LSTM word-LM train-step tokens/sec (BASELINE.json config 'LSTM
    language model' — reference example/rnn/word_lm trains a 2x650 LSTM on
    PTB with bptt=35, batch=32; no imgs/sec-style number is published
    in-tree so vs_baseline is mfu/0.60 like the BERT mode). The step is the
    full compiled fwd (lax.scan fused LSTM) + CE + bwd + SGD update."""
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon.model_zoo.word_lm import RNNModel
    from mxnet_tpu.parallel import DistributedTrainer, make_mesh

    bptt = int(os.environ.get("MXTPU_BENCH_SEQLEN", 35))
    batch = int(os.environ.get("MXTPU_BENCH_BATCH", 32))
    vocab, embed, hidden, layers = 10000, 650, 650, 2

    ctx = mx.tpu()
    dev = jax.devices()[0]
    mesh = make_mesh([("dp", 1)], devices=[dev])

    class SeqCE(gluon.loss.SoftmaxCrossEntropyLoss):
        def hybrid_forward(self, F, pred, label):
            return super().hybrid_forward(
                F, pred.reshape((-1, vocab)), label.reshape((-1,)))

    def run_at(b, collect_ms=False):
        with ctx:
            # dropout 0: measure the math, not rng (same stance as
            # bench_bert)
            net = RNNModel(vocab, embed, hidden, layers, dropout=0.0)
            net.initialize(mx.init.Xavier())
            rng = np.random.RandomState(0)
            tok = mx.nd.array(rng.randint(0, vocab, (bptt, b))
                              .astype(np.int32), ctx=ctx, dtype="int32")
            lab = mx.nd.array(rng.randint(0, vocab, (bptt, b))
                              .astype(np.float32), ctx=ctx)
            net(tok)
        tr = DistributedTrainer(
            net, "sgd", {"learning_rate": 1.0},
            loss=SeqCE(), mesh=mesh, amp_dtype=AMP_DTYPE)
        for _ in range(WARMUP):
            tr.step(tok, lab)
        tr.step(tok, lab).asnumpy()
        t0 = time.perf_counter()
        for _ in range(ITERS):
            loss = tr.step(tok, lab)
        loss.asnumpy()
        tps = b * bptt * ITERS / (time.perf_counter() - t0)
        ms = []
        if collect_ms:
            for _ in range(ITERS):
                t1 = time.perf_counter()
                tr.step(tok, lab).asnumpy()
                ms.append((time.perf_counter() - t1) * 1e3)
        return tps, ms

    tokens_per_sec, step_ms = run_at(batch, collect_ms=True)

    # fwd FLOPs/token: 4 gates x (h x in + h x h) MACs x 2 per layer,
    # + decoder h x vocab x 2; train = 3x fwd
    fwd = sum(2 * 4 * (hidden * (embed if l == 0 else hidden)
                       + hidden * hidden) for l in range(layers))
    fwd += 2 * hidden * vocab
    flops_per_token = 3 * fwd
    peak = _chip_peak_tflops(dev)
    mfu = (tokens_per_sec * flops_per_token / (peak * 1e12)) if peak else None

    out = {
        "metric": "lstm_word_lm_train_tokens_per_sec",
        "value": round(tokens_per_sec, 2),
        "unit": "tokens/sec",
        "vs_baseline": round(mfu / 0.60, 3) if mfu is not None else None,
        "dtype": AMP_DTYPE or "float32",
        "baseline": {"target_mfu": 0.60,
                     "note": "no in-tree reference LSTM number; ratio is "
                             "mfu/target (same stance as bert mode)"},
        "batch": batch, "bptt": bptt,
        "flops_per_token": flops_per_token,
        "peak_bf16_tflops": peak,
        "mfu": round(mfu, 4) if mfu is not None else None,
    }
    out.update(_percentiles(step_ms))
    # sweep point: the bs=32 headline is latency-bound on the recurrence;
    # a larger batch shows how much of the gap is batch size vs kernel
    # (same stance as the CNN _sweep_segment; TPU only, best-effort)
    if getattr(dev, "platform", "cpu") != "cpu":
        try:
            sb = int(os.environ.get("MXTPU_BENCH_SWEEP_BATCH") or 256)
            if sb and sb != batch:
                stps, _ = run_at(sb)
                out["sweep_batch"] = sb
                out["sweep_tokens_per_sec"] = round(stps, 2)
                if peak:
                    out["sweep_mfu"] = round(
                        stps * flops_per_token / (peak * 1e12), 4)
        except Exception as e:  # noqa: BLE001 — sweep is best-effort extra
            out["sweep_error"] = str(e)[:200]
    print(json.dumps(out))


def _fail_json(metric, error):
    """The one-JSON-line contract when nothing can be measured: no value,
    the reason, exit non-zero."""
    print(json.dumps({"metric": metric, "value": None, "unit": None,
                      "vs_baseline": None, "error": error}), flush=True)
    raise SystemExit(1)


def _metric_name():
    return {"score": "%s_score_bs%d_imgs_per_sec" % (NET, BATCH),
            "score_int8": "%s_score_int8_bs%d_imgs_per_sec" % (NET, BATCH),
            "bert": "bert_base_train_tokens_per_sec",
            "lstm": "lstm_word_lm_train_tokens_per_sec",
            "train_sharded": "mlp_train_sharded_%s_bs%d_imgs_per_sec"
                             % (os.environ.get("MXTPU_BENCH_SHARDED_IMPL",
                                               "fused"), BATCH)}.get(
                MODE, "%s_train_bs%d_imgs_per_sec" % (NET, BATCH))


def _require_accelerator():
    """bench.py measures on the chip and nowhere else: `mx.tpu()` quietly
    resolves to a CPU device when no accelerator exists (context.py), so
    the platform is read from the devices themselves, and a CPU-only
    process gets no number under a device metric's name."""
    import jax

    try:
        dev = jax.devices()[0]
    except RuntimeError as e:
        _fail_json(_metric_name(), "jax backend init failed: %s"
                                   % str(e)[:500])
    if dev.platform == "cpu":
        _fail_json(_metric_name(),
                   "no accelerator: jax.devices()[0].platform is 'cpu'; "
                   "bench.py reports device rates only (chip_smoke.py "
                   "--rehearse exercises the same paths on the CPU)")
    return dev


def main():
    # validate the net/mode pair up front so a typo still emits the
    # one-JSON-line contract instead of a bare KeyError in the .log
    tables = {"train": _TRAIN_NETS, "score": _SCORE_NETS,
              "score_int8": _SCORE_NETS}
    if MODE in tables and NET not in tables[MODE]:
        print(json.dumps({
            "metric": "%s_%s_bs%d_imgs_per_sec" % (NET, MODE, BATCH),
            "value": None, "unit": "imgs/sec", "vs_baseline": None,
            "error": "unknown MXTPU_BENCH_NET %r for mode %r; valid: %s"
                     % (NET, MODE, sorted(tables[MODE]))}))
        raise SystemExit(1)
    _require_accelerator()
    # arm both persistent compile tiers: each capture mode is a fresh
    # process recompiling the same step. The framework's executable-
    # artifact tier (MXTPU_COMPILE_CACHE -> mxnet_tpu.compile, read lazily
    # at first fill) and jax's HLO-keyed cache as backstop for executables
    # the artifact tier can't serialize; the latter stays wherever
    # JAX_COMPILATION_CACHE_DIR put it.
    from mxnet_tpu.base import enable_persistent_compile_cache

    if not os.environ.get("MXTPU_COMPILE_CACHE"):
        os.environ["MXTPU_COMPILE_CACHE"] = "1"
    enable_persistent_compile_cache()
    if MODE == "score":
        bench_score()
    elif MODE == "score_int8":
        bench_score_int8()
    elif MODE == "bert":
        bench_bert()
    elif MODE == "lstm":
        bench_lstm()
    elif MODE == "train_sharded":
        bench_train_sharded()
    elif MODE == "goodput":
        bench_train_goodput()
    elif MODE == "train_input":
        bench_train_input()
    else:
        bench_train()


if __name__ == "__main__":
    main()
