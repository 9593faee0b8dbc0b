#!/usr/bin/env python
"""Offline (CPU-verifiable) performance evidence for perf-sensitive changes.

Two consecutive rounds shipped perf work with zero on-chip signal. This
tool produces the evidence that CAN be produced without the chip, for each
touched segment:

- the lowered HLO (``jit(fn).lower().as_text()``) of the before/after
  variants, summarized as an opcode histogram diff (which passes
  disappeared, what got fused, where the kernel boundaries are), and
- an analytic FLOP / HBM-byte account of the segment (what the change
  moves, with the arithmetic written down so the first chip run can
  confirm or refute it).

Outputs one markdown file per segment under docs/perf_evidence/ (committed,
so reviewers diff them like code):

  python tools/perf_evidence.py [segment ...]    # default: all

Segments: conv_epilogue, stem_s2d, lstm_fused, int8_quant.
Runs entirely on CPU (JAX_PLATFORMS=cpu recommended); Pallas kernels lower
in interpret mode. tools/bench_capture.sh runs this before the bench pass so
every capture window ships with matched offline evidence at the same SHA.
"""
from __future__ import annotations

import collections
import os
import re
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "docs",
                       "perf_evidence")

_OPCODE_RE = re.compile(
    r"=\s+\"?([a-z_]+)\.([a-z_0-9]+)")  # `%3 = stablehlo.dot_general ...`


def hlo_histogram(text):
    """Opcode -> count histogram of a lowered (StableHLO MLIR) module."""
    hist = collections.Counter()
    for line in text.splitlines():
        m = _OPCODE_RE.search(line)
        if m:
            hist[m.group(2)] += 1
    return hist


def hist_diff_table(ha, hb, label_a, label_b):
    rows = ["| opcode | %s | %s | Δ |" % (label_a, label_b),
            "|---|---|---|---|"]
    for op in sorted(set(ha) | set(hb),
                     key=lambda o: -(abs(ha.get(o, 0) - hb.get(o, 0)))):
        a, b = ha.get(op, 0), hb.get(op, 0)
        if a == 0 and b == 0:
            continue
        rows.append("| %s | %d | %d | %+d |" % (op, a, b, b - a))
    return "\n".join(rows)


def lower_text(fn, *args):
    import jax

    return jax.jit(fn).lower(*args).as_text()


def gb(x):
    return "%.3f" % (x / 1e9)


def write_md(name, body):
    os.makedirs(OUT_DIR, exist_ok=True)
    path = os.path.join(OUT_DIR, name + ".md")
    with open(path, "w") as f:
        f.write(body)
    print("wrote", os.path.relpath(path))


def _git_sha():
    import subprocess

    try:
        return subprocess.check_output(
            ["git", "rev-parse", "--short", "HEAD"],
            cwd=os.path.dirname(__file__)).decode().strip()
    except Exception:  # noqa: BLE001
        return "unknown"


def _header(title):
    return ("# %s\n\ngenerated by `tools/perf_evidence.py` at %s "
            "(CPU lowering; Pallas in interpret mode — re-run after any "
            "change to the touched segment)\n\n" % (title, _git_sha()))


# ---------------------------------------------------------------------------
# Segment: fused conv epilogue (BN stats + normalize + ReLU + residual add)
# ---------------------------------------------------------------------------

def seg_conv_epilogue():
    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax import lax

    from mxnet_tpu.ops import nn as N

    n, h, w, c = 32, 14, 14, 256
    cin, k = 256, 3
    it = 2  # bf16
    dt = jnp.bfloat16
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(n, h, w, cin), dt)
    wt = jnp.asarray(rng.randn(c, k, k, cin) * 0.05, dt)
    res = jnp.asarray(rng.randn(n, h, w, c), dt)
    gamma = jnp.ones((c,), jnp.float32)
    beta = jnp.zeros((c,), jnp.float32)
    dn = lax.conv_dimension_numbers(x.shape, wt.shape,
                                    ("NHWC", "OHWI", "NHWC"))

    def seg(epi):
        def f(x, wt, res, gamma, beta):
            y = lax.conv_general_dilated(x, wt, (1, 1), [(1, 1), (1, 1)],
                                         dimension_numbers=dn)
            out, mm, mv = N._bn_act(y, res, gamma, beta,
                                    jnp.zeros((c,)), jnp.ones((c,)),
                                    1e-3, 0.9, False, False, 3, "relu",
                                    True)
            return jnp.sum(out.astype(jnp.float32))

        # train step: value + grads through the epilogue
        def step(x, wt, res, gamma, beta):
            g = jax.grad(f, argnums=(0, 1, 2, 3, 4))(
                x, wt, res, gamma, beta)
            return f(x, wt, res, gamma, beta), g

        prev = os.environ.get("MXTPU_PALLAS_CONV_EPILOGUE")
        os.environ["MXTPU_PALLAS_CONV_EPILOGUE"] = epi
        try:
            txt = lower_text(step, x, wt, res, gamma, beta)
        finally:
            if prev is None:
                os.environ.pop("MXTPU_PALLAS_CONV_EPILOGUE", None)
            else:
                os.environ["MXTPU_PALLAS_CONV_EPILOGUE"] = prev
        return txt

    txt_off = seg("0")
    txt_on = seg("1")
    s_act = n * h * w * c * it  # one activation-sized HBM pass, bytes

    body = _header("Fused conv-epilogue (BN stats + normalize + ReLU + "
                   "residual add)")
    body += """## Segment

`conv3x3(NHWC, bf16, %dx%dx%dx%d) -> BatchNormAddRelu(train)` forward +
full input/weight/residual/affine gradients. Variant A is the unfused
pure-jnp path (`MXTPU_PALLAS_CONV_EPILOGUE=0`: custom-vjp BN + separate
add + relu ops); variant B the fused Pallas kernel pair
(`ops/pallas_kernels.conv_epilogue`, lowered in interpret mode on CPU —
on TPU each `pallas_call` below is one fused VMEM-resident pass).

## Analytic HBM traffic (one activation pass S = N*H*W*C*itemsize = %s GB)

Counting full-activation HBM passes around ONE residual conv epilogue
(XLA reliably fuses elementwise chains but offers no cross-reduction
guarantee; the unfused row is the round-4 profile's observed shape —
stats, normalize, relu and add each materialized):

| pass | unfused (A) | fused Pallas (B) |
|---|---|---|
| forward stats        | read x | read x |
| forward normalize    | read x, write y1 | — (fused into apply) |
| forward relu         | read y1, write y2 | — |
| forward residual add | read y2 + res, write out | read x + res, write out (ONE pass) |
| **fwd subtotal**     | **6 S read+write** | **4 S** |
| backward reductions  | read ct, out (mask), x | read ct, out, x (one fused pass) |
| backward dx + dres   | read ct/x again, write dx, dres | read ct/x/out, write dx + dres |
| **bwd subtotal**     | **~7 S** (separate mask/add/reduce passes) | **~8 S worst-case, fused into exactly 2 passes** |

Forward saves 2 full activation passes per epilogue (~%s GB here); at
ResNet-50 bs256 there are 53 epilogues/step — the round-4 profile put the
whole elementwise-around-conv bucket at ~30 GB/step of the measured 67.
Expected effect (to be CONFIRMED on the chip via
`bench_capture.sh` A-B rows `train_nhwc_epifuse` / `train_nhwc_epioff`):
step HBM traffic drops by roughly the forward-side 2S×53 ≈ 10-15 GB plus
whatever XLA was failing to fuse in backward.

## HLO opcode histogram (A = unfused jnp, B = fused Pallas/interpret)

""" % (n, h, w, c, gb(s_act), gb(2 * s_act))
    body += hist_diff_table(hlo_histogram(txt_off), hlo_histogram(txt_on),
                            "A unfused", "B fused")
    body += ("\n\nHLO sizes: A %d lines, B %d lines. The `custom_call` / "
             "callback rows in B are the two Pallas kernel boundaries "
             "(stats, apply) plus their backward pair — on TPU these are "
             "`tpu_custom_call` fused kernels.\n"
             % (len(txt_off.splitlines()), len(txt_on.splitlines())))
    write_md("conv_epilogue", body)


# ---------------------------------------------------------------------------
# Segment: space-to-depth stem
# ---------------------------------------------------------------------------

def seg_stem_s2d():
    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax import lax

    from mxnet_tpu.gluon.model_zoo.vision.resnet import stem_weight_to_s2d
    from mxnet_tpu.ops import tensor as T

    n, hw = 8, 224
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(n, hw, hw, 3), jnp.bfloat16)
    w7 = jnp.asarray(rng.randn(64, 7, 7, 3) * 0.05, jnp.bfloat16)
    w4 = jnp.asarray(stem_weight_to_s2d(np.asarray(w7, np.float32), "NHWC"),
                     jnp.bfloat16)

    def stem7(x, w):
        dn = lax.conv_dimension_numbers(x.shape, w.shape,
                                        ("NHWC", "OHWI", "NHWC"))
        y = lax.conv_general_dilated(x, w, (2, 2), [(3, 3), (3, 3)],
                                     dimension_numbers=dn)
        return jnp.sum(y.astype(jnp.float32))

    def stem4(x, w):
        z = T.space_to_depth(x, block_size=2, layout="NHWC")
        z = jnp.pad(z, ((0, 0), (2, 1), (2, 1), (0, 0)))
        dn = lax.conv_dimension_numbers(z.shape, w.shape,
                                        ("NHWC", "OHWI", "NHWC"))
        y = lax.conv_general_dilated(z, w, (1, 1), [(0, 0), (0, 0)],
                                     dimension_numbers=dn)
        return jnp.sum(y.astype(jnp.float32))

    g7 = lambda x, w: jax.grad(stem7, argnums=(0, 1))(x, w)  # noqa: E731
    g4 = lambda x, w: jax.grad(stem4, argnums=(0, 1))(x, w)  # noqa: E731
    txt7 = lower_text(lambda x, w: (stem7(x, w), g7(x, w)), x, w7)
    txt4 = lower_text(lambda x, w: (stem4(x, w), g4(x, w)), x, w4)

    out_pix = n * (hw // 2) * (hw // 2)
    fl7 = 2 * out_pix * 64 * 3 * 49
    fl4 = 2 * out_pix * 64 * 12 * 16
    body = _header("Space-to-depth stem (7x7/s2 over 3ch -> 4x4/s1 over "
                   "12ch)")
    body += """## Segment

ResNet stem at %dx%d bs%d, forward + input/weight grads, NHWC bf16.
Variant A: reference `7x7/s2/pad3` conv over 3 channels. Variant B:
`space_to_depth(2)` + asymmetric (2,1) pad + `4x4/s1` VALID conv over 12
channels, with the weight produced by the exact transform
`stem_weight_to_s2d` (numerically identical output — tested in
tests/test_conv_epilogue.py).

## Analytic FLOP / utilization account

| | A: 7x7/s2 (3ch) | B: s2d + 4x4/s1 (12ch) |
|---|---|---|
| MACs per output pixel | 3*49 = 147 | 12*16 = 192 |
| total fwd FLOPs | %.2f G | %.2f G (x%.2f) |
| contraction depth per MXU pass | 3 (of 128 lanes) | 12 (of 128 lanes) |
| input HBM bytes | %s GB | %s GB (same data, regrouped) |

The transform spends %.0f%% MORE arithmetic but feeds the MXU a 4x deeper
contraction and removes the stride-2 gather from the data path — the
round-4 probe (`conv_probe_r04.jsonl`) measured the 3-channel stem at
82 TF/s fwd and **16 TF/s dgrad** (inherently MXU-hostile; the stride-2
dgrad rewrite made it worse, 4.2 TF/s). The s2d form's dgrad is a
stride-1 VALID conv — the shape class the same probe measured at
190+ TF/s. Net expected stem time: fwd roughly break-even, backward
several-fold faster; stem was ~8%% of the bs256 step.
A-B capture rows: `train_nhwc_s2d` vs `train_nhwc` (and
`train_nhwc_epifuse_s2d` for the combined configuration).

## HLO opcode histogram (fwd+grad)

""" % (hw, hw, n, fl7 / 1e9, fl4 / 1e9, fl4 / fl7,
       gb(n * hw * hw * 3 * 2), gb(n * hw * hw * 3 * 2),
       100.0 * (fl4 - fl7) / fl7)
    body += hist_diff_table(hlo_histogram(txt7), hlo_histogram(txt4),
                            "A 7x7/s2", "B s2d+4x4")
    body += "\n"
    write_md("stem_s2d", body)


# ---------------------------------------------------------------------------
# Segment: fused Pallas LSTM layer (retroactive, round-5 change)
# ---------------------------------------------------------------------------

def seg_lstm_fused():
    import numpy as np
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import pallas_kernels as pk
    from mxnet_tpu.ops import rnn as rnn_mod

    t, b, hsz = 35, 32, 650          # the word-LM bench shape
    it = 2
    rng = np.random.RandomState(0)
    gx = jnp.asarray(rng.randn(t, b, 4 * hsz) * 0.1, jnp.bfloat16)
    wh = jnp.asarray(rng.randn(4 * hsz, hsz) * 0.1, jnp.bfloat16)
    h0 = jnp.zeros((b, hsz), jnp.bfloat16)
    c0 = jnp.zeros((b, hsz), jnp.bfloat16)
    bh = jnp.zeros((4 * hsz,), jnp.bfloat16)

    def scan_path(gx, wh):
        step = rnn_mod._cell_step("lstm", hsz)
        (hT, cT), ys = jax.lax.scan(
            lambda c, g: step(c, g, wh, bh), (h0, c0), gx)
        return jnp.sum(ys.astype(jnp.float32))

    def pallas_path(gx, wh):
        ys, hT, cT = pk.lstm_layer(gx, wh, h0, c0)
        return jnp.sum(ys.astype(jnp.float32))

    txt_a = lower_text(lambda gx, wh: (scan_path(gx, wh),
                                       jax.grad(scan_path, (0, 1))(gx, wh)),
                       gx, wh)
    txt_b = lower_text(lambda gx, wh: (pallas_path(gx, wh),
                                       jax.grad(pallas_path, (0, 1))(gx, wh)),
                       gx, wh)

    whh_bytes = 4 * hsz * hsz * it
    per_step_scan = whh_bytes + b * hsz * (4 + 1 + 2) * it
    per_step_pallas = b * 4 * hsz * it * 2 + b * hsz * (2 * it + 4)
    body = _header("Fused Pallas LSTM layer (retroactive — round-5 change)")
    body += """## Segment

Word-LM layer shape T=%d B=%d H=%d bf16, fwd + (gx, w_hh) grads.
Variant A: `lax.scan` over timesteps (`ops/rnn.py` fallback). Variant B:
`pallas_kernels.lstm_layer` (grid = time axis, w_hh VMEM-resident;
interpret-mode lowering on CPU).

## Analytic per-step HBM traffic

| | A: lax.scan | B: fused kernel |
|---|---|---|
| w_hh read | %s MB EVERY step (%.1f MB x %d steps) | once (VMEM-resident) |
| per-step activation traffic | ~%s KB | ~%s KB (gx in, ys/c/gates out) |
| whole-layer fwd HBM | ~%s MB | ~%s MB |

Measured basis: BENCH_local_r04_lstm recorded 5.3%% MFU for the scan
path — each step re-reads the %.1f MB recurrent weight and launches a
tiny (32x650)x(650x2600) matmul. The fused kernel leaves only the
per-step slices; the backward's dW_hh collapses to ONE (T*B, H)x(T*B, 4H)
MXU matmul. A-B capture row: `lstm` bench with `MXTPU_PALLAS_LSTM=0/1`
(`lstm_scan` row in bench_capture.sh).

## HLO opcode histogram (fwd+grad)

""" % (t, b, hsz,
       "%.1f" % (whh_bytes / 1e6), whh_bytes / 1e6, t,
       "%.0f" % ((per_step_scan - whh_bytes) / 1e3),
       "%.0f" % (per_step_pallas / 1e3),
       "%.1f" % (t * per_step_scan / 1e6),
       "%.1f" % ((whh_bytes + t * per_step_pallas) / 1e6),
       whh_bytes / 1e6)
    body += hist_diff_table(hlo_histogram(txt_a), hlo_histogram(txt_b),
                            "A scan", "B fused")
    body += "\n"
    write_md("lstm_fused", body)


# ---------------------------------------------------------------------------
# Segment: INT8 offline-quantized conv (retroactive, round-5 change)
# ---------------------------------------------------------------------------

def seg_int8_quant():
    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax import lax

    n, hw, cin, cout, k = 8, 28, 128, 128, 3
    rng = np.random.RandomState(0)
    xb = jnp.asarray(rng.randn(n, hw, hw, cin), jnp.bfloat16)
    wb = jnp.asarray(rng.randn(cout, k, k, cin) * 0.05, jnp.bfloat16)
    xi = jnp.asarray(rng.randint(-127, 128, (n, hw, hw, cin)), jnp.int8)
    wi = jnp.asarray(rng.randint(-127, 128, (cout, k, k, cin)), jnp.int8)
    dn = lax.conv_dimension_numbers(xb.shape, wb.shape,
                                    ("NHWC", "OHWI", "NHWC"))

    def conv_bf16(x, w):
        return lax.conv_general_dilated(x, w, (1, 1), [(1, 1), (1, 1)],
                                        dimension_numbers=dn)

    def conv_int8(x, w):
        acc = lax.conv_general_dilated(
            x, w, (1, 1), [(1, 1), (1, 1)], dimension_numbers=dn,
            preferred_element_type=jnp.int32)
        # deployed epilogue: static-scale requantize (tools/int8_probe.py)
        return jnp.clip(jnp.round(acc.astype(jnp.float32) * 7.3e-4),
                        -127, 127).astype(jnp.int8)

    txt_a = lower_text(conv_bf16, xb, wb)
    txt_b = lower_text(conv_int8, xi, wi)

    act = n * hw * hw * cin
    wbytes = cout * k * k * cin
    body = _header("INT8 offline weight quantization (retroactive — "
                   "round-5 change)")
    body += """## Segment

`conv3x3` %dx%dx%dx%d -> %d, NHWC. Variant A: bf16 in/out. Variant B:
int8 in, int32 accumulate, static-scale requantize to int8 (the deployed
`score_int8` epilogue; weights quantized OFFLINE by
`contrib/quantization.quantize_params`, so the step never re-reads fp32
weights).

## Analytic HBM bytes

| tensor | A bf16 | B int8 |
|---|---|---|
| activation in | %s MB | %s MB (x0.5) |
| weight | %s KB | %s KB (x0.5, loaded from the offline int8 param) |
| activation out | %s MB | %s MB (int8 after requantize) |

The int8 path halves every HBM stream; the round-4 probe
(`INT8_PROBE`/`conv_probe_r04.jsonl`, re-armed in bench_capture.sh)
answers whether the v5-lite MXU runs int8 matmuls at >= bf16 rate — if
yes, `score_int8` combines both wins. The wraparound in the probe's
carry-perturbation (127+1 -> -128) was fixed this round (XOR
perturbation, tools/int8_probe.py), so the int8 and bf16 loops now run
on the same weight distribution.

## HLO opcode histogram

""" % (n, hw, hw, cin, cout,
       "%.2f" % (act * 2 / 1e6), "%.2f" % (act / 1e6),
       "%.0f" % (wbytes * 2 / 1e3), "%.0f" % (wbytes / 1e3),
       "%.2f" % (n * hw * hw * cout * 2 / 1e6),
       "%.2f" % (n * hw * hw * cout / 1e6))
    body += hist_diff_table(hlo_histogram(txt_a), hlo_histogram(txt_b),
                            "A bf16", "B int8")
    body += "\n"
    write_md("int8_quant", body)


SEGMENTS = {
    "conv_epilogue": seg_conv_epilogue,
    "stem_s2d": seg_stem_s2d,
    "lstm_fused": seg_lstm_fused,
    "int8_quant": seg_int8_quant,
}


def main(argv):
    names = argv or sorted(SEGMENTS)
    for name in names:
        if name not in SEGMENTS:
            raise SystemExit("unknown segment %r (have: %s)"
                             % (name, ", ".join(sorted(SEGMENTS))))
        SEGMENTS[name]()


if __name__ == "__main__":
    main(sys.argv[1:])
