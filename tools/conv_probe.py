"""Per-shape conv backward probe: measure fwd / dgrad / wgrad TFLOP/s for
the ResNet-50 conv shapes in NCHW vs NHWC dimension numbers on the real
chip, to find where backward MFU goes and whether logical layout matters.

Timing methodology: each measurement runs ITERS kernel executions inside a
single jitted `lax.fori_loop` whose carry feeds a numerically-negligible
scalar (scaled 1e-30; exact *0 would constant-fold) from each iteration's
output into one of the next iteration's operands. The data dependency
stops XLA from overlapping/hoisting iterations, so one wall-clock
measurement of the loop divides into per-iteration time. A free-running
Python loop (the previous version) measured only dispatch throughput and
reported impossible TFLOP/s.

Which operand carries the chain matters:
- fwd / dgrad chain through the *weight* (tiny, free to perturb);
- wgrad's operands are the input and the cotangent, so the chain goes
  through a freshly-filled cotangent; the fill costs one HBM pass over
  the output, measured separately (`fill` loop) and subtracted.
"""
import json
import os
import time

BATCH = int(os.environ.get("MXTPU_PROBE_BATCH", 256))
ITERS = int(os.environ.get("MXTPU_PROBE_ITERS", 400))

# (cin, cout, hw, k, stride) — representative ResNet-50 bulk shapes
SHAPES = [
    (3, 64, 224, 7, 2),     # stem
    (64, 64, 56, 3, 1),     # layer1 3x3
    (64, 256, 56, 1, 1),    # layer1 expand
    (128, 128, 28, 3, 1),   # layer2 3x3
    (256, 256, 14, 3, 1),   # layer3 3x3 (deepest bulk)
    (512, 512, 7, 3, 1),    # layer4 3x3
    (256, 512, 28, 1, 2),   # downsample 1x1/2
]


_RTT = None


def _rtt():
    """One dispatch+fetch round trip: every timing below fetches its carry
    scalar to the host and subtracts this baseline."""
    global _RTT
    if _RTT is None:
        import jax
        import jax.numpy as jnp

        tiny = jax.jit(lambda v: v + 1.0)
        z = jnp.zeros((), jnp.float32)
        float(tiny(z))
        samples = []
        for _ in range(5):
            t0 = time.perf_counter()
            float(tiny(z))
            samples.append(time.perf_counter() - t0)
        _RTT = min(samples)
        print(json.dumps({"rtt_ms": round(_RTT * 1e3, 3)}), flush=True)
    return _RTT


def _timed(loop, *args):
    float(loop(*args))  # compile + warm; fetch forces real completion
    t0 = time.perf_counter()
    float(loop(*args))
    dt = time.perf_counter() - t0
    return max(dt - _rtt(), 1e-9) / ITERS


def main():
    import jax
    import jax.numpy as jnp
    from jax import lax

    def chain(val):
        # full reduce: every output element feeds the carry, so XLA cannot
        # narrow the producing kernel to a single-element slice (a [0]
        # element chain let the simplifier collapse each conv to one
        # output-pixel dot product). The reduce fuses into the kernel's
        # epilogue; *1e-30 keeps the perturbation numerically nil without
        # the exact-zero constant fold.
        return jnp.sum(val, dtype=jnp.float32) * 1e-30

    for (cin, cout, hw, k, s) in SHAPES:
        pad = k // 2
        ho = hw // s
        flops = 2 * BATCH * cout * ho * ho * cin * k * k
        row = {"cin": cin, "cout": cout, "hw": hw, "k": k, "s": s,
               "gflops": round(flops / 1e9, 1)}
        # weight specs mirror the framework's _conv_dnums (ops/nn.py):
        # NCHW carries OIHW weights, NHWC carries OHWI — probing the
        # exact dimension numbers the zoo's layout= path emits
        for layout, kspec in {"NCHW": "OIHW", "NHWC": "OHWI"}.items():
            dn = lax.conv_dimension_numbers(
                (1, 1, 1, 1), (1, 1, 1, 1), (layout, kspec, layout))
            if layout == "NCHW":
                xs = (BATCH, cin, hw, hw)
                os_ = (BATCH, cout, ho, ho)
                ws = (cout, cin, k, k)
            else:
                xs = (BATCH, hw, hw, cin)
                os_ = (BATCH, ho, ho, cout)
                ws = (cout, k, k, cin)
            x = jax.random.normal(jax.random.PRNGKey(0), xs,
                                  jnp.float32).astype(jnp.bfloat16)
            w = jax.random.normal(jax.random.PRNGKey(1), ws,
                                  jnp.float32).astype(jnp.bfloat16)

            def conv(xx, ww, dn=dn):
                return lax.conv_general_dilated(
                    xx, ww, window_strides=(s, s),
                    padding=[(pad, pad), (pad, pad)],
                    dimension_numbers=dn)

            @jax.jit
            def fwd_loop(x, w):
                def body(_, c):
                    return chain(conv(x, w + c.astype(w.dtype)))
                return lax.fori_loop(0, ITERS, body, jnp.zeros((), jnp.float32))

            @jax.jit
            def dgrad_loop(x, w):
                # d/dx of sum(conv): cotangent is constant ones (hoisted);
                # the dgrad conv runs with the chained weight each iteration
                # and the unused forward conv is DCE'd — this times dgrad
                # alone.
                def body(_, c):
                    g = jax.grad(
                        lambda xx: conv(xx, w + c.astype(w.dtype))
                        .astype(jnp.float32).sum())(x)
                    return chain(g)
                return lax.fori_loop(0, ITERS, body, jnp.zeros((), jnp.float32))

            @jax.jit
            def wgrad_loop(x, w):
                # wgrad contracts input with cotangent; the chain must ride
                # the cotangent (input is loop-invariant, weight is not an
                # operand). Fill cost measured by fill_loop and subtracted.
                def body(_, c):
                    ct = jnp.full(os_, 1, jnp.bfloat16) + c.astype(jnp.bfloat16)
                    _, pull = jax.vjp(lambda ww: conv(x, ww), w)
                    gw, = pull(ct)
                    return chain(gw)
                return lax.fori_loop(0, ITERS, body, jnp.zeros((), jnp.float32))

            @jax.jit
            def fill_loop(x, w):
                def body(_, c):
                    ct = jnp.full(os_, 1, jnp.bfloat16) + c.astype(jnp.bfloat16)
                    return chain(ct)
                return lax.fori_loop(0, ITERS, body, jnp.zeros((), jnp.float32))

            # dgrad REWRITE candidate (the VERDICT escalation path): XLA
            # lowers the autodiff dgrad as an lhs-dilated conv; this
            # variant materializes the zero-stuffing explicitly and runs a
            # PLAIN stride-1 conv over it. Only meaningful for s > 1 (at
            # s=1 the two are the same program). NCHW only (the rewrite
            # decision rides whichever layout wins the base measurements).
            dgrad_rw_loop = None
            if s > 1 and layout == "NCHW":
                ho_, wo_ = hw // s, hw // s

                def upsample(ct):
                    b_ = ct.shape[0]
                    z = jnp.zeros((b_, cout, ho_, s, wo_, s), ct.dtype)
                    z = z.at[:, :, :, 0, :, 0].set(ct)
                    return z.reshape(b_, cout, ho_ * s, wo_ * s)

                def dgrad_rewrite(ct, ww):
                    # dx = up(ct) (*) rot180(w)^T, stride 1. The
                    # zero-stuffed map has length Ho*s == H (trailing
                    # s-1 zeros included), so the plain conv needs
                    # lo = k-1-pad and hi = pad to land on exactly H:
                    # H + lo + hi - k + 1 = H.
                    w_rot = jnp.flip(ww, axis=(-1, -2)).transpose(
                        (1, 0, 2, 3))
                    lo = k - 1 - pad
                    return lax.conv_general_dilated(
                        upsample(ct), w_rot, (1, 1),
                        padding=[(lo, pad), (lo, pad)],
                        dimension_numbers=dn)

                # correctness gate at the real shape: the rewrite must
                # match the autodiff dgrad before its timing can count.
                # bf16 accumulation order differs between the two
                # programs, so the tolerance is RELATIVE to the output
                # magnitude (an absolute 1e-2 is below one bf16 ULP at
                # the stem's ~30-magnitude outputs and would spuriously
                # reject a correct rewrite)
                ct_probe = jax.random.normal(
                    jax.random.PRNGKey(2), os_, jnp.float32) \
                    .astype(jnp.bfloat16)
                ref_dx = jax.jit(lambda c: jax.vjp(
                    lambda xx: conv(xx, w), x)[1](c)[0])(ct_probe)
                got_dx = jax.jit(dgrad_rewrite)(ct_probe, w)
                diff = (ref_dx - got_dx).astype(jnp.float32)
                scale = float(jnp.max(jnp.abs(
                    ref_dx.astype(jnp.float32)))) or 1.0
                err = float(jnp.max(jnp.abs(diff))) / scale
                if err > 0.05:
                    row.setdefault("rewrite_error", {})[layout] = err
                else:
                    @jax.jit
                    def dgrad_rw_loop(x_, w_):
                        def body(_, c):
                            ct = jnp.full(os_, 1, jnp.bfloat16) \
                                + c.astype(jnp.bfloat16)
                            return chain(dgrad_rewrite(ct, w_))
                        return lax.fori_loop(0, ITERS, body,
                                             jnp.zeros((), jnp.float32))

            dt_f = _timed(fwd_loop, x, w)
            dt_d = _timed(dgrad_loop, x, w)
            dt_fill = _timed(fill_loop, x, w)
            dt_w = max(_timed(wgrad_loop, x, w) - dt_fill, 1e-9)
            row[layout] = {
                "fwd_tflops": round(flops / dt_f / 1e12, 1),
                "dgrad_tflops": round(flops / dt_d / 1e12, 1),
                "wgrad_tflops": round(flops / dt_w / 1e12, 1),
                "fwd_ms": round(dt_f * 1e3, 3),
                "dgrad_ms": round(dt_d * 1e3, 3),
                "wgrad_ms": round(dt_w * 1e3, 3),
                "fill_ms": round(dt_fill * 1e3, 3),
            }
            if dgrad_rw_loop is not None:
                dt_rw = max(_timed(dgrad_rw_loop, x, w) - dt_fill, 1e-9)
                row[layout]["dgrad_rewrite_ms"] = round(dt_rw * 1e3, 3)
                row[layout]["dgrad_rewrite_tflops"] = round(
                    flops / dt_rw / 1e12, 1)
        print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
