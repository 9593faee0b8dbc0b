"""INT8 vs bf16 kernel probe: does int8 pay on this chip, per shape?

Answers the round-4 finding that the int8 scoring path is SLOWER than
bf16 (BENCH_local_r04_score_int8: 3502 vs 5644 img/s). Three hypotheses:
(a) XLA doesn't lower s8xs8->s32 convs to the MXU int8 path and upcasts
instead, (b) the conv itself is fast but the requantize epilogue
(scale/round/clip/cast between layers) breaks fusion, (c) overhead
elsewhere. This probe times, per ResNet-50 bulk shape:

  - bf16 conv            (the fp baseline the quantized path must beat)
  - int8 conv -> int32   (raw quantized kernel)
  - int8 conv + requantize epilogue -> int8 (the deployed pattern)

and the same trio for a big FC-shaped dot. Methodology identical to
tools/conv_probe.py: chained fori_loop with a full-reduce carry, one RTT
subtracted (see docs/perf_notes.md "Timing methodology").

Run on the chip: python tools/int8_probe.py   (writes JSONL to stdout)
"""
import json
import os
import time

BATCH = int(os.environ.get("MXTPU_PROBE_BATCH", 256))
ITERS = int(os.environ.get("MXTPU_PROBE_ITERS", 200))

# (cin, cout, hw, k, stride) — ResNet-50 bulk shapes (conv_probe.py list)
SHAPES = [
    (64, 64, 56, 3, 1),
    (64, 256, 56, 1, 1),
    (128, 128, 28, 3, 1),
    (256, 256, 14, 3, 1),
    (512, 512, 7, 3, 1),
    (256, 512, 28, 1, 2),
]

_RTT = None


def _rtt():
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
    float(loop(*args))
    t0 = time.perf_counter()
    float(loop(*args))
    return max(time.perf_counter() - t0 - _rtt(), 1e-9) / ITERS


def main():
    import jax

    import jax.numpy as jnp
    from jax import lax

    def chain(val):
        return jnp.sum(val, dtype=jnp.float32) * 1e-30

    def probe_conv(cin, cout, hw, k, s):
        pad = k // 2
        ho = hw // s
        flops = 2 * BATCH * cout * ho * ho * cin * k * k
        xs = (BATCH, cin, hw, hw)
        ws = (cout, cin, k, k)
        key = jax.random.PRNGKey(0)
        xf = jax.random.normal(key, xs, jnp.float32)
        wf = jax.random.normal(jax.random.PRNGKey(1), ws, jnp.float32)
        xb, wb = xf.astype(jnp.bfloat16), wf.astype(jnp.bfloat16)
        xi = jnp.clip(jnp.round(xf * 20), -127, 127).astype(jnp.int8)
        wi = jnp.clip(jnp.round(wf * 20), -127, 127).astype(jnp.int8)

        def conv(xx, ww, pet=None):
            kw = {"preferred_element_type": pet} if pet is not None else {}
            return lax.conv_general_dilated(
                xx, ww, window_strides=(s, s),
                padding=[(pad, pad), (pad, pad)],
                dimension_numbers=("NCHW", "OIHW", "NCHW"), **kw)

        @jax.jit
        def bf16_loop(x, w):
            def body(_, c):
                return chain(conv(x, w + c.astype(w.dtype)))
            return lax.fori_loop(0, ITERS, body, jnp.zeros((), jnp.float32))

        @jax.jit
        def int8_loop(x, w):
            def body(_, c):
                # perturb via the int8 weight: XOR with a 0/1 derived
                # from the carry — unlike `w + bit`, XOR cannot wrap int8
                # (127+1 -> -128 flipped perturbed weights to the extreme,
                # so the int8 and bf16 loops computed on slightly different
                # weight distributions)
                wp = w ^ (c * 1e30).astype(jnp.int8)  # c ~ 1e-30 -> 0 or 1
                return chain(conv(x, wp, jnp.int32))
            return lax.fori_loop(0, ITERS, body, jnp.zeros((), jnp.float32))

        @jax.jit
        def int8_rq_loop(x, w):
            def body(_, c):
                wp = w ^ (c * 1e30).astype(jnp.int8)
                acc = conv(x, wp, jnp.int32)
                # deployed epilogue: static-scale requantize to int8
                q = jnp.clip(jnp.round(acc.astype(jnp.float32) * 7.3e-4),
                             -127, 127).astype(jnp.int8)
                return chain(q)
            return lax.fori_loop(0, ITERS, body, jnp.zeros((), jnp.float32))

        row = {"cin": cin, "cout": cout, "hw": hw, "k": k, "s": s}
        for name, loop, a, b in (("bf16", bf16_loop, xb, wb),
                                 ("int8", int8_loop, xi, wi),
                                 ("int8_rq", int8_rq_loop, xi, wi)):
            try:
                dt = _timed(loop, a, b)
                row[name + "_tflops"] = round(flops / dt / 1e12, 1)
            except Exception as e:  # noqa: BLE001 — record, keep probing
                row[name + "_error"] = str(e)[:120]
        print(json.dumps(row), flush=True)

    def probe_dot(m, kk, n):
        flops = 2 * m * kk * n
        key = jax.random.PRNGKey(2)
        af = jax.random.normal(key, (m, kk), jnp.float32)
        bf = jax.random.normal(jax.random.PRNGKey(3), (kk, n), jnp.float32)
        ab, bb = af.astype(jnp.bfloat16), bf.astype(jnp.bfloat16)
        ai = jnp.clip(jnp.round(af * 20), -127, 127).astype(jnp.int8)
        bi = jnp.clip(jnp.round(bf * 20), -127, 127).astype(jnp.int8)

        def loops(pet):
            @jax.jit
            def loop(a, b):
                def body(_, c):
                    if pet is jnp.int32:  # int8 operands: XOR, no wraparound
                        bp = b ^ (c * 1e30).astype(b.dtype)
                    else:
                        bp = b + c.astype(b.dtype)
                    kw = {"preferred_element_type": pet} if pet else {}
                    return chain(jnp.dot(a, bp, **kw))
                return lax.fori_loop(0, ITERS, body,
                                     jnp.zeros((), jnp.float32))
            return loop

        row = {"dot": [m, kk, n]}
        for name, loop, a, b in (("bf16", loops(None), ab, bb),
                                 ("int8", loops(jnp.int32), ai, bi)):
            try:
                dt = _timed(loop, a, b)
                row[name + "_tflops"] = round(flops / dt / 1e12, 1)
            except Exception as e:  # noqa: BLE001
                row[name + "_error"] = str(e)[:120]
        print(json.dumps(row), flush=True)

    dev = jax.devices()[0]
    print(json.dumps({"device": getattr(dev, "device_kind", str(dev)),
                      "batch": BATCH, "iters": ITERS}), flush=True)
    probe_dot(4096, 4096, 4096)
    for shp in SHAPES:
        probe_conv(*shp)


if __name__ == "__main__":
    main()
