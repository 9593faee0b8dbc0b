#!/usr/bin/env python
"""Communication micro-benchmark (reference: tools/bandwidth/measure.py —
times kvstore push+pull of model-sized gradient arrays across devices).

Two layers are measured, mirroring how the reference separates kvstore
strategy from raw link speed:

1. ``kvstore`` mode — `kv.push` + `kv.pull` per parameter of a model-zoo
   network (the reference's default workload: resnet gradients), through
   the store type under test (`local` / `device`), optionally with 2-bit
   gradient compression (`--gc-type 2bit`).
2. ``collective`` mode — raw XLA collectives (`psum`, `all_gather`,
   `reduce_scatter`, `ppermute`) over the device mesh, the primitives the
   TPU kvstore lowers to (SURVEY §5.8: the NCCL/ps-lite replacement).

Reported number is allreduce algorithmic bandwidth
``2 * bytes * (n-1)/n / time`` per device (the standard NCCL-tests
accounting), so results are comparable across device counts.

Run on the 8-virtual-device CPU mesh (default when no accelerator):
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python tools/bandwidth.py --mode collective --sizes-mb 1,16,64
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def parse_args():
    p = argparse.ArgumentParser(description="kvstore/collective bandwidth "
                                "benchmark (reference tools/bandwidth)")
    p.add_argument("--mode", choices=["kvstore", "collective"],
                   default="kvstore")
    p.add_argument("--network", type=str, default="resnet50_v1",
                   help="model-zoo network whose param shapes form the "
                        "kvstore workload (reference --network)")
    p.add_argument("--kv-store", type=str, default="device",
                   help="kvstore type to benchmark (reference --kv-store)")
    p.add_argument("--num-batches", type=int, default=5)
    p.add_argument("--gc-type", type=str, default="none",
                   help="gradient compression: none|2bit (reference "
                        "--gc-type)")
    p.add_argument("--ndev", type=int, default=2,
                   help="kvstore mode: per-key device-copy count pushed "
                        "per batch (the reference's --gpus list length)")
    p.add_argument("--test-results", type=int, default=1,
                   help="verify push+pull numerics against a local sum")
    p.add_argument("--sizes-mb", type=str, default="4,16,64",
                   help="collective mode: comma list of buffer sizes (MB)")
    p.add_argument("--json", action="store_true",
                   help="emit one JSON line per measurement")
    return p.parse_args()


def _algbw(nbytes, n_dev, dt):
    """allreduce algorithmic bandwidth per device, GB/s."""
    if dt <= 0:
        return float("inf")
    return 2.0 * nbytes * (n_dev - 1) / n_dev / dt / 1e9


def bench_kvstore(args):
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo import vision

    net_fn = getattr(vision, args.network, None)
    if net_fn is None:
        raise SystemExit("unknown network %r (model zoo exports: %s)"
                         % (args.network, [n for n in dir(vision)
                                           if not n.startswith("_")][:20]))
    net = net_fn()
    net.initialize(mx.init.Xavier())
    x = mx.nd.zeros((1, 3, 224, 224))
    net(x)  # materialize deferred shapes

    kv = mx.kv.create(args.kv_store)
    if args.gc_type != "none":
        kv.set_gradient_compression({"type": args.gc_type})

    params = [(name, p.data()) for name, p in
              sorted(net.collect_params().items()) if p.grad_req != "null"]
    shapes = [tuple(v.shape) for _, v in params]
    total_bytes = sum(int(np.prod(s)) * 4 for s in shapes)
    rng = np.random.RandomState(0)
    grads = [mx.nd.array(rng.uniform(-1, 1, s).astype(np.float32))
             for s in shapes]
    for i, (name, _v) in enumerate(params):
        kv.init(i, mx.nd.zeros(shapes[i]))

    # each key is pushed as a list of `ndev` per-device copies — kvstore
    # sums the group and replaces the stored value (reference push
    # semantics); pull broadcasts it back. This is one allreduce per param.
    ndev = args.ndev
    results = []
    for batch in range(args.num_batches):
        t0 = time.perf_counter()
        for i in range(len(params)):
            kv.push(i, [grads[i]] * ndev)
        outs = [mx.nd.zeros(shapes[i]) for i in range(len(params))]
        for i in range(len(params)):
            kv.pull(i, out=outs[i])
        for o in outs:
            o.wait_to_read()
        dt = time.perf_counter() - t0
        results.append(dt)
        row = {"batch": batch, "time_s": round(dt, 4),
               "mb": round(total_bytes / 1e6, 2), "ndev": ndev,
               "gbps": round(_algbw(total_bytes, ndev, dt), 3)}
        print(json.dumps(row) if args.json else
              "batch %(batch)d: %(mb).1f MB x%(ndev)d pushed+pulled in "
              "%(time_s).3fs (%(gbps).2f GB/s)" % row)

    if args.test_results and args.gc_type == "none":
        # stored value = sum of the ndev pushed copies (reference
        # tools/bandwidth/measure.py error check: pulled vs ndev * grad)
        got = outs[0].asnumpy()
        want = grads[0].asnumpy() * ndev
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
        print("numerics ok (stored = %d x grad)" % ndev)
    best = min(results)
    print("%s: %d params, %.1f MB, best %.3fs"
          % (args.kv_store, len(params), total_bytes / 1e6, best))


def bench_collective(args):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mxnet_tpu.parallel import make_mesh, named_sharding

    devs = jax.devices()
    n = len(devs)
    mesh = make_mesh([("dp", n)], devices=devs)
    from jax.sharding import PartitionSpec as P

    sh = named_sharding(mesh, P("dp"))
    repl = named_sharding(mesh, P())

    ops = {
        "psum": (lambda x: jax.lax.psum(x, "dp"), sh, repl),
        "all_gather": (lambda x: jax.lax.all_gather(x, "dp", tiled=True),
                       sh, repl),
        "reduce_scatter": (
            lambda x: jax.lax.psum_scatter(x, "dp", tiled=True), sh, sh),
        "ppermute": (lambda x: jax.lax.ppermute(
            x, "dp", [(i, (i + 1) % n) for i in range(n)]), sh, sh),
    }

    try:
        from jax import shard_map
    except ImportError:
        from jax.experimental.shard_map import shard_map

    for size_mb in (float(s) for s in args.sizes_mb.split(",")):
        nfloat = int(size_mb * 1e6 / 4)
        # divisible by n^2: shard_map splits by n, reduce_scatter again by n
        nfloat = max(n * n, nfloat - nfloat % (n * n))
        x = jnp.arange(nfloat, dtype=jnp.float32)
        nbytes = nfloat * 4
        for name, (fn, in_sh, out_sh) in ops.items():
            try:
                body = shard_map(fn, mesh=mesh, in_specs=P("dp"),
                                 out_specs=out_sh.spec, check_vma=False)
            except TypeError:  # pre-0.9 jax uses check_rep
                body = shard_map(fn, mesh=mesh, in_specs=P("dp"),
                                 out_specs=out_sh.spec, check_rep=False)
            f = jax.jit(body, in_shardings=in_sh, out_shardings=out_sh)
            xd = jax.device_put(x, in_sh)
            f(xd).block_until_ready()  # compile
            t0 = time.perf_counter()
            iters = 10
            for _ in range(iters):
                out = f(xd)
            out.block_until_ready()
            dt = (time.perf_counter() - t0) / iters
            row = {"collective": name, "mb": round(nbytes / 1e6, 2),
                   "n_dev": n, "time_ms": round(dt * 1e3, 3),
                   "algbw_gbps": round(_algbw(nbytes, n, dt), 3)}
            print(json.dumps(row) if args.json else
                  "%(collective)14s %(mb)8.1f MB x%(n_dev)d: "
                  "%(time_ms)8.3f ms  %(algbw_gbps)8.2f GB/s" % row)


def main():
    args = parse_args()
    if args.mode == "collective":
        bench_collective(args)
    else:
        bench_kvstore(args)


if __name__ == "__main__":
    main()
