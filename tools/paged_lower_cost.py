#!/usr/bin/env python3
"""What `paged_attention` costs a program's set-up, with no chip: seconds to
trace, to lower for `tpu` and to compile for a *described* TPU v5e the
function `o = o + paged_attention(o, k_i, v_i, tables, lengths)` over N
pools, a decode bucket at a time, at the benchmark cells' shapes; and the
kernel body's equation count. The counts and the seconds are the CPU
host's and the chip's compiler's: they size `setup_trace_lower_s` and
`setup_backend_compile_s`, they are no device time.

    JAX_PLATFORMS=cpu python tools/paged_lower_cost.py
    JAX_PLATFORMS=cpu python tools/paged_lower_cost.py \\
        --against /path/to/other/tree/mxnet_tpu/ops/pallas_kernels.py

`--against` loads a second copy of the kernels' module (another commit's)
and prints its rows beside this tree's. A process's first lowering pays
jax's and Mosaic's imports: a throwaway shape goes first.
"""
import argparse
import importlib.util
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

# name: heads, KV heads, head size, pool pages, page size, pages a
# sequence, dtype, decode buckets (chipbench/configs, chipbench/traffic)
CELLS = {
    "gpt2s_chat_open": (12, 12, 64, 3072, 16, 44, "float32", (16, 32, 64)),
    "gpt2s_docs_closed": (12, 12, 64, 3072, 16, 61, "float32", (16, 32)),
    "lfm2_reason_closed": (32, 8, 64, 8192, 16, 64, "bfloat16",
                           (32, 64, 128)),
}


def load(path, name):
    spec = importlib.util.spec_from_file_location("mxnet_tpu.ops." + name,
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        for sub in (v if isinstance(v, (list, tuple)) else [v]):
            sub = getattr(sub, "jaxpr", sub)
            if hasattr(sub, "eqns"):
                yield sub


def _equations(jaxpr, inside):
    """Equations of a jaxpr and of every sub-jaxpr that lie inside a
    `pallas_call` (`inside`: this jaxpr already does)."""
    total = 0
    for eqn in jaxpr.eqns:
        kernel = inside or eqn.primitive.name == "pallas_call"
        total += inside + sum(_equations(sub, kernel)
                              for sub in _sub_jaxprs(eqn))
    return total


def kernel_equations(fn, args):
    """Equations inside the `pallas_call`s of fn's jaxpr (0: no kernel)."""
    import jax

    return _equations(jax.make_jaxpr(fn)(*args).jaxpr, False)


def shapes(cell, bucket, layers, sharding=None):
    """The stacked function's arguments for a cell (a name of CELLS, or
    such a tuple)."""
    import jax
    import jax.numpy as jnp

    h, kv, d, pages, ps, maxp, dtype, _ = CELLS.get(cell, cell)
    cp = -(-kv * d // 128) * 128

    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dt), sharding=sharding)

    pool = s((pages, ps, cp), dtype)
    return (s((bucket, h, d), dtype), [pool] * layers, [pool] * layers,
            s((bucket, maxp), "int32"), s((bucket,), "int32"))


def stacked(mod, kv):
    def fn(o, ks, vs, tables, lengths):
        for k, v in zip(ks, vs):
            o = o + mod.paged_attention(o, k, v, tables, lengths,
                                        kv_heads=kv)
        return o

    return fn


def measure(mod, cell, bucket, layers, sharding):
    import jax

    fn = jax.jit(stacked(mod, CELLS.get(cell, cell)[1]))
    args = shapes(cell, bucket, layers, sharding)
    t0 = time.perf_counter()
    traced = fn.trace(*args)
    t1 = time.perf_counter()
    lowered = traced.lower()
    t2 = time.perf_counter()
    compiled = lowered.compile()
    t3 = time.perf_counter()
    assert "tpu_custom_call" in compiled.as_text(), "the jnp path was taken"
    return {"trace_s": t1 - t0, "lower_s": t2 - t1, "compile_s": t3 - t2}


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--against", help="another tree's pallas_kernels.py")
    ap.add_argument("--layers", type=int, nargs="+", default=[1, 12])
    ap.add_argument("--cells", nargs="+", default=sorted(CELLS))
    opts = ap.parse_args()

    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    from mxnet_tpu.ops import pallas_kernels as here

    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    sharding = jax.sharding.SingleDeviceSharding(topo.devices[0])
    mods = {"this": here}
    if opts.against:
        mods["against"] = load(opts.against, "_against_pallas_kernels")
    for mod in mods.values():
        mod._use_interpret = lambda: False
    os.environ["MXTPU_PALLAS_DECODE"] = "1"
    for mod in mods.values():           # the process's first lowering
        measure(mod, (2, 2, 64, 64, 16, 4, "float32", (8,)), 8, 1, sharding)
    for cell in opts.cells:
        kv = CELLS[cell][1]
        for bucket in CELLS[cell][-1]:
            for name, mod in mods.items():
                row = {"cell": cell, "bucket": bucket, "tree": name,
                       "kernel_equations": kernel_equations(
                           stacked(mod, kv), shapes(cell, bucket, 1))}
                for layers in opts.layers:
                    got = measure(mod, cell, bucket, layers, sharding)
                    row["layers_%d" % layers] = {
                        k: round(v, 3) for k, v in got.items()}
                    row["layers_%d" % layers]["sum_s"] = round(
                        sum(got.values()), 3)
                print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
