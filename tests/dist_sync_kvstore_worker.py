"""Worker body for the multi-process dist kvstore test (reference:
tests/nightly/dist_sync_kvstore.py — push/pull/row_sparse/compression
numerics across real localhost processes).

Run via tools/launch.py (sets MXTPU_COORDINATOR / MXTPU_NUM_WORKERS /
MXTPU_PROCESS_ID); each process asserts the cross-rank numerics and prints
one OK line the parent test greps for."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# the process group must exist before the first jax computation (package
# import is computation-free) — init_process_group resolves rank/size from
# whichever launcher spawned us (MXTPU_*, DMLC_*, OMPI_*/PMI_*, SLURM_*)
from mxnet_tpu.parallel import collectives  # noqa: E402

collectives.init_process_group()

import numpy as np  # noqa: E402

import mxnet_tpu as mx  # noqa: E402


def main():
    collectives.init_process_group()
    kv = mx.kv.create("dist_sync")
    n = kv.num_workers
    r = kv.rank
    assert n == int(os.environ["MXTPU_NUM_WORKERS"]), (n, os.environ)

    # --- dense push: store becomes the cross-rank sum -------------------
    kv.init("dense", mx.nd.zeros((4, 3)))
    kv.push("dense", mx.nd.full((4, 3), r + 1.0))
    out = mx.nd.zeros((4, 3))
    kv.pull("dense", out=out)
    expect = sum(i + 1.0 for i in range(n))
    np.testing.assert_allclose(out.asnumpy(), expect, rtol=1e-6)

    # --- multi-device-style grouped push (list of values) ---------------
    kv.init("grp", mx.nd.zeros((2,)))
    kv.push("grp", [mx.nd.full((2,), r + 1.0), mx.nd.full((2,), r + 1.0)])
    out = mx.nd.zeros((2,))
    kv.pull("grp", out=out)
    np.testing.assert_allclose(out.asnumpy(), 2 * expect, rtol=1e-6)

    # --- row_sparse_pull -------------------------------------------------
    kv.init("rsp", mx.nd.zeros((6, 2)))
    grad = np.zeros((6, 2), np.float32)
    grad[r::2] = r + 1.0   # disjoint rows per rank (n=2)
    kv.push("rsp", mx.nd.array(grad))
    rows = mx.nd.array(np.array([0, 1, 5], np.int64), dtype="int64")
    sparse_out = mx.nd.zeros((3, 2))
    kv.row_sparse_pull("rsp", out=sparse_out, row_ids=rows)
    got = sparse_out.asnumpy()
    dense = np.zeros((6, 2), np.float32)
    for i in range(n):
        g = np.zeros((6, 2), np.float32)
        g[i::2] = i + 1.0
        dense += g
    np.testing.assert_allclose(got[0], dense[0], rtol=1e-6)
    np.testing.assert_allclose(got[2], dense[5], rtol=1e-6)

    # --- 2-bit compression with error feedback across ranks -------------
    kv2 = mx.kv.create("dist_sync")
    kv2.set_gradient_compression({"type": "2bit", "threshold": 0.5})
    kv2.init("c", mx.nd.zeros((3,)))
    # rank r pushes 0.3: below threshold -> nothing sent first push,
    # residual flushes on the second push (0.6 >= 0.5 per rank)
    kv2.push("c", mx.nd.full((3,), 0.3))
    out = mx.nd.zeros((3,))
    kv2.pull("c", out=out)
    np.testing.assert_allclose(out.asnumpy(), 0.0, atol=1e-7)
    kv2.push("c", mx.nd.full((3,), 0.3))
    kv2.pull("c", out=out)
    np.testing.assert_allclose(out.asnumpy(), 0.5 * n, rtol=1e-6)

    # --- barrier ---------------------------------------------------------
    collectives.barrier()
    print("DIST_KV_OK rank=%d/%d" % (r, n), flush=True)


if __name__ == "__main__":
    main()
