"""Imperative flat C ABI (VERDICT r2 #5 — settle N14): drive the NDArray /
invoke-by-creator / autograd entry points of libmxtpu_capi.so through
ctypes exactly as a C host would, and compare against in-process Python.
A separate test compiles a real plain-C host against mxtpu_c_api.h to
prove the embedded-interpreter boot path."""
import ctypes
import os
import shutil
import subprocess
import sys
import sysconfig

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.lib import native


def _capi():
    lib = native.get_capi()
    if lib is None:
        pytest.skip("native toolchain unavailable (libmxtpu_capi build "
                    "failed)")
    c = ctypes
    # full argtypes: a bare int (e.g. `creators[i]`) passed where a handle
    # is expected would otherwise be truncated to 32 bits by ctypes'
    # default conversion — a segfault, not an error
    lib.MXGetLastError.restype = c.c_char_p
    lib.MXNDArrayCreateEx.argtypes = [
        c.POINTER(c.c_uint), c.c_uint, c.c_int, c.c_int, c.c_int, c.c_int,
        c.POINTER(c.c_void_p)]
    lib.MXNDArrayFree.argtypes = [c.c_void_p]
    lib.MXNDArraySyncCopyFromCPU.argtypes = [
        c.c_void_p, c.c_void_p, c.c_size_t]
    lib.MXNDArraySyncCopyToCPU.argtypes = [
        c.c_void_p, c.c_void_p, c.c_size_t]
    lib.MXNDArrayGetShape.argtypes = [
        c.c_void_p, c.POINTER(c.c_uint), c.POINTER(c.POINTER(c.c_uint))]
    lib.MXNDArrayGetDType.argtypes = [c.c_void_p, c.POINTER(c.c_int)]
    lib.MXNDArrayGetContext.argtypes = [
        c.c_void_p, c.POINTER(c.c_int), c.POINTER(c.c_int)]
    lib.MXNDArrayGetGrad.argtypes = [c.c_void_p, c.POINTER(c.c_void_p)]
    lib.MXSymbolGetAtomicSymbolName.argtypes = [
        c.c_void_p, c.POINTER(c.c_char_p)]
    lib.MXImperativeInvoke.argtypes = [
        c.c_void_p, c.c_int, c.POINTER(c.c_void_p), c.POINTER(c.c_int),
        c.POINTER(c.POINTER(c.c_void_p)), c.c_int,
        c.POINTER(c.c_char_p), c.POINTER(c.c_char_p)]
    lib.MXImperativeInvokeSpineFree.argtypes = [c.POINTER(c.c_void_p)]
    lib.MXAutogradMarkVariables.argtypes = [
        c.c_uint, c.POINTER(c.c_void_p), c.POINTER(c.c_uint),
        c.POINTER(c.c_void_p)]
    lib.MXAutogradBackward.argtypes = [
        c.c_uint, c.POINTER(c.c_void_p), c.POINTER(c.c_void_p), c.c_int]
    return lib


def _create(lib, arr):
    """NDArrayHandle from a numpy array (create + SyncCopyFromCPU)."""
    dtype_enum = {"float32": 0, "float64": 1, "float16": 2, "uint8": 3,
                  "int32": 4, "int8": 5, "int64": 6}[arr.dtype.name]
    shape = (ctypes.c_uint * arr.ndim)(*arr.shape)
    h = ctypes.c_void_p()
    rc = lib.MXNDArrayCreateEx(shape, arr.ndim, 1, 0, 0, dtype_enum,
                               ctypes.byref(h))
    assert rc == 0, lib.MXGetLastError().decode()
    buf = np.ascontiguousarray(arr)
    rc = lib.MXNDArraySyncCopyFromCPU(h, buf.ctypes.data, buf.size)
    assert rc == 0, lib.MXGetLastError().decode()
    return h


def _to_numpy(lib, h, shape, dtype=np.float32):
    out = np.empty(shape, dtype)
    n = int(np.prod(shape)) if shape else 1
    rc = lib.MXNDArraySyncCopyToCPU(h, out.ctypes.data, n)
    assert rc == 0, lib.MXGetLastError().decode()
    return out


def _creator(lib, name):
    n = ctypes.c_uint()
    arr = ctypes.POINTER(ctypes.c_void_p)()
    assert lib.MXSymbolListAtomicSymbolCreators(
        ctypes.byref(n), ctypes.byref(arr)) == 0
    for i in range(n.value):
        cname = ctypes.c_char_p()
        assert lib.MXSymbolGetAtomicSymbolName(
            arr[i], ctypes.byref(cname)) == 0
        if cname.value.decode() == name:
            return ctypes.c_void_p(arr[i])
    raise AssertionError("creator %s not found among %d ops"
                         % (name, n.value))


def _invoke(lib, creator, inputs, attrs):
    ins = (ctypes.c_void_p * len(inputs))(*[i.value for i in inputs])
    keys = (ctypes.c_char_p * len(attrs))(
        *[k.encode() for k in attrs])
    vals = (ctypes.c_char_p * len(attrs))(
        *[str(v).encode() for v in attrs.values()])
    n_out = ctypes.c_int()
    outs = ctypes.POINTER(ctypes.c_void_p)()
    rc = lib.MXImperativeInvoke(creator, len(inputs), ins,
                                ctypes.byref(n_out), ctypes.byref(outs),
                                len(attrs), keys, vals)
    assert rc == 0, lib.MXGetLastError().decode()
    handles = [ctypes.c_void_p(outs[i]) for i in range(n_out.value)]
    lib.MXImperativeInvokeSpineFree(outs)
    return handles


def test_ndarray_views_and_sync():
    """Slice/At/Reshape views, storage type, and the wait calls
    (reference c_api.cc NDArray block)."""
    lib = _capi()
    c = ctypes
    lib.MXNDArraySlice.argtypes = [c.c_void_p, c.c_uint, c.c_uint,
                                   c.POINTER(c.c_void_p)]
    lib.MXNDArrayAt.argtypes = [c.c_void_p, c.c_uint,
                                c.POINTER(c.c_void_p)]
    lib.MXNDArrayReshape.argtypes = [c.c_void_p, c.c_int,
                                     c.POINTER(c.c_int),
                                     c.POINTER(c.c_void_p)]
    lib.MXNDArrayGetStorageType.argtypes = [c.c_void_p,
                                            c.POINTER(c.c_int)]
    lib.MXNDArrayWaitToRead.argtypes = [c.c_void_p]

    arr = np.arange(12, dtype=np.float32).reshape(4, 3)
    h = _create(lib, arr)

    out = c.c_void_p()
    assert lib.MXNDArraySlice(h, 1, 3, c.byref(out)) == 0
    np.testing.assert_array_equal(_to_numpy(lib, out, (2, 3)), arr[1:3])
    lib.MXNDArrayFree(out)

    assert lib.MXNDArrayAt(h, 2, c.byref(out)) == 0
    np.testing.assert_array_equal(_to_numpy(lib, out, (3,)), arr[2])
    lib.MXNDArrayFree(out)

    dims = (c.c_int * 2)(6, 2)
    assert lib.MXNDArrayReshape(h, 2, dims, c.byref(out)) == 0
    np.testing.assert_array_equal(_to_numpy(lib, out, (6, 2)),
                                  arr.reshape(6, 2))
    lib.MXNDArrayFree(out)

    st = c.c_int(-7)
    assert lib.MXNDArrayGetStorageType(h, c.byref(st)) == 0
    assert st.value == 0  # dense
    assert lib.MXNDArrayWaitToRead(h) == 0
    assert lib.MXNDArrayWaitAll() == 0

    # error contract: OOB indices/ranges fail with rc=-1 + message, not
    # silently clamped data (the reference CHECK-fails too)
    assert lib.MXNDArrayAt(h, 99, c.byref(out)) == -1
    assert b"out of range" in lib.MXGetLastError()
    assert lib.MXNDArraySlice(h, 1, 99, c.byref(out)) == -1
    assert lib.MXNDArraySlice(h, 3, 1, c.byref(out)) == -1
    assert b"invalid range" in lib.MXGetLastError()
    lib.MXNDArrayFree(h)


def test_version_and_op_listing():
    lib = _capi()
    v = ctypes.c_int()
    assert lib.MXGetVersion(ctypes.byref(v)) == 0 and v.value > 0
    n = ctypes.c_uint()
    arr = ctypes.POINTER(ctypes.c_char_p)()
    assert lib.MXListAllOpNames(ctypes.byref(n), ctypes.byref(arr)) == 0
    names = {arr[i].decode() for i in range(n.value)}
    assert n.value > 300
    assert {"FullyConnected", "Convolution", "softmax"} <= names


def test_ndarray_create_copy_shape_dtype():
    lib = _capi()
    x = np.arange(12, dtype=np.float32).reshape(3, 4) * 0.5
    h = _create(lib, x)
    ndim = ctypes.c_uint()
    pdata = ctypes.POINTER(ctypes.c_uint)()
    assert lib.MXNDArrayGetShape(h, ctypes.byref(ndim),
                                 ctypes.byref(pdata)) == 0
    assert [pdata[i] for i in range(ndim.value)] == [3, 4]
    dt = ctypes.c_int()
    assert lib.MXNDArrayGetDType(h, ctypes.byref(dt)) == 0 and dt.value == 0
    devt, devi = ctypes.c_int(), ctypes.c_int()
    assert lib.MXNDArrayGetContext(h, ctypes.byref(devt),
                                   ctypes.byref(devi)) == 0
    assert devt.value == 1 and devi.value == 0
    np.testing.assert_array_equal(_to_numpy(lib, h, (3, 4)), x)
    assert lib.MXNDArrayFree(h) == 0

    # int32 path
    xi = np.array([[1, 2], [3, 4]], np.int32)
    hi = _create(lib, xi)
    assert lib.MXNDArrayGetDType(hi, ctypes.byref(dt)) == 0
    assert dt.value == 4
    np.testing.assert_array_equal(_to_numpy(lib, hi, (2, 2), np.int32), xi)
    lib.MXNDArrayFree(hi)


def test_imperative_invoke_matches_python():
    lib = _capi()
    rs = np.random.RandomState(0)
    x = rs.uniform(-1, 1, (2, 5)).astype(np.float32)
    w = rs.uniform(-1, 1, (3, 5)).astype(np.float32)
    b = rs.uniform(-1, 1, (3,)).astype(np.float32)
    ref = mx.nd.FullyConnected(mx.nd.array(x), mx.nd.array(w),
                               mx.nd.array(b), num_hidden=3).asnumpy()
    fc = _creator(lib, "FullyConnected")
    hx, hw, hb = _create(lib, x), _create(lib, w), _create(lib, b)
    outs = _invoke(lib, fc, [hx, hw, hb], {"num_hidden": 3})
    assert len(outs) == 1
    np.testing.assert_allclose(_to_numpy(lib, outs[0], (2, 3)), ref,
                               rtol=1e-5, atol=1e-6)
    # string-enum + tuple attrs parse like dmlc::Parameter (pooling)
    img = rs.uniform(0, 1, (1, 2, 4, 4)).astype(np.float32)
    pref = mx.nd.Pooling(mx.nd.array(img), kernel=(2, 2), stride=(2, 2),
                         pool_type="max").asnumpy()
    pool = _creator(lib, "Pooling")
    hp = _create(lib, img)
    pouts = _invoke(lib, pool, [hp],
                    {"kernel": "(2, 2)", "stride": "(2, 2)",
                     "pool_type": "max"})
    np.testing.assert_allclose(_to_numpy(lib, pouts[0], (1, 2, 2, 2)),
                               pref, rtol=1e-6)
    for h in [hx, hw, hb, hp] + outs + pouts:
        lib.MXNDArrayFree(h)


def test_autograd_record_backward_grad():
    """The c_api_ndarray.cc:257-281 surface: mark variables, record an op
    chain, backward, read the gradient — all through the C ABI."""
    lib = _capi()
    x = np.array([[1.0, 2.0], [3.0, 4.0]], np.float32)
    hx = _create(lib, x)
    hg = _create(lib, np.zeros_like(x))
    reqs = (ctypes.c_uint * 1)(1)  # write
    vars_ = (ctypes.c_void_p * 1)(hx.value)
    grads = (ctypes.c_void_p * 1)(hg.value)
    assert lib.MXAutogradMarkVariables(1, vars_, reqs, grads) == 0, \
        lib.MXGetLastError().decode()

    prev = ctypes.c_int()
    assert lib.MXAutogradSetIsRecording(1, ctypes.byref(prev)) == 0
    assert lib.MXAutogradSetIsTraining(1, None) == 0
    try:
        sq = _creator(lib, "square")
        mean = _creator(lib, "mean")
        h1 = _invoke(lib, sq, [hx], {})
        h2 = _invoke(lib, mean, h1, {})
    finally:
        lib.MXAutogradSetIsRecording(0, ctypes.byref(prev))
        lib.MXAutogradSetIsTraining(0, None)

    heads = (ctypes.c_void_p * 1)(h2[0].value)
    assert lib.MXAutogradBackward(1, heads, None, 0) == 0, \
        lib.MXGetLastError().decode()

    gh = ctypes.c_void_p()
    assert lib.MXNDArrayGetGrad(hx, ctypes.byref(gh)) == 0
    assert gh.value is not None
    got = _to_numpy(lib, gh, (2, 2))
    np.testing.assert_allclose(got, 2.0 * x / x.size, rtol=1e-6)
    for h in [hx, hg, gh] + h1 + h2:
        lib.MXNDArrayFree(h)


_C_HOST = r"""
#include <stdio.h>
#include <stdlib.h>
#include <string.h>
#include "mxtpu_c_api.h"

int main(void) {
  int version = 0;
  if (MXGetVersion(&version) != 0 || version <= 0) {
    fprintf(stderr, "version: %s\n", MXGetLastError());
    return 1;
  }
  mx_uint shape[2] = {2, 3};
  NDArrayHandle a, b;
  if (MXNDArrayCreateEx(shape, 2, 1, 0, 0, 0, &a) != 0) return 2;
  if (MXNDArrayCreateEx(shape, 2, 1, 0, 0, 0, &b) != 0) return 3;
  float va[6] = {1, 2, 3, 4, 5, 6}, vb[6] = {10, 20, 30, 40, 50, 60};
  if (MXNDArraySyncCopyFromCPU(a, va, 6) != 0) return 4;
  if (MXNDArraySyncCopyFromCPU(b, vb, 6) != 0) return 5;

  mx_uint n_ops = 0;
  AtomicSymbolCreator *creators = NULL;
  if (MXSymbolListAtomicSymbolCreators(&n_ops, &creators) != 0) return 6;
  AtomicSymbolCreator add = NULL;
  for (mx_uint i = 0; i < n_ops; ++i) {
    const char *name = NULL;
    MXSymbolGetAtomicSymbolName(creators[i], &name);
    if (strcmp(name, "elemwise_add") == 0) add = creators[i];
  }
  if (add == NULL) return 7;

  NDArrayHandle ins[2];
  ins[0] = a; ins[1] = b;
  int n_out = 0;
  NDArrayHandle *outs = NULL;
  if (MXImperativeInvoke(add, 2, ins, &n_out, &outs, 0, NULL, NULL) != 0) {
    fprintf(stderr, "invoke: %s\n", MXGetLastError());
    return 8;
  }
  float res[6];
  if (MXNDArraySyncCopyToCPU(outs[0], res, 6) != 0) return 9;
  for (int i = 0; i < 6; ++i)
    if (res[i] != va[i] + vb[i]) return 10;
  MXNDArrayFree(outs[0]);
  MXImperativeInvokeSpineFree(outs);
  MXNDArrayFree(a);
  MXNDArrayFree(b);
  printf("C_HOST_OK version=%d ops=%u\n", version, n_ops);
  return 0;
}
"""


def test_plain_c_host(tmp_path):
    """Compile a REAL C program against mxtpu_c_api.h and run it outside
    any Python process: exercises the embedded-interpreter boot
    (Py_InitializeEx) that ctypes-based tests never reach."""
    lib = _capi()  # ensures the .so is built
    gcc = shutil.which("gcc") or shutil.which("cc")
    if gcc is None:
        pytest.skip("no C compiler")
    libdir = os.path.dirname(native._CAPI._so_path)
    incdir = os.path.join(libdir, "include")
    src = tmp_path / "host.c"
    src.write_text(_C_HOST)
    exe = str(tmp_path / "host")
    pylibdir = sysconfig.get_config_var("LIBDIR") or ""
    subprocess.run(
        [gcc, str(src), "-o", exe, "-I", incdir,
         "-L", libdir, "-l:libmxtpu_capi.so",
         "-Wl,-rpath," + libdir, "-Wl,-rpath," + pylibdir],
        check=True, capture_output=True)
    env = dict(os.environ)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"   # the embedded interpreter stays off
    #                                 any accelerator
    res = subprocess.run([exe], capture_output=True, text=True, env=env,
                         timeout=240)
    assert res.returncode == 0, (res.stdout, res.stderr)
    assert "C_HOST_OK" in res.stdout


def test_backward_with_null_ograd_entry():
    """Review find: NULL entries in ograd_handles mean 'default head
    gradient' in the reference ABI and must not crash."""
    lib = _capi()
    x = np.array([2.0, 3.0], np.float32)
    hx = _create(lib, x)
    hg = _create(lib, np.zeros_like(x))
    reqs = (ctypes.c_uint * 1)(1)
    vars_ = (ctypes.c_void_p * 1)(hx.value)
    grads = (ctypes.c_void_p * 1)(hg.value)
    assert lib.MXAutogradMarkVariables(1, vars_, reqs, grads) == 0
    prev = ctypes.c_int()
    lib.MXAutogradSetIsRecording(1, ctypes.byref(prev))
    try:
        sq = _creator(lib, "square")
        h1 = _invoke(lib, sq, [hx], {})
    finally:
        lib.MXAutogradSetIsRecording(0, ctypes.byref(prev))
    heads = (ctypes.c_void_p * 1)(h1[0].value)
    null_ograds = (ctypes.c_void_p * 1)(None)
    assert lib.MXAutogradBackward(1, heads, null_ograds, 0) == 0, \
        lib.MXGetLastError().decode()
    gh = ctypes.c_void_p()
    assert lib.MXNDArrayGetGrad(hx, ctypes.byref(gh)) == 0
    np.testing.assert_allclose(_to_numpy(lib, gh, (2,)), 2.0 * x)
    for h in [hx, hg, gh] + h1:
        lib.MXNDArrayFree(h)


def test_repeated_recording_cycles_do_not_accumulate_tape():
    """Review find: flag-style SetIsRecording loops must reset the tape on
    each fresh outermost recording (like the record() scope), or tape
    nodes/freed keys accumulate without bound."""
    from mxnet_tpu import autograd

    lib = _capi()
    x = np.ones((4,), np.float32)
    hx = _create(lib, x)
    hg = _create(lib, np.zeros_like(x))
    reqs = (ctypes.c_uint * 1)(1)
    vars_ = (ctypes.c_void_p * 1)(hx.value)
    grads = (ctypes.c_void_p * 1)(hg.value)
    assert lib.MXAutogradMarkVariables(1, vars_, reqs, grads) == 0
    sq = _creator(lib, "square")
    prev = ctypes.c_int()
    sizes = []
    for _ in range(3):
        lib.MXAutogradSetIsRecording(1, ctypes.byref(prev))
        h1 = _invoke(lib, sq, [hx], {})
        lib.MXAutogradSetIsRecording(0, ctypes.byref(prev))
        heads = (ctypes.c_void_p * 1)(h1[0].value)
        assert lib.MXAutogradBackward(1, heads, None, 0) == 0, \
            lib.MXGetLastError().decode()
        sizes.append(len(autograd._st().tape) + len(autograd._st().freed))
        for h in h1:
            lib.MXNDArrayFree(h)
    assert sizes[0] == sizes[-1], sizes  # no growth across cycles
    lib.MXNDArrayFree(hx)
    lib.MXNDArrayFree(hg)


def test_imperative_invoke_inplace_outputs():
    """Review find: the reference in-place contract — caller-provided
    *outputs are written into (the sgd_update-on-weight idiom)."""
    lib = _capi()
    w = np.array([1.0, 2.0, 3.0], np.float32)
    hx = _create(lib, w)
    hout = _create(lib, np.zeros_like(w))
    sq = _creator(lib, "square")
    ins = (ctypes.c_void_p * 1)(hx.value)
    given = (ctypes.c_void_p * 1)(hout.value)
    outs_ptr = ctypes.cast(given, ctypes.POINTER(ctypes.c_void_p))
    n_out = ctypes.c_int(1)
    rc = lib.MXImperativeInvoke(sq, 1, ins, ctypes.byref(n_out),
                                ctypes.byref(outs_ptr), 0, None, None)
    assert rc == 0, lib.MXGetLastError().decode()
    assert n_out.value == 1
    # the CALLER's handle now holds the result; no new handle allocated
    np.testing.assert_allclose(_to_numpy(lib, hout, (3,)), w * w)
    lib.MXNDArrayFree(hx)
    lib.MXNDArrayFree(hout)


def test_sync_copy_to_cpu_size_validated():
    """Review find: size (elements) must match the array — no silent
    truncation, no size==0 'copy everything' overflow."""
    lib = _capi()
    h = _create(lib, np.ones((2, 3), np.float32))
    buf = np.empty(6, np.float32)
    assert lib.MXNDArraySyncCopyToCPU(h, buf.ctypes.data, 3) != 0
    assert b"size" in lib.MXGetLastError()
    assert lib.MXNDArraySyncCopyToCPU(h, buf.ctypes.data, 0) != 0
    assert lib.MXNDArraySyncCopyToCPU(h, buf.ctypes.data, 6) == 0
    lib.MXNDArrayFree(h)
