"""Python bridge for the imperative flat C ABI (libmxtpu_capi.so).

Reference: src/c_api/c_api_ndarray.cc (`MXImperativeInvoke` :132) +
c_api.cc NDArray create/copy/shape entry points + autograd control
(c_api_ndarray.cc:257-281). The C layer (lib/src_capi/c_api.cc) owns the
handle lifetime and marshals raw bytes/strings; every NDArray/op/autograd
semantic lives here. Each `_capi_*` function takes/returns only
plain-Python values (bytes, tuples, ints) plus NDArray objects whose
references the C side holds.

Attribute strings: the reference parses op params from strings via
dmlc::Parameter reflection; here `ast.literal_eval` covers the same
surface (numbers, bools, tuples), with plain words (e.g. pool_type
values) passing through as strings.
"""
from __future__ import annotations

import ast

import numpy as _np

from .base import MXNetError

# the reference's dtype enum (python/mxnet/base.py _DTYPE_MX_TO_NP order,
# mirrored by include/mxnet/ndarray.h)
_DTYPE_MX_TO_NP = {0: _np.float32, 1: _np.float64, 2: _np.float16,
                   3: _np.uint8, 4: _np.int32, 5: _np.int8, 6: _np.int64}
_DTYPE_NP_TO_MX = {_np.dtype(v).name: k for k, v in _DTYPE_MX_TO_NP.items()}

_DEVTYPE = {1: "cpu", 2: "gpu", 3: "cpu_pinned", 5: "cpu_shared", 6: "tpu"}
_DEVTYPE_TO_INT = {v: k for k, v in _DEVTYPE.items()}


def _ctx(dev_type, dev_id):
    from .context import Context

    return Context(_DEVTYPE.get(int(dev_type), "cpu"), int(dev_id))


def _capi_nd_create(shape, dev_type, dev_id, dtype):
    from . import ndarray as nd

    np_dt = _DTYPE_MX_TO_NP.get(int(dtype))
    if np_dt is None:
        raise MXNetError("unsupported dtype enum %d" % dtype)
    return nd.zeros(tuple(int(s) for s in shape),
                    ctx=_ctx(dev_type, dev_id), dtype=np_dt)


def _capi_nd_sync_copy_from(arr, raw):
    expected = int(_np.prod(arr.shape)) if arr.shape else 1
    host = _np.frombuffer(bytes(raw), dtype=arr.dtype)
    if host.size != expected:
        raise MXNetError("SyncCopyFromCPU: got %d elements, NDArray holds "
                         "%d" % (host.size, expected))
    from . import ndarray as nd

    arr._set_data(nd.array(host.reshape(arr.shape), ctx=arr.context,
                           dtype=arr.dtype)._data)


def _capi_nd_sync_copy_to(arr):
    return _np.ascontiguousarray(arr.asnumpy()).tobytes()


def _capi_nd_shape(arr):
    return tuple(int(d) for d in arr.shape)


def _capi_nd_dtype(arr):
    name = _np.dtype(arr.dtype).name
    if name not in _DTYPE_NP_TO_MX:
        raise MXNetError("dtype %s has no reference enum value" % name)
    return _DTYPE_NP_TO_MX[name]


def _capi_nd_context(arr):
    ctx = arr.context
    return _DEVTYPE_TO_INT.get(ctx.device_type, 1), int(ctx.device_id)


def _capi_nd_itemsize(arr):
    """Element byte width — authoritative in ONE place (the C side must
    not duplicate the dtype-enum table)."""
    return int(_np.dtype(arr.dtype).itemsize)


def _capi_list_ops():
    from . import ops

    return sorted(ops.list_ops())


def _parse_attr(val):
    """Reference semantics: op params arrive as strings and are parsed by
    dmlc::Parameter; literal_eval covers numbers/bools/tuples, anything
    else stays a string (enum-valued params like pool_type='max')."""
    s = val.decode() if isinstance(val, bytes) else val
    try:
        return ast.literal_eval(s)
    except (ValueError, SyntaxError):
        return s


def _parse_attrs(keys, vals):
    """One parsing site for every C-ABI (keys, vals) string-attr pair
    (invoke, symbol creation, iterator creation)."""
    return {k.decode() if isinstance(k, bytes) else k: _parse_attr(v)
            for k, v in zip(keys, vals)}


def _capi_invoke(op_name, inputs, keys, vals, outs=None):
    """MXImperativeInvoke core: op by name, NDArray inputs, string attrs.
    With `outs` (the reference's in-place contract) results are written
    into the given arrays; returns a list of output NDArrays either way."""
    from .ndarray import invoke

    attrs = _parse_attrs(keys, vals)
    out = invoke(op_name, tuple(inputs), attrs,
                 out=list(outs) if outs is not None else None)
    return list(out) if isinstance(out, (list, tuple)) else [out]


def _capi_autograd_set_recording(flag):
    from . import autograd

    return 1 if autograd.set_recording(bool(flag)) else 0


def _capi_autograd_set_training(flag):
    from . import autograd

    return 1 if autograd.set_training(bool(flag)) else 0


_GRAD_REQ = {0: "null", 1: "write", 2: "add"}


def _capi_mark_variables(variables, reqs, gradients):
    from . import autograd

    req_names = [_GRAD_REQ.get(int(r), "write") for r in reqs]
    autograd.mark_variables(list(variables), list(gradients), req_names)


def _capi_backward(outputs, ograds, retain_graph):
    from . import autograd

    heads = list(outputs)
    head_grads = None if ograds is None else list(ograds)
    autograd.backward(heads, head_grads, retain_graph=bool(retain_graph))


def _capi_get_grad(arr):
    return arr.grad  # None when no gradient buffer is attached


def _capi_nd_slice(arr, begin, end):
    begin, end = int(begin), int(end)
    n = arr.shape[0] if arr.shape else 0
    # reference MXNDArraySlice CHECK-fails on bad ranges; numpy-style
    # clamping would hand a C host silently short data with rc=0
    if not 0 <= begin < end <= n:
        raise MXNetError("MXNDArraySlice: invalid range [%d, %d) for "
                         "axis-0 size %d" % (begin, end, n))
    return arr[begin:end]


def _capi_nd_at(arr, idx):
    idx = int(idx)
    n = arr.shape[0] if arr.shape else 0
    if not 0 <= idx < n:
        raise MXNetError("MXNDArrayAt: index %d out of range for axis-0 "
                         "size %d" % (idx, n))
    return arr[idx]


def _capi_nd_reshape(arr, dims):
    return arr.reshape(tuple(int(d) for d in dims))


def _capi_nd_storage_type(arr):
    # reference enum: -1 undefined, 0 default (dense), 1 row_sparse, 2 csr
    st = getattr(arr, "stype", "default")
    return {"default": 0, "row_sparse": 1, "csr": 2}.get(st, 0)


def _capi_nd_wait_to_read(arr):
    arr.wait_to_read()


def _capi_wait_all():
    from . import ndarray as nd

    nd.waitall()


# -- symbol section (reference: c_api_symbolic.cc) --------------------------
# A C SymbolHandle owns a _SymRec. CreateAtomicSymbol makes a node with no
# inputs (sym=None); Compose instantiates it through the generated mx.sym
# op function — after that every symbol fn operates on .sym.


class _SymRec:
    __slots__ = ("op", "attrs", "sym")

    def __init__(self, op=None, attrs=None, sym=None):
        self.op = op
        self.attrs = attrs or {}
        self.sym = sym

    def require(self):
        if self.sym is None:
            raise ValueError(
                "symbol %r has not been composed yet (MXSymbolCompose "
                "binds its inputs, reference c_api_symbolic.cc:481)"
                % (self.op,))
        return self.sym


def _capi_sym_create_variable(name):
    from . import symbol as sym_mod

    return _SymRec(sym=sym_mod.Variable(name))


def _capi_sym_create_atomic(op_name, keys, vals):
    return _SymRec(op=op_name, attrs=_parse_attrs(keys, vals))


def _capi_sym_compose(rec, name, keys, args):
    from . import symbol as sym_mod

    syms = [a.require() for a in args]
    if keys and len(keys) != len(syms):
        raise ValueError(
            "MXSymbolCompose: %d keys for %d inputs (keys must be "
            "all-positional or one per input)" % (len(keys), len(syms)))
    kwargs = dict(rec.attrs)
    if name:
        kwargs["name"] = name
    fn = getattr(sym_mod, rec.op)
    if keys:
        kwargs.update({k.decode() if isinstance(k, bytes) else k: s
                       for k, s in zip(keys, syms)})
        rec.sym = fn(**kwargs)
    else:
        rec.sym = fn(*syms, **kwargs)


def _capi_sym_copy(rec):
    return _SymRec(op=rec.op, attrs=dict(rec.attrs), sym=rec.require())


def _capi_sym_group(recs):
    from . import symbol as sym_mod

    return _SymRec(sym=sym_mod.Group([r.require() for r in recs]))


def _capi_sym_internals(rec):
    return _SymRec(sym=rec.require().get_internals())


def _capi_sym_get_output(rec, index):
    return _SymRec(sym=rec.require()[int(index)])


def _capi_sym_list_arguments(rec):
    return list(rec.require().list_arguments())


def _capi_sym_list_outputs(rec):
    return list(rec.require().list_outputs())


def _capi_sym_list_aux(rec):
    return list(rec.require().list_auxiliary_states())


def _capi_sym_tojson(rec):
    return rec.require().tojson()


def _capi_sym_from_json(js):
    from .symbol import symbol as sym_impl

    return _SymRec(sym=sym_impl.load_json(
        js.decode() if isinstance(js, bytes) else js))


def _capi_sym_infer_shape(rec, keys, shapes, partial):
    """keys + per-key shape tuples -> (arg, out, aux shape lists,
    complete). Unknown-by-position keys ('' entries) follow
    list_arguments order like the reference's positional CSR form."""
    s = rec.require()
    kwargs = {}
    names = s.list_arguments()
    for i, (k, shp) in enumerate(zip(keys, shapes)):
        k = k.decode() if isinstance(k, bytes) else k
        kwargs[k if k else names[i]] = tuple(int(d) for d in shp)
    fn = s.infer_shape_partial if partial else s.infer_shape
    try:
        arg, out, aux = fn(**kwargs)
    except Exception:
        if partial:
            raise
        # under-specified shapes are NOT an error in the reference C API
        # (c_api_symbolic.cc): it succeeds with *complete = 0
        return ([], [], [], 0)
    complete = arg is not None and all(
        x is not None and all(d > 0 for d in x) for x in (arg + out + aux))
    return (arg or [], out or [], aux or [], 1 if complete else 0)


def _capi_executor_bind(rec, dev_type, dev_id, in_args, arg_grads,
                        grad_reqs, aux_states):
    s = rec.require()
    ctx = _ctx(dev_type, dev_id)
    names = s.list_arguments()
    args = dict(zip(names, in_args))
    args_grad = {n: g for n, g in zip(names, arg_grads) if g is not None}
    grad_req = {n: _GRAD_REQ.get(int(r), "write")
                for n, r in zip(names, grad_reqs)}
    return s.bind(ctx, args=args, args_grad=args_grad or None,
                  grad_req=grad_req, aux_states=list(aux_states) or None)


def _capi_executor_forward(executor, is_train):
    executor.forward(is_train=bool(is_train))


def _capi_executor_outputs(executor):
    return list(executor.outputs)


def _capi_executor_backward(executor, head_grads):
    executor.backward(out_grads=list(head_grads) if head_grads else None)


def _capi_executor_arg_grads(executor):
    return list(executor.grad_arrays)


def _capi_sym_get_name(rec):
    name = rec.require().name
    return (name or "", 1 if name is not None else 0)


def _capi_sym_get_attr(rec, key):
    key = key.decode() if isinstance(key, bytes) else key
    val = rec.require().attr(key)
    return (str(val) if val is not None else "",
            1 if val is not None else 0)


def _capi_sym_set_attr(rec, key, val):
    from .symbol.symbol import _wrap_attr_keys

    key = key.decode() if isinstance(key, bytes) else key
    val = val.decode() if isinstance(val, bytes) else val
    s = rec.require()
    # user attrs store __key__-wrapped (they must never reach op kwargs)
    # and as RAW strings — the reference MXSymbolSetAttr contract; no
    # _parse_attr here or set/get round-trips would re-format values
    s._outputs[0][0].attrs.update(_wrap_attr_keys({key: val}))


def _unwrap_attr_key(k):
    return k[2:-2] if k.startswith("__") and k.endswith("__") and len(k) > 4 \
        else k


def _capi_sym_list_attr(rec, shallow):
    """Flattened [k1, v1, k2, v2, ...]; deep form prefixes descendant
    node names as 'name$key' (reference c_api_symbolic.cc ListAttr).
    User attrs present themselves under their unwrapped names, the form
    the reference stores and the C host wrote."""
    s = rec.require()
    pairs = []
    if shallow:
        node = s._outputs[0][0]
        for k, v in sorted(node.attrs.items()):
            pairs += [_unwrap_attr_key(str(k)), str(v)]
    else:
        for name, attrs in sorted(s.attr_dict().items()):
            for k, v in sorted(attrs.items()):
                pairs += ["%s$%s" % (name, _unwrap_attr_key(str(k))),
                          str(v)]
    return pairs


def _capi_atomic_symbol_info(op_name):
    """(description, arg_names, arg_type_infos, arg_descriptions,
    key_var_num_args) derived from the generated op function's
    caller-facing signature (reference reads dmlc::Parameter reflection;
    here the signature IS the parameter surface)."""
    import inspect

    from . import ndarray as nd

    op_name = op_name.decode() if isinstance(op_name, bytes) else op_name
    from . import ops

    opdef = ops.get(op_name)
    fn = opdef.fn  # the raw op fn carries the real parameter surface
    doc = (getattr(getattr(nd, op_name, None), "__doc__", None)
           or fn.__doc__ or "").strip()
    names, types = [], []
    has_varargs = False
    try:
        params = list(inspect.signature(fn).parameters.values())
        if opdef.needs_rng and params:
            params = params[1:]  # the PRNG key is runtime-injected
        for p in params:
            if p.kind == inspect.Parameter.VAR_POSITIONAL:
                has_varargs = True
                continue
            if p.kind == inspect.Parameter.VAR_KEYWORD:
                continue
            names.append(p.name)
            types.append("" if p.default is inspect.Parameter.empty
                         else "optional, default=%r" % (p.default,))
    except (TypeError, ValueError):
        pass
    # the reference's key_var_num_args is the COUNT parameter's name
    # (hosts pass {num_args: N} when composing variadic ops), not the
    # *args name itself
    var_args = ""
    if has_varargs:
        var_args = "num_args" if "num_args" in names else ""
    return (doc, names, types, [""] * len(names), var_args)


# -- kvstore section (reference: c_api.cc MXKVStore*) -----------------------

def _capi_kv_create(name):
    from . import kvstore

    return kvstore.create(name.decode() if isinstance(name, bytes) else name)


def _capi_kv_init(kv, keys, vals):
    kv.init(list(keys), list(vals))


def _capi_kv_push(kv, keys, vals, priority):
    kv.push(list(keys), list(vals), priority=int(priority))


def _capi_kv_pull(kv, keys, outs, priority):
    kv.pull(list(keys), out=list(outs), priority=int(priority))


def _capi_kv_type(kv):
    return kv.type


def _capi_kv_rank(kv):
    return int(kv.rank)


def _capi_kv_group_size(kv):
    return int(kv.num_workers)


def _capi_kv_barrier(kv):
    kv.barrier()


def _capi_kv_set_updater(kv, fn_addr, handle_addr):
    """Install a C updater callback: `fn_addr` is the C function pointer
    void (*)(int key, NDArrayHandle recv, NDArrayHandle local, void*).
    The trampoline materializes fresh C handles for each call; the C side
    frees them via MXNDArrayFree per the reference contract."""
    import ctypes

    from .lib import native

    CB = ctypes.CFUNCTYPE(None, ctypes.c_int, ctypes.c_void_p,
                          ctypes.c_void_p, ctypes.c_void_p)
    cb = CB(fn_addr)
    lib = native.get_capi()
    lib.mxtpu_capi_wrap_handle.restype = ctypes.c_void_p
    lib.mxtpu_capi_wrap_handle.argtypes = [ctypes.py_object]
    lib.MXNDArrayFree.argtypes = [ctypes.c_void_p]

    def updater(key, recv, local):
        # hand the C callback real NDArrayHandles: heap structs whose
        # first member is the PyObject*, made on the C side to keep one
        # allocator for new/delete. Ownership follows the reference
        # MXKVStoreUpdater contract: the UPDATER frees recv and local
        # (c_api.h: "It's this updater's responsibility to delete recv
        # and local") — the trampoline must NOT free them too.
        hr = lib.mxtpu_capi_wrap_handle(ctypes.py_object(recv))
        hl = lib.mxtpu_capi_wrap_handle(ctypes.py_object(local))
        cb(int(key), hr, hl, handle_addr)

    kv._capi_updater = updater  # keep the CFUNCTYPE alive
    kv.set_updater(updater)


# -- data-iterator section (reference: c_api.cc MXDataIter*) ----------------
# A DataIterCreator handle is an interned iterator-name string (the same
# scheme as op creators); an iterator handle owns the Python DataIter
# plus its current batch.

# the file-fed iterators (the reference's C creators are the compiled
# file-based ones; NDArrayIter is a Python-side construct there too)
_DATA_ITERS = ("MNISTIter", "CSVIter", "LibSVMIter", "ImageRecordIter")


def _capi_list_data_iters():
    return list(_DATA_ITERS)


def _capi_iter_create(name, keys, vals):
    from . import io

    name = name.decode() if isinstance(name, bytes) else name
    if name not in _DATA_ITERS:
        raise ValueError("unknown data iter %r (have %s)"
                         % (name, ", ".join(_DATA_ITERS)))
    it = getattr(io, name)(**_parse_attrs(keys, vals))
    return {"iter": iter(it), "src": it, "batch": None}


def _capi_iter_next(state):
    try:
        state["batch"] = next(state["iter"])
        return 1
    except StopIteration:
        state["batch"] = None
        return 0


def _capi_iter_before_first(state):
    state["src"].reset()
    state["iter"] = iter(state["src"])
    state["batch"] = None


def _batch(state):
    b = state["batch"]
    if b is None:
        raise ValueError("no current batch: call MXDataIterNext first")
    return b


def _capi_iter_get_data(state):
    return _batch(state).data[0]


def _capi_iter_get_label(state):
    b = _batch(state)
    if not b.label:
        raise ValueError("batch carries no label")
    return b.label[0]


def _capi_iter_get_pad(state):
    return int(_batch(state).pad or 0)


# -- NDArray save/load (reference: c_api.cc MXNDArraySave/Load) -------------

def _capi_nd_save(fname, arrays, keys):
    from . import ndarray as nd

    fname = fname.decode() if isinstance(fname, bytes) else fname
    if keys:
        nd.save(fname, {k.decode() if isinstance(k, bytes) else k: a
                        for k, a in zip(keys, arrays)})
    else:
        nd.save(fname, list(arrays))


def _capi_nd_load(fname):
    from . import ndarray as nd

    fname = fname.decode() if isinstance(fname, bytes) else fname
    data = nd.load(fname)
    if isinstance(data, dict):
        names = list(data.keys())
        return names, [data[n] for n in names]
    return [], list(data)


def _capi_version():
    from . import __version__

    parts = (str(__version__).split("+")[0].split("."))
    nums = [int("".join(c for c in p if c.isdigit()) or 0) for p in parts[:3]]
    while len(nums) < 3:
        nums.append(0)
    return nums[0] * 10000 + nums[1] * 100 + nums[2]
