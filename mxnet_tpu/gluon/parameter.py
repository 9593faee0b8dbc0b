"""Gluon Parameter / ParameterDict.

Reference: python/mxnet/gluon/parameter.py (Parameter :43 — deferred init,
per-ctx data/grad copies, grad_req; ParameterDict :508). TPU-native notes:
per-ctx copies remain for API parity (the local-DP path); the distributed
path (mxnet_tpu.parallel) instead shards ONE logical array over a Mesh with
NamedSharding — per-device copies become XLA-managed replicas.
"""
from __future__ import annotations

from collections import OrderedDict

import numpy as _np

from ..base import MXNetError, np_dtype
from ..context import Context, cpu, current_context
from .. import ndarray as nd
from ..ndarray import NDArray
from .. import initializer


class DeferredInitializationError(MXNetError):
    """Parameter accessed before shape known (reference: parameter.py:38)."""


class Parameter:
    """A trainable parameter (reference: parameter.py:43)."""

    def __init__(self, name, grad_req="write", shape=None, dtype="float32",
                 lr_mult=1.0, wd_mult=1.0, init=None, allow_deferred_init=False,
                 differentiable=True, stype="default", grad_stype="default"):
        self.name = name
        self._grad_req = grad_req if differentiable else "null"
        if isinstance(shape, int):
            shape = (shape,)
        self._shape = tuple(shape) if shape is not None else None
        self.dtype = np_dtype(dtype)
        self.lr_mult = lr_mult
        self.wd_mult = wd_mult
        self.init = init
        self.allow_deferred_init = allow_deferred_init
        self._differentiable = differentiable
        self._stype = stype
        self._grad_stype = grad_stype
        self._data = None   # OrderedDict[Context, NDArray]
        self._grad = None
        self._deferred_init = ()
        self._ctx_list = None

    def __repr__(self):
        return "Parameter %s (shape=%s, dtype=%s)" % (self.name, self._shape, self.dtype)

    # -- shape (mergeable for deferred init) ------------------------------
    @property
    def shape(self):
        return self._shape

    @shape.setter
    def shape(self, new_shape):
        if self._shape is None:
            self._shape = tuple(new_shape)
            return
        # 0 is the unknown-dim wildcard on either side (reference:
        # parameter.py shape setter — weight sharing with deferred init
        # passes 0 for dims the sharing layer hasn't inferred yet)
        assert len(self._shape) == len(new_shape) and all(
            s == 0 or n == 0 or s == n
            for s, n in zip(self._shape, new_shape)), \
            "cannot update shape %s -> %s for %s" % (self._shape, new_shape, self.name)
        self._shape = tuple(s if n == 0 else n
                            for s, n in zip(self._shape, new_shape))

    @property
    def grad_req(self):
        return self._grad_req

    @grad_req.setter
    def grad_req(self, req):
        assert req in ("write", "add", "null")
        if not self._differentiable:
            req = "null"
        if self._grad_req == req:
            return
        self._grad_req = req
        if req == "null":
            self._grad = None
            if self._data:
                for arr in self._data.values():
                    arr._grad = None
                    arr._grad_req = "null"
        elif self._data is not None:
            self._init_grad()

    # -- init --------------------------------------------------------------
    def initialize(self, init=None, ctx=None, default_init=None,
                   force_reinit=False):
        """reference: parameter.py Parameter.initialize"""
        if self._data is not None and not force_reinit:
            return
        if ctx is None:
            ctx = [current_context()]
        if isinstance(ctx, Context):
            ctx = [ctx]
        if init is None:
            init = self.init if self.init is not None else (default_init or initializer.Uniform())
        if self._shape is None or any(s == 0 for s in self._shape):
            if self.allow_deferred_init:
                self._deferred_init = (init, ctx)
                return
            raise MXNetError("cannot initialize %s: shape %s unknown; set "
                             "allow_deferred_init or give full shape"
                             % (self.name, self._shape))
        self._init_impl(init, ctx)

    def _init_impl(self, init, ctx_list):
        host = nd.zeros(self._shape, ctx=cpu(), dtype=self.dtype)
        init_obj = initializer.create(init) if isinstance(init, str) else init
        init_obj(initializer.InitDesc(self.name), host)
        self._ctx_list = list(ctx_list)
        self._data = OrderedDict((c, host.copyto(c)) for c in ctx_list)
        self._deferred_init = ()
        if self._grad_req != "null":
            self._init_grad()

    def _init_grad(self):
        self._grad = OrderedDict(
            (c, nd.zeros(self._shape, ctx=c, dtype=self.dtype)) for c in self._data)
        from .. import autograd

        for c, arr in self._data.items():
            autograd.mark_variables([arr], [self._grad[c]], self._grad_req)

    def _finish_deferred_init(self):
        if not self._deferred_init:
            return
        init, ctx = self._deferred_init
        if self._shape is None or any(s == 0 for s in self._shape):
            raise DeferredInitializationError(
                "Parameter %s has unknown shape %s" % (self.name, self._shape))
        self._init_impl(init, ctx)

    # -- access ------------------------------------------------------------
    def _check_and_get(self, store, ctx):
        if store is None:
            if self._deferred_init:
                raise DeferredInitializationError(
                    "Parameter %s not initialized yet (deferred)" % self.name)
            raise MXNetError(
                "Parameter %s has not been initialized. Call .initialize() first"
                % self.name)
        if ctx is None:
            if len(store) == 1:
                return next(iter(store.values()))
            ctx = current_context()
        if ctx in store:
            return store[ctx]
        raise MXNetError("Parameter %s not initialized on context %s (has %s)"
                         % (self.name, ctx, list(store)))

    def data(self, ctx=None):
        return self._check_and_get(self._data, ctx)

    def list_data(self):
        self._check_and_get(self._data, list(self._data)[0] if self._data else None)
        return list(self._data.values())

    def grad(self, ctx=None):
        if self._grad is None and self._data is not None:
            raise MXNetError("Parameter %s grad_req='null'" % self.name)
        return self._check_and_get(self._grad, ctx)

    def list_grad(self):
        return list(self._grad.values()) if self._grad else []

    def list_ctx(self):
        if self._data is None and self._deferred_init:
            return self._deferred_init[1]
        return list(self._data) if self._data else []

    def zero_grad(self):
        if self._grad is None:
            return
        for g in self._grad.values():
            g[:] = 0

    def set_data(self, data):
        self.shape = data.shape
        if self._data is None:
            assert self._deferred_init, \
                "set_data on uninitialized parameter %s" % self.name
            self._deferred_init = self._deferred_init[:2] + (data,)
            init, ctx = self._deferred_init[:2]
            self._init_impl(initializer.Constant(0), ctx)
            for c in self._data:
                self._data[c]._set_data(data.as_in_context(c)._data)
            return
        for c in self._data:
            self._data[c]._set_data(data.as_in_context(c)._data)

    def adopt(self, array, ctx=None):
        """Take a device array as this parameter's data as it is: no
        initializer, no host copy, no gradient buffer (``grad_req`` becomes
        ``null``). For models loaded to serve, whose parameters would not fit
        the device twice (serving.generate.load_lm)."""
        ctx = ctx if ctx is not None else current_context()
        if self._shape is not None and len(self._shape) == len(array.shape) \
                and any(s and s != a for s, a in zip(self._shape,
                                                     array.shape)):
            raise MXNetError("parameter %s has shape %s, the array %s"
                             % (self.name, self._shape, tuple(array.shape)))
        self._shape = tuple(int(d) for d in array.shape)
        self.dtype = str(array.dtype)
        self._grad_req = "null"
        self._grad = None
        self._ctx_list = [ctx]
        self._data = OrderedDict([(ctx, nd.NDArray(array, ctx=ctx))])
        self._deferred_init = ()

    def row_sparse_data(self, row_id):
        raise MXNetError("row_sparse parameters: use stype='row_sparse' (sparse "
                         "module) — dense fallback active in this build")

    def reset_ctx(self, ctx):
        if isinstance(ctx, Context):
            ctx = [ctx]
        if self._data:
            data = next(iter(self._data.values()))
            self._data = OrderedDict((c, data.as_in_context(c)) for c in ctx)
            if self._grad_req != "null":
                self._init_grad()
        elif self._deferred_init:
            init, _ = self._deferred_init
            self._deferred_init = (init, ctx)

    def cast(self, dtype):
        self.dtype = np_dtype(dtype)
        if self._data is None:
            return
        for c in list(self._data):
            self._data[c]._set_data(self._data[c].astype(dtype)._data)
        if self._grad:
            for c in list(self._grad):
                self._grad[c]._set_data(self._grad[c].astype(dtype)._data)
            from .. import autograd

            for c, arr in self._data.items():
                autograd.mark_variables([arr], [self._grad[c]], self._grad_req)

    def var(self):
        from .. import symbol

        return symbol.var(self.name, shape=self._shape, dtype=self.dtype)


class Constant(Parameter):
    """Non-differentiable constant parameter (reference: parameter.py Constant)."""

    def __init__(self, name, value):
        if not isinstance(value, NDArray):
            value = nd.array(value)
        self.value = value

        class _CInit(initializer.Initializer):
            def __call__(self, desc, arr):
                arr[:] = value.asnumpy()

        super().__init__(name, grad_req="null", shape=value.shape,
                         dtype=value.dtype, init=_CInit(), differentiable=False)


class ParameterDict:
    """Dict of Parameters with prefix + sharing (reference: parameter.py:508)."""

    def __init__(self, prefix="", shared=None):
        self._prefix = prefix
        self._params = OrderedDict()
        self._shared = shared

    @property
    def prefix(self):
        return self._prefix

    def __repr__(self):
        return "ParameterDict(%s)" % ", ".join(str(p) for p in self._params.values())

    def items(self):
        return self._params.items()

    def keys(self):
        return self._params.keys()

    def values(self):
        return self._params.values()

    def __iter__(self):
        return iter(self._params)

    def __len__(self):
        return len(self._params)

    def __getitem__(self, key):
        return self._params[key]

    def __contains__(self, key):
        return key in self._params

    def get(self, name, **kwargs):
        """Create-or-retrieve `prefix+name` (reference: parameter.py get)."""
        name = self._prefix + name
        param = self._get_impl(name)
        if param is None:
            param = Parameter(name, **kwargs)
            self._params[name] = param
        else:
            shape = kwargs.get("shape")
            if shape is not None:
                param.shape = (shape,) if isinstance(shape, int) else tuple(shape)
        return param

    def get_constant(self, name, value=None):
        name = self._prefix + name
        param = self._get_impl(name)
        if param is None:
            if value is None:
                raise MXNetError("no constant %s and no value given" % name)
            param = Constant(name, value)
            self._params[name] = param
        return param

    def _get_impl(self, name):
        if name in self._params:
            return self._params[name]
        if self._shared is not None and name in self._shared._params:
            self._params[name] = self._shared._params[name]
            return self._params[name]
        return None

    def update(self, other):
        for k, v in other.items():
            if k in self._params:
                assert self._params[k] is v, "duplicate parameter name %s" % k
            else:
                self._params[k] = v

    def initialize(self, init=None, ctx=None, verbose=False, force_reinit=False):
        for p in self._params.values():
            p.initialize(init=None, ctx=ctx, default_init=init, force_reinit=force_reinit)

    def zero_grad(self):
        for p in self._params.values():
            p.zero_grad()

    def reset_ctx(self, ctx):
        for p in self._params.values():
            p.reset_ctx(ctx)

    def setattr(self, name, value):
        for p in self._params.values():
            setattr(p, name, value)

    def save(self, filename, strip_prefix=""):
        args = {}
        for p in self._params.values():
            block = p.list_data()
            weight = sum(b.copyto(cpu()) for b in block) / len(block)
            name = p.name
            if strip_prefix and name.startswith(strip_prefix):
                name = name[len(strip_prefix):]
            args[name] = weight
        nd.save(filename, args)

    def load(self, filename, ctx=None, allow_missing=False,
             ignore_extra=False, restore_prefix=""):
        loaded = nd.load(filename)
        # checkpoint files prefix entries with arg:/aux: (reference
        # model.py:394 format); strip for parameter matching
        loaded = {(k.split(":", 1)[1] if k.startswith(("arg:", "aux:"))
                   else k): v for k, v in loaded.items()}
        if restore_prefix:
            loaded = {restore_prefix + k: v for k, v in loaded.items()}
        if not allow_missing:
            for name in self.keys():
                assert name in loaded, \
                    "Parameter %s missing in file %s" % (name, filename)
        for name, val in loaded.items():
            if name not in self._params:
                if not ignore_extra:
                    raise MXNetError("Parameter %s in file not in ParameterDict" % name)
                continue
            p = self._params[name]
            if p._data is None:
                p.shape = val.shape
                p.initialize(ctx=ctx or [current_context()])
                if p._deferred_init:
                    p._finish_deferred_init()
            p.set_data(val)
