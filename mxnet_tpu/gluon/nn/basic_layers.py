"""Basic neural network layers.

Reference: python/mxnet/gluon/nn/basic_layers.py (Dense, Dropout, BatchNorm,
InstanceNorm, LayerNorm, Embedding, Flatten, Lambda, HybridLambda,
Sequential, HybridSequential, activations in activations.py)."""
from __future__ import annotations

import numpy as _np

from ... import ndarray as nd
from ...base import MXNetError
from ..block import Block, HybridBlock

__all__ = ["Sequential", "HybridSequential", "Dense", "Dropout", "BatchNorm",
           "InstanceNorm", "LayerNorm", "Embedding", "Flatten", "Lambda",
           "HybridLambda", "Activation", "LeakyReLU", "PReLU", "ELU", "SELU",
           "Swish", "GELU"]


class Sequential(Block):
    """Sequential container (reference: basic_layers.py:29)."""

    def __init__(self, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)

    def add(self, *blocks):
        for block in blocks:
            self.register_child(block)

    def forward(self, x):
        for block in self._children.values():
            x = block(x)
        return x

    def __getitem__(self, key):
        layers = list(self._children.values())[key]
        if isinstance(layers, list):
            net = type(self)(prefix=self._prefix)
            with net.name_scope():
                net.add(*layers)
            return net
        return layers

    def __len__(self):
        return len(self._children)


class HybridSequential(HybridBlock):
    """Hybridizable sequential container (reference: basic_layers.py:99)."""

    def __init__(self, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)

    def add(self, *blocks):
        for block in blocks:
            self.register_child(block)

    def _eager_forward(self, x):
        for block in self._children.values():
            x = block(x)
        return x

    def hybrid_forward(self, F, x):
        for block in self._children.values():
            x = block(x)
        return x

    def __getitem__(self, key):
        layers = list(self._children.values())[key]
        if isinstance(layers, list):
            net = type(self)(prefix=self._prefix)
            with net.name_scope():
                net.add(*layers)
            return net
        return layers

    def __len__(self):
        return len(self._children)


class Dense(HybridBlock):
    """Fully-connected layer (reference: basic_layers.py:161). Lowers to
    FullyConnected -> one MXU matmul."""

    def __init__(self, units, activation=None, use_bias=True, flatten=True,
                 dtype="float32", weight_initializer=None, bias_initializer="zeros",
                 in_units=0, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        with self.name_scope():
            self._units = units
            self._in_units = in_units
            self._flatten = flatten
            self.weight = self.params.get("weight", shape=(units, in_units),
                                          init=weight_initializer, dtype=dtype,
                                          allow_deferred_init=True)
            if use_bias:
                self.bias = self.params.get("bias", shape=(units,),
                                            init=bias_initializer, dtype=dtype,
                                            allow_deferred_init=True)
            else:
                self.bias = None
            self.act = Activation(activation, prefix=activation + "_") \
                if activation is not None else None

    def _shape_hook(self, x):
        if self.weight.shape and self.weight.shape[1] == 0:
            in_units = int(_np.prod(x.shape[1:])) if self._flatten else x.shape[-1]
            self.weight.shape = (self._units, in_units)

    def hybrid_forward(self, F, x, weight, bias=None):
        out = F.FullyConnected(x, weight, bias, num_hidden=self._units,
                               flatten=self._flatten, no_bias=bias is None)
        if self.act is not None:
            out = self.act(out)
        return out


class Activation(HybridBlock):
    def __init__(self, activation, prefix=None, params=None):
        self._act_type = activation
        super().__init__(prefix=prefix, params=params)

    def _alias(self):
        return self._act_type

    def hybrid_forward(self, F, x):
        return F.Activation(x, act_type=self._act_type)


class LeakyReLU(HybridBlock):
    def __init__(self, alpha, **kwargs):
        super().__init__(**kwargs)
        self._alpha = alpha

    def hybrid_forward(self, F, x):
        return F.LeakyReLU(x, act_type="leaky", slope=self._alpha)


class PReLU(HybridBlock):
    def __init__(self, alpha_initializer=None, **kwargs):
        super().__init__(**kwargs)
        from ... import initializer as init_mod

        with self.name_scope():
            self.alpha = self.params.get("alpha", shape=(1,),
                                         init=alpha_initializer or init_mod.Constant(0.25))

    def hybrid_forward(self, F, x, alpha):
        return F.LeakyReLU(x, gamma=alpha, act_type="prelu")


class ELU(HybridBlock):
    def __init__(self, alpha=1.0, **kwargs):
        super().__init__(**kwargs)
        self._alpha = alpha

    def hybrid_forward(self, F, x):
        return F.LeakyReLU(x, act_type="elu", slope=self._alpha)


class SELU(HybridBlock):
    def hybrid_forward(self, F, x):
        return F.LeakyReLU(x, act_type="selu")


class GELU(HybridBlock):
    def hybrid_forward(self, F, x):
        return F.LeakyReLU(x, act_type="gelu")


class Swish(HybridBlock):
    def __init__(self, beta=1.0, **kwargs):
        super().__init__(**kwargs)
        self._beta = beta

    def hybrid_forward(self, F, x):
        return x * F.sigmoid(self._beta * x)


class Dropout(HybridBlock):
    def __init__(self, rate, axes=(), **kwargs):
        super().__init__(**kwargs)
        self._rate = rate
        self._axes = axes

    def hybrid_forward(self, F, x):
        return F.Dropout(x, p=self._rate, axes=self._axes)


class Flatten(HybridBlock):
    def hybrid_forward(self, F, x):
        return F.Flatten(x)


class BatchNorm(HybridBlock):
    """Batch normalization (reference: basic_layers.py:310). Moving stats are
    aux parameters updated functionally (see ops/nn.py batch_norm).

    TPU extension: `act_type="relu"` folds the following activation into the
    op (BatchNormRelu), and calling the layer with a second input —
    ``bn(x, residual)`` — folds a residual add in front of the activation
    (BatchNormAddRelu). Parameter names/shapes are identical to the plain
    layer, so fused and unfused models share checkpoints, and the fused op
    lowers exactly as the separate ones do (ops/nn.py `_bn_act`)."""

    def __init__(self, axis=None, momentum=0.9, epsilon=1e-5, center=True, scale=True,
                 use_global_stats=False, beta_initializer="zeros",
                 gamma_initializer="ones", running_mean_initializer="zeros",
                 running_variance_initializer="ones", in_channels=0,
                 act_type=None, **kwargs):
        super().__init__(**kwargs)
        self._act_type = act_type
        if axis is None:
            # reference default is the channels-first axis (1); inside a
            # channels-last layout_scope the default follows the layout
            from .conv_layers import in_channels_last_scope

            axis = -1 if in_channels_last_scope() else 1
        self._kwargs = {"axis": axis, "eps": epsilon, "momentum": momentum,
                        "fix_gamma": not scale, "use_global_stats": use_global_stats}
        self._axis = axis
        self._in_channels = in_channels
        with self.name_scope():
            self.gamma = self.params.get("gamma",
                                         grad_req="write" if scale else "null",
                                         shape=(in_channels,), init=gamma_initializer,
                                         allow_deferred_init=True,
                                         differentiable=scale)
            self.beta = self.params.get("beta",
                                        grad_req="write" if center else "null",
                                        shape=(in_channels,), init=beta_initializer,
                                        allow_deferred_init=True,
                                        differentiable=center)
            self.running_mean = self.params.get("running_mean", grad_req="null",
                                                shape=(in_channels,),
                                                init=running_mean_initializer,
                                                allow_deferred_init=True,
                                                differentiable=False)
            self.running_var = self.params.get("running_var", grad_req="null",
                                               shape=(in_channels,),
                                               init=running_variance_initializer,
                                               allow_deferred_init=True,
                                               differentiable=False)

    def _shape_hook(self, x, addend=None):
        if self._in_channels == 0:
            c = x.shape[self._axis]
            for p in (self.gamma, self.beta, self.running_mean, self.running_var):
                if p.shape and p.shape[0] == 0:
                    p.shape = (c,)

    def cast(self, dtype):
        if _np.dtype(dtype) == _np.float16:
            dtype = "float32"  # BN stats stay fp32 (reference does the same)
        super().cast(dtype)

    def hybrid_forward(self, F, x, addend=None, gamma=None, beta=None,
                       running_mean=None, running_var=None):
        if addend is not None:
            if self._act_type is None:
                raise ValueError(
                    "BatchNorm: a residual input requires act_type "
                    "(the fused BatchNormAddRelu path)")
            return F.BatchNormAddRelu(x, addend, gamma, beta, running_mean,
                                      running_var, act_type=self._act_type,
                                      **self._kwargs)
        if self._act_type is not None:
            return F.BatchNormRelu(x, gamma, beta, running_mean, running_var,
                                   act_type=self._act_type, **self._kwargs)
        return F.BatchNorm(x, gamma, beta, running_mean, running_var, **self._kwargs)


class InstanceNorm(HybridBlock):
    def __init__(self, axis=1, epsilon=1e-5, center=True, scale=False,
                 beta_initializer="zeros", gamma_initializer="ones",
                 in_channels=0, **kwargs):
        super().__init__(**kwargs)
        self._epsilon = epsilon
        self._axis = axis
        with self.name_scope():
            self.gamma = self.params.get("gamma", grad_req="write" if scale else "null",
                                         shape=(in_channels,), init=gamma_initializer,
                                         allow_deferred_init=True)
            self.beta = self.params.get("beta", grad_req="write" if center else "null",
                                        shape=(in_channels,), init=beta_initializer,
                                        allow_deferred_init=True)

    def _shape_hook(self, x):
        c = x.shape[self._axis]
        for p in (self.gamma, self.beta):
            if p.shape and p.shape[0] == 0:
                p.shape = (c,)

    def hybrid_forward(self, F, x, gamma, beta):
        return F.InstanceNorm(x, gamma, beta, eps=self._epsilon)


class LayerNorm(HybridBlock):
    """Layer normalization (reference: basic_layers.py:480) — the BERT/
    transformer normalizer; fused by XLA into neighbouring ops."""

    def __init__(self, axis=-1, epsilon=1e-5, center=True, scale=True,
                 beta_initializer="zeros", gamma_initializer="ones",
                 in_channels=0, **kwargs):
        super().__init__(**kwargs)
        self._axis = axis
        self._epsilon = epsilon
        with self.name_scope():
            self.gamma = self.params.get("gamma", grad_req="write" if scale else "null",
                                         shape=(in_channels,), init=gamma_initializer,
                                         allow_deferred_init=True)
            self.beta = self.params.get("beta", grad_req="write" if center else "null",
                                        shape=(in_channels,), init=beta_initializer,
                                        allow_deferred_init=True)

    def _shape_hook(self, x):
        c = x.shape[self._axis]
        for p in (self.gamma, self.beta):
            if p.shape and p.shape[0] == 0:
                p.shape = (c,)

    def hybrid_forward(self, F, x, gamma, beta):
        return F.LayerNorm(x, gamma, beta, axis=self._axis, eps=self._epsilon)


class Embedding(HybridBlock):
    """Embedding lookup (reference: basic_layers.py:550). Gather on TPU.
    sparse_grad=True marks the weight's grad_stype row_sparse: Trainer casts
    the tape gradient to row_sparse and sparse-capable optimizers take the
    lazy row-update path (untouched rows skip wd/momentum — same semantics
    as the reference's sparse kernels)."""

    def __init__(self, input_dim, output_dim, dtype="float32",
                 weight_initializer=None, sparse_grad=False, **kwargs):
        super().__init__(**kwargs)
        self._input_dim = input_dim
        self._output_dim = output_dim
        with self.name_scope():
            self.weight = self.params.get("weight", shape=(input_dim, output_dim),
                                          init=weight_initializer, dtype=dtype,
                                          grad_stype="row_sparse" if sparse_grad
                                          else "default")

    def hybrid_forward(self, F, x, weight):
        return F.Embedding(x, weight, input_dim=self._input_dim,
                           output_dim=self._output_dim)


class Lambda(Block):
    def __init__(self, function, prefix=None):
        super().__init__(prefix=prefix)
        if isinstance(function, str):
            assert hasattr(nd, function), "function %s not found in nd" % function
            self._func_impl = getattr(nd, function)
        else:
            self._func_impl = function

    def forward(self, *args):
        return self._func_impl(*args)


class HybridLambda(HybridBlock):
    def __init__(self, function, prefix=None):
        super().__init__(prefix=prefix)
        if isinstance(function, str):
            self._func_name = function
            self._func = None
        else:
            self._func = function
            self._func_name = getattr(function, "__name__", "lambda")

    def hybrid_forward(self, F, *args):
        fn = self._func if self._func is not None else getattr(F, self._func_name)
        return fn(*args)
