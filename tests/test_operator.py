"""Per-op numerical sweep over the ENTIRE op registry.

Mirrors the reference's tests/python/unittest/test_operator.py (~7k LoC of
per-op value+gradient checks) with three oracles applied to every registered
op on small shapes:

  1. forward value check — exact numpy reference where one exists, else
     shape/dtype/finiteness invariants (or a custom structural check);
  2. numeric-gradient check — central finite differences of sum(outputs)
     vs the autograd/vjp backward (reference: test_utils.py numeric_grad /
     check_numeric_gradient);
  3. naive-vs-jit consistency — the op run through the naive op-by-op
     interpreter must match the jit-compiled run (reference:
     test_utils.py check_consistency cross-backend oracle).

`test_registry_fully_covered` asserts every name in ops.list_ops() is either
swept here or in EXCLUDED with a reason — new ops can't land untested.
"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, engine, ops
from mxnet_tpu.ndarray import register as _ndreg
from mxnet_tpu.test_utils import assert_almost_equal

# one generated eager function per registry entry — the exact code path
# users hit through mx.nd.* (ndarray/register.py populate())
_FNS = {}


def _fn(name):
    if name not in _FNS:
        _FNS[name] = _ndreg._make_function(ops.get(name))
    return _FNS[name]


def _to_nd(a):
    from mxnet_tpu.ndarray import NDArray

    if isinstance(a, NDArray):
        return a
    a = np.asarray(a)
    return mx.nd.array(a, dtype=str(a.dtype))


def _outs(res):
    if isinstance(res, (list, tuple)):
        return list(res)
    return [res]


def _outs_np(res):
    return [o.asnumpy() for o in _outs(res)]


def run_op(name, arrays, attrs):
    mx.random.seed(77)
    return _fn(name)(*[_to_nd(a) for a in arrays], **attrs)


# ---------------------------------------------------------------------------
# case table
# ---------------------------------------------------------------------------

class Case:
    """One sweep entry for a canonical op name."""

    def __init__(self, name, arrays=(), attrs=None, grad=None, ref=None,
                 tol=1e-4, grad_tol=2e-2, check=None, naive=True, cid=None):
        self.name = name
        self.arrays = [np.asarray(a) for a in arrays]
        self.attrs = attrs or {}
        self.grad = grad            # None | list of wrt arg indices
        self.ref = ref              # callable(*np_arrays) -> np | [np]
        self.tol = tol
        self.grad_tol = grad_tol
        self.check = check          # callable(list_of_np_outs, case)
        self.naive = naive
        self.cid = cid or name

    def __repr__(self):
        return "Case(%s)" % self.cid


CASES = []
_seen_ids = set()


def case(name, *arrays, **kw):
    c = Case(name, arrays, **kw)
    assert c.cid not in _seen_ids, "duplicate case id %s" % c.cid
    _seen_ids.add(c.cid)
    CASES.append(c)


_rng = np.random.RandomState(42)


def U(*shape, lo=-1.0, hi=1.0):
    return _rng.uniform(lo, hi, size=shape).astype(np.float32)


def P(*shape, lo=0.5, hi=2.0):
    return U(*shape, lo=lo, hi=hi)


# -- unary elementwise float ops (numpy references) -------------------------
_UNARY = {
    # name: (numpy_fn, (lo, hi), differentiable)
    "abs": (np.abs, (0.2, 1.0), True),
    "arccos": (np.arccos, (-0.8, 0.8), True),
    "arccosh": (np.arccosh, (1.2, 3.0), True),
    "arcsin": (np.arcsin, (-0.8, 0.8), True),
    "arcsinh": (np.arcsinh, (-2.0, 2.0), True),
    "arctan": (np.arctan, (-2.0, 2.0), True),
    "arctanh": (np.arctanh, (-0.8, 0.8), True),
    "cbrt": (np.cbrt, (0.3, 2.0), True),
    "ceil": (np.ceil, (-2.0, 2.0), False),
    "cos": (np.cos, (-2.0, 2.0), True),
    "cosh": (np.cosh, (-2.0, 2.0), True),
    "degrees": (np.degrees, (-2.0, 2.0), True),
    "erf": (lambda x: np.vectorize(__import__("math").erf)(x).astype(x.dtype),
            (-1.5, 1.5), True),
    "exp": (np.exp, (-1.0, 1.0), True),
    "expm1": (np.expm1, (-1.0, 1.0), True),
    "fix": (np.fix, (-2.0, 2.0), False),
    "floor": (np.floor, (-2.0, 2.0), False),
    "gamma": (lambda x: np.vectorize(__import__("math").gamma)(x).astype(x.dtype),
              (0.7, 2.5), True),
    "gammaln": (lambda x: np.vectorize(__import__("math").lgamma)(x).astype(x.dtype),
                (0.7, 2.5), True),
    "identity": (lambda x: x, (-1.0, 1.0), True),
    "log": (np.log, (0.3, 3.0), True),
    "log10": (np.log10, (0.3, 3.0), True),
    "log1p": (np.log1p, (-0.5, 2.0), True),
    "log2": (np.log2, (0.3, 3.0), True),
    "logical_not": (lambda x: (x == 0).astype(np.float32), (-1.0, 1.0), False),
    "negative": (np.negative, (-1.0, 1.0), True),
    "radians": (np.radians, (-2.0, 2.0), True),
    "rcbrt": (lambda x: 1.0 / np.cbrt(x), (0.5, 2.0), True),
    "reciprocal": (np.reciprocal, (0.5, 2.0), True),
    "relu": (lambda x: np.maximum(x, 0), (0.2, 1.0), True),
    "rint": (np.rint, (-2.0, 2.0), False),
    "round": (lambda x: np.floor(x + 0.5), (-2.0, 2.0), False),
    "rsqrt": (lambda x: 1.0 / np.sqrt(x), (0.5, 2.0), True),
    "sigmoid": (lambda x: 1 / (1 + np.exp(-x)), (-2.0, 2.0), True),
    "sign": (np.sign, (0.2, 1.0), False),
    "sin": (np.sin, (-2.0, 2.0), True),
    "sinh": (np.sinh, (-2.0, 2.0), True),
    "softsign": (lambda x: x / (1 + np.abs(x)), (-2.0, 2.0), True),
    "sqrt": (np.sqrt, (0.5, 2.0), True),
    "square": (np.square, (-2.0, 2.0), True),
    "tan": (np.tan, (-1.0, 1.0), True),
    "tanh": (np.tanh, (-2.0, 2.0), True),
    "trunc": (np.trunc, (-2.0, 2.0), False),
    "zeros_like": (np.zeros_like, (-1.0, 1.0), False),
    "ones_like": (np.ones_like, (-1.0, 1.0), False),
    "erfinv": (None, (-0.6, 0.6), True),  # no closed-form numpy ref
}
for _name, (_npfn, (_lo, _hi), _diff) in _UNARY.items():
    case(_name, U(2, 3, lo=_lo, hi=_hi),
         ref=(lambda f: (lambda x: f(x)))(_npfn) if _npfn else None,
         grad=[0] if _diff else None)

case("BlockGrad", U(2, 3), ref=lambda x: x,
     check=lambda outs, c: None, cid="BlockGrad")


def _blockgrad_zero_grad():
    x = _to_nd(U(2, 3))
    x.attach_grad()
    with autograd.record():
        y = _fn("BlockGrad")(x)
        y.sum().backward()
    assert float(np.abs(x.grad.asnumpy()).sum()) == 0.0


# -- binary elementwise + broadcast ----------------------------------------
_BINARY = {
    "elemwise_add": (np.add, True), "elemwise_sub": (np.subtract, True),
    "elemwise_mul": (np.multiply, True), "elemwise_div": (np.divide, True),
    "elemwise_maximum": (np.maximum, True), "elemwise_minimum": (np.minimum, True),
    "elemwise_hypot": (np.hypot, True),
    "elemwise_power": (np.power, True), "elemwise_mod": (np.fmod, False),
    "elemwise_equal": (lambda a, b: (a == b).astype(np.float32), False),
    "elemwise_not_equal": (lambda a, b: (a != b).astype(np.float32), False),
    "elemwise_greater": (lambda a, b: (a > b).astype(np.float32), False),
    "elemwise_greater_equal": (lambda a, b: (a >= b).astype(np.float32), False),
    "elemwise_lesser": (lambda a, b: (a < b).astype(np.float32), False),
    "elemwise_lesser_equal": (lambda a, b: (a <= b).astype(np.float32), False),
    "elemwise_logical_and": (lambda a, b: ((a != 0) & (b != 0)).astype(np.float32), False),
    "elemwise_logical_or": (lambda a, b: ((a != 0) | (b != 0)).astype(np.float32), False),
    "elemwise_logical_xor": (lambda a, b: ((a != 0) ^ (b != 0)).astype(np.float32), False),
}
for _name, (_npfn, _diff) in _BINARY.items():
    a, b = P(2, 3), P(2, 3, lo=0.6, hi=1.8)
    case(_name, a, b, ref=_npfn, grad=[0, 1] if _diff else None)

_BCAST = {
    "broadcast_add": np.add, "broadcast_sub": np.subtract,
    "broadcast_mul": np.multiply, "broadcast_div": np.divide,
    "broadcast_maximum": np.maximum, "broadcast_minimum": np.minimum,
    "broadcast_hypot": np.hypot, "broadcast_power": np.power,
    "broadcast_mod": np.fmod,
    "broadcast_equal": lambda a, b: (a == b).astype(np.float32),
    "broadcast_not_equal": lambda a, b: (a != b).astype(np.float32),
    "broadcast_greater": lambda a, b: (a > b).astype(np.float32),
    "broadcast_greater_equal": lambda a, b: (a >= b).astype(np.float32),
    "broadcast_lesser": lambda a, b: (a < b).astype(np.float32),
    "broadcast_lesser_equal": lambda a, b: (a <= b).astype(np.float32),
    "broadcast_logical_and": lambda a, b: ((a != 0) & (b != 0)).astype(np.float32),
    "broadcast_logical_or": lambda a, b: ((a != 0) | (b != 0)).astype(np.float32),
    "broadcast_logical_xor": lambda a, b: ((a != 0) ^ (b != 0)).astype(np.float32),
}
_BCAST_DIFF = {"broadcast_add", "broadcast_sub", "broadcast_mul",
               "broadcast_div", "broadcast_maximum", "broadcast_minimum",
               "broadcast_hypot", "broadcast_power"}
for _name, _npfn in _BCAST.items():
    a, b = P(2, 3), P(1, 3, lo=0.6, hi=1.8)
    case(_name, a, b, ref=_npfn,
         grad=[0, 1] if _name in _BCAST_DIFF else None)

# scalar-op family
_SCALAR = {
    "_plus_scalar": lambda x, s: x + s, "_minus_scalar": lambda x, s: x - s,
    "_rminus_scalar": lambda x, s: s - x, "_mul_scalar": lambda x, s: x * s,
    "_div_scalar": lambda x, s: x / s, "_rdiv_scalar": lambda x, s: s / x,
    "_mod_scalar": lambda x, s: np.fmod(x, s),
    "_rmod_scalar": lambda x, s: np.fmod(s, x),
    "_power_scalar": lambda x, s: np.power(x, s),
    "_rpower_scalar": lambda x, s: np.power(s, x),
    "_maximum_scalar": lambda x, s: np.maximum(x, s),
    "_minimum_scalar": lambda x, s: np.minimum(x, s),
    "_hypot_scalar": lambda x, s: np.hypot(x, s),
    "_equal_scalar": lambda x, s: (x == s).astype(np.float32),
    "_not_equal_scalar": lambda x, s: (x != s).astype(np.float32),
    "_greater_scalar": lambda x, s: (x > s).astype(np.float32),
    "_greater_equal_scalar": lambda x, s: (x >= s).astype(np.float32),
    "_lesser_scalar": lambda x, s: (x < s).astype(np.float32),
    "_lesser_equal_scalar": lambda x, s: (x <= s).astype(np.float32),
    "_logical_and_scalar": lambda x, s: ((x != 0) & (s != 0)).astype(np.float32),
    "_logical_or_scalar": lambda x, s: ((x != 0) | (s != 0)).astype(np.float32),
    "_logical_xor_scalar": lambda x, s: ((x != 0) ^ (s != 0)).astype(np.float32),
}
_SCALAR_DIFF = {"_plus_scalar", "_minus_scalar", "_rminus_scalar",
                "_mul_scalar", "_div_scalar", "_rdiv_scalar",
                "_power_scalar", "_maximum_scalar", "_minimum_scalar",
                "_hypot_scalar"}
for _name, _npfn in _SCALAR.items():
    x = P(2, 3)
    case(_name, x, attrs={"scalar": 1.5},
         ref=(lambda f: (lambda a, scalar=1.5: f(a, scalar)))(_npfn),
         grad=[0] if _name in _SCALAR_DIFF else None)

case("_add_scalar", P(2, 3), attrs={"scalar": 0.5},
     ref=lambda a, scalar=0.5: a + scalar, grad=[0])
case("_sub_scalar", P(2, 3), attrs={"scalar": 0.5},
     ref=lambda a, scalar=0.5: a - scalar, grad=[0])
case("smooth_l1", U(2, 3, lo=-2, hi=2), attrs={"scalar": 1.0},
     ref=lambda x, scalar=1.0: np.where(
         np.abs(x) < 1.0 / scalar ** 2, 0.5 * (x * scalar) ** 2,
         np.abs(x) - 0.5 / scalar ** 2),
     grad=[0])
case("clip", U(2, 3, lo=-2, hi=2), attrs={"a_min": -0.5, "a_max": 0.5},
     ref=lambda x, a_min=-0.5, a_max=0.5: np.clip(x, a_min, a_max))
case("add_n", U(2, 3), U(2, 3), U(2, 3),
     ref=lambda *xs: sum(xs), grad=[0, 1, 2])
case("where", (U(2, 3) > 0).astype(np.float32), U(2, 3), U(2, 3),
     ref=lambda c, x, y: np.where(c != 0, x, y), grad=[1, 2])
case("quadratic", U(2, 3), attrs={"a": 2.0, "b": -1.0, "c": 0.5},
     ref=lambda x, a=2.0, b=-1.0, c=0.5: a * x * x + b * x + c, grad=[0])
case("div_sqrt_dim", U(2, 8),
     ref=lambda x: x / np.sqrt(8.0), grad=[0])

# -- reductions -------------------------------------------------------------
_x_red = U(2, 3, 4)
case("sum", _x_red, attrs={"axis": 1}, ref=lambda x, axis=1: x.sum(axis=1),
     grad=[0])
case("sum", _x_red, attrs={"axis": (0, 2), "keepdims": True},
     ref=lambda x, **kw: x.sum(axis=(0, 2), keepdims=True),
     grad=[0], cid="sum_keepdims")
case("sum", _x_red, attrs={"axis": 1, "exclude": True},
     ref=lambda x, **kw: x.sum(axis=(0, 2)), cid="sum_exclude")
case("sum_axis", _x_red, attrs={"axis": 2},
     ref=lambda x, axis=2: x.sum(axis=2))
case("mean", _x_red, attrs={"axis": 1}, ref=lambda x, axis=1: x.mean(axis=1),
     grad=[0])
case("prod", P(2, 3), attrs={"axis": 1},
     ref=lambda x, axis=1: x.prod(axis=1), grad=[0])
case("max", _x_red, attrs={"axis": 1}, ref=lambda x, axis=1: x.max(axis=1))
case("min", _x_red, attrs={"axis": 1}, ref=lambda x, axis=1: x.min(axis=1))
_x_nan = U(2, 4).copy()
_x_nan[0, 1] = np.nan
case("nansum", _x_nan, attrs={"axis": 1},
     ref=lambda x, axis=1: np.nansum(x, axis=1))
case("nanprod", _x_nan, attrs={"axis": 1},
     ref=lambda x, axis=1: np.nanprod(x, axis=1))
case("norm", U(2, 3), attrs={"ord": 2, "axis": 1},
     ref=lambda x, **kw: np.linalg.norm(x, ord=2, axis=1), grad=[0])
case("norm", U(2, 3), attrs={"ord": 1, "axis": 1},
     ref=lambda x, **kw: np.abs(x).sum(axis=1), cid="norm_l1")
case("argmax", _x_red, attrs={"axis": 1},
     ref=lambda x, axis=1: x.argmax(axis=1).astype(np.float32))
case("argmin", _x_red, attrs={"axis": 1},
     ref=lambda x, axis=1: x.argmin(axis=1).astype(np.float32))
case("argmax_channel", U(3, 5),
     ref=lambda x: x.argmax(axis=1).astype(np.float32))
case("pick", U(3, 4), np.array([0, 2, 1], np.float32), attrs={"axis": 1},
     ref=lambda x, i, axis=1: x[np.arange(3), i.astype(int)], grad=[0])
case("softmax_cross_entropy", U(3, 4), np.array([0, 2, 1], np.float32),
     ref=lambda x, lab: -np.take_along_axis(
         np.log(np.exp(x - x.max(1, keepdims=True))
                / np.exp(x - x.max(1, keepdims=True)).sum(1, keepdims=True)),
         lab.astype(int)[:, None], axis=1).sum(),
     tol=1e-3)

# -- shape / indexing -------------------------------------------------------
_x43 = U(4, 3)
case("reshape", _x43, attrs={"shape": (3, 4)},
     ref=lambda x, shape=(3, 4): x.reshape(3, 4), grad=[0])
case("Reshape", _x43, attrs={"shape": (2, 6)},
     ref=lambda x, shape=(2, 6): x.reshape(2, 6))
case("reshape", _x43, attrs={"shape": (-1, 2)},
     ref=lambda x, shape=None: x.reshape(-1, 2), cid="reshape_infer")
case("transpose", U(2, 3, 4), attrs={"axes": (2, 0, 1)},
     ref=lambda x, axes=None: x.transpose(2, 0, 1), grad=[0])
case("transpose", _x43, ref=lambda x: x.T, cid="transpose_default")
case("expand_dims", _x43, attrs={"axis": 1},
     ref=lambda x, axis=1: x[:, None, :])
case("squeeze", U(3, 1, 2), attrs={"axis": 1},
     ref=lambda x, axis=1: x.squeeze(1))
case("Flatten", U(2, 3, 4), ref=lambda x: x.reshape(2, 12), grad=[0])
case("SwapAxis", U(2, 3, 4), attrs={"dim1": 0, "dim2": 2},
     ref=lambda x, **kw: np.swapaxes(x, 0, 2))
case("flip", U(2, 4), attrs={"axis": 1},
     ref=lambda x, axis=1: x[:, ::-1])
case("tile", _x43, attrs={"reps": (2, 1)},
     ref=lambda x, reps=(2, 1): np.tile(x, (2, 1)), grad=[0])
case("repeat", _x43, attrs={"repeats": 2, "axis": 1},
     ref=lambda x, repeats=2, axis=1: np.repeat(x, 2, axis=1), grad=[0])
case("Pad", U(1, 2, 3, 3),
     attrs={"mode": "constant", "pad_width": (0, 0, 0, 0, 1, 1, 1, 1),
            "constant_value": 0.0},
     ref=lambda x, **kw: np.pad(x, ((0, 0), (0, 0), (1, 1), (1, 1))),
     grad=[0])
case("Pad", U(1, 2, 3, 3),
     attrs={"mode": "edge", "pad_width": (0, 0, 0, 0, 1, 1, 1, 1)},
     ref=lambda x, **kw: np.pad(x, ((0, 0), (0, 0), (1, 1), (1, 1)), "edge"),
     cid="Pad_edge")
case("slice", U(4, 5), attrs={"begin": (1, 0), "end": (3, 4)},
     ref=lambda x, **kw: x[1:3, 0:4], grad=[0])
case("slice", U(4, 6), attrs={"begin": (0, 1), "end": (4, 6), "step": (2, 2)},
     ref=lambda x, **kw: x[::2, 1::2], cid="slice_step")
case("slice_axis", U(4, 5), attrs={"axis": 1, "begin": 1, "end": 4},
     ref=lambda x, **kw: x[:, 1:4], grad=[0])
case("slice_like", U(4, 5), U(2, 3),
     ref=lambda x, y: x[:2, :3])
case("SliceChannel", U(2, 6), attrs={"num_outputs": 3, "axis": 1},
     ref=lambda x, **kw: [x[:, 0:2], x[:, 2:4], x[:, 4:6]])
case("Concat", U(2, 2), U(2, 3), attrs={"dim": 1},
     ref=lambda a, b, dim=1: np.concatenate([a, b], axis=1), grad=[0, 1])
case("stack", U(2, 3), U(2, 3), attrs={"axis": 1},
     ref=lambda a, b, axis=1: np.stack([a, b], axis=1), grad=[0, 1])
case("broadcast_to", U(1, 3), attrs={"shape": (4, 3)},
     ref=lambda x, shape=None: np.broadcast_to(x, (4, 3)), grad=[0])
case("broadcast_axis", U(1, 3), attrs={"axis": 0, "size": 4},
     ref=lambda x, **kw: np.broadcast_to(x, (4, 3)))
case("broadcast_like", U(1, 3), U(4, 3),
     ref=lambda x, y: np.broadcast_to(x, (4, 3)))
case("depth_to_space", U(1, 8, 2, 3), attrs={"block_size": 2},
     check=lambda outs, c: outs[0].shape == (1, 2, 4, 6) or
     pytest.fail("bad d2s shape %s" % (outs[0].shape,)))
case("space_to_depth", U(1, 2, 4, 6), attrs={"block_size": 2},
     check=lambda outs, c: outs[0].shape == (1, 8, 2, 3) or
     pytest.fail("bad s2d shape %s" % (outs[0].shape,)))


def _d2s_roundtrip():
    x = U(1, 8, 2, 3)
    d = _outs_np(run_op("depth_to_space", [x], {"block_size": 2}))[0]
    back = _outs_np(run_op("space_to_depth", [d], {"block_size": 2}))[0]
    assert_almost_equal(back, x)


case("diag", U(3, 3), ref=lambda x: np.diag(x), grad=[0])
case("one_hot", np.array([0, 2, 1], np.float32), attrs={"depth": 4},
     ref=lambda i, depth=4: np.eye(4, dtype=np.float32)[i.astype(int)])
case("gather_nd", U(3, 4), np.array([[0, 2], [1, 3]], np.float32),
     ref=lambda x, i: x[i[0].astype(int), i[1].astype(int)], grad=[0])
case("scatter_nd", np.array([1.5, 2.5], np.float32),
     np.array([[0, 2], [1, 3]], np.float32), attrs={"shape": (3, 4)},
     check=lambda outs, c: assert_almost_equal(
         outs[0][[0, 2], [1, 3]], np.array([1.5, 2.5])))
case("_scatter_set_nd", U(3, 4), np.array([9.0, 8.0], np.float32),
     np.array([[0, 2], [1, 3]], np.float32), attrs={"shape": (3, 4)},
     check=lambda outs, c: assert_almost_equal(
         outs[0][[0, 2], [1, 3]], np.array([9.0, 8.0])))
case("take", U(4, 3), np.array([0, 2], np.float32), attrs={"axis": 0},
     ref=lambda x, i, axis=0: x[i.astype(int)], grad=[0])
case("batch_take", U(3, 4), np.array([0, 2, 1], np.float32),
     ref=lambda x, i: x[np.arange(3), i.astype(int)])
case("Embedding", np.array([[0, 2], [1, 0]], np.float32), U(4, 5),
     attrs={"input_dim": 4, "output_dim": 5},
     ref=lambda i, w, **kw: w[i.astype(int)], grad=[1])
# static-shape TPU semantics: selected rows compacted to the front, rest
# zero-padded to the input size (documented divergence in ops/contrib.py)
case("boolean_mask", U(4, 3), np.array([1, 0, 1, 1], np.float32),
     ref=lambda x, m: np.concatenate(
         [x[m.astype(bool)], np.zeros((1, 3), np.float32)]))
case("index_copy", U(4, 3), np.array([0, 2], np.float32), U(2, 3),
     check=lambda outs, c: assert_almost_equal(
         outs[0][[0, 2]], c.arrays[2]))
case("index_array", U(2, 3),
     check=lambda outs, c: assert_almost_equal(
         outs[0][..., 0], np.arange(2)[:, None] * np.ones((1, 3))))
case("reverse", U(3, 4), attrs={"axis": 0},
     ref=lambda x, axis=0: x[::-1])
case("sort", U(2, 5), attrs={"axis": 1},
     ref=lambda x, axis=1: np.sort(x, axis=1))
case("sort", U(2, 5, lo=0, hi=1), attrs={"axis": 1, "is_ascend": False},
     ref=lambda x, **kw: -np.sort(-x, axis=1), cid="sort_desc")
case("argsort", U(2, 5), attrs={"axis": 1},
     ref=lambda x, **kw: np.argsort(x, axis=1).astype(np.float32))
case("topk", U(2, 6), attrs={"k": 2, "ret_typ": "value"},
     ref=lambda x, **kw: -np.sort(-x, axis=1)[:, :2])
case("topk", U(2, 6), attrs={"k": 2, "ret_typ": "indices"},
     ref=lambda x, **kw: np.argsort(-x, axis=1)[:, :2].astype(np.float32),
     cid="topk_indices")
case("shape_array", U(2, 3),
     ref=lambda x: np.array([2, 3], np.int64), tol=0)
case("size_array", U(2, 3), ref=lambda x: np.array([6], np.int64), tol=0)
case("Cast", U(2, 3), attrs={"dtype": "int32"},
     check=lambda outs, c: outs[0].dtype == np.int32 or
     pytest.fail("cast dtype %s" % outs[0].dtype))
case("_contrib_arange_like", U(2, 3),
     ref=lambda x: np.arange(6, dtype=np.float32).reshape(2, 3))
case("histogram", np.array([0.1, 0.4, 0.6, 0.9, 0.2], np.float32),
     attrs={"bin_cnt": 2, "range": (0.0, 1.0)},
     check=lambda outs, c: assert_almost_equal(
         outs[0], np.array([3, 2], np.float32)))
case("khatri_rao", U(2, 3), U(4, 3),
     check=lambda outs, c: outs[0].shape == (8, 3) or
     pytest.fail("khatri_rao shape %s" % (outs[0].shape,)))

# creation ops
case("_arange", attrs={"start": 1.0, "stop": 7.0, "step": 2.0},
     ref=lambda **kw: np.arange(1.0, 7.0, 2.0, dtype=np.float32))
case("_linspace", attrs={"start": 0.0, "stop": 1.0, "num": 5},
     ref=lambda **kw: np.linspace(0, 1, 5, dtype=np.float32))
case("_eye", attrs={"N": 3, "M": 4, "k": 1},
     ref=lambda **kw: np.eye(3, 4, 1, dtype=np.float32))
case("_full", attrs={"shape": (2, 3), "value": 1.5},
     ref=lambda **kw: np.full((2, 3), 1.5, np.float32))
case("_ones", attrs={"shape": (2, 3)},
     ref=lambda **kw: np.ones((2, 3), np.float32))
case("_zeros", attrs={"shape": (2, 3)},
     ref=lambda **kw: np.zeros((2, 3), np.float32))

# -- matmul family ----------------------------------------------------------
case("dot", U(3, 4), U(4, 2), ref=lambda a, b: a @ b, grad=[0, 1],
     tol=1e-3)
case("dot", U(4, 3), U(4, 2), attrs={"transpose_a": True},
     ref=lambda a, b, **kw: a.T @ b, cid="dot_ta", tol=1e-3)
case("batch_dot", U(2, 3, 4), U(2, 4, 2),
     ref=lambda a, b: np.einsum("bij,bjk->bik", a, b), grad=[0, 1],
     tol=1e-3)

# -- nn ops -----------------------------------------------------------------
case("Activation", U(2, 3, lo=-2, hi=2), attrs={"act_type": "relu"},
     ref=lambda x, act_type=None: np.maximum(x, 0))
case("Activation", U(2, 3), attrs={"act_type": "tanh"},
     ref=lambda x, act_type=None: np.tanh(x), cid="Activation_tanh",
     grad=[0])
case("Activation", U(2, 3), attrs={"act_type": "sigmoid"},
     ref=lambda x, act_type=None: 1 / (1 + np.exp(-x)),
     cid="Activation_sigmoid")
case("Activation", U(2, 3), attrs={"act_type": "softrelu"},
     ref=lambda x, act_type=None: np.log1p(np.exp(x)),
     cid="Activation_softrelu", grad=[0])
case("LeakyReLU", U(2, 3, lo=-2, hi=2), attrs={"act_type": "leaky",
                                               "slope": 0.1},
     ref=lambda x, **kw: np.where(x > 0, x, 0.1 * x), grad=[0])
case("LeakyReLU", U(2, 3, lo=-2, hi=2), attrs={"act_type": "elu",
                                               "slope": 0.5},
     ref=lambda x, **kw: np.where(x > 0, x, 0.5 * np.expm1(x)),
     cid="LeakyReLU_elu")
case("softmax", U(2, 5),
     ref=lambda x, axis=-1: np.exp(x - x.max(-1, keepdims=True))
     / np.exp(x - x.max(-1, keepdims=True)).sum(-1, keepdims=True),
     grad=[0])
case("log_softmax", U(2, 5),
     ref=lambda x, axis=-1: x - x.max(-1, keepdims=True)
     - np.log(np.exp(x - x.max(-1, keepdims=True)).sum(-1, keepdims=True)),
     grad=[0])
# `Softmax` is the reference's deprecated alias of SoftmaxOutput
# (takes data + label) — src/operator/softmax_output.cc
case("Softmax", U(2, 5), np.array([0, 3], np.float32),
     check=lambda outs, c: assert_almost_equal(
         outs[0].sum(axis=1), np.ones(2), rtol=1e-4, atol=1e-4))
case("FullyConnected", U(2, 6), U(4, 6), np.zeros(4, np.float32),
     attrs={"num_hidden": 4},
     ref=lambda x, w, b, **kw: x @ w.T + b, grad=[0, 1, 2], tol=1e-3)
case("Convolution", U(1, 2, 5, 5), U(3, 2, 3, 3), np.zeros(3, np.float32),
     attrs={"kernel": (3, 3), "num_filter": 3}, grad=[0, 1, 2],
     check=lambda outs, c: outs[0].shape == (1, 3, 3, 3) or
     pytest.fail("conv shape %s" % (outs[0].shape,)))
case("Convolution", U(1, 2, 5, 5), U(3, 2, 3, 3),
     attrs={"kernel": (3, 3), "num_filter": 3, "no_bias": True,
            "stride": (2, 2), "pad": (1, 1)},
     cid="Convolution_stride",
     check=lambda outs, c: outs[0].shape == (1, 3, 3, 3) or
     pytest.fail("conv stride shape %s" % (outs[0].shape,)))
case("Deconvolution", U(1, 3, 3, 3), U(3, 2, 3, 3),
     attrs={"kernel": (3, 3), "num_filter": 2, "no_bias": True},
     grad=[0, 1],
     check=lambda outs, c: outs[0].shape == (1, 2, 5, 5) or
     pytest.fail("deconv shape %s" % (outs[0].shape,)))
case("Pooling", U(1, 2, 4, 4), attrs={"kernel": (2, 2), "stride": (2, 2),
                                      "pool_type": "max"},
     grad=[0],
     check=lambda outs, c: outs[0].shape == (1, 2, 2, 2) or
     pytest.fail("pool shape %s" % (outs[0].shape,)))
case("Pooling", U(1, 2, 4, 4), attrs={"kernel": (2, 2), "stride": (2, 2),
                                      "pool_type": "avg"},
     ref=lambda x, **kw: x.reshape(1, 2, 2, 2, 2, 2).mean(axis=(3, 5)),
     cid="Pooling_avg", grad=[0])
case("Pooling", U(1, 2, 4, 4), attrs={"global_pool": True,
                                      "pool_type": "avg"},
     ref=lambda x, **kw: x.mean(axis=(2, 3), keepdims=True),
     cid="Pooling_global")
case("BatchNorm", U(2, 3, 4, 4), np.ones(3, np.float32),
     np.zeros(3, np.float32), np.zeros(3, np.float32),
     np.ones(3, np.float32), attrs={"fix_gamma": False},
     check=lambda outs, c: outs[0].shape == (2, 3, 4, 4) or
     pytest.fail("bn shape"))
case("BatchNormRelu", U(2, 3, 4, 4), np.ones(3, np.float32),
     np.zeros(3, np.float32), np.zeros(3, np.float32),
     np.ones(3, np.float32), attrs={"fix_gamma": False},
     check=lambda outs, c: (outs[0].shape == (2, 3, 4, 4)
                            and float(outs[0].min()) >= 0.0) or
     pytest.fail("bn+relu shape/sign"))
case("BatchNormAddRelu", U(2, 3, 4, 4), U(2, 3, 4, 4),
     np.ones(3, np.float32), np.zeros(3, np.float32),
     np.zeros(3, np.float32), np.ones(3, np.float32),
     attrs={"fix_gamma": False},
     check=lambda outs, c: (outs[0].shape == (2, 3, 4, 4)
                            and float(outs[0].min()) >= 0.0) or
     pytest.fail("bn+add+relu shape/sign"))
case("LayerNorm", U(2, 6), np.ones(6, np.float32), np.zeros(6, np.float32),
     ref=lambda x, g, b, **kw: (x - x.mean(-1, keepdims=True))
     / np.sqrt(x.var(-1, keepdims=True) + 1e-5),
     grad=[0], tol=1e-3)
case("InstanceNorm", U(2, 3, 5), np.ones(3, np.float32),
     np.zeros(3, np.float32),
     check=lambda outs, c: abs(float(outs[0].mean())) < 1e-4 or
     pytest.fail("instancenorm not centered"))
case("L2Normalization", U(2, 4),
     ref=lambda x, **kw: x / np.sqrt((x * x).sum(
         axis=tuple(range(1, x.ndim)), keepdims=True) + 1e-10),
     grad=[0])
case("LRN", U(1, 4, 3, 3), attrs={"nsize": 3},
     check=lambda outs, c: outs[0].shape == (1, 4, 3, 3) or
     pytest.fail("lrn shape"))
case("Dropout", U(2, 3), attrs={"p": 0.5},
     ref=lambda x, **kw: x)  # eval mode = identity
case("SoftmaxOutput", U(3, 4), np.array([0, 2, 1], np.float32),
     check=lambda outs, c: assert_almost_equal(
         outs[0].sum(axis=1), np.ones(3), rtol=1e-4, atol=1e-4))
case("LinearRegressionOutput", U(3, 2), U(3, 2), ref=lambda x, y: x)
case("MAERegressionOutput", U(3, 2), U(3, 2), ref=lambda x, y: x)
case("LogisticRegressionOutput", U(3, 2), U(3, 2),
     ref=lambda x, y: 1 / (1 + np.exp(-x)))
case("SVMOutput", U(3, 4), np.array([0, 2, 1], np.float32),
     ref=lambda x, y, **kw: x)
case("MakeLoss", P(2, 3), ref=lambda x, **kw: x)
case("IdentityAttachKLSparseReg", U(2, 3, lo=0.01, hi=0.99),
     ref=lambda x, **kw: x)
case("SequenceMask", U(3, 2, 4), np.array([1, 3], np.float32),
     attrs={"use_sequence_length": True, "value": 0.0},
     check=lambda outs, c: (abs(outs[0][1, 0]).sum() == 0
                            and abs(outs[0][2, 1]).sum() > 0) or
     pytest.fail("seq mask wrong"))
case("SequenceLast", U(3, 2, 4), np.array([1, 3], np.float32),
     attrs={"use_sequence_length": True},
     check=lambda outs, c: assert_almost_equal(
         outs[0][0], c.arrays[0][0, 0]))
case("SequenceReverse", U(3, 2, 4),
     ref=lambda x: x[::-1])
case("UpSampling", U(1, 2, 3, 3), attrs={"scale": 2,
                                         "sample_type": "nearest"},
     ref=lambda x, **kw: x.repeat(2, axis=2).repeat(2, axis=3))
case("BilinearResize2D", U(1, 2, 3, 3), attrs={"height": 6, "width": 6},
     check=lambda outs, c: outs[0].shape == (1, 2, 6, 6) or
     pytest.fail("resize shape"))
case("AdaptiveAvgPooling2D", U(1, 2, 6, 6), attrs={"output_size": (2, 2)},
     ref=lambda x, **kw: x.reshape(1, 2, 2, 3, 2, 3).mean(axis=(3, 5)))
case("GridGenerator", U(2, 6), attrs={"transform_type": "affine",
                                      "target_shape": (4, 4)},
     check=lambda outs, c: outs[0].shape == (2, 2, 4, 4) or
     pytest.fail("grid shape %s" % (outs[0].shape,)))


def _identity_affine_sampler():
    """BilinearSampler/SpatialTransformer with the identity affine theta
    must reproduce the input (reference semantics test)."""
    x = U(1, 2, 4, 4)
    theta = np.array([[1, 0, 0, 0, 1, 0]], np.float32)
    grid = _outs_np(run_op("GridGenerator", [theta],
                           {"transform_type": "affine",
                            "target_shape": (4, 4)}))[0]
    out = _outs_np(run_op("BilinearSampler", [x, grid], {}))[0]
    assert_almost_equal(out, x, rtol=1e-4, atol=1e-4)
    out2 = _outs_np(run_op("SpatialTransformer", [x, theta],
                           {"target_shape": (4, 4),
                            "transform_type": "affine"}))[0]
    assert_almost_equal(out2, x, rtol=1e-4, atol=1e-4)


case("ROIPooling", P(1, 2, 8, 8), np.array([[0, 0, 0, 7, 7]], np.float32),
     attrs={"pooled_size": (2, 2), "spatial_scale": 1.0},
     check=lambda outs, c: outs[0].shape == (1, 2, 2, 2) or
     pytest.fail("roi shape"))
case("ROIAlign", P(1, 2, 8, 8), np.array([[0, 0, 0, 7, 7]], np.float32),
     attrs={"pooled_size": (2, 2), "spatial_scale": 1.0},
     check=lambda outs, c: outs[0].shape == (1, 2, 2, 2) or
     pytest.fail("roialign shape"))
case("Correlation", U(1, 2, 5, 5), U(1, 2, 5, 5),
     attrs={"kernel_size": 1, "max_displacement": 1, "stride1": 1,
            "stride2": 1, "pad_size": 1},
     check=lambda outs, c: outs[0].shape[0] == 1 or pytest.fail("corr"))
case("CTCLoss", U(4, 2, 5), np.array([[1, 2], [2, 3]], np.float32),
     check=lambda outs, c: (outs[0].shape == (2,)
                            and np.isfinite(outs[0]).all()) or
     pytest.fail("ctc loss %s" % outs[0]))


def _ctc_loss_vs_torch():
    """CTCLoss numerics vs torch.nn.functional.ctc_loss (independent oracle;
    reference used warp-ctc — src/operator/contrib/ctc_loss.cc)."""
    torch = pytest.importorskip("torch")
    T, B, C = 6, 2, 5
    x = U(T, B, C)
    labels = np.array([[1, 2, 0], [3, 1, 2]], np.float32)  # 0 = padding
    out = _outs_np(run_op("CTCLoss", [x, labels], {}))[0]
    logp = torch.log_softmax(torch.tensor(x), dim=-1)
    tl = torch.nn.functional.ctc_loss(
        logp, torch.tensor([[1, 2], [3, 1, 2][0:3]][0]) if False else
        torch.tensor([[1, 2, 0], [3, 1, 2]], dtype=torch.long),
        input_lengths=torch.tensor([T, T]),
        target_lengths=torch.tensor([2, 3]),
        blank=0, reduction="none", zero_infinity=True)
    assert_almost_equal(out, tl.numpy(), rtol=1e-3, atol=1e-3)


# lstm flat param size: gates*H*(in+H+2) = 4*5*(4+5+2) = 220
# (reference: rnn-inl.h GetParamSize)
case("RNN", U(3, 2, 4), U(220), np.zeros((1, 2, 5), np.float32),
     np.zeros((1, 2, 5), np.float32),
     attrs={"state_size": 5, "num_layers": 1, "mode": "lstm"},
     naive=False,
     check=lambda outs, c: outs[0].shape == (3, 2, 5) or
     pytest.fail("rnn shape %s" % (outs[0].shape,)))
case("RNN", U(3, 2, 4), U(1 * 3 * 5 * (4 + 5 + 2)),
     np.zeros((1, 2, 5), np.float32),
     attrs={"state_size": 5, "num_layers": 1, "mode": "gru"},
     naive=False, cid="RNN_gru",
     check=lambda outs, c: outs[0].shape == (3, 2, 5) or
     pytest.fail("gru shape %s" % (outs[0].shape,)))

# -- contrib ----------------------------------------------------------------
case("fft", U(2, 8),
     check=lambda outs, c: assert_almost_equal(
         outs[0].reshape(2, 8, 2)[..., 0], np.fft.fft(c.arrays[0]).real,
         rtol=1e-3, atol=1e-3))
case("ifft", U(2, 16),
     check=lambda outs, c: outs[0].shape == (2, 8) or
     pytest.fail("ifft shape %s" % (outs[0].shape,)))
case("count_sketch", U(2, 6), np.array([0, 1, 2, 0, 1, 2], np.float32),
     np.array([1, -1, 1, -1, 1, -1], np.float32), attrs={"out_dim": 3},
     check=lambda outs, c: outs[0].shape == (2, 3) or
     pytest.fail("sketch shape"))
case("box_iou", np.array([[0, 0, 2, 2]], np.float32),
     np.array([[1, 1, 3, 3]], np.float32),
     ref=lambda a, b, **kw: np.array([[1.0 / 7.0]], np.float32),
     tol=1e-4)
case("box_nms", np.array([[[1, 0.9, 0, 0, 2, 2],
                           [1, 0.8, 0.1, 0.1, 2, 2],
                           [1, 0.7, 5, 5, 7, 7]]], np.float32),
     attrs={"overlap_thresh": 0.5, "coord_start": 2, "score_index": 1,
            "id_index": 0},
     check=lambda outs, c: (outs[0][0, 1, 1] < 0) or
     pytest.fail("nms should suppress 2nd box's score: %s" % outs[0]))
case("MultiBoxPrior", U(1, 2, 4, 4), attrs={"sizes": (0.5,),
                                            "ratios": (1.0,)},
     check=lambda outs, c: outs[0].shape == (1, 16, 4) or
     pytest.fail("prior shape %s" % (outs[0].shape,)))
case("MultiBoxTarget",
     np.array([[[0.1, 0.1, 0.4, 0.4], [0.6, 0.6, 0.9, 0.9]]], np.float32),
     np.array([[[0, 0.1, 0.1, 0.45, 0.45]]], np.float32),
     np.zeros((1, 2, 2), np.float32),
     check=lambda outs, c: len(outs) == 3 or pytest.fail("mbt outs"))
case("MultiBoxDetection",
     np.array([[[0.1, 0.2], [0.8, 0.3]]], np.float32).transpose(0, 2, 1),
     np.zeros((1, 8), np.float32),
     np.array([[[0.1, 0.1, 0.4, 0.4], [0.6, 0.6, 0.9, 0.9]]], np.float32),
     check=lambda outs, c: outs[0].shape[2] == 6 or pytest.fail("mbd"))
case("_contrib_index_array", U(2, 3), attrs={"axes": (1,)},
     ref=lambda x, axes=None: np.broadcast_to(
         np.arange(3, dtype=np.int64)[None, :, None], (2, 3, 1)).astype(np.int64),
     cid="index_array_axes",
     check=None)

# -- linalg -----------------------------------------------------------------
_A = U(3, 3) + 3 * np.eye(3, dtype=np.float32)   # well-conditioned
_SPD = (_A @ _A.T + np.eye(3, dtype=np.float32)).astype(np.float32)
case("linalg_gemm", U(2, 3), U(3, 4), U(2, 4), attrs={"alpha": 0.5,
                                                      "beta": 2.0},
     ref=lambda a, b, c, **kw: 0.5 * a @ b + 2.0 * c, grad=[0, 1, 2],
     tol=1e-3)
case("linalg_gemm2", U(2, 3), U(3, 4),
     ref=lambda a, b, **kw: a @ b, grad=[0, 1], tol=1e-3)
case("linalg_syrk", U(2, 3), attrs={"alpha": 1.0},
     ref=lambda a, **kw: a @ a.T, tol=1e-3)
case("linalg_potrf", _SPD,
     ref=lambda a: np.linalg.cholesky(a), tol=1e-3)
# potri input is the Cholesky factor L; output is inv(L @ L.T)
# (reference: la_op.cc potri semantics)
case("linalg_potri", np.linalg.cholesky(_SPD).astype(np.float32),
     ref=lambda L: np.linalg.inv(L @ L.T), tol=2e-2)
case("linalg_trmm", np.tril(_A).astype(np.float32), U(3, 3),
     ref=lambda a, b, **kw: a @ b, tol=1e-3)
case("linalg_trsm", np.tril(_A).astype(np.float32), U(3, 3),
     ref=lambda a, b, **kw: np.linalg.solve(a, b), tol=1e-2)
case("linalg_det", _A, ref=lambda a: np.linalg.det(a)[None].reshape(()),
     tol=1e-2, check=lambda outs, c: assert_almost_equal(
         outs[0], np.linalg.det(c.arrays[0]), rtol=1e-3, atol=1e-2))
case("linalg_slogdet", _SPD,
     check=lambda outs, c: assert_almost_equal(
         outs[1], np.linalg.slogdet(c.arrays[0])[1], rtol=1e-3, atol=1e-3))
case("linalg_inverse", _A, ref=lambda a: np.linalg.inv(a), tol=1e-2)
case("linalg_extractdiag", U(3, 3), ref=lambda a, **kw: np.diag(a))
case("linalg_makediag", U(3,), ref=lambda a, **kw: np.diag(a))
case("linalg_sumlogdiag", _SPD,
     ref=lambda a: np.log(np.diag(a)).sum().reshape(()), tol=1e-3,
     check=lambda outs, c: assert_almost_equal(
         outs[0], np.log(np.diag(c.arrays[0])).sum(), rtol=1e-3, atol=1e-3))
case("linalg_syevd", _SPD,
     check=lambda outs, c: assert_almost_equal(
         np.sort(outs[1]), np.sort(np.linalg.eigvalsh(c.arrays[0])),
         rtol=1e-3, atol=1e-3))
case("linalg_gelqf", U(2, 4),
     check=lambda outs, c: assert_almost_equal(
         outs[0] @ outs[1], c.arrays[0], rtol=1e-3, atol=1e-3))

# -- random (statistical + determinism checks) ------------------------------

def _stat_check(lo, hi, mean_lo, mean_hi):
    def chk(outs, c):
        o = outs[0].astype(np.float64)
        assert o.shape == tuple(c.attrs.get("shape", o.shape)), o.shape
        assert np.all(o >= lo) and np.all(o <= hi), (o.min(), o.max())
        m = o.mean()
        assert mean_lo <= m <= mean_hi, "mean %s outside [%s, %s]" % (
            m, mean_lo, mean_hi)
    return chk


case("_random_uniform", attrs={"low": 0.0, "high": 1.0, "shape": (500,)},
     naive=False, check=_stat_check(0.0, 1.0, 0.4, 0.6))
case("_random_normal", attrs={"loc": 0.0, "scale": 1.0, "shape": (800,)},
     naive=False, check=_stat_check(-6, 6, -0.15, 0.15))
case("_random_exponential", attrs={"lam": 2.0, "shape": (800,)},
     naive=False, check=_stat_check(0, np.inf, 0.35, 0.65))
case("_random_gamma", attrs={"alpha": 2.0, "beta": 1.0, "shape": (800,)},
     naive=False, check=_stat_check(0, np.inf, 1.7, 2.3))
case("_random_poisson", attrs={"lam": 3.0, "shape": (800,)},
     naive=False, check=_stat_check(0, np.inf, 2.6, 3.4))
case("_random_negative_binomial", attrs={"k": 4, "p": 0.5, "shape": (800,)},
     naive=False, check=_stat_check(0, np.inf, 3.2, 4.8))
case("_random_generalized_negative_binomial",
     attrs={"mu": 2.0, "alpha": 0.4, "shape": (800,)},
     naive=False, check=_stat_check(0, np.inf, 1.5, 2.5))
case("_random_randint", attrs={"low": 0, "high": 10, "shape": (500,)},
     naive=False, check=_stat_check(0, 9, 3.5, 5.5))
case("multinomial", P(3, 4, lo=0.1, hi=1.0), attrs={"shape": (8,)},
     naive=False,
     check=lambda outs, c: (outs[0].shape == (3, 8)
                            and outs[0].min() >= 0
                            and outs[0].max() < 4) or
     pytest.fail("multinomial out %s" % outs[0]))
case("_shuffle", np.arange(12, dtype=np.float32).reshape(12, 1),
     naive=False,
     check=lambda outs, c: assert_almost_equal(
         np.sort(outs[0].ravel()), np.arange(12, dtype=np.float32)))
case("_sample_unique_zipfian", attrs={"range_max": 50, "shape": (1, 20)},
     naive=False,
     check=lambda outs, c: (outs[0].shape == (1, 20)
                            and len(set(outs[0].ravel().tolist())) == 20) or
     pytest.fail("zipfian not unique"))
# temperature<=0 is the greedy contract: exact argmax, rng ignored
case("_sample_token", P(4, 16, lo=-3.0, hi=3.0),
     attrs={"temperature": 0.0}, naive=False,
     check=lambda outs, c: assert_almost_equal(
         outs[0], np.argmax(c.arrays[0], axis=-1).astype(np.int32)))
case("_sample_token", P(4, 16, lo=-3.0, hi=3.0),
     attrs={"temperature": 0.7, "top_k": 3, "top_p": 0.9}, naive=False,
     cid="_sample_token_topk",
     check=lambda outs, c: (outs[0].shape == (4,)
                            and all(o in np.argsort(row)[-3:]
                                    for o, row in zip(outs[0],
                                                      c.arrays[0]))) or
     pytest.fail("top-k sample escaped the top 3: %s" % outs[0]))


def _seeded_rng_reproducible():
    """mx.random.seed makes op-level RNG reproducible (reference: §7(e)
    stateless threefry key plumbing replaces per-op Resource RNG)."""
    mx.random.seed(123)
    a = _fn("_random_uniform")(shape=(16,)).asnumpy()
    mx.random.seed(123)
    b = _fn("_random_uniform")(shape=(16,)).asnumpy()
    c = _fn("_random_uniform")(shape=(16,)).asnumpy()
    assert_almost_equal(a, b)
    assert np.abs(b - c).max() > 1e-6, "consecutive draws identical"


# -- optimizer update kernels ----------------------------------------------
_w, _g = P(4, 3), U(4, 3)


def _sgd_ref(w, g, lr=0.01, wd=0.0, rescale_grad=1.0, **kw):
    return w - lr * (rescale_grad * g + wd * w)


case("sgd_update", _w, _g, attrs={"lr": 0.1, "wd": 0.01},
     check=lambda outs, c: assert_almost_equal(
         outs[0], _sgd_ref(_w, _g, lr=0.1, wd=0.01), rtol=1e-5, atol=1e-5))
case("sgd_mom_update", _w, _g, np.zeros_like(_w),
     attrs={"lr": 0.1, "momentum": 0.9},
     check=lambda outs, c: assert_almost_equal(
         outs[0], _sgd_ref(_w, _g, lr=0.1), rtol=1e-5, atol=1e-5))
case("mp_sgd_update", _w.astype(np.float16), _g.astype(np.float16),
     _w.astype(np.float32), attrs={"lr": 0.1},
     check=lambda outs, c: outs[0].dtype == np.float16 or
     pytest.fail("mp weight dtype %s" % outs[0].dtype))
case("mp_sgd_mom_update", _w.astype(np.float16), _g.astype(np.float16),
     np.zeros_like(_w, np.float32), _w.astype(np.float32),
     attrs={"lr": 0.1},
     check=lambda outs, c: outs[0].dtype == np.float16 or
     pytest.fail("mp mom weight dtype"))


def _adam_ref(w, g, m, v, lr, beta1=0.9, beta2=0.999, eps=1e-8,
              wd=0.0, rescale=1.0):
    g = rescale * g + wd * w
    m2 = beta1 * m + (1 - beta1) * g
    v2 = beta2 * v + (1 - beta2) * g * g
    return w - lr * m2 / (np.sqrt(v2) + eps), m2, v2


case("adam_update", _w, _g, np.zeros_like(_w), np.zeros_like(_w),
     attrs={"lr": 0.1},
     check=lambda outs, c: assert_almost_equal(
         outs[0], _adam_ref(_w, _g, np.zeros_like(_w), np.zeros_like(_w),
                            0.1)[0], rtol=1e-5, atol=1e-5))
for _name, _arrs in {
    "adamw_update": [_w, _g, np.zeros_like(_w), np.zeros_like(_w)],
    "adagrad_update": [_w, _g, np.zeros_like(_w)],
    "adadelta_update": [_w, _g, np.zeros_like(_w), np.zeros_like(_w)],
    "rmsprop_update": [_w, _g, np.zeros_like(_w)],
    "rmspropalex_update": [_w, _g, np.zeros_like(_w), np.zeros_like(_w),
                           np.zeros_like(_w)],
    "ftrl_update": [_w, _g, np.zeros_like(_w), np.zeros_like(_w)],
    "ftml_update": [_w, _g, np.zeros_like(_w), np.zeros_like(_w),
                    np.zeros_like(_w)],
    "nag_mom_update": [_w, _g, np.zeros_like(_w)],
    "signsgd_update": [_w, _g],
    "signum_update": [_w, _g, np.zeros_like(_w)],
}.items():
    case(_name, *_arrs,
         check=(lambda outs, c: (np.isfinite(outs[0]).all()
                                 and np.abs(outs[0] - _w).max() > 1e-8) or
                pytest.fail("%s made no finite update" % c.name)))
# interleaved (w0, g0, w1, g1) — the reference's MultiSGD data layout
case("multi_sgd_update", _w, _g, P(2, 2), U(2, 2),
     attrs={"num_weights": 2, "lrs": (0.1, 0.2), "wds": (0.0, 0.0)},
     check=lambda outs, c: assert_almost_equal(
         outs[0], _w - 0.1 * _g, rtol=1e-5, atol=1e-5))
case("multi_sgd_mom_update", _w, _g, np.zeros_like(_w), P(2, 2), U(2, 2),
     np.zeros((2, 2), np.float32),
     attrs={"num_weights": 2, "lrs": (0.1, 0.2), "wds": (0.0, 0.0),
            "momentum": 0.9},
     check=lambda outs, c: assert_almost_equal(
         outs[0], _w - 0.1 * _g, rtol=1e-5, atol=1e-5))
case("_sparse_adagrad_update", _w, _g, np.zeros_like(_w),
     attrs={"lr": 0.1},
     check=lambda outs, c: np.isfinite(outs[0]).all() or
     pytest.fail("sparse adagrad"))

# -- quantization -----------------------------------------------------------
case("quantize", U(2, 3), np.array([-1.0], np.float32),
     np.array([1.0], np.float32),
     check=lambda outs, c: outs[0].dtype == np.int8 or
     pytest.fail("quantize dtype %s" % outs[0].dtype))
case("quantize_v2", U(2, 3), attrs={"min_calib_range": -1.0,
                                    "max_calib_range": 1.0},
     check=lambda outs, c: outs[0].dtype == np.int8 or
     pytest.fail("quantize_v2 dtype"))
case("dequantize",
     np.array([[-127, 0, 127]], np.int8), np.array([-1.0], np.float32),
     np.array([1.0], np.float32),
     check=lambda outs, c: assert_almost_equal(
         outs[0], np.array([[-1, 0, 1]], np.float32), rtol=1e-2, atol=1e-2))
case("requantize", np.array([[1000, -2000]], np.int32),
     np.array([-10.0], np.float32), np.array([10.0], np.float32),
     attrs={"min_calib_range": -1.0, "max_calib_range": 1.0},
     check=lambda outs, c: outs[0].dtype == np.int8 or
     pytest.fail("requantize dtype"))


def _quantized_dense_roundtrip():
    """quantized_fully_connected ~ fp32 FullyConnected after dequantize."""
    x, w = U(2, 4), U(3, 4)
    b = np.zeros(3, np.float32)
    q = lambda a: np.clip(np.round(a * 127), -127, 127).astype(np.int8)
    mn, mx_ = np.float32(-1), np.float32(1)
    outs = _outs_np(run_op(
        "quantized_fully_connected",
        [q(x), q(w), np.zeros(3, np.int8), mn, mx_, mn, mx_],
        {"num_hidden": 3}))
    fp = x @ w.T + b
    deq = outs[0].astype(np.float32)
    scale = (outs[2] - outs[1]) and None
    # int32 accum output scaled by (1/127)^2
    assert_almost_equal(deq * (1.0 / 127) ** 2, fp, rtol=5e-2, atol=5e-2)


def _quantized_conv_shape():
    x = np.clip(np.round(U(1, 2, 5, 5) * 127), -127, 127).astype(np.int8)
    w = np.clip(np.round(U(3, 2, 3, 3) * 127), -127, 127).astype(np.int8)
    mn, mx_ = np.float32(-1), np.float32(1)
    outs = _outs_np(run_op(
        "quantized_conv",
        [x, w, np.zeros(3, np.int8), mn, mx_, mn, mx_],
        {"kernel": (3, 3), "num_filter": 3, "no_bias": True}))
    assert outs[0].shape == (1, 3, 3, 3)


def _quantized_pooling_matches_fp32():
    """quantized max/avg pooling tracks fp32 pooling of the dequantized
    data; range passes through (reference: quantized_pooling.cc)."""
    x = U(1, 2, 4, 4)
    q = np.clip(np.round(x * 127), -127, 127).astype(np.int8)
    mn, mx_ = np.float32(-1), np.float32(1)
    for ptype in ("max", "avg"):
        outs = _outs_np(run_op(
            "quantized_pooling", [q, mn, mx_],
            {"kernel": (2, 2), "stride": (2, 2), "pool_type": ptype}))
        assert outs[0].dtype == np.int8
        assert outs[1] == mn and outs[2] == mx_
        fp = _outs_np(run_op("Pooling", [x],
                             {"kernel": (2, 2), "stride": (2, 2),
                              "pool_type": ptype}))[0]
        deq = outs[0].astype(np.float32) / 127.0
        assert_almost_equal(deq, fp, rtol=2e-2, atol=2e-2)


def _quantized_act_flatten_pass_through():
    """quantized relu clamps int8 at 0 and keeps thresholds; quantized
    flatten collapses shape only (reference: quantized_activation.cc,
    quantized_flatten-inl.h)."""
    x = U(2, 3, 2, 2)
    q = np.clip(np.round(x * 127), -127, 127).astype(np.int8)
    mn, mx_ = np.float32(-1), np.float32(1)
    outs = _outs_np(run_op("quantized_act", [q, mn, mx_],
                           {"act_type": "relu"}))
    assert outs[0].dtype == np.int8
    np.testing.assert_array_equal(outs[0], np.maximum(q, 0))
    assert outs[1] == mn and outs[2] == mx_
    with pytest.raises(Exception):
        run_op("quantized_act", [q, mn, mx_], {"act_type": "tanh"})

    outs = _outs_np(run_op("quantized_flatten", [q, mn, mx_], {}))
    assert outs[0].shape == (2, 12) and outs[0].dtype == np.int8
    np.testing.assert_array_equal(outs[0], q.reshape(2, 12))
    assert outs[1] == mn and outs[2] == mx_


def _quantized_concat_rescales_to_widest_range():
    """reference: quantized_concat.cc — inputs rescale to the largest
    [min, max]; output carries that range."""
    a, b = U(2, 3), U(2, 3) * 0.5
    qa = np.clip(np.round(a * 127), -127, 127).astype(np.int8)
    # b quantized at range [-0.5, 0.5]: scale 254
    qb = np.clip(np.round(b * 254), -127, 127).astype(np.int8)
    outs = _outs_np(run_op(
        "quantized_concat",
        [qa, qb, np.float32(-1), np.float32(1),
         np.float32(-0.5), np.float32(0.5)],
        {"num_args": 2, "dim": 1}))
    assert outs[0].shape == (2, 6) and outs[0].dtype == np.int8
    assert outs[1] <= -1.0 and outs[2] >= 1.0
    out_scale = 127.0 / max(abs(outs[1]), abs(outs[2]))
    deq = outs[0].astype(np.float32) / out_scale
    assert_almost_equal(deq, np.concatenate([a, b], axis=1),
                        rtol=3e-2, atol=3e-2)


# -- round-2 op additions (VERDICT item: missing ops) -----------------------

def _np_im2col(x, kh, kw, sh, sw, ph, pw):
    n, c, h, w = x.shape
    xp = np.pad(x, ((0, 0), (0, 0), (ph, ph), (pw, pw)))
    oh = (h + 2 * ph - kh) // sh + 1
    ow = (w + 2 * pw - kw) // sw + 1
    cols = np.zeros((n, c * kh * kw, oh * ow), x.dtype)
    for i in range(kh):
        for j in range(kw):
            patch = xp[:, :, i:i + sh * oh:sh, j:j + sw * ow:sw]
            cols[:, (np.arange(c) * kh * kw + i * kw + j)] = \
                patch.reshape(n, c, -1)
    return cols


case("digamma", P(3, 4, lo=0.5, hi=3.0),
     ref=lambda x: __import__("scipy.special",
                              fromlist=["psi"]).psi(x).astype(np.float32),
     grad=[0])
case("hard_sigmoid", U(3, 4, lo=-4, hi=4),
     ref=lambda x: np.clip(0.2 * x + 0.5, 0, 1), grad=[0])
case("hard_sigmoid", U(3, 4, lo=-4, hi=4), attrs={"alpha": 0.5, "beta": 0.1},
     ref=lambda x, **kw: np.clip(0.5 * x + 0.1, 0, 1),
     cid="hard_sigmoid_ab")
case("unravel_index", np.array([0, 5, 11], np.int64),
     attrs={"shape": (3, 4)},
     ref=lambda x, **kw: np.stack(np.unravel_index(x, (3, 4))).astype(x.dtype))
case("ravel_multi_index", np.array([[1, 2], [1, 3]], np.int64),
     attrs={"shape": (3, 4)},
     ref=lambda x, **kw: np.ravel_multi_index(
         tuple(x), (3, 4)).astype(x.dtype))
case("im2col", U(2, 3, 5, 5),
     attrs={"kernel": (3, 3), "stride": (1, 1), "pad": (1, 1)},
     ref=lambda x, **kw: _np_im2col(x, 3, 3, 1, 1, 1, 1), grad=[0])
case("im2col", U(1, 2, 6, 6),
     attrs={"kernel": (2, 2), "stride": (2, 2), "pad": (0, 0)},
     ref=lambda x, **kw: _np_im2col(x, 2, 2, 2, 2, 0, 0),
     cid="im2col_stride")
case("col2im", np.ones((1, 2 * 9, 25), np.float32),
     attrs={"output_size": (5, 5), "kernel": (3, 3), "stride": (1, 1),
            "pad": (1, 1)}, grad=[0],
     check=lambda outs, c: (outs[0].shape == (1, 2, 5, 5)
                            and abs(outs[0][0, 0, 2, 2] - 9.0) < 1e-5)
     or pytest.fail("col2im scatter-add wrong: %s" % outs[0][0, 0]))

case("_contrib_Proposal", P(1, 2 * 6, 4, 4, lo=0.0, hi=1.0),
     U(1, 4 * 6, 4, 4, lo=-0.1, hi=0.1),
     np.array([[64, 64, 1.0]], np.float32),
     attrs={"rpn_pre_nms_top_n": 40, "rpn_post_nms_top_n": 8,
            "feature_stride": 16, "scales": (2, 4), "ratios": (0.5, 1, 2)},
     naive=False,
     check=lambda outs, c: (outs[0].shape == (8, 5)
                            and (outs[0][:, 3] >= outs[0][:, 1]).all()
                            and outs[0][:, 1:].min() >= 0
                            and outs[0][:, 1:].max() <= 63)
     or pytest.fail("Proposal rois invalid: %s" % outs[0]))

_dc_x = U(1, 4, 6, 6)
_dc_w = U(5, 4, 3, 3)
case("_contrib_DeformableConvolution", _dc_x,
     np.zeros((1, 2 * 9, 6, 6), np.float32), _dc_w,
     attrs={"kernel": (3, 3), "pad": (1, 1), "num_filter": 5,
            "no_bias": True}, grad=[0, 2],
     check=lambda outs, c: np.allclose(
         outs[0],
         run_op("Convolution", [c.arrays[0], c.arrays[2]],
                {"kernel": (3, 3), "pad": (1, 1), "num_filter": 5,
                 "no_bias": True}).asnumpy(), atol=1e-4)
     or pytest.fail("deformable(offset=0) != Convolution"))
# offset gradient checked away from integer sampling positions (bilinear
# interpolation is non-differentiable exactly at cell corners — same caveat
# as the reference's finite-difference tests)
case("_contrib_DeformableConvolution", _dc_x,
     U(1, 2 * 9, 6, 6, lo=0.2, hi=0.4), _dc_w,
     attrs={"kernel": (3, 3), "pad": (1, 1), "num_filter": 5,
            "no_bias": True}, grad=[0, 1, 2], grad_tol=5e-2,
     cid="DeformableConvolution_offset_grad")

case("_sample_uniform", np.array([0.0, 10.0], np.float32),
     np.array([1.0, 20.0], np.float32), attrs={"shape": (600,)}, naive=False,
     check=lambda outs, c: (outs[0].shape == (2, 600)
                            and 0.4 < outs[0][0].mean() < 0.6
                            and 14.0 < outs[0][1].mean() < 16.0)
     or pytest.fail("sample_uniform stats %s" % outs[0].mean(axis=1)))
case("_sample_normal", np.array([0.0, 50.0], np.float32),
     np.array([1.0, 2.0], np.float32), attrs={"shape": (800,)}, naive=False,
     check=lambda outs, c: (abs(outs[0][0].mean()) < 0.2
                            and 49.0 < outs[0][1].mean() < 51.0)
     or pytest.fail("sample_normal stats %s" % outs[0].mean(axis=1)))
case("_sample_gamma", np.array([2.0, 4.0], np.float32),
     np.array([1.0, 0.5], np.float32), attrs={"shape": (900,)}, naive=False,
     check=lambda outs, c: (1.6 < outs[0][0].mean() < 2.4
                            and 1.6 < outs[0][1].mean() < 2.4)
     or pytest.fail("sample_gamma stats %s" % outs[0].mean(axis=1)))
case("_sample_exponential", np.array([1.0, 4.0], np.float32),
     attrs={"shape": (900,)}, naive=False,
     check=lambda outs, c: (0.8 < outs[0][0].mean() < 1.25
                            and 0.2 < outs[0][1].mean() < 0.32)
     or pytest.fail("sample_exponential stats %s" % outs[0].mean(axis=1)))
case("_sample_poisson", np.array([1.0, 6.0], np.float32),
     attrs={"shape": (900,)}, naive=False,
     check=lambda outs, c: (0.8 < outs[0][0].mean() < 1.25
                            and 5.3 < outs[0][1].mean() < 6.7)
     or pytest.fail("sample_poisson stats %s" % outs[0].mean(axis=1)))
case("_sample_negative_binomial", np.array([4.0], np.float32),
     np.array([0.5], np.float32), attrs={"shape": (900,)}, naive=False,
     check=lambda outs, c: 3.2 < outs[0][0].mean() < 4.9
     or pytest.fail("sample_nb stats %s" % outs[0].mean()))
case("_sample_generalized_negative_binomial", np.array([3.0], np.float32),
     np.array([0.3], np.float32), attrs={"shape": (900,)}, naive=False,
     check=lambda outs, c: 2.4 < outs[0][0].mean() < 3.7
     or pytest.fail("sample_gnb stats %s" % outs[0].mean()))


def _moe_ref(tok, gw, wi, wo):
    """Dense per-token reference for top-1 switch routing (capacity ample)."""
    logits = tok @ gw.T
    p = np.exp(logits - logits.max(1, keepdims=True))
    p /= p.sum(1, keepdims=True)
    sel = p.argmax(1)
    gv = p.max(1)
    return np.stack([gv[i] * (np.maximum(tok[i] @ wi[sel[i]], 0) @ wo[sel[i]])
                     for i in range(len(tok))])


# strictly-positive tokens/in-weights keep every relu pre-activation away
# from the kink, so the finite-difference oracle is valid
_moe_tok = P(12, 8, lo=0.2, hi=1.0)
_moe_gw = U(4, 8)
_moe_wi = P(4, 8, 16, lo=0.05, hi=0.3)
_moe_wo = U(4, 16, 8)
case("_contrib_switch_moe", _moe_tok, _moe_gw, _moe_wi, _moe_wo,
     attrs={"capacity_factor": 4.0}, grad=[0, 2, 3], naive=True,
     check=lambda outs, c: (np.allclose(
         outs[0], _moe_ref(*c.arrays), atol=1e-4)
         and outs[1].shape == () and outs[1] >= 1.0 - 1e-5)
     or pytest.fail("switch_moe mismatch vs dense routing reference"))


def _topk_moe_ref(tok, gw, wi, wo, k=2):
    """dense top-k routing at unbounded capacity, normalized gates"""
    logits = tok @ gw.T
    p = np.exp(logits - logits.max(1, keepdims=True))
    p /= p.sum(1, keepdims=True)
    out = np.zeros_like(tok)
    for i in range(len(tok)):
        top = np.argsort(-p[i])[:k]
        gv = p[i][top] / p[i][top].sum()
        for g, e in zip(gv, top):
            out[i] += g * (np.maximum(tok[i] @ wi[e], 0) @ wo[e])
    return out


case("_contrib_topk_moe", _moe_tok, _moe_gw, _moe_wi, _moe_wo,
     attrs={"k": 2, "capacity_factor": 8.0}, grad=[0, 2, 3], naive=True,
     check=lambda outs, c: (np.allclose(
         outs[0], _topk_moe_ref(*c.arrays), atol=1e-4)
         and outs[1].shape == () and outs[1] >= 1.0 - 1e-5
         and outs[2].shape == () and outs[2] >= 0.0)
     or pytest.fail("topk_moe mismatch vs dense top-2 routing reference"))


# ---------------------------------------------------------------------------
# exclusions (name -> reason). Every registry op must be swept or listed.
# ---------------------------------------------------------------------------

EXCLUDED = {
    "Custom": "needs a user-registered python op; covered by "
              "tests/test_custom_op.py",
    "_contrib_flash_attention": "pallas kernel; numerics covered by "
                                "tests/test_pallas.py",
    "_contrib_boolean_mask": "alias of boolean_mask (swept)",
    "_contrib_count_sketch": "alias of count_sketch (swept)",
    "_contrib_fft": "alias of fft (swept)",
    "_contrib_ifft": "alias of ifft (swept)",
    "_contrib_div_sqrt_dim": "alias of div_sqrt_dim (swept)",
    "_contrib_quadratic": "alias of quadratic (swept)",
    "_contrib_index_copy": "alias of index_copy (swept)",
    "_contrib_box_iou": "alias of box_iou (swept)",
    "_contrib_box_nms": "alias of box_nms (swept)",
    "_contrib_arange_like": "swept as _contrib_arange_like case",
    "_contrib_AdaptiveAvgPooling2D": "alias of AdaptiveAvgPooling2D (swept)",
    "_contrib_BilinearResize2D": "alias of BilinearResize2D (swept)",
    "_contrib_CTCLoss": "alias of CTCLoss (swept)",
    "_contrib_MultiBoxPrior": "alias of MultiBoxPrior (swept)",
    "_contrib_MultiBoxTarget": "alias of MultiBoxTarget (swept)",
    "_contrib_MultiBoxDetection": "alias of MultiBoxDetection (swept)",
    "_contrib_ROIAlign": "alias of ROIAlign (swept)",
    "_contrib_quantize": "alias of quantize (swept)",
    "_contrib_quantize_v2": "alias of quantize_v2 (swept)",
    "_contrib_dequantize": "alias of dequantize (swept)",
    "_contrib_requantize": "alias of requantize (swept)",
    "_contrib_quantized_conv": "quantized conv roundtrip test below",
    "_contrib_quantized_pooling": "quantized pooling test below",
    "quantized_pooling": "alias of _contrib_quantized_pooling",
    "_contrib_quantized_concat": "quantized concat test below",
    "quantized_concat": "alias of _contrib_quantized_concat",
    "_image_to_tensor": "image op family test below",
    "to_tensor": "alias of _image_to_tensor",
    "_image_normalize": "image op family test below",
    "image_normalize": "alias of _image_normalize",
    "_image_resize": "image op family test below",
    "image_resize": "alias of _image_resize",
    "_image_crop": "image op family test below",
    "image_crop": "alias of _image_crop",
    "_contrib_quantized_act": "quantized act/flatten test below",
    "quantized_act": "alias of _contrib_quantized_act",
    "_contrib_quantized_activation": "alias of _contrib_quantized_act",
    "_contrib_quantized_flatten": "quantized act/flatten test below",
    "quantized_flatten": "alias of _contrib_quantized_flatten",
    "_contrib_dgl_csr_neighbor_uniform_sample": "dgl suite (test_dgl.py)",
    "dgl_csr_neighbor_uniform_sample": "dgl suite (test_dgl.py)",
    "_contrib_dgl_csr_neighbor_non_uniform_sample": "dgl suite (test_dgl.py)",
    "dgl_csr_neighbor_non_uniform_sample": "dgl suite (test_dgl.py)",
    "_contrib_dgl_subgraph": "dgl suite (test_dgl.py)",
    "dgl_subgraph": "dgl suite (test_dgl.py)",
    "_contrib_edge_id": "dgl suite (test_dgl.py)",
    "edge_id": "dgl suite (test_dgl.py)",
    "_contrib_dgl_adjacency": "dgl suite (test_dgl.py)",
    "dgl_adjacency": "dgl suite (test_dgl.py)",
    "_contrib_dgl_graph_compact": "dgl suite (test_dgl.py)",
    "dgl_graph_compact": "dgl suite (test_dgl.py)",
    "_rnn_state_zeros": "mx.rnn begin_state plumbing (test_rnn_cells.py)",
    "_rnn_fused_state_zeros": "mx.rnn begin_state plumbing "
                              "(test_rnn_cells.py)",
    "_contrib_quantized_fully_connected": "quantized dense roundtrip test "
                                          "below",
    "_contrib_adamw_update": "alias of adamw_update (swept)",
    "_sample_multinomial": "alias of multinomial (swept)",
    # the decoder-LM blocks of PR 39: the zoo block and the engine built on
    # them are held to the plain reference's logits in tests/test_lfm2.py
    "RMSNorm": "LM block; numerics covered by tests/test_lfm2.py",
    "_contrib_rope": "LM block; numerics covered by tests/test_lfm2.py",
    "_contrib_swiglu_ffn": "LM block; numerics covered by "
                           "tests/test_lfm2.py",
    "_contrib_short_conv": "LM block; numerics covered by "
                           "tests/test_lfm2.py",
    "_contrib_gated_short_conv": "LM block; numerics covered by "
                                 "tests/test_lfm2.py",
    "_contrib_causal_attention": "LM block; numerics covered by "
                                 "tests/test_lfm2.py",
    "_contrib_sigmoid_topk_moe": "drop-free expert layer (grouped product "
                                 "through a pallas kernel); numerics "
                                 "covered by tests/test_lfm2.py",
}

_ALIAS_OK = set()
for _c in CASES:
    _ALIAS_OK.add(_c.name)
    _ALIAS_OK.add(ops.get(_c.name).name)   # canonical name of the case's op
# swept by standalone structural tests below rather than table cases
_ALIAS_OK.update({"BilinearSampler", "SpatialTransformer"})


def test_registry_fully_covered():
    missing = []
    for name in ops.list_ops():
        canon = ops.get(name).name
        if name in EXCLUDED or canon in EXCLUDED:
            continue
        if name in _ALIAS_OK or canon in _ALIAS_OK:
            continue
        missing.append(name)
    assert not missing, (
        "ops with no sweep case and no exclusion reason: %s" % missing)


# ---------------------------------------------------------------------------
# the sweep
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("c", CASES, ids=lambda c: c.cid)
def test_forward(c):
    res = run_op(c.name, c.arrays, c.attrs)
    outs = _outs_np(res)
    assert len(outs) >= 1
    if c.ref is not None:
        expected = c.ref(*c.arrays, **c.attrs)
        expected = expected if isinstance(expected, list) else [expected]
        for o, e in zip(outs, expected):
            e = np.asarray(e)
            assert o.shape == tuple(e.shape), (
                "%s: shape %s vs expected %s" % (c.cid, o.shape, e.shape))
            assert_almost_equal(o, e, rtol=max(c.tol, 1e-7),
                                atol=max(c.tol, 1e-7),
                                names=("out", "expected"))
    else:
        for o in outs:
            if np.issubdtype(o.dtype, np.floating):
                assert np.isfinite(o).all(), "%s: non-finite fwd" % c.cid
    if c.check is not None:
        c.check(outs, c)


_GRAD_CASES = [c for c in CASES if c.grad]


@pytest.mark.parametrize("c", _GRAD_CASES, ids=lambda c: c.cid)
def test_numeric_gradient(c):
    f = _fn(c.name)

    def loss_np(arrs):
        outs = _outs_np(run_op(c.name, arrs, c.attrs))
        return float(sum(np.asarray(o, np.float64).sum() for o in outs))

    # autograd side
    nds = [_to_nd(a) for a in c.arrays]
    for i in c.grad:
        nds[i].attach_grad()
    mx.random.seed(77)
    with autograd.record():
        res = f(*nds, **c.attrs)
        outs = _outs(res)
        loss = outs[0].sum()
        for o in outs[1:]:
            loss = loss + o.sum()
    loss.backward()

    eps = 1e-2
    for i in c.grad:
        a = c.arrays[i].astype(np.float64)
        num = np.zeros_like(a)
        flat, nflat = a.reshape(-1), num.reshape(-1)
        for j in range(flat.size):
            old = flat[j]
            arrs = [x.copy() for x in c.arrays]
            arrs[i] = a.astype(np.float32)
            af = arrs[i].reshape(-1)
            af[j] = old + eps
            fp = loss_np(arrs)
            af[j] = old - eps
            fm = loss_np(arrs)
            nflat[j] = (fp - fm) / (2 * eps)
        got = nds[i].grad.asnumpy()
        assert_almost_equal(num, got, rtol=c.grad_tol, atol=c.grad_tol,
                            names=("numeric_arg%d" % i, "autograd_arg%d" % i))


_NAIVE_CASES = [c for c in CASES if c.naive]


@pytest.mark.parametrize("c", _NAIVE_CASES, ids=lambda c: c.cid)
def test_naive_vs_jit(c):
    jit_outs = _outs_np(run_op(c.name, c.arrays, c.attrs))
    with engine.naive_engine():
        naive_outs = _outs_np(run_op(c.name, c.arrays, c.attrs))
    assert len(jit_outs) == len(naive_outs)
    for a, b in zip(jit_outs, naive_outs):
        if np.issubdtype(a.dtype, np.floating):
            assert_almost_equal(a, b, rtol=1e-5, atol=1e-5,
                                names=("jit", "naive"))
        else:
            assert (np.asarray(a) == np.asarray(b)).all(), c.cid


# ---------------------------------------------------------------------------
# structural/standalone checks referenced from the tables above
# ---------------------------------------------------------------------------

def test_blockgrad_zero_grad():
    _blockgrad_zero_grad()


def test_depth_space_roundtrip():
    _d2s_roundtrip()


def test_identity_affine_sampler():
    _identity_affine_sampler()


def test_ctc_loss_vs_torch():
    _ctc_loss_vs_torch()


def test_seeded_rng_reproducible():
    _seeded_rng_reproducible()


def test_rng_chain_survives_outer_jit():
    """Tracing an eager rng-consuming op under an OUTER jax.jit (e.g.
    jitting a model forward that contains Dropout) must not persist staged
    tracers into the global key chain — regression: the poisoned chain made
    every later trace fail with a leaked-tracer error."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu import random as _rnd
    from mxnet_tpu.ndarray import NDArray

    mx.random.seed(7)

    def f(x):
        # inference-mode Dropout: identity output, but the invoke layer
        # still draws a key for the rng-consuming opdef
        return mx.nd.Dropout(NDArray(x), p=0.5)._data

    xj = jnp.ones((4, 4), jnp.float32)
    jax.jit(f)(xj)
    assert not isinstance(_rnd._get().key, jax.core.Tracer)
    jax.jit(lambda x: f(x) + 1.0)(xj)  # second trace used to raise
    # the eager chain still works and stays reproducible
    mx.random.seed(7)
    a = mx.nd.random.uniform(shape=(3,)).asnumpy()
    mx.random.seed(7)
    b = mx.nd.random.uniform(shape=(3,)).asnumpy()
    np.testing.assert_array_equal(a, b)


def test_quantized_dense_roundtrip():
    _quantized_dense_roundtrip()


def test_quantized_conv_shape():
    _quantized_conv_shape()


def test_quantized_pooling_matches_fp32():
    _quantized_pooling_matches_fp32()


def test_quantized_concat_rescales():
    _quantized_concat_rescales_to_widest_range()


def test_quantized_act_flatten():
    _quantized_act_flatten_pass_through()


def test_image_op_family():
    """mx.nd.image.* namespace (reference src/operator/image/):
    to_tensor HWC->CHW [0,1]; per-channel normalize; resize (int /
    (w,h) / keep_ratio); fixed-window crop; batched variants."""
    rng = np.random.RandomState(0)
    raw = rng.randint(0, 255, (8, 6, 3)).astype(np.uint8)
    img = mx.nd.array(raw, dtype="uint8")

    t = mx.nd.image.to_tensor(img)
    assert t.shape == (3, 8, 6) and t.dtype == np.float32
    np.testing.assert_allclose(t.asnumpy(),
                               raw.transpose(2, 0, 1) / 255.0, rtol=1e-6)
    batch = mx.nd.array(raw[None], dtype="uint8")
    assert mx.nd.image.to_tensor(batch).shape == (1, 3, 8, 6)

    n = mx.nd.image.normalize(t, mean=(0.5, 0.4, 0.3), std=(0.2, 0.2, 0.2))
    np.testing.assert_allclose(
        n.asnumpy(),
        (raw.transpose(2, 0, 1) / 255.0
         - np.array([0.5, 0.4, 0.3])[:, None, None]) / 0.2,
        rtol=1e-5, atol=1e-6)

    r = mx.nd.image.resize(img, size=4)
    assert r.shape == (4, 4, 3)
    rk = mx.nd.image.resize(img, size=4, keep_ratio=True)
    assert rk.shape == (5, 4, 3)  # short side (w=6) -> 4, h scales to 5
    rwh = mx.nd.image.resize(img, size=(2, 6))  # (w, h)
    assert rwh.shape == (6, 2, 3)

    c = mx.nd.image.crop(img, x=1, y=2, width=3, height=4)
    np.testing.assert_array_equal(c.asnumpy(), raw[2:6, 1:4])

    # normalize demands a float input (int mean/std would truncate to 0)
    with pytest.raises(mx.base.MXNetError, match="float"):
        mx.nd.image.normalize(img, mean=(0.5,), std=(0.2,))
    # size is required
    with pytest.raises(mx.base.MXNetError, match="size"):
        mx.nd.image.resize(img)

    # flat op namespaces exist too (reference nd/op.py + symbol/op.py)
    assert mx.nd.op.relu is mx.nd.relu
    assert hasattr(mx.sym.op, "FullyConnected")
    # and the legacy torch aliases (reference __init__.py `as th`)
    assert hasattr(mx, "torch") and hasattr(mx, "th")
