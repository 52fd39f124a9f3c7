"""Registry-wide operator sweep.

Modeled on the reference's tests/python/unittest/test_operator.py: every
registered op runs forward on small inputs, differentiable ops additionally
pass check_numeric_gradient (finite differences vs the tape), and ops with a
numpy counterpart are value-checked against it.

Coverage is ENFORCED: an op registered without a sweep spec (and not in the
reasoned exemption table) fails test_every_op_has_spec — nothing is skipped
silently.
"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.ops.registry import _REGISTRY
from mxnet_tpu.ops import apply_op
from mxnet_tpu.test_utils import assert_almost_equal, check_numeric_gradient

RNG = np.random.RandomState(7)


def _canonical_ops():
    """Built-in op library only: ops registered at runtime through the
    custom-op bridge (mx.operator.register in other tests / user code) are
    dynamic and not part of the sweep contract."""
    return {op.name: op for op in _REGISTRY.values()
            if getattr(op.fn, "__module__", "").startswith("mxnet_tpu.ops")}


# ---------------------------------------------------------------- builders

def rnd(*s):
    return (RNG.randn(*s) * 0.5).astype(np.float32)


def pos(*s):
    return RNG.uniform(0.5, 1.5, s).astype(np.float32)


def unit(*s):
    return RNG.uniform(-0.8, 0.8, s).astype(np.float32)


def gt1(*s):
    return RNG.uniform(1.2, 2.0, s).astype(np.float32)


def probs(*s):
    x = RNG.uniform(0.1, 1.0, s)
    return (x / x.sum(axis=-1, keepdims=True)).astype(np.float32)


SPECS = {}


def spec(name, inputs=(), attrs=None, ref=None, grad=None, fwd_only=None,
         rtol=1e-4):
    """Register a sweep spec.  fwd_only gives the REASON gradient checking
    is skipped for a differentiable op (non-smooth point, stochastic, ...).
    Keys are canonicalized so a spec under an alias covers the op."""
    canon = _REGISTRY[name].name if name in _REGISTRY else name
    SPECS[canon] = dict(inputs=inputs, attrs=attrs or {}, ref=ref, grad=grad,
                        fwd_only=fwd_only, rtol=rtol)


# --------------------------------------------------------- unary elementwise

_UNARY = {
    "negative": (rnd, np.negative), "abs": (rnd, np.abs),
    "sign": (rnd, np.sign), "exp": (rnd, np.exp), "expm1": (rnd, np.expm1),
    "sin": (rnd, np.sin), "cos": (rnd, np.cos),
    "sinh": (rnd, np.sinh), "cosh": (rnd, np.cosh), "tanh": (rnd, np.tanh),
    "arctan": (rnd, np.arctan), "arcsinh": (rnd, np.arcsinh),
    "degrees": (rnd, np.degrees), "radians": (rnd, np.radians),
    "sigmoid": (rnd, lambda x: 1 / (1 + np.exp(-x))),
    "softsign": (rnd, lambda x: x / (1 + np.abs(x))),
    "square": (rnd, np.square),
    "erf": (rnd, None),
    "log": (pos, np.log), "log10": (pos, np.log10), "log2": (pos, np.log2),
    "log1p": (pos, np.log1p), "sqrt": (pos, np.sqrt),
    "rsqrt": (pos, lambda x: 1 / np.sqrt(x)), "cbrt": (pos, np.cbrt),
    "rcbrt": (pos, lambda x: 1 / np.cbrt(x)),
    "reciprocal": (pos, np.reciprocal),
    "gammaln": (pos, None), "gamma": (pos, None), "digamma": (pos, None),
    "arcsin": (unit, np.arcsin), "arccos": (unit, np.arccos),
    "arctanh": (unit, np.arctanh), "erfinv": (unit, None),
    "tan": (unit, np.tan), "arccosh": (gt1, np.arccosh),
}
for _name, (_mk, _ref) in _UNARY.items():
    spec(_name, inputs=(lambda mk=_mk: [mk(3, 4)]),
         ref=(lambda x, _r=_ref, **_: _r(x)) if _ref else None)

# sign/abs have kinks at 0 but our samples avoid exact 0; sign's grad is 0
spec("sign", inputs=lambda: [pos(3, 4)], ref=lambda x, **_: np.sign(x),
     fwd_only="piecewise-constant: numeric fd is 0/undefined at any eps")

_UNARY_NODIFF = {
    "rint": np.rint, "ceil": np.ceil, "floor": np.floor, "trunc": np.trunc,
    "round": np.round,
    "logical_not": lambda x: np.logical_not(x).astype(np.float32),
    "isnan": lambda x: np.isnan(x).astype(np.float32),
    "isinf": lambda x: np.isinf(x).astype(np.float32),
    "isfinite": lambda x: np.isfinite(x).astype(np.float32),
}
for _name, _ref in _UNARY_NODIFF.items():
    spec(_name, inputs=lambda: [rnd(3, 4)],
         ref=(lambda x, _r=_ref, **_: _r(x)))

spec("relu", inputs=lambda: [pos(3, 4)], ref=lambda x, **_: np.maximum(x, 0))
spec("clip", inputs=lambda: [rnd(3, 4)], attrs={"a_min": -0.3, "a_max": 0.3},
     ref=lambda x, **a: np.clip(x, -0.3, 0.3),
     fwd_only="kinked at clip bounds; fd across the kink is wrong")
spec("cast", inputs=lambda: [rnd(3, 4)], attrs={"dtype": "float64"},
     ref=lambda x, **_: x.astype(np.float64))
spec("smooth_l1", inputs=lambda: [rnd(3, 4)], attrs={"scalar": 1.0})

# ------------------------------------------------------------------ binary

_BINARY = {
    "broadcast_add": np.add, "broadcast_sub": np.subtract,
    "broadcast_mul": np.multiply, "broadcast_maximum": np.maximum,
    "broadcast_minimum": np.minimum, "broadcast_hypot": np.hypot,
    "arctan2": np.arctan2,
}
for _name, _ref in _BINARY.items():
    spec(_name, inputs=lambda: [rnd(3, 4), rnd(3, 4)],
         ref=(lambda a, b, _r=_ref, **_: _r(a, b)),
         fwd_only=("max/min kink when operands cross"
                   if "max" in _name or "min" in _name else None))
# atan2 is smooth only away from the negative-x branch cut: keep x positive
spec("arctan2", inputs=lambda: [rnd(3, 4), pos(3, 4)],
     ref=lambda a, b, **_: np.arctan2(a, b))
# hypot's gradient is ill-conditioned near the origin: bound operands away
spec("broadcast_hypot", inputs=lambda: [pos(3, 4), pos(3, 4)],
     ref=lambda a, b, **_: np.hypot(a, b))
spec("broadcast_div", inputs=lambda: [rnd(3, 4), pos(3, 4)],
     ref=lambda a, b, **_: a / b)
spec("broadcast_power", inputs=lambda: [pos(3, 4), rnd(3, 4)],
     ref=lambda a, b, **_: a ** b)
spec("broadcast_mod", inputs=lambda: [pos(3, 4) * 3, pos(3, 4)],
     ref=lambda a, b, **_: np.mod(a, b),
     fwd_only="step discontinuities at multiples of the divisor")

_CMP = {
    "broadcast_equal": np.equal, "broadcast_not_equal": np.not_equal,
    "broadcast_greater": np.greater,
    "broadcast_greater_equal": np.greater_equal,
    "broadcast_lesser": np.less, "broadcast_lesser_equal": np.less_equal,
    "broadcast_logical_and": np.logical_and,
    "broadcast_logical_or": np.logical_or,
    "broadcast_logical_xor": np.logical_xor,
}
for _name, _ref in _CMP.items():
    spec(_name, inputs=lambda: [rnd(3, 4), rnd(3, 4)],
         ref=(lambda a, b, _r=_ref, **_: _r(a, b).astype(np.float32)))

# -------------------------------------------------------------- reductions

for _name, _np_fn in [("sum", np.sum), ("mean", np.mean),
                      ("prod", np.prod), ("nansum", np.nansum),
                      ("nanprod", np.nanprod)]:
    spec(_name, inputs=lambda: [pos(3, 4)], attrs={"axis": 1},
         ref=(lambda x, _r=_np_fn, **_: _r(x, axis=1)))
for _name, _np_fn in [("max", np.max), ("min", np.min)]:
    spec(_name, inputs=lambda: [rnd(3, 4)], attrs={"axis": 1},
         ref=(lambda x, _r=_np_fn, **_: _r(x, axis=1)),
         fwd_only="argmax ties make fd unstable")
spec("norm", inputs=lambda: [pos(3, 4)], attrs={"ord": 2},
     ref=lambda x, **_: np.sqrt((x ** 2).sum()))
spec("logsumexp", inputs=lambda: [rnd(3, 4)], attrs={"axis": 1},
     ref=lambda x, **_: np.log(np.exp(x).sum(axis=1)))
spec("argmax", inputs=lambda: [rnd(3, 4)], attrs={"axis": 1},
     ref=lambda x, **_: np.argmax(x, axis=1).astype(np.float32))
spec("argmin", inputs=lambda: [rnd(3, 4)], attrs={"axis": 1},
     ref=lambda x, **_: np.argmin(x, axis=1).astype(np.float32))
spec("moments", inputs=lambda: [rnd(3, 4)], attrs={"axes": (0, 1)})

# ---------------------------------------------------------------- shape ops

spec("reshape", inputs=lambda: [rnd(3, 4)], attrs={"shape": (4, 3)},
     ref=lambda x, **_: x.reshape(4, 3))
spec("transpose", inputs=lambda: [rnd(3, 4)],
     ref=lambda x, **_: x.T)
spec("swapaxes", inputs=lambda: [rnd(2, 3, 4)], attrs={"dim1": 0, "dim2": 2},
     ref=lambda x, **_: np.swapaxes(x, 0, 2))
spec("flatten", inputs=lambda: [rnd(2, 3, 4)],
     ref=lambda x, **_: x.reshape(2, 12))
spec("expand_dims", inputs=lambda: [rnd(3, 4)], attrs={"axis": 1},
     ref=lambda x, **_: x[:, None])
spec("squeeze", inputs=lambda: [rnd(3, 1, 4)],
     ref=lambda x, **_: x.squeeze())
spec("broadcast_to", inputs=lambda: [rnd(1, 4)], attrs={"shape": (3, 4)},
     ref=lambda x, **_: np.broadcast_to(x, (3, 4)))
spec("broadcast_axis", inputs=lambda: [rnd(1, 4)],
     attrs={"axis": 0, "size": 3},
     ref=lambda x, **_: np.broadcast_to(x, (3, 4)))
spec("broadcast_like", inputs=lambda: [rnd(1, 4), rnd(3, 4)],
     ref=lambda a, b, **_: np.broadcast_to(a, b.shape))
spec("reshape_like", inputs=lambda: [rnd(3, 4), rnd(4, 3)],
     ref=lambda a, b, **_: a.reshape(4, 3))
spec("tile", inputs=lambda: [rnd(2, 3)], attrs={"reps": (2, 2)},
     ref=lambda x, **_: np.tile(x, (2, 2)))
spec("repeat", inputs=lambda: [rnd(2, 3)], attrs={"repeats": 2, "axis": 1},
     ref=lambda x, **_: np.repeat(x, 2, axis=1))
spec("pad", inputs=lambda: [rnd(1, 1, 3, 3)],
     attrs={"mode": "constant", "pad_width": (0, 0, 0, 0, 1, 1, 1, 1)})
spec("concat", inputs=lambda: [rnd(2, 3), rnd(2, 3)], attrs={"dim": 1},
     ref=lambda a, b, **_: np.concatenate([a, b], axis=1))
spec("stack", inputs=lambda: [rnd(2, 3), rnd(2, 3)], attrs={"axis": 0},
     ref=lambda a, b, **_: np.stack([a, b]))
spec("split", inputs=lambda: [rnd(2, 4)],
     attrs={"num_outputs": 2, "axis": 1})
spec("slice_axis", inputs=lambda: [rnd(3, 4)],
     attrs={"axis": 1, "begin": 1, "end": 3},
     ref=lambda x, **_: x[:, 1:3])
spec("slice", inputs=lambda: [rnd(3, 4)],
     attrs={"begin": (0, 1), "end": (2, 3)},
     ref=lambda x, **_: x[0:2, 1:3])
spec("slice_like", inputs=lambda: [rnd(3, 4), rnd(2, 2)],
     ref=lambda a, b, **_: a[:2, :2])
spec("_slice_index", inputs=lambda: [rnd(3, 4)], attrs={"index": 1})
spec("reverse", inputs=lambda: [rnd(3, 4)], attrs={"axis": 0},
     ref=lambda x, **_: x[::-1])
spec("diag", inputs=lambda: [rnd(4, 4)],
     ref=lambda x, **_: np.diag(x))
spec("zeros_like", inputs=lambda: [rnd(3, 4)],
     ref=lambda x, **_: np.zeros_like(x))
spec("ones_like", inputs=lambda: [rnd(3, 4)],
     ref=lambda x, **_: np.ones_like(x))
spec("full_like", inputs=lambda: [rnd(3, 4)], attrs={"fill_value": 2.5},
     ref=lambda x, **_: np.full_like(x, 2.5))
spec("shape_array", inputs=lambda: [rnd(3, 4)],
     ref=lambda x, **_: np.array([3, 4]))
spec("size_array", inputs=lambda: [rnd(3, 4)],
     ref=lambda x, **_: np.array([12]))
spec("cumsum", inputs=lambda: [rnd(3, 4)], attrs={"axis": 1},
     ref=lambda x, **_: np.cumsum(x, axis=1))
spec("cumprod", inputs=lambda: [pos(3, 4)], attrs={"axis": 1},
     ref=lambda x, **_: np.cumprod(x, axis=1))
spec("depth_to_space", inputs=lambda: [rnd(1, 8, 2, 2)],
     attrs={"block_size": 2})
spec("space_to_depth", inputs=lambda: [rnd(1, 2, 4, 4)],
     attrs={"block_size": 2})
spec("where", inputs=lambda: [
    (RNG.rand(3, 4) > 0.5).astype(np.float32), rnd(3, 4), rnd(3, 4)],
     ref=lambda c, a, b, **_: np.where(c > 0, a, b),
     fwd_only="condition input is boolean; fd on it is meaningless")

# ---------------------------------------------------------------- indexing

spec("take", inputs=lambda: [rnd(5, 3), np.array([0, 2, 4], np.float32)],
     attrs={"axis": 0}, ref=lambda x, i, **_: x[i.astype(int)],
     fwd_only="integer index input breaks uniform fd")
spec("Embedding", inputs=lambda: [np.array([0, 2, 1], np.float32),
                                  rnd(4, 5)],
     attrs={"input_dim": 4, "output_dim": 5},
     ref=lambda i, w, **_: w[i.astype(int)],
     fwd_only="integer index input breaks uniform fd")
spec("one_hot", inputs=lambda: [np.array([0, 2], np.float32)],
     attrs={"depth": 3},
     ref=lambda i, **_: np.eye(3, dtype=np.float32)[i.astype(int)])
spec("pick", inputs=lambda: [rnd(3, 4), np.array([0, 1, 2], np.float32)],
     attrs={"axis": 1},
     ref=lambda x, i, **_: x[np.arange(3), i.astype(int)],
     fwd_only="integer index input breaks uniform fd")
spec("gather_nd", inputs=lambda: [rnd(3, 4),
                                  np.array([[0, 2], [1, 3]], np.float32)],
     ref=lambda x, i, **_: x[i[0].astype(int), i[1].astype(int)],
     fwd_only="integer index input breaks uniform fd")
spec("scatter_nd", inputs=lambda: [rnd(2),
                                   np.array([[0, 2], [1, 3]], np.float32)],
     attrs={"shape": (3, 4)}, fwd_only="integer index input breaks fd")
spec("take_along_axis", inputs=lambda: [rnd(3, 4),
                                        np.zeros((3, 1), np.float32)],
     attrs={"axis": 1}, fwd_only="integer index input breaks uniform fd")
spec("boolean_mask", inputs=lambda: [rnd(4, 3),
                                     np.array([1, 0, 1, 1], np.float32)])
spec("batch_take", inputs=lambda: [rnd(3, 4),
                                   np.array([0, 2, 1], np.float32)],
     ref=lambda x, i, **_: x[np.arange(3), i.astype(int)],
     fwd_only="integer index input breaks uniform fd")
spec("sort", inputs=lambda: [rnd(3, 4)], attrs={"axis": 1},
     ref=lambda x, **_: np.sort(x, axis=1),
     fwd_only="permutation ties make fd unstable")
spec("argsort", inputs=lambda: [rnd(3, 4)], attrs={"axis": 1},
     ref=lambda x, **_: np.argsort(x, axis=1).astype(np.float32))
spec("topk", inputs=lambda: [rnd(3, 4)], attrs={"k": 2, "axis": 1})
spec("shuffle", inputs=lambda: [rnd(4, 3)],
     fwd_only="stochastic output")
spec("argmax_channel", inputs=lambda: [rnd(3, 4)],
     ref=lambda x, **_: np.argmax(x, axis=1).astype(np.float32))
spec("unravel_index", inputs=lambda: [np.array([1, 5], np.float32)],
     attrs={"shape": (2, 3)})
spec("ravel_multi_index",
     inputs=lambda: [np.array([[0, 1], [1, 2]], np.float32)],
     attrs={"shape": (2, 3)},
     ref=lambda x, **_: np.array([1, 5], np.float32))

# ------------------------------------------------------------------ linalg

spec("dot", inputs=lambda: [rnd(3, 4), rnd(4, 2)],
     ref=lambda a, b, **_: a @ b)
spec("batch_dot", inputs=lambda: [rnd(2, 3, 4), rnd(2, 4, 2)],
     ref=lambda a, b, **_: a @ b)
spec("batch_dot_auto", inputs=lambda: [rnd(2, 3, 4), rnd(2, 4, 2)],
     ref=lambda a, b, **_: a @ b)
spec("linalg_gemm2", inputs=lambda: [rnd(3, 4), rnd(4, 2)],
     ref=lambda a, b, **_: a @ b)
spec("linalg_gemm", inputs=lambda: [rnd(3, 4), rnd(4, 2), rnd(3, 2)],
     ref=lambda a, b, c, **_: a @ b + c)


def _spd(n):
    a = RNG.randn(n, n).astype(np.float32)
    return (a @ a.T + n * np.eye(n, dtype=np.float32))


spec("linalg_potrf", inputs=lambda: [_spd(3)],
     ref=lambda a, **_: np.linalg.cholesky(a), rtol=1e-3)
spec("linalg_potri", inputs=lambda: [np.linalg.cholesky(_spd(3))],
     rtol=1e-3)
spec("linalg_trmm", inputs=lambda: [np.tril(pos(3, 3)), rnd(3, 2)],
     ref=lambda a, b, **_: np.tril(a) @ b, rtol=1e-3)
spec("linalg_trsm", inputs=lambda: [np.tril(pos(3, 3)) +
                                    2 * np.eye(3, dtype=np.float32),
                                    rnd(3, 2)], rtol=1e-3)
spec("linalg_syrk", inputs=lambda: [rnd(3, 4)],
     ref=lambda a, **_: a @ a.T, rtol=1e-3)
spec("linalg_sumlogdiag", inputs=lambda: [_spd(3)],
     ref=lambda a, **_: np.log(np.diag(a)).sum(), rtol=1e-3)
spec("linalg_extractdiag", inputs=lambda: [rnd(4, 4)],
     ref=lambda a, **_: np.diag(a))
spec("linalg_makediag", inputs=lambda: [rnd(4)],
     ref=lambda a, **_: np.diag(a))
spec("linalg_extracttrian", inputs=lambda: [rnd(3, 3)])
spec("linalg_maketrian", inputs=lambda: [rnd(6)])
spec("linalg_gelqf", inputs=lambda: [rnd(2, 4)],
     fwd_only="LQ factor sign ambiguity makes fd unstable")
spec("linalg_syevd", inputs=lambda: [_spd(3)],
     fwd_only="eigenvector sign ambiguity makes fd unstable")
spec("linalg_inverse", inputs=lambda: [_spd(3)],
     ref=lambda a, **_: np.linalg.inv(a), rtol=1e-3)
spec("linalg_det", inputs=lambda: [_spd(3)],
     ref=lambda a, **_: np.linalg.det(a), rtol=1e-3)
spec("linalg_slogdet", inputs=lambda: [_spd(3)],
     fwd_only="multi-output with sign output constant a.e.")
spec("khatri_rao", inputs=lambda: [rnd(2, 3), rnd(4, 3)])
spec("L2Normalization", inputs=lambda: [pos(3, 4)],
     ref=lambda x, **_: x / np.sqrt((x ** 2).sum(axis=1,
                                                 keepdims=True) + 1e-10))

# ---------------------------------------------------------------------- nn

spec("FullyConnected", inputs=lambda: [rnd(2, 3), rnd(4, 3), rnd(4)],
     attrs={"num_hidden": 4},
     ref=lambda x, w, b, **_: x @ w.T + b)
spec("Convolution", inputs=lambda: [rnd(1, 2, 5, 5), rnd(3, 2, 3, 3),
                                    rnd(3)],
     attrs={"kernel": (3, 3), "num_filter": 3}, rtol=1e-3)
spec("Deconvolution", inputs=lambda: [rnd(1, 2, 3, 3), rnd(2, 3, 3, 3)],
     attrs={"kernel": (3, 3), "num_filter": 3, "no_bias": True}, rtol=1e-3)
spec("Pooling", inputs=lambda: [rnd(1, 2, 4, 4)],
     attrs={"kernel": (2, 2), "stride": (2, 2), "pool_type": "avg"})
spec("BatchNorm", inputs=lambda: [rnd(2, 3, 4, 4), pos(3), rnd(3),
                                  rnd(3), pos(3)],
     attrs={"fix_gamma": False, "training": True},
     fwd_only="multi-output op; grad covered via gluon BatchNorm tests")
spec("LayerNorm", inputs=lambda: [rnd(3, 4), pos(4), rnd(4)])
spec("GroupNorm", inputs=lambda: [rnd(2, 4, 3, 3), pos(4), rnd(4)],
     attrs={"num_groups": 2})
spec("InstanceNorm", inputs=lambda: [rnd(2, 3, 4, 4), pos(3), rnd(3)])
spec("LRN", inputs=lambda: [rnd(1, 6, 3, 3)], attrs={"nsize": 3},
     fwd_only="multi-output (out, scale); value checked by shape")
spec("softmax", inputs=lambda: [rnd(3, 4)],
     ref=lambda x, **_: np.exp(x) / np.exp(x).sum(-1, keepdims=True))
spec("log_softmax", inputs=lambda: [rnd(3, 4)],
     ref=lambda x, **_: x - x.max(-1, keepdims=True) -
     np.log(np.exp(x - x.max(-1, keepdims=True)).sum(-1, keepdims=True)))
spec("softmin", inputs=lambda: [rnd(3, 4)],
     ref=lambda x, **_: np.exp(-x) / np.exp(-x).sum(-1, keepdims=True))
spec("SoftmaxActivation", inputs=lambda: [rnd(3, 4)])
spec("SoftmaxOutput", inputs=lambda: [rnd(3, 4),
                                      np.array([0, 1, 2], np.float32)],
     fwd_only="op defines its own implicit-loss gradient (p - onehot)")
spec("softmax_cross_entropy",
     inputs=lambda: [rnd(3, 4), np.array([0, 1, 2], np.float32)],
     fwd_only="integer label input breaks uniform fd")
spec("Activation", inputs=lambda: [rnd(3, 4)], attrs={"act_type": "tanh"},
     ref=lambda x, **_: np.tanh(x))
spec("LeakyReLU", inputs=lambda: [pos(3, 4)],
     attrs={"act_type": "leaky", "slope": 0.1},
     ref=lambda x, **_: np.where(x > 0, x, 0.1 * x))
spec("hard_sigmoid", inputs=lambda: [unit(3, 4)],
     ref=lambda x, **_: np.clip(0.2 * x + 0.5, 0, 1))
spec("Dropout", inputs=lambda: [rnd(3, 4)], attrs={"p": 0.5},
     fwd_only="stochastic")
spec("BlockGrad", inputs=lambda: [rnd(3, 4)], ref=lambda x, **_: x,
     fwd_only="gradient is zero by definition; fd sees the primal")
spec("identity", inputs=lambda: [rnd(3, 4)], ref=lambda x, **_: x)
spec("make_loss", inputs=lambda: [rnd(3, 4)], ref=lambda x, **_: x)
spec("UpSampling", inputs=lambda: [rnd(1, 2, 3, 3)], attrs={"scale": 2})
spec("CTCLoss", inputs=lambda: [rnd(4, 2, 5),
                                np.array([[1, 2], [2, 3]], np.float32)],
     fwd_only="integer label input breaks uniform fd")
spec("LinearRegressionOutput", inputs=lambda: [rnd(3, 2), rnd(3, 2)],
     fwd_only="op defines its own implicit-loss gradient")
spec("LogisticRegressionOutput", inputs=lambda: [rnd(3, 2), rnd(3, 2)],
     fwd_only="op defines its own implicit-loss gradient")
spec("MAERegressionOutput", inputs=lambda: [rnd(3, 2), rnd(3, 2)],
     fwd_only="op defines its own implicit-loss gradient")
spec("SVMOutput", inputs=lambda: [rnd(3, 4),
                                  np.array([0, 1, 2], np.float32)],
     fwd_only="op defines its own implicit-loss gradient")
spec("RNN", inputs=lambda: [rnd(3, 2, 4),
                            rnd(4 * 5 * 4 + 4 * 5 * 5 + 8 * 5).ravel(),
                            rnd(1, 2, 5), rnd(1, 2, 5)],
     attrs={"state_size": 5, "num_layers": 1, "mode": "lstm"},
     fwd_only="multi-output stateful op; covered by test_gluon_rnn")

# --------------------------------------------------------------- sequences

spec("SequenceMask", inputs=lambda: [rnd(4, 2, 3),
                                     np.array([2, 4], np.float32)],
     attrs={"use_sequence_length": True},
     fwd_only="length input is integer-valued")
spec("SequenceLast", inputs=lambda: [rnd(4, 2, 3),
                                     np.array([2, 4], np.float32)],
     attrs={"use_sequence_length": True},
     fwd_only="length input is integer-valued")
spec("SequenceReverse", inputs=lambda: [rnd(4, 2, 3),
                                        np.array([2, 4], np.float32)],
     attrs={"use_sequence_length": True},
     fwd_only="length input is integer-valued")

# ----------------------------------------------------------------- spatial


def _affine_grid_inputs():
    # scaled-down affine keeps every sample point strictly inside the image
    # and AWAY from integer pixel coordinates — the bilinear kernel's
    # weight-derivative is discontinuous there and breaks finite differences
    theta = np.tile(np.array([0.45, 0, 0.05, 0, 0.45, 0.05], np.float32),
                    (2, 1))
    return [theta]


def _safe_grid(n, c, h, w, size):
    """Normalized sampling grid whose pixel coords have fraction in
    [0.25, 0.75] (no fd across bilinear kinks)."""
    px = RNG.randint(0, size - 1, (n, c, h, w)) + \
        RNG.uniform(0.3, 0.7, (n, c, h, w))
    return (2.0 * px / (size - 1) - 1.0).astype(np.float32)


spec("GridGenerator", inputs=_affine_grid_inputs,
     attrs={"transform_type": "affine", "target_shape": (3, 3)})
spec("BilinearSampler",
     inputs=lambda: [rnd(1, 2, 4, 4), _safe_grid(1, 2, 3, 3, 4)])
spec("SpatialTransformer",
     inputs=lambda: [rnd(2, 2, 4, 4)] + _affine_grid_inputs(),
     attrs={"target_shape": (3, 3)})
spec("_contrib_BilinearResize2D", inputs=lambda: [rnd(1, 2, 4, 4)],
     attrs={"height": 6, "width": 6},
     fwd_only="output grid rows land on integer source coords "
              "(bilinear kink) by construction")
spec("_contrib_ROIAlign",
     inputs=lambda: [rnd(1, 2, 6, 6),
                     np.array([[0, 0, 0, 4, 4]], np.float32)],
     attrs={"pooled_size": (2, 2), "spatial_scale": 1.0},
     fwd_only="roi coordinate input is index-like")
spec("_contrib_DeformableConvolution",
     inputs=lambda: [rnd(1, 2, 5, 5),
                     RNG.uniform(0.25, 0.55, (1, 18, 3, 3))
                     .astype(np.float32),
                     rnd(3, 2, 3, 3)],
     attrs={"kernel": (3, 3), "num_filter": 3, "no_bias": True}, rtol=1e-3)
spec("Correlation", inputs=lambda: [rnd(1, 2, 5, 5), rnd(1, 2, 5, 5)],
     attrs={"max_displacement": 1, "pad_size": 1})

# -------------------------------------------------------------------- fft

spec("_contrib_fft", inputs=lambda: [rnd(2, 8)])
spec("_contrib_ifft", inputs=lambda: [rnd(2, 16)])


def test_fft_roundtrip():
    x = rnd(2, 8)
    f = apply_op("_contrib_fft", mx.nd.array(x))
    back = apply_op("_contrib_ifft", f).asnumpy()
    assert_almost_equal(back / 8.0, x, rtol=1e-4, atol=1e-5)


# ----------------------------------------------------------------- random

for _name in ["_random_uniform", "_random_normal", "_random_gamma",
              "_random_exponential", "_random_poisson",
              "_random_negative_binomial",
              "_random_generalized_negative_binomial", "_random_randint"]:
    spec(_name, inputs=lambda: [], attrs={"shape": (50,)})
for _name in ["_random_uniform_like", "_random_normal_like",
              "_random_gamma_like", "_random_exponential_like",
              "_random_poisson_like", "_random_negative_binomial_like",
              "_random_generalized_negative_binomial_like"]:
    spec(_name, inputs=lambda: [rnd(5, 4)])
for _name in ["sample_uniform", "sample_normal"]:
    spec(_name, inputs=lambda: [pos(3), pos(3) + 1.0], attrs={"shape": (4,)})
spec("sample_gamma", inputs=lambda: [pos(3), pos(3)], attrs={"shape": (4,)})
spec("sample_exponential", inputs=lambda: [pos(3)], attrs={"shape": (4,)})
spec("sample_poisson", inputs=lambda: [pos(3) * 3], attrs={"shape": (4,)})
spec("sample_negative_binomial",
     inputs=lambda: [np.full(3, 2.0, np.float32),
                     np.full(3, 0.5, np.float32)],
     attrs={"shape": (4,)})
spec("sample_generalized_negative_binomial",
     inputs=lambda: [pos(3) * 2, pos(3)], attrs={"shape": (4,)})
spec("sample_multinomial", inputs=lambda: [probs(3, 5)],
     attrs={"shape": (4,)})


def test_random_statistics():
    """Sanity: uniform in range, normal roughly centered."""
    mx.random.seed(11)
    u = apply_op("_random_uniform", low=2.0, high=3.0,
                 shape=(500,)).asnumpy()
    assert u.min() >= 2.0 and u.max() <= 3.0 and abs(u.mean() - 2.5) < 0.1
    n = apply_op("_random_normal", loc=-1.0, scale=0.5,
                 shape=(2000,)).asnumpy()
    assert abs(n.mean() + 1.0) < 0.1 and abs(n.std() - 0.5) < 0.1


# -------------------------------------------------------------- optimizers

spec("sgd_update", inputs=lambda: [rnd(4), rnd(4)],
     attrs={"lr": 0.1, "wd": 0.01},
     ref=lambda w, g, **_: w - 0.1 * (g + 0.01 * w),
     fwd_only="pure update formula; value-checked against numpy")
spec("sgd_mom_update", inputs=lambda: [rnd(4), rnd(4), rnd(4)],
     attrs={"lr": 0.1, "momentum": 0.9},
     fwd_only="pure update formula; value-checked in test_optim_update_ops")
for _name, _n in [("mp_sgd_update", 3), ("mp_sgd_mom_update", 4),
                  ("nag_mom_update", 3), ("mp_nag_mom_update", 4),
                  ("adam_update", 4), ("ftml_update", 5),
                  ("rmsprop_update", 3), ("rmspropalex_update", 5),
                  ("ftrl_update", 4), ("signsgd_update", 2),
                  ("signum_update", 3)]:
    # weight + small grad, then POSITIVE state tensors: second-moment /
    # accumulator states go through sqrt in most of these updates
    spec(_name, inputs=(lambda n=_n: [rnd(4), rnd(4) * 0.1] +
                        [pos(4) * 0.01 for _ in range(n - 2)]),
         attrs={"lr": 0.1},
         fwd_only="pure update formula; value-checked in "
                  "test_optim_update_ops")
for _name, _per, _extra in [("multi_sgd_update", 2, {}),
                            ("multi_sgd_mom_update", 3,
                             {"momentum": 0.9}),
                            ("multi_mp_sgd_update", 3, {}),
                            ("multi_mp_sgd_mom_update", 4,
                             {"momentum": 0.9})]:
    spec(_name,
         inputs=(lambda p=_per: [rnd(3) for _ in range(2 * p)]),
         attrs=dict({"lrs": (0.1, 0.2), "wds": (0.0, 0.01),
                     "num_weights": 2}, **_extra),
         fwd_only="pure update formula; value-checked in "
                  "test_optim_update_ops")
spec("multi_sum_sq", inputs=lambda: [rnd(3), rnd(4)],
     attrs={"num_arrays": 2},
     ref=lambda a, b, **_: np.array([(a ** 2).sum(), (b ** 2).sum()]))
spec("multi_lars", inputs=lambda: [pos(3), pos(3), pos(3), pos(3) * 0.01],
     attrs={"eta": 0.001})
spec("_adamw_update",
     inputs=lambda: [rnd(4), rnd(4), rnd(4), pos(4),
                     np.ones((1,), np.float32)],
     attrs={"lr": 0.01},
     fwd_only="pure update formula; tensor rescale input")
spec("_mp_adamw_update",
     inputs=lambda: [rnd(4), rnd(4), rnd(4), pos(4), rnd(4),
                     np.ones((1,), np.float32)],
     attrs={"lr": 0.01},
     fwd_only="pure update formula; tensor rescale input")
spec("lamb_update_phase1", inputs=lambda: [rnd(4), rnd(4), rnd(4), pos(4)],
     attrs={"t": 1}, fwd_only="pure update formula")
spec("lamb_update_phase2",
     inputs=lambda: [rnd(4), rnd(4), pos(1), pos(1)],
     attrs={"lr": 0.1}, fwd_only="pure update formula")


def test_optim_update_ops_match_numpy():
    w, g, m = rnd(5), rnd(5), rnd(5)
    nw, nm = apply_op("sgd_mom_update", mx.nd.array(w), mx.nd.array(g),
                      mx.nd.array(m), lr=0.1, momentum=0.9, wd=0.01)
    em = 0.9 * m - 0.1 * (g + 0.01 * w)
    assert_almost_equal(nm.asnumpy(), em, rtol=1e-5)
    assert_almost_equal(nw.asnumpy(), w + em, rtol=1e-5)

    mean, var = rnd(5), pos(5)
    nw, nmean, nvar = apply_op("adam_update", mx.nd.array(w), mx.nd.array(g),
                               mx.nd.array(mean), mx.nd.array(var),
                               lr=0.01, beta1=0.9, beta2=0.999, epsilon=1e-8)
    emean = 0.9 * mean + 0.1 * g
    evar = 0.999 * var + 0.001 * g * g
    assert_almost_equal(nmean.asnumpy(), emean, rtol=1e-5)
    assert_almost_equal(
        nw.asnumpy(), w - 0.01 * emean / (np.sqrt(evar) + 1e-8), rtol=1e-5)

    outs = apply_op("multi_sgd_update", mx.nd.array(w), mx.nd.array(g),
                    mx.nd.array(w * 2), mx.nd.array(g * 2),
                    lrs=(0.1, 0.2), wds=(0.0, 0.0), num_weights=2)
    assert_almost_equal(outs[0].asnumpy(), w - 0.1 * g, rtol=1e-5)
    assert_almost_equal(outs[1].asnumpy(), 2 * w - 0.2 * 2 * g, rtol=1e-5)


# ---------------------------------------------------- contrib / quant / etc
# (pre-round-3 contrib ops: forward smoke via specs; their math is covered by
# tests/test_contrib.py)

spec("_contrib_box_iou", inputs=lambda: [
    np.array([[0, 0, 2, 2]], np.float32),
    np.array([[1, 1, 3, 3]], np.float32)],
    fwd_only="coordinate inputs; fd meaningless")
spec("_contrib_box_nms", inputs=lambda: [
    np.array([[0, 0.9, 0, 0, 2, 2], [0, 0.8, 0, 0, 2, 2]], np.float32)],
    fwd_only="selection op")
spec("_contrib_box_encode", inputs=lambda: [
    np.ones((1, 2), np.float32),                  # samples: all positive
    np.zeros((1, 2), np.float32),                 # matches -> ref row 0
    np.array([[[0, 0, 2, 2], [1, 1, 3, 3]]], np.float32),   # anchors
    np.array([[[0, 0, 2, 2], [1, 1, 3, 3]]], np.float32)],  # refs
    fwd_only="coordinate transform")
spec("_contrib_box_decode", inputs=lambda: [
    np.zeros((1, 2, 4), np.float32), np.zeros((1, 2, 4), np.float32)],
    fwd_only="coordinate transform")
spec("_contrib_bipartite_matching",
     inputs=lambda: [np.array([[0.9, 0.1], [0.2, 0.8]], np.float32)],
     attrs={"threshold": 0.5}, fwd_only="assignment op")
spec("_contrib_MultiBoxPrior", inputs=lambda: [rnd(1, 2, 4, 4)],
     attrs={"sizes": (0.5,), "ratios": (1.0,)},
     fwd_only="anchor generator")
spec("ROIPooling", inputs=lambda: [rnd(1, 2, 6, 6),
                                   np.array([[0, 0, 0, 4, 4]], np.float32)],
     attrs={"pooled_size": (2, 2), "spatial_scale": 1.0},
     fwd_only="max-pool selection inside rois")
spec("_contrib_quantize_v2", inputs=lambda: [rnd(3, 4)],
     fwd_only="discretization")
spec("_contrib_dequantize", inputs=lambda: [
    (RNG.randint(-127, 127, (3, 4))).astype(np.int8),
    np.array([-1.0], np.float32), np.array([1.0], np.float32)],
    fwd_only="int8 input")
spec("_sim_quant", inputs=lambda: [rnd(3, 4)],
     fwd_only="discretization (straight-through estimator)")
spec("_contrib_quantized_fully_connected",
     inputs=lambda: [rnd(2, 6), rnd(3, 6)],
     attrs={"amax_data": 2.0, "amax_weight": 2.0, "no_bias": True},
     fwd_only="int8 execution path; int8 error is ABSOLUTE (amax/127 "
              "grid), checked at proper tolerance in test_contrib")
spec("_contrib_quantized_conv",
     inputs=lambda: [rnd(1, 2, 5, 5), rnd(3, 2, 3, 3)],
     attrs={"amax_data": 2.0, "amax_weight": 2.0, "kernel": (3, 3),
            "no_bias": True},
     fwd_only="int8 execution path; accuracy covered in test_contrib")

spec("MultiBoxTarget", inputs=lambda: [
    np.array([[[0.0, 0.0, 0.4, 0.4], [0.5, 0.5, 0.9, 0.9]]], np.float32),
    np.array([[[1, 0.05, 0.05, 0.35, 0.35]]], np.float32),
    probs(1, 3, 2)],
    fwd_only="target assignment op (matching/mining)")
spec("MultiBoxDetection", inputs=lambda: [
    probs(1, 3, 2), rnd(1, 8) * 0.1,
    np.array([[[0.0, 0.0, 0.4, 0.4], [0.5, 0.5, 0.9, 0.9]]], np.float32)],
    fwd_only="decode + NMS selection op")
spec("pallas_softmax", inputs=lambda: [rnd(3, 8)],
     ref=lambda x, **_: np.exp(x - x.max(-1, keepdims=True)) /
     np.exp(x - x.max(-1, keepdims=True)).sum(-1, keepdims=True),
     fwd_only="pallas kernel; registered non-differentiable")
spec("pallas_scale_bias_relu", inputs=lambda: [rnd(3, 8), pos(8), rnd(8)],
     ref=lambda x, s, b, **_: np.maximum(x * s + b, 0),
     fwd_only="pallas kernel; registered non-differentiable")


def _np_attention(q, k, v, **_):
    s = np.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(q.shape[-1])
    e = np.exp(s - s.max(-1, keepdims=True))
    p = e / e.sum(-1, keepdims=True)
    return np.einsum("bhqk,bhkd->bhqd", p, v)


spec("pallas_flash_attention",
     inputs=lambda: [rnd(1, 2, 4, 8), rnd(1, 2, 4, 8), rnd(1, 2, 4, 8)],
     ref=_np_attention,
     fwd_only="pallas kernel; registered non-differentiable "
              "(inference escape hatch; training uses XLA attention)")

# MultiBoxTarget/Detection-style ops registered under other names get their
# own specs here if present; the meta test below catches any addition that
# forgets to add one.

# --------------------------------------------------------------- creation

spec("_zeros", attrs={"shape": (2, 3)},
     ref=lambda **_: np.zeros((2, 3), np.float32))
spec("_ones", attrs={"shape": (2, 3)},
     ref=lambda **_: np.ones((2, 3), np.float32))
spec("_full", attrs={"shape": (2, 3), "value": 1.5},
     ref=lambda **_: np.full((2, 3), 1.5, np.float32))
spec("_arange", attrs={"start": 1, "stop": 7, "step": 2},
     ref=lambda **_: np.arange(1, 7, 2, np.float32))
spec("_linspace", attrs={"start": 0.0, "stop": 1.0, "num": 5},
     ref=lambda **_: np.linspace(0, 1, 5, dtype=np.float32))
spec("_eye", attrs={"N": 3},
     ref=lambda **_: np.eye(3, dtype=np.float32))

# ------------------------------------------------- round-4 long-tail ops

_SCALAR_REFS = {
    "_plus_scalar": lambda a, s: a + s,
    "_minus_scalar": lambda a, s: a - s,
    "_rminus_scalar": lambda a, s: s - a,
    "_mul_scalar": lambda a, s: a * s,
    "_div_scalar": lambda a, s: a / s,
    "_rdiv_scalar": lambda a, s: s / a,
    "_power_scalar": lambda a, s: np.power(a, s),
    "_hypot_scalar": lambda a, s: np.hypot(a, s),
    "_equal_scalar": lambda a, s: (a == s).astype(a.dtype),
    "_not_equal_scalar": lambda a, s: (a != s).astype(a.dtype),
    "_greater_scalar": lambda a, s: (a > s).astype(a.dtype),
    "_greater_equal_scalar": lambda a, s: (a >= s).astype(a.dtype),
    "_lesser_scalar": lambda a, s: (a < s).astype(a.dtype),
    "_lesser_equal_scalar": lambda a, s: (a <= s).astype(a.dtype),
    "_logical_and_scalar": lambda a, s:
        ((a != 0) & bool(s)).astype(a.dtype),
    "_logical_or_scalar": lambda a, s:
        ((a != 0) | bool(s)).astype(a.dtype),
    "_logical_xor_scalar": lambda a, s:
        ((a != 0) ^ bool(s)).astype(a.dtype),
    "_scatter_plus_scalar": lambda a, s: a + s,
    "_scatter_minus_scalar": lambda a, s: a - s,
}
for _n, _f in _SCALAR_REFS.items():
    spec(_n, inputs=lambda: [pos(3, 4)], attrs={"scalar": 1.3},
         ref=lambda a, scalar=1.3, _f=_f: _f(a, scalar))
spec("_mod_scalar", inputs=lambda: [pos(3, 4)], attrs={"scalar": 1.3},
     ref=lambda a, scalar=1.3: np.mod(a, scalar),
     fwd_only="non-smooth at wrap points")
spec("_rmod_scalar", inputs=lambda: [gt1(3, 4)], attrs={"scalar": 1.3},
     ref=lambda a, scalar=1.3: np.mod(scalar, a),
     fwd_only="non-smooth at wrap points")
spec("_rpower_scalar", inputs=lambda: [unit(3, 4)], attrs={"scalar": 1.3},
     ref=lambda a, scalar=1.3: np.power(scalar, a))
spec("_maximum_scalar", inputs=lambda: [pos(3, 4)], attrs={"scalar": 1.3},
     ref=lambda a, scalar=1.3: np.maximum(a, scalar),
     fwd_only="non-smooth at the scalar crossing")
spec("_minimum_scalar", inputs=lambda: [pos(3, 4)], attrs={"scalar": 1.3},
     ref=lambda a, scalar=1.3: np.minimum(a, scalar),
     fwd_only="non-smooth at the scalar crossing")

spec("add_n", inputs=lambda: [rnd(3, 4), rnd(3, 4), rnd(3, 4)],
     ref=lambda *a: a[0] + a[1] + a[2])
spec("amp_cast", inputs=lambda: [rnd(3, 4)], attrs={"dtype": "float16"},
     fwd_only="pure dtype cast")
spec("amp_multicast", inputs=lambda: [rnd(3, 4), rnd(3, 4)],
     attrs={"num_outputs": 2}, fwd_only="pure dtype cast")
spec("cast_storage", inputs=lambda: [rnd(3, 4)],
     attrs={"stype": "default"}, ref=lambda a, **_: a)
spec("fix", inputs=lambda: [rnd(3, 4) * 3], ref=lambda a: np.fix(a))
spec("_histogram", inputs=lambda: [rnd(40)], attrs={"bin_cnt": 5})
spec("_identity_with_attr_like_rhs",
     inputs=lambda: [rnd(3, 4), rnd(3, 4)],
     ref=lambda a, b: a,
     fwd_only="identity plumbing node; rhs carries no gradient")
spec("_zeros_without_dtype", inputs=(), attrs={"shape": (2, 3)},
     ref=lambda **_: np.zeros((2, 3), np.float32), grad=False)
spec("_rnn_param_concat", inputs=lambda: [rnd(3, 2), rnd(4, 2)],
     attrs={"dim": 0}, ref=lambda a, b, **_: np.concatenate([a, b], 0))
spec("_split_v2", inputs=lambda: [rnd(4, 6)],
     attrs={"indices": (2,), "axis": 1},
     ref=lambda a, **_: tuple(np.split(a, [2], axis=1)))
spec("_square_sum", inputs=lambda: [rnd(3, 4)], attrs={"axis": 1},
     ref=lambda a, axis=1: np.sum(a * a, axis=axis))
spec("_sparse_retain", inputs=lambda: [rnd(5, 3), np.array([1., 3.])],
     fwd_only="integer row-index input")
spec("_scatter_set_nd",
     inputs=lambda: [rnd(4, 5), rnd(3),
                     np.array([[0, 1, 2], [1, 2, 3]], np.float32)],
     fwd_only="integer index input")
spec("_scatter_elemwise_div", inputs=lambda: [rnd(3, 4), pos(3, 4)],
     ref=lambda a, b: a / b)
spec("_slice_assign",
     inputs=lambda: [rnd(4, 5), rnd(2, 2)],
     attrs={"begin": (0, 1), "end": (2, 3)})
spec("_slice_assign_scalar", inputs=lambda: [rnd(4, 5)],
     attrs={"begin": (0, 1), "end": (2, 3), "scalar": 7.0})
spec("_unravel_index", inputs=lambda: [np.array([5., 7.])],
     attrs={"shape": (3, 4)}, grad=False)
spec("_sample_unique_zipfian", inputs=(),
     attrs={"range_max": 1000, "shape": (6,)}, grad=False)
spec("Crop", inputs=lambda: [rnd(1, 2, 6, 6)],
     attrs={"h_w": (4, 4), "offset": (1, 1)},
     ref=lambda a, **_: a[:, :, 1:5, 1:5])
spec("IdentityAttachKLSparseReg", inputs=lambda: [pos(3, 4)],
     ref=lambda a, **_: a)
spec("_image_to_tensor", inputs=lambda: [pos(5, 6, 3) * 100],
     ref=lambda a: np.transpose(a.astype(np.float32) / 255.0, (2, 0, 1)))
spec("_image_normalize", inputs=lambda: [pos(3, 5, 6)],
     attrs={"mean": (0.5,), "std": (2.0,)},
     ref=lambda a, **_: (a - 0.5) / 2.0)
spec("_image_resize", inputs=lambda: [pos(5, 6, 3)],
     attrs={"size": (4, 3)})
spec("_image_crop", inputs=lambda: [pos(6, 8, 3)],
     attrs={"x": 1, "y": 2, "width": 4, "height": 3},
     ref=lambda a, **_: a[2:5, 1:5, :])

# fused optimizer updates: forward-value ops (state transitions), the
# training-path gradients never flow through them
spec("_multi_adamw_update",
     inputs=lambda: [rnd(4), rnd(4), rnd(4) * 0, pos(4),
                     np.ones(1, np.float32)],
     attrs={"lrs": (0.1,), "wds": (0.01,), "etas": (1.0,)}, grad=False)
spec("_multi_mp_adamw_update",
     inputs=lambda: [rnd(4), rnd(4), rnd(4) * 0, pos(4), rnd(4),
                     np.ones(1, np.float32)],
     attrs={"lrs": (0.1,), "wds": (0.01,), "etas": (1.0,)}, grad=False)
spec("preloaded_multi_sgd_update",
     inputs=lambda: [rnd(4), rnd(4), np.array([0.1], np.float32),
                     np.array([0.0], np.float32)], grad=False)
spec("preloaded_multi_sgd_mom_update",
     inputs=lambda: [rnd(4), rnd(4), rnd(4), np.array([0.1], np.float32),
                     np.array([0.0], np.float32)],
     attrs={"momentum": 0.9}, grad=False)
spec("preloaded_multi_mp_sgd_update",
     inputs=lambda: [rnd(4), rnd(4), rnd(4), np.array([0.1], np.float32),
                     np.array([0.0], np.float32)], grad=False)
spec("preloaded_multi_mp_sgd_mom_update",
     inputs=lambda: [rnd(4), rnd(4), rnd(4), rnd(4),
                     np.array([0.1], np.float32),
                     np.array([0.0], np.float32)],
     attrs={"momentum": 0.9}, grad=False)
spec("_sparse_adagrad_update",
     inputs=lambda: [rnd(4, 3), rnd(4, 3), pos(4, 3)],
     attrs={"lr": 0.1}, grad=False)
spec("_contrib_group_adagrad_update",
     inputs=lambda: [rnd(4, 3), rnd(4, 3), pos(4, 1)],
     attrs={"lr": 0.1}, grad=False)
spec("all_finite", inputs=lambda: [rnd(3, 4)], grad=False,
     ref=lambda a, **_: np.array([1.0], np.float32))
spec("multi_all_finite", inputs=lambda: [rnd(3), rnd(3)], grad=False,
     ref=lambda *a, **_: np.array([1.0], np.float32))
spec("reset_arrays", inputs=lambda: [rnd(3), rnd(2, 2)], grad=False,
     ref=lambda a, b, **_: (np.zeros_like(a), np.zeros_like(b)))

# contrib completion
spec("_contrib_quadratic", inputs=lambda: [rnd(3, 4)],
     attrs={"a": 1.0, "b": 2.0, "c": 3.0},
     ref=lambda x, a=1.0, b=2.0, c=3.0: a * x * x + b * x + c)
spec("_contrib_allclose", inputs=lambda: [rnd(3, 4)] * 2, grad=False)
spec("_contrib_arange_like", inputs=lambda: [rnd(3, 4)], grad=False,
     ref=lambda a, **_: np.arange(12, dtype=np.float32).reshape(3, 4))
spec("_contrib_index_copy",
     inputs=lambda: [rnd(5, 3), np.array([1., 3.]), rnd(2, 3)],
     fwd_only="integer index input")
spec("_contrib_index_array", inputs=lambda: [rnd(2, 3)], grad=False)
spec("_contrib_getnnz", inputs=lambda: [rnd(3, 4)], grad=False)
spec("_contrib_edge_id",
     inputs=lambda: [np.array([0., 2., 3.]), np.array([1., 2., 2.]),
                     np.array([10., 11., 12.]), np.array([0., 1.]),
                     np.array([2., 2.])], grad=False)
spec("_contrib_count_sketch",
     inputs=lambda: [rnd(2, 4), np.array([0., 1., 0., 1.]),
                     np.array([1., -1., 1., -1.])],
     attrs={"out_dim": 2}, grad=False)
spec("_contrib_hawkesll",
     inputs=lambda: [pos(2), pos(2) * 0.2, pos(2), pos(1, 2) * 0,
                     pos(1, 3), np.zeros((1, 3), np.float32),
                     np.array([3.]), np.array([2.0])],
     fwd_only="integer marks input; params differentiate via jax.vjp")
spec("_contrib_AdaptiveAvgPooling2D", inputs=lambda: [rnd(1, 2, 4, 4)],
     attrs={"output_size": (2, 2)},
     ref=lambda a, **_: a.reshape(1, 2, 2, 2, 2, 2).mean((3, 5)))
spec("_contrib_div_sqrt_dim", inputs=lambda: [rnd(3, 4)],
     ref=lambda a: a / np.sqrt(4.0))
spec("_contrib_gradientmultiplier", inputs=lambda: [rnd(3, 4)],
     attrs={"scalar": -1.0}, ref=lambda a, **_: a,
     fwd_only="gradient deliberately rescaled vs numeric")
spec("_contrib_round_ste", inputs=lambda: [rnd(3, 4) * 3],
     ref=lambda a: np.round(a),
     fwd_only="straight-through gradient intentionally differs")
spec("_contrib_sign_ste", inputs=lambda: [rnd(3, 4)],
     ref=lambda a: np.sign(a),
     fwd_only="straight-through gradient intentionally differs")
spec("_contrib_quantize",
     inputs=lambda: [unit(3, 4), np.array([-1.]), np.array([1.])],
     grad=False)
spec("_contrib_requantize",
     inputs=lambda: [(RNG.randint(-1000, 1000, (3, 4))).astype(np.float32),
                     np.array([-1.]), np.array([1.])], grad=False)
spec("_contrib_quantized_act",
     inputs=lambda: [(RNG.randint(-127, 127, (3, 4))).astype(np.float32),
                     np.array([-1.]), np.array([1.])],
     attrs={"act_type": "relu"}, grad=False)
spec("_contrib_quantized_flatten",
     inputs=lambda: [(RNG.randint(-127, 127, (2, 3, 4))).astype(np.float32),
                     np.array([-1.]), np.array([1.])], grad=False)
spec("_contrib_quantized_concat",
     inputs=lambda: [(RNG.randint(-127, 127, (2, 3))).astype(np.float32),
                     (RNG.randint(-127, 127, (2, 3))).astype(np.float32),
                     np.array([-1.]), np.array([-2.]),
                     np.array([1.]), np.array([2.])],
     attrs={"dim": 1, "num_args": 2}, grad=False)
spec("_contrib_quantized_elemwise_add",
     inputs=lambda: [(RNG.randint(-127, 127, (3, 4))).astype(np.float32),
                     (RNG.randint(-127, 127, (3, 4))).astype(np.float32),
                     np.array([-1.]), np.array([1.]),
                     np.array([-2.]), np.array([2.])], grad=False)
spec("_contrib_quantized_pooling",
     inputs=lambda: [(RNG.randint(-127, 127, (1, 2, 4, 4))
                      ).astype(np.float32),
                     np.array([-1.]), np.array([1.])],
     attrs={"kernel": (2, 2), "stride": (2, 2)}, grad=False)
spec("_contrib_quantized_batch_norm",
     inputs=lambda: [(RNG.randint(-127, 127, (2, 3, 4, 4))
                      ).astype(np.float32),
                     pos(3), rnd(3), rnd(3), pos(3),
                     np.array([-1.]), np.array([1.])], grad=False)
spec("_contrib_calibrate_entropy",
     inputs=lambda: [np.histogram(RNG.randn(2000), bins=64)[0]
                     .astype(np.float32),
                     np.histogram(RNG.randn(2000), bins=64)[1]
                     .astype(np.float32)],
     attrs={"num_quantized_bins": 31}, grad=False)
spec("_contrib_PSROIPooling",
     inputs=lambda: [rnd(1, 8, 6, 6),
                     np.array([[0, 0, 0, 20, 20]], np.float32)],
     attrs={"spatial_scale": 0.25, "output_dim": 2, "pooled_size": 2},
     grad=False)
spec("_contrib_DeformablePSROIPooling",
     inputs=lambda: [rnd(1, 8, 6, 6),
                     np.array([[0, 0, 0, 20, 20]], np.float32)],
     attrs={"spatial_scale": 0.25, "output_dim": 2, "pooled_size": 2,
            "no_trans": True}, grad=False)
spec("_contrib_RROIAlign",
     inputs=lambda: [rnd(1, 3, 8, 8),
                     np.array([[0, 12, 12, 8, 6, 30]], np.float32)],
     attrs={"pooled_size": (2, 2), "spatial_scale": 0.25}, grad=False)
spec("_contrib_Proposal",
     inputs=lambda: [probs(1, 2, 4, 4), rnd(1, 4, 4, 4) * 0.1,
                     np.array([[64, 64, 1.0]], np.float32)],
     attrs={"rpn_pre_nms_top_n": 12, "rpn_post_nms_top_n": 4,
            "scales": (8,), "ratios": (1.0,), "feature_stride": 16},
     grad=False)

EXEMPT = {
    # name -> reason a forward sweep invocation is impossible/meaningless
    "_copy_to_device": "requires a jax.Device attr; covered by "
                       "tests/test_train_autograd.py's cross-device "
                       "training gate",
}


def test_every_op_has_spec():
    ops = _canonical_ops()
    missing = [n for n in sorted(ops)
               if n not in SPECS and n not in EXEMPT]
    assert not missing, (
        "ops registered without a sweep spec (add a spec or a reasoned "
        "EXEMPT entry): %s" % missing)


def test_all_specs_point_at_real_ops():
    ops = _canonical_ops()
    stale = [n for n in SPECS if n not in set(ops) | set(_REGISTRY)]
    assert not stale, "specs for unregistered ops: %s" % stale


@pytest.mark.parametrize("name", sorted(SPECS))
def test_op_forward_and_grad(name):
    if name not in _REGISTRY:
        pytest.fail("spec for unknown op %s" % name)
    op = _REGISTRY[name]
    s = SPECS[name]
    builder = s["inputs"]
    arrays = builder() if callable(builder) else list(builder)
    nd_in = [mx.nd.array(a) for a in arrays]
    out = apply_op(op, *nd_in, **s["attrs"])
    outs = out if isinstance(out, (list, tuple)) else [out]
    for o in outs:
        host = o.asnumpy()
        assert np.isfinite(host.astype(np.float64)).all() or \
            "quant" in name, "%s produced non-finite values" % name
    if s["ref"] is not None:
        expect = s["ref"](*arrays, **s["attrs"])
        expects = expect if isinstance(expect, tuple) else (expect,)
        for o, e in zip(outs, expects):
            assert_almost_equal(o.asnumpy(), e, rtol=s["rtol"],
                                atol=1e-4, names=(name, "numpy"))
    differentiable = op.differentiable if s["grad"] is None else s["grad"]
    if differentiable and s["fwd_only"] is None and arrays:
        def f(*nds):
            r = apply_op(op, *nds, **s["attrs"])
            return r[0] if isinstance(r, (list, tuple)) else r
        check_numeric_gradient(f, arrays)
