"""Tensor op library: elementwise / broadcast / scalar / reduction / matrix /
indexing / init / ordering ops.

Capability parity with reference `src/operator/tensor/` (elemwise_*.cc,
broadcast_reduce-inl.h, matrix_op-inl.h, indexing_op.h, dot-inl.h,
ordering_op.cc, init_op.cc — see SURVEY.md Appendix A for the name
inventory). Implementation is pure jax.numpy/lax: eager calls dispatch op-by-op
through XLA; symbolic executors trace these same functions into one HloModule,
which subsumes the reference's mshadow kernel + Kernel<OP,xpu>::Launch idiom.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from ..base import MXNetError
from .registry import OpDef, OP_REGISTRY, REQUIRED, register

# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------


def _norm_axes(axis, ndim, exclude=False):
    if axis is None or (isinstance(axis, tuple) and len(axis) == 0):
        axes = tuple(range(ndim))
    else:
        if isinstance(axis, int):
            axis = (axis,)
        axes = tuple(sorted(a % ndim if a < 0 else a for a in axis))
    if exclude:
        axes = tuple(i for i in range(ndim) if i not in axes)
    return axes


def _reg(name, fn, params=None, inputs=("data",), num_outputs=1, aliases=()):
    opdef = OpDef(name, fn, params=params, inputs=inputs, num_outputs=num_outputs, aliases=aliases)
    if name in OP_REGISTRY:
        raise MXNetError("op %r registered twice" % name)
    OP_REGISTRY[name] = opdef
    for a in aliases:
        OP_REGISTRY.setdefault(a, opdef)


def _def_unary(name, fn, aliases=()):
    _reg(name, lambda attrs, x, _fn=fn: _fn(x), inputs=("data",), aliases=aliases)


def _def_binary(name, fn, aliases=()):
    _reg(name, lambda attrs, a, b, _fn=fn: _fn(a, b), inputs=("lhs", "rhs"), aliases=aliases)


def _def_scalar(name, fn, aliases=()):
    # output keeps the input dtype (reference elemwise_binary_scalar_op semantics)
    _reg(
        name,
        lambda attrs, a, _fn=fn: _fn(a, jnp.asarray(attrs.scalar, dtype=a.dtype)),
        params={"scalar": (float, 0.0)},
        inputs=("data",),
        aliases=aliases,
    )


# ---------------------------------------------------------------------------
# unary math (reference src/operator/tensor/elemwise_unary_op_basic.cc etc.)
# ---------------------------------------------------------------------------

_UNARY = {
    "abs": jnp.abs,
    "sign": jnp.sign,
    "negative": jnp.negative,
    "reciprocal": jnp.reciprocal,
    "square": jnp.square,
    "sqrt": jnp.sqrt,
    "rsqrt": lambda x: lax.rsqrt(x),
    "cbrt": jnp.cbrt,
    "rcbrt": lambda x: 1.0 / jnp.cbrt(x),
    "exp": jnp.exp,
    "expm1": jnp.expm1,
    "log": jnp.log,
    "log10": jnp.log10,
    "log2": jnp.log2,
    "log1p": jnp.log1p,
    "sin": jnp.sin,
    "cos": jnp.cos,
    "tan": jnp.tan,
    "sinh": jnp.sinh,
    "cosh": jnp.cosh,
    "tanh": jnp.tanh,
    "arcsin": jnp.arcsin,
    "arccos": jnp.arccos,
    "arctan": jnp.arctan,
    "arcsinh": jnp.arcsinh,
    "arccosh": jnp.arccosh,
    "arctanh": jnp.arctanh,
    "degrees": jnp.degrees,
    "radians": jnp.radians,
    "floor": jnp.floor,
    "ceil": jnp.ceil,
    "round": jnp.round,
    "rint": jnp.rint,
    "fix": jnp.trunc,  # fix == round-toward-zero; jnp.fix is deprecated in jax 0.9
    "trunc": jnp.trunc,
    "gamma": jax.scipy.special.gamma,
    "gammaln": jax.scipy.special.gammaln,
    "erf": jax.scipy.special.erf,
    "erfinv": jax.scipy.special.erfinv,
    "relu": lambda x: jnp.maximum(x, 0),
    "sigmoid": jax.nn.sigmoid,
    "hard_sigmoid": lambda x: jnp.clip(0.2 * x + 0.5, 0.0, 1.0),
    "softsign": jax.nn.soft_sign,
    "logical_not": lambda x: (x == 0).astype(x.dtype),
    "_copy": lambda x: x,
    "ones_like": jnp.ones_like,
    "zeros_like": jnp.zeros_like,
}
for _n, _f in _UNARY.items():
    _def_unary(_n, _f)

_reg("BlockGrad", lambda attrs, x: lax.stop_gradient(x), aliases=("stop_gradient",))
def _make_loss(attrs, x):
    """Identity forward; backward emits grad_scale (optionally normalized)
    like the reference MakeLossOp (make_loss.cc: grad = grad_scale, divided
    by batch size for normalization='batch' or by the count of entries
    above valid_thresh for 'valid')."""
    scale = attrs.get("grad_scale", 1.0)
    norm = attrs.get("normalization", "null")
    valid_thresh = attrs.get("valid_thresh", 0.0)

    @jax.custom_vjp
    def f(x):
        return x

    def fwd(x):
        return x, x

    def bwd(res, g):
        s = jnp.asarray(scale, g.dtype)
        if norm == "batch":
            s = s / res.shape[0]
        elif norm == "valid":
            s = s / jnp.maximum(
                jnp.sum((res > valid_thresh).astype(g.dtype)), 1.0)
        return (g * s,)

    f.defvjp(fwd, bwd)
    return f(x)


_reg(
    "make_loss",
    _make_loss,
    params={"grad_scale": (float, 1.0), "valid_thresh": (float, 0.0),
            "normalization": (str, "null")},
    aliases=("MakeLoss_", "MakeLoss"),
)
_reg(
    "smooth_l1",
    lambda attrs, x: jnp.where(
        jnp.abs(x) < 1.0 / (attrs.scalar ** 2),
        0.5 * (x * attrs.scalar) ** 2,
        jnp.abs(x) - 0.5 / (attrs.scalar ** 2),
    ),
    params={"scalar": (float, 1.0)},
)
_reg(
    "clip",
    lambda attrs, x: jnp.clip(x, attrs.a_min, attrs.a_max),
    params={"a_min": (float, REQUIRED), "a_max": (float, REQUIRED)},
)
_reg(
    "Cast",
    lambda attrs, x: x.astype(attrs.dtype),
    params={"dtype": ("dtype", REQUIRED)},
    aliases=("cast",),
)


# ---------------------------------------------------------------------------
# binary elementwise + broadcast (reference elemwise_binary_op*.cc,
# elemwise_binary_broadcast_op*.cc)
# ---------------------------------------------------------------------------

def _logical_xor(a, b):
    return ((a != 0) ^ (b != 0)).astype(a.dtype)


# plain-operator forms: the jnp.<ufunc> wrappers add ~25us of eager
# dispatch per call that the __add__-style operator path skips entirely
def _op_add(a, b):
    return a + b


def _op_sub(a, b):
    return a - b


def _op_mul(a, b):
    return a * b


def _op_div(a, b):
    return a / b


_BINARY = {
    "elemwise_add": (_op_add, ("_add", "_plus", "_Plus")),
    "elemwise_sub": (_op_sub, ("_sub", "_minus", "_Minus")),
    "elemwise_mul": (_op_mul, ("_mul", "_Mul")),
    "elemwise_div": (_op_div, ("_div", "_Div")),
    "_grad_add": (_op_add, ()),
    "_mod": (jnp.mod, ("_Mod",)),
    "_power": (jnp.power, ("_Power", "pow")),
    "_hypot": (jnp.hypot, ()),
    "_maximum": (jnp.maximum, ("_Maximum",)),
    "_minimum": (jnp.minimum, ("_Minimum",)),
    "_equal": (lambda a, b: (a == b).astype(a.dtype), ("_Equal",)),
    "_not_equal": (lambda a, b: (a != b).astype(a.dtype), ("_Not_Equal",)),
    "_greater": (lambda a, b: (a > b).astype(a.dtype), ("_Greater",)),
    "_greater_equal": (lambda a, b: (a >= b).astype(a.dtype), ("_Greater_Equal",)),
    "_lesser": (lambda a, b: (a < b).astype(a.dtype), ("_Lesser",)),
    "_lesser_equal": (lambda a, b: (a <= b).astype(a.dtype), ("_Lesser_Equal",)),
    "_logical_and": (lambda a, b: ((a != 0) & (b != 0)).astype(a.dtype), ()),
    "_logical_or": (lambda a, b: ((a != 0) | (b != 0)).astype(a.dtype), ()),
    "_logical_xor": (_logical_xor, ()),
}
for _n, (_f, _al) in _BINARY.items():
    _def_binary(_n, _f, aliases=_al)

# broadcast_* family shares implementations (jnp broadcasts natively)
_BCAST = {
    "broadcast_add": _op_add,
    "broadcast_sub": _op_sub,
    "broadcast_mul": _op_mul,
    "broadcast_div": _op_div,
    "broadcast_mod": jnp.mod,
    "broadcast_power": jnp.power,
    "broadcast_hypot": jnp.hypot,
    "broadcast_maximum": jnp.maximum,
    "broadcast_minimum": jnp.minimum,
    "broadcast_equal": lambda a, b: (a == b).astype(a.dtype),
    "broadcast_not_equal": lambda a, b: (a != b).astype(a.dtype),
    "broadcast_greater": lambda a, b: (a > b).astype(a.dtype),
    "broadcast_greater_equal": lambda a, b: (a >= b).astype(a.dtype),
    "broadcast_lesser": lambda a, b: (a < b).astype(a.dtype),
    "broadcast_lesser_equal": lambda a, b: (a <= b).astype(a.dtype),
    "broadcast_logical_and": lambda a, b: ((a != 0) & (b != 0)).astype(a.dtype),
    "broadcast_logical_or": lambda a, b: ((a != 0) | (b != 0)).astype(a.dtype),
    "broadcast_logical_xor": _logical_xor,
}
for _n, _f in _BCAST.items():
    _def_binary(_n, _f)

# scalar variants (reference elemwise_binary_scalar_op*.cc)
_SCALAR = {
    "_plus_scalar": (lambda a, s: a + s, ("_PlusScalar",)),
    "_minus_scalar": (lambda a, s: a - s, ("_MinusScalar",)),
    "_rminus_scalar": (lambda a, s: s - a, ("_RMinusScalar",)),
    "_mul_scalar": (lambda a, s: a * s, ("_MulScalar",)),
    "_div_scalar": (lambda a, s: a / s, ("_DivScalar",)),
    "_rdiv_scalar": (lambda a, s: s / a, ("_RDivScalar",)),
    "_mod_scalar": (lambda a, s: jnp.mod(a, s), ("_ModScalar",)),
    "_rmod_scalar": (lambda a, s: jnp.mod(s, a), ("_RModScalar",)),
    "_power_scalar": (lambda a, s: jnp.power(a, s), ("_PowerScalar",)),
    "_rpower_scalar": (lambda a, s: jnp.power(s, a), ("_RPowerScalar",)),
    "_maximum_scalar": (jnp.maximum, ("_MaximumScalar",)),
    "_minimum_scalar": (jnp.minimum, ("_MinimumScalar",)),
    "_hypot_scalar": (jnp.hypot, ()),
    "_equal_scalar": (lambda a, s: (a == s).astype(a.dtype), ()),
    "_not_equal_scalar": (lambda a, s: (a != s).astype(a.dtype), ()),
    "_greater_scalar": (lambda a, s: (a > s).astype(a.dtype), ()),
    "_greater_equal_scalar": (lambda a, s: (a >= s).astype(a.dtype), ()),
    "_lesser_scalar": (lambda a, s: (a < s).astype(a.dtype), ()),
    "_lesser_equal_scalar": (lambda a, s: (a <= s).astype(a.dtype), ()),
    "_logical_and_scalar": (lambda a, s: ((a != 0) & (s != 0)).astype(a.dtype), ()),
    "_logical_or_scalar": (lambda a, s: ((a != 0) | (s != 0)).astype(a.dtype), ()),
    "_logical_xor_scalar": (_logical_xor, ()),
}
for _n, (_f, _al) in _SCALAR.items():
    _def_scalar(_n, _f, aliases=_al)

_reg(
    "add_n",
    lambda attrs, *xs: sum(xs[1:], xs[0]),
    params={"num_args": (int, 1)},
    inputs=lambda attrs: ["arg%d" % i for i in range(attrs.get("num_args", 1))],
    aliases=("ElementWiseSum", "_sum"),
)


# ---------------------------------------------------------------------------
# reductions (reference broadcast_reduce_op_value.cc)
# ---------------------------------------------------------------------------

_REDUCE_PARAMS = {"axis": (tuple, None), "keepdims": (bool, False), "exclude": (bool, False)}


def _def_reduce(name, fn, aliases=()):
    def f(attrs, x, _fn=fn):
        axes = _norm_axes(attrs.axis, x.ndim, attrs.exclude)
        return _fn(x, axis=axes, keepdims=attrs.keepdims)

    _reg(name, f, params=dict(_REDUCE_PARAMS), aliases=aliases)


_def_reduce("sum", jnp.sum, aliases=("sum_axis",))
_def_reduce("mean", jnp.mean)
_def_reduce("prod", jnp.prod)
_def_reduce("nansum", jnp.nansum)
_def_reduce("nanprod", jnp.nanprod)
_def_reduce("max", jnp.max, aliases=("max_axis",))
_def_reduce("min", jnp.min, aliases=("min_axis",))
_reg(
    "norm",
    lambda attrs, x: jnp.sqrt(jnp.sum(jnp.square(x), axis=_norm_axes(attrs.axis, x.ndim), keepdims=attrs.keepdims))
    if attrs.ord == 2
    else jnp.sum(jnp.abs(x), axis=_norm_axes(attrs.axis, x.ndim), keepdims=attrs.keepdims),
    params={"ord": (int, 2), "axis": (tuple, None), "keepdims": (bool, False)},
)
_reg(
    "_square_sum",
    lambda attrs, x: jnp.sum(jnp.square(x), axis=_norm_axes(attrs.axis, x.ndim, attrs.exclude), keepdims=attrs.keepdims),
    params=dict(_REDUCE_PARAMS),
)


def _arg_reduce(fn):
    def f(attrs, x):
        if attrs.axis is None:
            return fn(x.reshape(-1), axis=0).astype(x.dtype)
        ax = attrs.axis[0] if isinstance(attrs.axis, tuple) else int(attrs.axis)
        out = fn(x, axis=ax)
        if attrs.keepdims:
            out = jnp.expand_dims(out, ax)
        return out.astype(x.dtype)

    return f


_reg("argmax", _arg_reduce(jnp.argmax), params={"axis": (tuple, None), "keepdims": (bool, False)})
_reg("argmin", _arg_reduce(jnp.argmin), params={"axis": (tuple, None), "keepdims": (bool, False)})
_reg("argmax_channel", lambda attrs, x: jnp.argmax(x, axis=1).astype(x.dtype))


# ---------------------------------------------------------------------------
# broadcast/shape manipulation (reference matrix_op-inl.h)
# ---------------------------------------------------------------------------


def _reshape_infer(shape, target):
    """MXNet Reshape semantics: 0 copies input dim, -1 infers, -2 copies rest,
    -3 merges two dims, -4 splits a dim (reference matrix_op-inl.h:95-180)."""
    out = []
    src = list(shape)
    i = 0
    j = 0
    while j < len(target):
        t = target[j]
        if t == 0:
            out.append(src[i]); i += 1
        elif t == -1:
            out.append(-1); i += 1
        elif t == -2:
            out.extend(src[i:]); i = len(src)
        elif t == -3:
            out.append(src[i] * src[i + 1]); i += 2
        elif t == -4:
            a, b = target[j + 1], target[j + 2]
            if a == -1:
                a = src[i] // b
            if b == -1:
                b = src[i] // a
            out.extend([a, b]); i += 1; j += 2
        else:
            out.append(t); i += 1
        j += 1
    if -1 in out:
        known = 1
        for v in out:
            if v != -1:
                known *= v
        total = 1
        for v in shape:
            total *= v
        out[out.index(-1)] = total // known
    return tuple(out)


_reg(
    "Reshape",
    lambda attrs, x: x.reshape(_reshape_infer(x.shape, attrs.shape) if attrs.shape else x.shape)
    if not attrs.reverse
    else x.reshape(tuple(reversed(_reshape_infer(tuple(reversed(x.shape)), tuple(reversed(attrs.shape)))))),
    params={"shape": (tuple, None), "reverse": (bool, False)},
    aliases=("reshape",),
)
_reg("Flatten", lambda attrs, x: x.reshape(x.shape[0], -1), aliases=("flatten",))
_reg(
    "transpose",
    lambda attrs, x: jnp.transpose(x, attrs.axes if attrs.axes else None),
    params={"axes": (tuple, None)},
)
_reg(
    "expand_dims",
    lambda attrs, x: jnp.expand_dims(x, attrs.axis),
    params={"axis": (int, REQUIRED)},
)
_reg(
    "squeeze",
    lambda attrs, x: jnp.squeeze(x, axis=attrs.axis if attrs.axis else None),
    params={"axis": (tuple, None)},
)


def _slice(attrs, x):
    nd = x.ndim
    begin = list(attrs.begin) + [None] * (nd - len(attrs.begin))
    end = list(attrs.end) + [None] * (nd - len(attrs.end))
    step = list(attrs.step) + [None] * (nd - len(attrs.step)) if attrs.step else [None] * nd
    idx = tuple(
        slice(
            None if b in (None,) else b,
            None if e in (None,) else e,
            None if s in (None, 0) else s,
        )
        for b, e, s in zip(begin, end, step)
    )
    return x[idx]


_reg(
    "slice",
    _slice,
    params={"begin": (tuple, REQUIRED), "end": (tuple, REQUIRED), "step": (tuple, None)},
    aliases=("crop",),
)
_reg(
    "slice_axis",
    lambda attrs, x: lax.slice_in_dim(
        x,
        attrs.begin if attrs.begin >= 0 else x.shape[attrs.axis] + attrs.begin,
        x.shape[attrs.axis] if attrs.end is None else (attrs.end if attrs.end >= 0 else x.shape[attrs.axis] + attrs.end),
        axis=attrs.axis % x.ndim,
    ),
    params={"axis": (int, REQUIRED), "begin": (int, REQUIRED), "end": (int, None)},
)
_reg(
    "slice_like",
    lambda attrs, x, like: x[
        tuple(
            slice(0, like.shape[i]) if (not attrs.axes or i in [a % x.ndim for a in attrs.axes]) else slice(None)
            for i in range(x.ndim)
        )
    ],
    params={"axes": (tuple, None)},
    inputs=("data", "shape_like"),
)
_reg(
    "Concat",
    lambda attrs, *xs: jnp.concatenate(xs, axis=attrs.dim),
    params={"num_args": (int, 1), "dim": (int, 1)},
    inputs=lambda attrs: ["arg%d" % i for i in range(attrs.get("num_args", 1))],
    aliases=("concat",),
)
_reg(
    "stack",
    lambda attrs, *xs: jnp.stack(xs, axis=attrs.axis),
    params={"num_args": (int, 1), "axis": (int, 0)},
    inputs=lambda attrs: ["arg%d" % i for i in range(attrs.get("num_args", 1))],
)
_reg(
    "SliceChannel",
    lambda attrs, x: tuple(
        jnp.squeeze(s, axis=attrs.axis) if attrs.squeeze_axis else s
        for s in jnp.split(x, attrs.num_outputs, axis=attrs.axis)
    ),
    params={"num_outputs": (int, REQUIRED), "axis": (int, 1), "squeeze_axis": (bool, False)},
    num_outputs=lambda attrs: attrs.num_outputs,
    aliases=("split",),
)
_reg(
    "tile",
    lambda attrs, x: jnp.tile(x, attrs.reps),
    params={"reps": (tuple, REQUIRED)},
)
_reg(
    "repeat",
    lambda attrs, x: jnp.repeat(x, attrs.repeats, axis=attrs.axis),
    params={"repeats": (int, REQUIRED), "axis": (int, None)},
)
_reg(
    "reverse",
    lambda attrs, x: jnp.flip(x, axis=attrs.axis),
    params={"axis": (tuple, REQUIRED)},
    aliases=("flip",),
)
_reg(
    "SwapAxis",
    lambda attrs, x: jnp.swapaxes(x, attrs.dim1, attrs.dim2),
    params={"dim1": (int, 0), "dim2": (int, 0)},
    aliases=("swapaxes",),
)
def _broadcast_to(attrs, x):
    tgt = attrs.shape
    if len(tgt) == x.ndim:  # 0 means "keep input dim" (reference semantics)
        tgt = tuple(t if t != 0 else s for t, s in zip(tgt, x.shape))
    return jnp.broadcast_to(x, tgt)


_reg("broadcast_to", _broadcast_to, params={"shape": (tuple, REQUIRED)})
_reg(
    "broadcast_axis",
    lambda attrs, x: jnp.broadcast_to(
        x,
        tuple(
            attrs.size[list(attrs.axis).index(i)] if i in attrs.axis else s
            for i, s in enumerate(x.shape)
        ),
    ),
    params={"axis": (tuple, REQUIRED), "size": (tuple, REQUIRED)},
    aliases=("broadcast_axes",),
)
_reg("broadcast_like", lambda attrs, x, like: jnp.broadcast_to(x, like.shape), inputs=("lhs", "rhs"))
_reg("reshape_like", lambda attrs, x, like: x.reshape(like.shape), inputs=("lhs", "rhs"))
_reg("shape_array", lambda attrs, x: jnp.asarray(x.shape, dtype=jnp.int64))
_reg("size_array", lambda attrs, x: jnp.asarray([x.size], dtype=jnp.int64))
_reg(
    "Pad",
    lambda attrs, x: jnp.pad(
        x,
        [(attrs.pad_width[2 * i], attrs.pad_width[2 * i + 1]) for i in range(x.ndim)],
        mode={"constant": "constant", "edge": "edge", "reflect": "reflect"}[attrs.mode],
        **({"constant_values": attrs.constant_value} if attrs.mode == "constant" else {}),
    ),
    params={"mode": (str, "constant"), "pad_width": (tuple, REQUIRED), "constant_value": (float, 0.0)},
    aliases=("pad",),
)
_reg(
    "depth_to_space",
    lambda attrs, x: _depth_to_space(x, attrs.block_size),
    params={"block_size": (int, REQUIRED)},
)
_reg(
    "space_to_depth",
    lambda attrs, x: _space_to_depth(x, attrs.block_size),
    params={"block_size": (int, REQUIRED)},
)


def _depth_to_space(x, b):
    n, c, h, w = x.shape
    x = x.reshape(n, b, b, c // (b * b), h, w)
    x = jnp.transpose(x, (0, 3, 4, 1, 5, 2))
    return x.reshape(n, c // (b * b), h * b, w * b)


def _space_to_depth(x, b):
    n, c, h, w = x.shape
    x = x.reshape(n, c, h // b, b, w // b, b)
    x = jnp.transpose(x, (0, 3, 5, 1, 2, 4))
    return x.reshape(n, c * b * b, h // b, w // b)


_reg(
    "diag",
    lambda attrs, x: jnp.diag(x, k=attrs.k) if x.ndim <= 2 else jnp.diagonal(x, offset=attrs.k, axis1=attrs.axis1, axis2=attrs.axis2),
    params={"k": (int, 0), "axis1": (int, 0), "axis2": (int, 1)},
)
_reg(
    "where",
    lambda attrs, cond, a, b: jnp.where(
        cond.reshape(cond.shape + (1,) * (a.ndim - cond.ndim)) != 0, a, b
    ),
    inputs=("condition", "x", "y"),
)

# ---------------------------------------------------------------------------
# dot / batch_dot (reference src/operator/tensor/dot-inl.h)
# ---------------------------------------------------------------------------


def _dot(attrs, a, b):
    """Contract last axis of a with first axis of b; result shape
    a.shape[:-1] + b.shape[1:] (reference dot-inl.h semantics)."""
    if a.ndim == 1 and b.ndim == 1:
        return jnp.dot(a, b)
    am = jnp.swapaxes(a, -1, -2) if attrs.transpose_a else a
    bm = jnp.swapaxes(b, 0, 1) if attrs.transpose_b else b
    if bm.ndim == 2:
        # matmul contracts am's last axis with bm's first and broadcasts
        # leading dims — identical to the tensordot below but ~5x cheaper to
        # dispatch eagerly (single primitive bind, no reshape chain)
        return jnp.matmul(am, bm)
    return jnp.tensordot(am, bm, axes=([am.ndim - 1], [0]))


_reg(
    "dot",
    _dot,
    params={"transpose_a": (bool, False), "transpose_b": (bool, False)},
    inputs=("lhs", "rhs"),
)


def _batch_dot(attrs, a, b):
    ta, tb = attrs.transpose_a, attrs.transpose_b
    am = jnp.swapaxes(a, -1, -2) if ta else a
    bm = jnp.swapaxes(b, -1, -2) if tb else b
    return jnp.matmul(am, bm)


_reg(
    "batch_dot",
    _batch_dot,
    params={"transpose_a": (bool, False), "transpose_b": (bool, False)},
    inputs=("lhs", "rhs"),
)
_reg(
    "khatri_rao",
    lambda attrs, *xs: _khatri_rao(xs),
    params={"num_args": (int, 1)},
    inputs=lambda attrs: ["arg%d" % i for i in range(attrs.get("num_args", 1))],
)


def _khatri_rao(mats):
    out = mats[0]
    for m in mats[1:]:
        out = jnp.einsum("i...,j...->ij...", out, m).reshape(out.shape[0] * m.shape[0], *out.shape[1:])
    return out


# ---------------------------------------------------------------------------
# indexing (reference src/operator/tensor/indexing_op.h)
# ---------------------------------------------------------------------------

_reg(
    "take",
    lambda attrs, a, idx: jnp.take(
        a,
        idx.astype(jnp.int32),
        axis=attrs.axis,
        mode={"clip": "clip", "wrap": "wrap", "raise": "clip"}[attrs.mode],
    ),
    params={"axis": (int, 0), "mode": (str, "clip")},
    inputs=("a", "indices"),
)
_reg(
    "batch_take",
    lambda attrs, a, idx: jnp.take_along_axis(
        a, idx.astype(jnp.int32).reshape(-1, 1), axis=1
    ).reshape(idx.shape),
    inputs=("a", "indices"),
)
_reg(
    "pick",
    lambda attrs, x, idx: _pick(attrs, x, idx),
    params={"axis": (int, -1), "keepdims": (bool, False), "mode": (str, "clip")},
    inputs=("data", "index"),
)


def _pick(attrs, x, idx):
    ax = attrs.axis % x.ndim
    idxe = jnp.expand_dims(idx.astype(jnp.int32), ax)
    out = jnp.take_along_axis(x, jnp.clip(idxe, 0, x.shape[ax] - 1), axis=ax)
    return out if attrs.keepdims else jnp.squeeze(out, axis=ax)


_reg(
    "Embedding",
    lambda attrs, data, weight: jnp.take(weight, data.astype(jnp.int32), axis=0),
    params={
        "input_dim": (int, REQUIRED),
        "output_dim": (int, REQUIRED),
        "dtype": ("dtype", None),
        "sparse_grad": (bool, False),
    },
    inputs=("data", "weight"),
)
_reg(
    "one_hot",
    lambda attrs, idx: (
        jax.nn.one_hot(idx.astype(jnp.int32), attrs.depth, dtype=attrs.dtype or jnp.float32)
        * (attrs.on_value - attrs.off_value)
        + attrs.off_value
    ),
    params={
        "depth": (int, REQUIRED),
        "on_value": (float, 1.0),
        "off_value": (float, 0.0),
        "dtype": ("dtype", None),
    },
    inputs=("indices",),
)
_reg(
    "gather_nd",
    lambda attrs, data, indices: data[tuple(indices.astype(jnp.int32))],
    inputs=("data", "indices"),
)


def _scatter_nd(attrs, data, indices):
    out = jnp.zeros(attrs.shape, dtype=data.dtype)
    return out.at[tuple(indices.astype(jnp.int32))].add(data)


_reg(
    "scatter_nd",
    _scatter_nd,
    params={"shape": (tuple, REQUIRED)},
    inputs=("data", "indices"),
)
_reg(
    "_scatter_set_nd",
    lambda attrs, lhs, rhs, indices: lhs.at[tuple(indices.astype(jnp.int32))].set(rhs),
    params={"shape": (tuple, None)},
    inputs=("lhs", "rhs", "indices"),
)
_reg(
    "_ravel_multi_index",
    lambda attrs, data: _ravel(attrs, data),
    params={"shape": (tuple, REQUIRED)},
    inputs=("data",),
)


def _ravel(attrs, data):
    shape = attrs.shape
    strides = []
    acc = 1
    for s in reversed(shape):
        strides.append(acc)
        acc *= s
    strides = jnp.asarray(list(reversed(strides)), dtype=data.dtype)
    return jnp.sum(data * strides.reshape(-1, *([1] * (data.ndim - 1))), axis=0)


def _unravel(attrs, data):
    shape = attrs.shape
    idx = data.astype(jnp.int64)
    outs = []
    for s in reversed(shape):
        outs.append(idx % s)
        idx = idx // s
    return jnp.stack(list(reversed(outs)), axis=0).astype(data.dtype)


_reg("_unravel_index", _unravel, params={"shape": (tuple, REQUIRED)}, inputs=("data",))

# ---------------------------------------------------------------------------
# init ops (reference src/operator/tensor/init_op.cc)
# ---------------------------------------------------------------------------

_reg(
    "_zeros",
    lambda attrs: jnp.zeros(attrs.shape or (), dtype=attrs.dtype or jnp.float32),
    params={"shape": (tuple, None), "dtype": ("dtype", None), "ctx": (str, "")},
    inputs=(),
)
_reg(
    "_ones",
    lambda attrs: jnp.ones(attrs.shape or (), dtype=attrs.dtype or jnp.float32),
    params={"shape": (tuple, None), "dtype": ("dtype", None), "ctx": (str, "")},
    inputs=(),
)
_reg(
    "_full",
    lambda attrs: jnp.full(attrs.shape or (), attrs.value, dtype=attrs.dtype or jnp.float32),
    params={"shape": (tuple, None), "value": (float, 0.0), "dtype": ("dtype", None), "ctx": (str, "")},
    inputs=(),
)
_reg(
    "_arange",
    lambda attrs: jnp.tile(
        jnp.arange(attrs.start, attrs.stop, attrs.step, dtype=attrs.dtype or jnp.float32),
        attrs.repeat,
    )
    if attrs.repeat == 1
    else jnp.repeat(
        jnp.arange(attrs.start, attrs.stop, attrs.step, dtype=attrs.dtype or jnp.float32),
        attrs.repeat,
    ),
    params={
        "start": (float, 0.0),
        "stop": (float, None),
        "step": (float, 1.0),
        "repeat": (int, 1),
        "dtype": ("dtype", None),
        "ctx": (str, ""),
        "infer_range": (bool, False),
    },
    inputs=(),
)
_reg(
    "_eye",
    lambda attrs: jnp.eye(attrs.N, attrs.M or None, k=attrs.k, dtype=attrs.dtype or jnp.float32),
    params={"N": (int, REQUIRED), "M": (int, 0), "k": (int, 0), "dtype": ("dtype", None), "ctx": (str, "")},
    inputs=(),
)
_reg(
    "_identity_with_attr_like_rhs",
    lambda attrs, lhs, rhs: lhs,
    inputs=("lhs", "rhs"),
)
_reg("_NoGradient", lambda attrs: jnp.zeros(()), inputs=())

# ---------------------------------------------------------------------------
# ordering ops (reference src/operator/tensor/ordering_op.cc)
# ---------------------------------------------------------------------------


def _topk(attrs, x):
    ax = x.ndim - 1 if attrs.axis is None else attrs.axis % x.ndim
    k = attrs.k if attrs.k > 0 else x.shape[ax]
    xm = jnp.moveaxis(x, ax, -1)
    if attrs.is_ascend:
        vals, idxs = lax.top_k(-xm, k)
        vals = -vals
    else:
        vals, idxs = lax.top_k(xm, k)
    vals = jnp.moveaxis(vals, -1, ax)
    idxs = jnp.moveaxis(idxs, -1, ax)
    rt = attrs.ret_typ
    if rt == "value":
        return vals
    if rt == "indices":
        return idxs.astype(attrs.dtype or jnp.float32)
    if rt == "mask":
        mask = jnp.zeros(jnp.moveaxis(x, ax, -1).shape, dtype=x.dtype)
        mask = mask.at[..., 0].set(0)  # shape anchor
        oh = jax.nn.one_hot(jnp.moveaxis(idxs, ax, -1), x.shape[ax], dtype=x.dtype).sum(axis=-2)
        return jnp.moveaxis(oh, -1, ax)
    return vals, idxs.astype(attrs.dtype or jnp.float32)


_reg(
    "topk",
    _topk,
    params={
        "axis": (int, -1),
        "k": (int, 1),
        "ret_typ": (str, "indices"),
        "is_ascend": (bool, False),
        "dtype": ("dtype", None),
    },
    num_outputs=lambda attrs: 2 if attrs.get("ret_typ") == "both" else 1,
)


def _sort(attrs, x):
    ax = x.ndim - 1 if attrs.axis is None else attrs.axis % x.ndim
    s = jnp.sort(x, axis=ax)
    return s if attrs.is_ascend else jnp.flip(s, axis=ax)


_reg("sort", _sort, params={"axis": (int, -1), "is_ascend": (bool, True)})


def _argsort(attrs, x):
    ax = x.ndim - 1 if attrs.axis is None else attrs.axis % x.ndim
    s = jnp.argsort(x, axis=ax)
    if not attrs.is_ascend:
        s = jnp.flip(s, axis=ax)
    return s.astype(attrs.dtype or jnp.float32)


_reg(
    "argsort",
    _argsort,
    params={"axis": (int, -1), "is_ascend": (bool, True), "dtype": ("dtype", None)},
)

# ---------------------------------------------------------------------------
# histogram
# ---------------------------------------------------------------------------


def _histogram(attrs, data, *bins):
    if bins:
        edges = bins[0]
        cnt, _ = jnp.histogram(data.reshape(-1), bins=edges)
        return cnt.astype(jnp.int64), edges
    rng = attrs.range or (float(jnp.min(data)), float(jnp.max(data)))
    cnt, edges = jnp.histogram(data.reshape(-1), bins=attrs.bin_cnt or 10, range=rng)
    return cnt.astype(jnp.int64), edges


_reg(
    "_histogram",
    _histogram,
    params={"bin_cnt": (int, None), "range": (tuple, None)},
    inputs=("data",),
    num_outputs=2,
)
