"""mx.nd — imperative operator frontend.

Generated-from-registry op namespace, mirroring reference
``python/mxnet/ndarray/register.py:29,156`` (which code-gens a Python function
per C++ op).  Here the registry holds pure jax functions; the wrapper unwraps
NDArrays, injects RNG keys / train-mode flags, executes eagerly (JAX async
dispatch ≡ engine push), wraps outputs, and tapes the call for autograd
(Imperative::Invoke + RecordOp, reference imperative.cc:87,183).
"""
from __future__ import annotations

import sys
import types

import numpy as np

from jax import named_scope as _named_scope
from jax.core import Tracer as _Tracer

from ..base import parse_attr, dtype_np
from .. import compile_cache as _compile_cache
from ..context import current_context, Context
from .. import profiler as _prof
from ..telemetry import tracing as _tracing
from ..ops import registry as _registry
from ..ops import _load_all  # noqa: F401  (populates the registry)
from .ndarray import NDArray, array, empty, concatenate, waitall, _wrap, _to_device

__all__ = [
    "NDArray",
    "array",
    "empty",
    "zeros",
    "ones",
    "full",
    "arange",
    "concatenate",
    "waitall",
    "save",
    "load",
    "op",
    "random",
]

# attrs that only make sense engine-side in the reference; accepted and ignored
_IGNORED_ATTRS = frozenset({"name", "attr", "__layout__", "cudnn_tune", "cudnn_off", "workspace"})

# ops whose tuple return is partially hidden unless an attr asks for it
_VISIBLE_RULES = {
    "BatchNorm": lambda attrs: 3 if attrs.get("output_mean_var") else 1,
    "LayerNorm": lambda attrs: 3 if attrs.get("output_mean_var") else 1,
    "_sample_multinomial": lambda attrs: 2 if attrs.get("get_prob") else 1,
    "RNN": lambda attrs: (
        (3 if attrs.get("mode", "lstm") == "lstm" else 2) if attrs.get("state_outputs") else 1
    ),
}


def _op_name(fn):
    """The registered operator's name (``fn.op`` is set by ops.registry),
    else the function's own."""
    op = getattr(fn, "op", None)
    return op.name if op is not None else getattr(fn, "__name__", "op")


def _tape_if_recording(fn, nd_inputs, jargs, attrs, nd_outputs):
    from .. import autograd

    if autograd.is_recording():
        autograd._record_op(fn, nd_inputs, jargs, attrs, nd_outputs)


def _invoke_raw(fn, nd_args, attrs, visible=None, ctx=None):
    """Execute a pure fn on NDArray args: unwrap → run → wrap → tape."""
    jargs = []
    nd_inputs = []
    traced = False
    for a in nd_args:
        if isinstance(a, NDArray):
            nd_inputs.append(a)
            a = a._data
        else:
            nd_inputs.append(None)
        jargs.append(a)
        traced = traced or isinstance(a, _Tracer)
    if traced:
        # under jit (the Gluon-functional path): the operator's name goes
        # into the HLO's op_name and the device trace's tf_op, so device
        # time reads by operator.  Trace-time metadata only.
        with _named_scope(_op_name(fn)):
            _compile_cache.note_op_traced()
            res = fn(*jargs, **attrs)
    else:
        # an eager operator is a program launched on the device
        _tracing.count("dispatch")
        if _prof._op_profiling_active():
            t0 = _prof._now_us()
            res = fn(*jargs, **attrs)
            _prof._emit_op(getattr(fn, "__name__", "op"), t0,
                           _prof._now_us() - t0)
        else:
            res = fn(*jargs, **attrs)
    multi = isinstance(res, tuple)
    outs = res if multi else (res,)
    if ctx is not None:
        outs = tuple(_to_device(o, ctx) for o in outs)
    nd_outs = [_wrap(o, ctx) for o in outs]
    _tape_if_recording(fn, nd_inputs, jargs, attrs, nd_outs)
    if not multi:
        return nd_outs[0]
    if visible is not None:
        nd_outs = nd_outs[:visible]
    return nd_outs[0] if len(nd_outs) == 1 else nd_outs


def _invoke(opdef, args, kwargs):
    kwargs = dict(kwargs)
    out_arr = kwargs.pop("out", None)
    ctx = kwargs.pop("ctx", None)
    for k in list(kwargs):
        if k in _IGNORED_ATTRS:
            kwargs.pop(k)
    args = list(args)
    # map named tensor args to positions
    if not opdef.variadic and opdef.arg_names:
        if len(args) > len(opdef.arg_names):
            # extra positional args are attrs passed positionally, MXNet-style
            # (e.g. nd.clip(x, a_min, a_max)); an extra NDArray is a real
            # arity error, not an attr
            extras = args[len(opdef.arg_names) :]
            args = args[: len(opdef.arg_names)]
            free_attrs = [a for a in opdef.attr_names if a not in kwargs]
            if len(extras) > len(free_attrs) or any(
                isinstance(e, NDArray) or getattr(e, "ndim", 0) > 0 for e in extras
            ):
                raise TypeError(
                    "%s takes at most %d tensor arguments (%d given)"
                    % (opdef.name, len(opdef.arg_names), len(args) + len(extras))
                )
            for a, v in zip(free_attrs, extras):
                kwargs[a] = v
        named = {}
        for i, a in enumerate(args):
            named[opdef.arg_names[i]] = a
        for an in opdef.arg_names:
            if an in kwargs:
                named[an] = kwargs.pop(an)
        args = [named.get(an, opdef.defaults.get(an)) for an in opdef.arg_names]
        while args and args[-1] is None and opdef.arg_names[len(args) - 1] not in named:
            args.pop()
    # attrs (Custom keeps raw strings: the prop contract passes kwargs
    # verbatim, reference operator.py register)
    keep_raw = opdef.name == "Custom"
    attrs = {}
    for k, v in kwargs.items():
        attrs[k] = parse_attr(v) if isinstance(v, str) and not keep_raw else v
    if "key" in opdef.attr_names and "key" not in attrs:
        from .. import random as _rnd

        attrs["key"] = _rnd.next_key()
    if "training" in opdef.attr_names and "training" not in attrs:
        from .. import autograd

        attrs["training"] = autograd.is_training()
    visible_rule = _VISIBLE_RULES.get(opdef.name)
    visible = visible_rule(attrs) if visible_rule else None
    result = _invoke_raw(opdef.fn, args, attrs, visible=visible, ctx=ctx)
    if opdef.mutates:
        # reference mutable-input ops (optimizer updates): extra outputs are
        # the new values of the named inputs, written back in place
        outs = result if isinstance(result, list) else [result]
        for i, mname in enumerate(opdef.mutates):
            idx = opdef.arg_names.index(mname)
            if idx < len(args) and isinstance(args[idx], NDArray):
                args[idx]._rebind(outs[1 + i]._data)
        result = outs[0]
    if out_arr is not None:
        target = result[0] if isinstance(result, list) else result
        out_arr._rebind(target._data)
        return out_arr
    return result


def _binary_dispatch(name, lhs, rhs, reverse=False):
    opdef = _registry.get(name)
    if isinstance(rhs, (np.ndarray, list, tuple)):
        rhs = array(rhs, dtype=lhs.dtype)
    a, b = (rhs, lhs) if reverse else (lhs, rhs)
    return _invoke(opdef, (a, b), {})


def _make_op_func(opdef, public_name):
    def op_func(*args, **kwargs):
        return _invoke(opdef, args, kwargs)

    op_func.__name__ = public_name.lstrip("_")
    op_func.__qualname__ = op_func.__name__
    op_func.__doc__ = opdef.__doc__
    op_func.opdef = opdef
    return op_func


# build the `op` namespace module with every registered op (incl. aliases)
op = types.ModuleType(__name__ + ".op")
op.__doc__ = "All registered operators (reference mx.nd.op namespace)."
for _name in _registry.list_ops(include_aliases=True):
    _f = _make_op_func(_registry.get(_name), _name)
    setattr(op, _name, _f)
    if not hasattr(sys.modules[__name__], _name):
        setattr(sys.modules[__name__], _name, _f)
sys.modules[op.__name__] = op

# contrib namespace: `_contrib_Foo` → `nd.contrib.Foo` (reference
# python/mxnet/ndarray/contrib.py generated the same way)
contrib = types.ModuleType(__name__ + ".contrib")
contrib.__doc__ = "Contrib (experimental) operators (reference mx.nd.contrib)."
for _name in _registry.list_ops(include_aliases=True):
    if _name.startswith("_contrib_"):
        setattr(contrib, _name[len("_contrib_"):], _make_op_func(_registry.get(_name), _name))
sys.modules[contrib.__name__] = contrib


def __getattr__(name):
    """Ops registered AFTER import (ops.registry.register at runtime —
    tutorials, tests, user extensions) resolve dynamically (PEP 562)."""
    if not name.startswith("__") and _registry.exists(name):
        return _make_op_func(_registry.get(name), name)
    raise AttributeError("module %r has no attribute %r" % (__name__, name))


# ---------------------------------------------------------------------------
# creation functions with ctx handling (reference ndarray.py zeros/ones/...)
# ---------------------------------------------------------------------------


def zeros(shape, ctx=None, dtype="float32", stype=None, **kwargs):
    import jax.numpy as jnp

    if stype is not None and stype != "default":
        from . import sparse as _sparse

        return _sparse.zeros(stype, shape, ctx=ctx, dtype=dtype or "float32")
    shape = (shape,) if isinstance(shape, int) else tuple(shape)
    out = jnp.zeros(shape, dtype=dtype_np(dtype or "float32"))
    return _wrap(_to_device(out, ctx) if ctx else out, ctx)


def ones(shape, ctx=None, dtype="float32", **kwargs):
    import jax.numpy as jnp

    shape = (shape,) if isinstance(shape, int) else tuple(shape)
    out = jnp.ones(shape, dtype=dtype_np(dtype or "float32"))
    return _wrap(_to_device(out, ctx) if ctx else out, ctx)


def full(shape, val, ctx=None, dtype="float32", **kwargs):
    import jax.numpy as jnp

    shape = (shape,) if isinstance(shape, int) else tuple(shape)
    out = jnp.full(shape, val, dtype=dtype_np(dtype or "float32"))
    return _wrap(_to_device(out, ctx) if ctx else out, ctx)


def arange(start, stop=None, step=1.0, repeat=1, ctx=None, dtype="float32"):
    import jax.numpy as jnp

    out = jnp.arange(start, stop, step, dtype=dtype_np(dtype or "float32"))
    if repeat != 1:
        out = jnp.repeat(out, repeat)
    return _wrap(_to_device(out, ctx) if ctx else out, ctx)


def maximum(lhs, rhs):
    """Elementwise max, scalar-aware (reference python/mxnet/ndarray/ndarray.py maximum)."""
    if isinstance(lhs, NDArray) and isinstance(rhs, NDArray):
        return _invoke(_registry.get("broadcast_maximum"), (lhs, rhs), {})
    if isinstance(lhs, NDArray):
        return _invoke(_registry.get("_maximum_scalar"), (lhs,), {"scalar": float(rhs)})
    return _invoke(_registry.get("_maximum_scalar"), (rhs,), {"scalar": float(lhs)})


def minimum(lhs, rhs):
    """Elementwise min, scalar-aware (reference ndarray.py minimum)."""
    if isinstance(lhs, NDArray) and isinstance(rhs, NDArray):
        return _invoke(_registry.get("broadcast_minimum"), (lhs, rhs), {})
    if isinstance(lhs, NDArray):
        return _invoke(_registry.get("_minimum_scalar"), (lhs,), {"scalar": float(rhs)})
    return _invoke(_registry.get("_minimum_scalar"), (rhs,), {"scalar": float(lhs)})


def zeros_like(arr, **kw):
    return _invoke(_registry.get("zeros_like"), (arr,), kw)


def ones_like(arr, **kw):
    return _invoke(_registry.get("ones_like"), (arr,), kw)


# ---------------------------------------------------------------------------
# serialization (reference MXNDArraySave/Load, src/c_api/c_api.cc:131-167)
# ---------------------------------------------------------------------------


def save(fname, data):
    """Save NDArray | list | dict of NDArrays (reference nd.save).

    Format: numpy .npz with a manifest key encoding list vs dict (portable,
    replacing the reference's dmlc binary format).
    """
    def _np(v):
        return v.asnumpy() if hasattr(v, "asnumpy") else np.asarray(v)

    # pass an open handle so numpy can't append ".npz" to the user's filename
    with open(fname, "wb") as f:
        if isinstance(data, (NDArray, np.ndarray)):
            np.savez(f, __mx_format__="single", a0=_np(data))
        elif isinstance(data, (list, tuple)):
            arrs = {"a%d" % i: _np(a) for i, a in enumerate(data)}
            np.savez(f, __mx_format__="list", **arrs)
        elif isinstance(data, dict):
            arrs = {"k_" + k: _np(v) for k, v in data.items()}
            np.savez(f, __mx_format__="dict", **arrs)
        else:
            raise TypeError(type(data))


def load(fname):
    """Load NDArrays saved by :func:`save`."""
    with np.load(fname, allow_pickle=False) as z:
        fmt = str(z["__mx_format__"])
        if fmt == "single":
            return [array(z["a0"])]
        if fmt == "list":
            n = len([k for k in z.files if k.startswith("a")])
            return [array(z["a%d" % i]) for i in range(n)]
        return {k[2:]: array(z[k]) for k in z.files if k.startswith("k_")}


# ---------------------------------------------------------------------------
# module-level arithmetic helpers (reference mxnet/ndarray/ndarray.py
# add/subtract/... — scalar-or-array aware; the NDArray magic methods
# already dispatch to broadcast/scalar ops, so delegate to them)
# ---------------------------------------------------------------------------

def _arith(name, op):
    def f(lhs, rhs):
        if not isinstance(lhs, NDArray) and not isinstance(rhs, NDArray):
            if np.isscalar(lhs) and np.isscalar(rhs):
                # reference _ufunc_helper returns a plain Python number
                # for scalar-scalar
                return op(lhs, rhs)
            lhs = array(lhs)
        return op(lhs, rhs)

    f.__name__ = name
    f.__doc__ = ("Element-wise %s with scalar-or-array operands "
                 "(reference ndarray.py %s)." % (name, name))
    return f


add = _arith("add", lambda l, r: l + r)
subtract = _arith("subtract", lambda l, r: l - r)
multiply = _arith("multiply", lambda l, r: l * r)
divide = _arith("divide", lambda l, r: l / r)
true_divide = _arith("true_divide", lambda l, r: l / r)
modulo = _arith("modulo", lambda l, r: l % r)
power = _arith("power", lambda l, r: l ** r)

# ---------------------------------------------------------------------------
# nd.random namespace (reference mxnet/ndarray/random.py)
# ---------------------------------------------------------------------------

random = types.ModuleType(__name__ + ".random")
random.__doc__ = "Random distribution generators (reference nd.random)."


def _make_random(fname, opname, posnames):
    opdef = _registry.get(opname)

    def rnd_func(*args, **kwargs):
        # reference nd.random samplers take their distribution params
        # positionally (mxnet/ndarray/random.py uniform(low, high, shape...));
        # map them onto the op's keyword-only params
        for name, val in zip(posnames, args):
            if name in kwargs:
                raise TypeError("%s() got multiple values for '%s'"
                                % (fname, name))
            kwargs[name] = val
        extra = args[len(posnames):]
        return _invoke(opdef, extra, kwargs)

    rnd_func.__name__ = fname
    rnd_func.__doc__ = opdef.__doc__
    return rnd_func


for _fname, _opname, _pos in [
    # trailing ctx/out: the reference samplers accept them positionally too
    # (mxnet/ndarray/random.py uniform(low, high, shape, dtype, ctx, out));
    # _invoke already handles both as keywords
    ("uniform", "_random_uniform", ("low", "high", "shape", "dtype", "ctx", "out")),
    ("normal", "_random_normal", ("loc", "scale", "shape", "dtype", "ctx", "out")),
    ("gamma", "_random_gamma", ("alpha", "beta", "shape", "dtype", "ctx", "out")),
    ("poisson", "_random_poisson", ("lam", "shape", "dtype", "ctx", "out")),
    ("negative_binomial", "_random_negative_binomial",
     ("k", "p", "shape", "dtype", "ctx", "out")),
    ("generalized_negative_binomial", "_random_generalized_negative_binomial",
     ("mu", "alpha", "shape", "dtype", "ctx", "out")),
    ("randint", "_random_randint", ("low", "high", "shape", "dtype", "ctx", "out")),
    ("multinomial", "_sample_multinomial", ()),
    ("shuffle", "_shuffle", ()),
]:
    setattr(random, _fname, _make_random(_fname, _opname, _pos))


def _random_exponential_frontend(scale=1.0, shape=(1,), dtype="float32",
                                 **kwargs):
    """Reference nd.random.exponential takes the MEAN (``scale``) and
    converts to the op's rate (mxnet/ndarray/random.py exponential:
    lam = 1/scale); the raw-rate form stays available as
    ``nd._random_exponential(lam=...)``."""
    opdef = _registry.get("_random_exponential")
    return _invoke(opdef, (), dict(lam=1.0 / scale, shape=shape,
                                   dtype=dtype, **kwargs))


random.exponential = _random_exponential_frontend
sys.modules[random.__name__] = random

# ---------------------------------------------------------------------------
# nd.sparse namespace (reference mxnet/ndarray/sparse.py)
# ---------------------------------------------------------------------------
from . import sparse  # noqa: E402
from .sparse import (  # noqa: E402,F401
    BaseSparseNDArray,
    CSRNDArray,
    RowSparseNDArray,
    cast_storage,
)

__all__ += ["sparse", "BaseSparseNDArray", "CSRNDArray", "RowSparseNDArray", "cast_storage"]

# ---------------------------------------------------------------------------
# fluent methods (reference mxnet/ndarray/ndarray.py "Convenience fluent
# method for X" set — x.log() ≡ nd.log(x) for every listed op).  Hand-written
# methods on NDArray win; only the missing ones are attached here.
# ---------------------------------------------------------------------------

_FLUENT = (
    "reshape_like zeros_like ones_like broadcast_axes repeat pad swapaxes "
    "split slice slice_axis slice_like take one_hot pick sort topk argsort "
    "argmax argmax_channel argmin clip abs sign flatten expand_dims tile "
    "transpose flip sum nansum prod nanprod mean max min norm round rint "
    "fix floor ceil trunc sin cos tan arcsin arccos arctan degrees radians "
    "sinh cosh tanh arcsinh arccosh arctanh exp expm1 log log10 log2 log1p "
    "sqrt rsqrt cbrt rcbrt square reciprocal relu sigmoid softmax "
    "log_softmax squeeze"
).split()


def _make_fluent(opname):
    opdef = _registry.get(opname)

    def fluent(self, *args, **kwargs):
        return _invoke(opdef, (self,) + args, kwargs)

    fluent.__name__ = opname
    fluent.__doc__ = ("Convenience fluent method for nd.%s (reference "
                      "ndarray.py fluent set)." % opname)
    return fluent


for _fname in _FLUENT:
    if not hasattr(NDArray, _fname) and _registry.exists(_fname):
        setattr(NDArray, _fname, _make_fluent(_fname))
