"""Block / HybridBlock — reference ``python/mxnet/gluon/block.py:124,656``.

TPU-native CachedOp: ``hybridize()`` captures the whole block body as ONE pure
function of (rng key, params, inputs) and compiles it with ``jax.jit`` per
shape/dtype/train-mode signature — the analog of
``src/imperative/cached_op.cc:807`` (Forward → Static/DynamicForward), where
the shape-signature cache mirrors ``SetForwardGraph``'s re-trace behavior.
The jitted call is recorded on the autograd tape as a single entry, so the
backward pass differentiates straight through the compiled computation.
"""
from __future__ import annotations

import re
import threading

import numpy as np

from .. import autograd
from .. import random as _rnd
from ..base import numeric_types
from ..ndarray.ndarray import NDArray, _wrap
from ..ndarray import _invoke_raw
from .parameter import Parameter, ParameterDict, DeferredInitializationError

__all__ = ["Block", "HybridBlock", "SymbolBlock", "remat", "loop"]

# per-capture invocation counts (thread-local, reset by _get_graph): lets
# a block invoked several times WITHIN one capture (weight sharing —
# siamese towers) get a distinct per-call name-prefix ordinal, while
# staying deterministic across captures and safe under concurrent
# captures of a shared block from several threads
_SYM_CAPTURE = threading.local()


def _sym_call_prefix(block):
    """Name prefix for one symbolic invocation of ``block`` (see above)."""
    counts = getattr(_SYM_CAPTURE, "counts", None)
    if counts is None:
        return block.prefix  # direct user symbolic call: plain prefix
    n = counts.get(id(block), -1) + 1
    counts[id(block)] = n
    return block.prefix if n == 0 else "%scall%d_" % (block.prefix, n)


class _BlockScope:
    """Name manager for nested blocks (reference block.py:34 _BlockScope)."""

    _current = threading.local()

    def __init__(self, block):
        self._block = block
        self._counter = {}
        self._old_scope = None

    @staticmethod
    def create(prefix, params, hint):
        current = getattr(_BlockScope._current, "value", None)
        if current is None:
            if prefix is None:
                import uuid

                prefix = "%s%d_" % (hint, _global_count(hint))
            if params is None:
                params = ParameterDict(prefix)
            else:
                params = ParameterDict(params.prefix, params)
            return prefix, params
        if prefix is None:
            count = current._counter.get(hint, 0)
            prefix = "%s%d_" % (hint, count)
            current._counter[hint] = count + 1
        if params is None:
            parent = current._block.params
            params = ParameterDict(parent.prefix + prefix, parent._shared)
        else:
            params = ParameterDict(params.prefix, params)
        return current._block.prefix + prefix, params

    def __enter__(self):
        if self._block._empty_prefix:
            return self
        self._old_scope = getattr(_BlockScope._current, "value", None)
        _BlockScope._current.value = self
        return self

    def __exit__(self, *a):
        if self._block._empty_prefix:
            return
        _BlockScope._current.value = self._old_scope


_GLOBAL_COUNTS = {}


def _global_count(hint):
    c = _GLOBAL_COUNTS.get(hint, 0)
    _GLOBAL_COUNTS[hint] = c + 1
    return c


def _flatten(args):
    """Flatten nested list/tuple of NDArrays; return flat list + structure spec."""
    if isinstance(args, NDArray):
        return [args], int(0)
    if args is None:
        return [], None
    if isinstance(args, (list, tuple)):
        flat, fmts = [], []
        for a in args:
            f, fmt = _flatten(a)
            flat.extend(f)
            fmts.append(fmt)
        return flat, fmts
    return [args], -1  # opaque non-tensor


def _remat_forward(block, args):
    """Trace ``block.forward`` under ``jax.checkpoint`` (see
    ``Block.set_remat``).  The block's params (incl. mutated aux like BN
    running stats) become explicit inputs/outputs of the rematted pure
    function so XLA saves only the block boundary, not its interior."""
    import jax

    flat_in, in_fmt = _flatten(args)
    if not all(isinstance(a, NDArray) for a in flat_in):
        return block.forward(*args)  # opaque args: run un-rematted
    params = sorted(block.collect_params().items())
    p_vals = tuple(p._data._data for _, p in params)
    in_vals = tuple(a._data for a in flat_in)
    fmt_box = [None]

    def pure(p_vals, in_vals):
        old = [p._data for _, p in params]
        for (_, p), v in zip(params, p_vals):
            p._data = NDArray(v)
        try:
            ins, _ = _regroup([NDArray(v) for v in in_vals], in_fmt)
            out = block.forward(*(ins if isinstance(ins, tuple) else (ins,)))
        finally:
            post = tuple(p._data._data for _, p in params)
            for (_, p), o in zip(params, old):
                p._data = o
        flat_out, out_fmt = _flatten(out)
        # non-NDArray outputs (ints, shapes, None) are trace-time constants:
        # carry them via the box, return only tensors through the checkpoint
        tensor_idx = [i for i, o in enumerate(flat_out)
                      if isinstance(o, NDArray)]
        fmt_box[0] = (out_fmt, tensor_idx, flat_out)
        return tuple(flat_out[i]._data for i in tensor_idx), post

    out_vals, post = jax.checkpoint(pure, prevent_cse=False)(p_vals, in_vals)
    for (_, p), v in zip(params, post):
        p._data = NDArray(v)
    out_fmt, tensor_idx, flat_template = fmt_box[0]
    merged = list(flat_template)
    for i, v in zip(tensor_idx, out_vals):
        merged[i] = NDArray(v)
    out, _ = _regroup(merged, out_fmt)
    return out


def _regroup(flat, fmt):
    if fmt is None:
        return None, flat
    if isinstance(fmt, int):
        if fmt == -1 or fmt == 0:
            return flat[0], flat[1:]
    assert isinstance(fmt, list)
    out = []
    for f in fmt:
        o, flat = _regroup(flat, f)
        out.append(o)
    return tuple(out), flat


class Block:
    """Base building block (reference gluon/block.py:124).

    Children and Parameters registered via attribute assignment; ``forward``
    defines computation on NDArrays.
    """

    def __init__(self, prefix=None, params=None):
        self._empty_prefix = prefix == ""
        self._prefix, self._params = _BlockScope.create(prefix, params, self._alias())
        self._name = self._prefix[:-1] if self._prefix.endswith("_") else self._prefix
        self._scope = _BlockScope(self)
        self._children = {}
        self._reg_params = {}
        self._forward_hooks = []
        self._forward_pre_hooks = []

    def _alias(self):
        return self.__class__.__name__.lower()

    def __repr__(self):
        modstr = "\n".join(
            "  (%s): %s" % (k, re.sub("\n", "\n  ", repr(v))) for k, v in self._children.items()
        )
        return "%s(\n%s\n)" % (self.__class__.__name__, modstr)

    def __setattr__(self, name, value):
        existing = getattr(self, name, None)
        if isinstance(existing, (Parameter, Block)) and not isinstance(value, type(existing)):
            raise TypeError(
                "Changing attribute type for %s from %s to %s is not allowed."
                % (name, type(existing), type(value))
            )
        if isinstance(value, Block):
            self.register_child(value, name)
        elif isinstance(value, Parameter):
            assert name not in self._reg_params or self._reg_params[name] is value, (
                "Overriding Parameter attribute %s is not allowed." % name
            )
            self._reg_params[name] = value
        super().__setattr__(name, value)

    @property
    def prefix(self):
        return self._prefix

    @property
    def name(self):
        return self._name

    def name_scope(self):
        return self._scope

    @property
    def params(self):
        return self._params

    def collect_params(self, select=None):
        """All Parameters of this block and children (reference block.py:278)."""
        ret = ParameterDict(self._params.prefix)
        if select is None:
            ret.update(self.params)
        else:
            pattern = re.compile(select)
            ret.update({n: p for n, p in self.params.items() if pattern.match(n)})
        for child in self._children.values():
            ret.update(child.collect_params(select=select))
        return ret

    def register_child(self, block, name=None):
        if name is None:
            name = str(len(self._children))
        self._children[name] = block

    def register_forward_hook(self, hook):
        self._forward_hooks.append(hook)
        return hook

    def register_forward_pre_hook(self, hook):
        self._forward_pre_hooks.append(hook)
        return hook

    def apply(self, fn):
        for child in self._children.values():
            child.apply(fn)
        fn(self)
        return self

    def initialize(self, init=None, ctx=None, verbose=False, force_reinit=False):
        self.collect_params().initialize(init, ctx, verbose, force_reinit)

    def hybridize(self, active=True, **kwargs):
        for child in self._children.values():
            child.hybridize(active, **kwargs)

    def cast(self, dtype):
        for child in self._children.values():
            child.cast(dtype)
        for p in self.params.values():
            p.cast(dtype)

    # -- parameter serialization -------------------------------------------
    def save_parameters(self, filename):
        """Save all parameters (reference block.py:335 save_params)."""
        params = self._collect_params_with_prefix()
        from ..ndarray import save as nd_save

        nd_save(filename, {k: v.data() for k, v in params.items() if v._data is not None})

    save_params = save_parameters

    def load_parameters(self, filename, ctx=None, allow_missing=False, ignore_extra=False):
        """Load parameters saved by save_parameters (reference block.py:397)."""
        from ..ndarray import load as nd_load

        loaded = nd_load(filename)
        params = self._collect_params_with_prefix()
        if not loaded and not params:
            return
        if not any("." in k for k in loaded.keys()):
            # legacy name-based format: delegate to ParameterDict.load
            self.collect_params().load(filename, ctx, allow_missing, ignore_extra, self.prefix)
            return
        if not allow_missing:
            for name in params:
                if name not in loaded:
                    raise IOError("Parameter '%s' is missing in file '%s'" % (name, filename))
        for name, arr in loaded.items():
            if name not in params:
                if not ignore_extra:
                    raise IOError("Parameter '%s' loaded from '%s' is not present in the Block" % (name, filename))
                continue
            params[name].set_data(arr)

    load_params = load_parameters

    def _collect_params_with_prefix(self, prefix=""):
        if prefix:
            prefix += "."
        ret = {prefix + k: v for k, v in self._reg_params.items()}
        for name, child in self._children.items():
            ret.update(child._collect_params_with_prefix(prefix + name))
        return ret

    # -- execution ----------------------------------------------------------
    def __call__(self, *args):
        for hook in self._forward_pre_hooks:
            hook(self, args)
        if getattr(self, "_remat", False) and _TRACING.active:
            out = _remat_forward(self, args)
        else:
            out = self.forward(*args)
        for hook in self._forward_hooks:
            hook(self, args, out)
        return out

    def set_remat(self, active=True):
        """Recompute this block's activations during backward instead of
        storing them (the reference's memory mirror,
        ``MXNET_BACKWARD_DO_MIRROR`` → gradient-mirror path in
        ``src/executor/graph_executor.cc InitFullGraph``; here
        ``jax.checkpoint`` applied to this block's subgraph when traced
        inside a CachedOp / ``gluon.functional`` train step).

        Trades FLOPs for activation memory; roughly speed-neutral on
        memory-bound models (ResNet-50 bf16 measured ~2% slower — see
        docs/PERF_NOTES.md — vs the reference mirror's ~30% cost).
        Returns self.
        """
        self._remat = bool(active)
        return self

    def forward(self, *args):
        raise NotImplementedError

    def summary(self, *inputs):
        from ..visualization import block_summary

        return block_summary(self, *inputs)


class HybridBlock(Block):
    """Block that can be compiled (reference gluon/block.py:656).

    Subclasses implement ``hybrid_forward(F, x, *, params...)`` where F is the
    ``nd`` or ``sym`` module.  After ``hybridize()``, calls are routed through
    a per-shape-signature jitted pure function — the CachedOp analog.
    """

    def __init__(self, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._active = False
        self._cached_graph = ()
        self._jit_cache = {}
        self._flags = {}

    def __setattr__(self, name, value):
        super().__setattr__(name, value)
        if isinstance(value, HybridBlock):
            self._clear_cached_op()

    def hybridize(self, active=True, **kwargs):
        self._active = active
        self._flags = kwargs
        self._clear_cached_op()
        super().hybridize(active, **kwargs)

    def cast(self, dtype):
        self._clear_cached_op()
        super().cast(dtype)

    def _clear_cached_op(self):
        self._cached_graph = ()
        self._jit_cache = {}
        self._sym_trace_failed = False

    # -- symbolic graph for shape inference / export ------------------------
    def _get_graph(self, *args):
        if not self._cached_graph:
            from .. import symbol as sym_mod

            flat_args, self._in_format = _flatten(args)
            # single input exports as "data" (the reference gluon export
            # convention deployment tooling expects); multi-input as dataN
            inputs = ([sym_mod.var("data")] if len(flat_args) == 1 else
                      [sym_mod.var("data%d" % i) for i in range(len(flat_args))])
            grouped, _ = _regroup(inputs, self._in_format)
            if not isinstance(grouped, tuple):
                grouped = (grouped,)
            # save/restore (not clobber) the ambient counts: a reentrant
            # capture — block A's hybrid_forward triggering B._get_graph
            # (e.g. an infer_shape inside the body) — must hand A's capture
            # back its outer per-call ordinals, or A's later shared-block
            # invocations would restart at call0 and collide (ADVICE round 5)
            prev_counts = getattr(_SYM_CAPTURE, "counts", None)
            _SYM_CAPTURE.counts = {}
            try:
                out = self._symbolic_forward(sym_mod, *grouped)
            finally:
                _SYM_CAPTURE.counts = prev_counts
            flat_out, self._out_format = _flatten(out)
            self._cached_graph = inputs, sym_mod.Group(flat_out) if len(flat_out) > 1 else flat_out[0]
        return self._cached_graph

    def _symbolic_forward(self, sym_mod, *args):
        from ..base import Prefix

        params = {name: p.var() for name, p in self._reg_params.items()}
        with Prefix(_sym_call_prefix(self)):  # see forward()'s symbol branch
            return self.hybrid_forward(sym_mod, *args, **params)

    def infer_shape(self, *args):
        """Resolve deferred parameter shapes from input shapes (reference
        block.py _deferred_infer_shape → infer_shape)."""
        inputs, out = self._get_graph(*args)
        flat_args, _ = _flatten(args)
        kwargs = {v.name: a.shape for v, a in zip(inputs, flat_args)}
        arg_shapes, _, aux_shapes = out.infer_shape(**kwargs)
        sdict = dict(zip(out.list_arguments(), arg_shapes))
        sdict.update(dict(zip(out.list_auxiliary_states(), aux_shapes)))
        for p in self.collect_params().values():
            if p._deferred_init is not None and p.name in sdict:
                p._finish_deferred_init(sdict[p.name])

    def export(self, path, epoch=0):
        """Export symbol json + params (reference block.py export)."""
        if not self._cached_graph:
            if getattr(self, "_sym_trace_failed", False):
                raise RuntimeError(
                    "export unavailable: this block's body could not be "
                    "traced symbolically (concrete .shape use or "
                    "train-only ops in hybrid_forward) — the forward ran, "
                    "but no symbol graph could be captured.")
            raise RuntimeError("Please first call block.hybridize() and then run forward once before calling export.")
        _, out = self._cached_graph
        out.save("%s-symbol.json" % path)
        from ..ndarray import save as nd_save

        arg = {}
        for name, p in self.collect_params().items():
            if p._data is not None:
                arg[("aux:" if p.grad_req == "null" else "arg:") + name] = p.data()
        nd_save("%s-%04d.params" % (path, epoch), arg)

    # -- execution ----------------------------------------------------------
    def forward(self, x, *args):
        """Dispatch to hybrid_forward with F=nd (eager) or F=sym."""
        from ..symbol.symbol import Symbol

        if isinstance(x, Symbol):
            from .. import symbol as sym_mod
            from ..base import Prefix

            params = {name: p.var() for name, p in self._reg_params.items()}
            # scope op-node names by the block's (absolute) prefix: layers
            # that name their op explicitly (BatchNorm's name="fwd") would
            # otherwise collide across instances, and the serializer walks
            # dedupe by name — a traced graph with two BN layers silently
            # dropped everything between them (reference gluon gets this
            # from _BlockScope's NameManager, python/mxnet/name.py).  A
            # weight-shared block invoked twice in one capture gets a
            # per-call ordinal (_sym_call_prefix) so auto names stay
            # unique too.
            with Prefix(_sym_call_prefix(self)):
                return self.hybrid_forward(sym_mod, x, *args, **params)
        from .. import ndarray as nd_mod

        try:
            params = {name: p.data() for name, p in self._reg_params.items()}
        except DeferredInitializationError:
            self.infer_shape(x, *args)
            for p in self.collect_params().values():
                if p._deferred_init is not None:
                    p._finish_deferred_init(p.shape)
            params = {name: p.data() for name, p in self._reg_params.items()}
        return self.hybrid_forward(nd_mod, x, *args, **params)

    def __call__(self, *args):
        from ..symbol.symbol import Symbol

        if (
            not self._active
            or _TRACING.active  # inside a parent CachedOp trace: run inline
            or (args and isinstance(args[0], Symbol))
        ):
            return super().__call__(*args)
        return self._call_cached_op(*args)

    def hybrid_forward(self, F, x, *args, **kwargs):
        raise NotImplementedError

    # -- CachedOp -----------------------------------------------------------
    def _call_cached_op(self, *args):
        flat_args, in_fmt = _flatten(args)
        # resolve any deferred params first (runs shape inference eagerly)
        try:
            params = self._cached_op_params()
        except DeferredInitializationError:
            self.infer_shape(*args)
            for p in self.collect_params().values():
                if p._deferred_init is not None:
                    p._finish_deferred_init(p.shape)
            params = self._cached_op_params()

        train = autograd.is_training()
        sig = (
            tuple((a.shape, str(a.dtype)) for a in flat_args),
            train,
            repr(in_fmt),
        )
        entry = self._jit_cache.get(sig)
        if entry is None:
            if (not self._cached_graph and not train
                    and not getattr(self, "_sym_trace_failed", False)):
                # opportunistically capture the symbolic graph so the
                # reference journey hybridize() -> forward -> export()
                # works; some bodies (train-mode target ops, concrete
                # .shape use) can't trace symbolically — remember the
                # failure so multi-scale eval doesn't re-trace per shape
                try:
                    grouped, _ = _regroup(flat_args, in_fmt)
                    self._get_graph(*grouped)
                except Exception:
                    self._sym_trace_failed = True
            entry = self._build_cached_op(flat_args, in_fmt, params, train)
            self._jit_cache[sig] = entry
        jit_fn, out_fmt_box, mutable = entry

        key = _rnd.next_key()
        res = _invoke_raw(jit_fn, [NDArray(key)] + [p._data for _, p in params] + flat_args, {})
        outs = res if isinstance(res, list) else [res]
        # split user outputs from mutated aux-state outputs
        n_aux = len(mutable)
        user_outs = outs[: len(outs) - n_aux]
        aux_outs = outs[len(outs) - n_aux :]
        for (_, p), new in zip(mutable, aux_outs):
            p._data._rebind(new._data)
        grouped, _ = _regroup(user_outs, out_fmt_box[0])
        return grouped

    def _cached_op_params(self):
        items = sorted(self.collect_params().items())
        for _, p in items:
            p.data()  # raises Deferred/RuntimeError with a clear message
        return items

    def _build_cached_op(self, flat_args, in_fmt, params, train):
        """Trace the block body once into a pure jitted fn.

        pure(key, *param_vals, *input_vals) -> (*out_vals, *new_aux_vals)
        """
        import jax

        out_fmt_box = [None]
        mutable = [(n, p) for n, p in params if p.grad_req == "null"]
        n_params = len(params)
        self_ref = self

        mutable_idx = [i for i, (_, p) in enumerate(params) if p.grad_req == "null"]

        def pure(key, *vals):
            param_vals = vals[:n_params]
            input_vals = vals[n_params:]

            def call():
                nd_inputs = [NDArray(v) for v in input_vals]
                grouped, _ = _regroup(nd_inputs, in_fmt)
                if not isinstance(grouped, tuple):
                    grouped = (grouped,)
                return Block.__call__(self_ref, *grouped)

            out, post = _swap_trace_call(params, param_vals, call, key, train)
            flat_out, out_fmt = _flatten(out)
            out_fmt_box[0] = out_fmt
            return tuple(o._data for o in flat_out) + tuple(post[i] for i in mutable_idx)

        return jax.jit(pure), out_fmt_box, mutable


class _TracingFlag(threading.local):
    active = False
    params = ()     # the (name, Parameter) items swapped in by the open trace


_TRACING = _TracingFlag()


def _swap_trace_call(params, param_vals, call, key, train):
    """Core of the CachedOp/functionalize trace (reference CachedOp captures a
    graph by running the block once, src/imperative/cached_op.cc:268): swap the
    given jax arrays into the Parameters, run ``call()`` under the tracing flag
    with a fixed RNG key, collect post-call param arrays (mutated aux state,
    e.g. BatchNorm running stats), then restore.  Returns (out, post_vals)."""
    swapped = []
    for (_, p), v in zip(params, param_vals):
        swapped.append((p, p._data))
        p._data = NDArray(v)
    prev_tracing, prev_params = _TRACING.active, _TRACING.params
    _TRACING.active, _TRACING.params = True, params
    try:
        with autograd.pause(train_mode=train), _rnd.key_provider(key):
            out = call()
        post = [p._data._data for _, p in params]
        return out, post
    finally:
        _TRACING.active, _TRACING.params = prev_tracing, prev_params
        for p, old in swapped:
            p._data = old


def remat(fn):
    """``fn`` over NDArrays (child blocks called inside, their parameters
    closed over) -> the same function, recomputed in the backward pass of a
    jitted train step instead of keeping its intermediates
    (``jax.checkpoint``).  Outside a trace, eager or symbolic, it is ``fn``
    itself: the autograd tape keeps what it records.  Composes with
    :func:`loop` either way round: a rematted function called inside a loop's
    body is recomputed once per pass of the backward loop, and only its
    arguments are kept for each pass."""
    import jax
    from jax.core import Tracer

    def wrapped(*args):
        if not any(isinstance(a, NDArray) and isinstance(a._data, Tracer)
                   for a in args):
            return fn(*args)

        def pure(*vals):
            out = fn(*[NDArray(v) for v in vals])
            if isinstance(out, (list, tuple)):
                return tuple(o._data for o in out)
            return out._data

        out = jax.checkpoint(pure)(*[a._data for a in args])
        if isinstance(out, tuple):
            return [NDArray(o) for o in out]
        return NDArray(out)

    return wrapped


def loop(fn, times):
    """Apply ``fn`` ``times`` times, each pass starting from what the last
    one left: ``fn(*carry) -> (carry, outputs)`` over NDArrays (child blocks
    called inside, their parameters closed over, so every pass runs the SAME
    weights and a weight's gradient is the sum over the passes); ``carry`` a
    list whose shapes and types no pass changes, ``outputs`` a list (may be
    empty) of what each pass hands out.  ``loop(fn, times)(*carry) -> (carry,
    outputs)``, each output stacked over the passes on a new leading axis.

    Under a jitted train step (a CachedOp, ``gluon.functional``) the passes
    are ONE ``lax.scan``: the body is traced and compiled once however many
    passes there are, and the backward pass is a loop too.  What the body
    keeps for the backward pass it keeps once per pass; wrap the expensive
    part in :func:`remat` to keep only its inputs.  Outside a trace, eager
    or symbolic, it is a Python loop with the same results.

    Auxiliary state (a ``grad_req='null'`` parameter the forward pass moves:
    BatchNorm's running statistics, a router's selection bias) cannot be
    moved inside the compiled loop: the body raises ``ValueError`` naming
    the parameter."""
    import jax
    from jax.core import Tracer

    def unrolled(*carry):
        from .. import ndarray as nd_mod, symbol as sym_mod

        passes = []
        for _ in range(times):
            carry, outs = fn(*carry)
            passes.append(outs)
        F = nd_mod if all(isinstance(a, NDArray) for a in carry) else sym_mod
        return list(carry), [F.stack(*each, axis=0) for each in zip(*passes)]

    def wrapped(*carry):
        if not any(isinstance(a, NDArray) and isinstance(a._data, Tracer)
                   for a in carry):
            return unrolled(*carry)
        aux = [(n, p) for n, p in _TRACING.params if p.grad_req == "null"]

        def body(vals, _):
            before = [p._data._data for _, p in aux]
            new, outs = fn(*[NDArray(v) for v in vals])
            moved = []
            for (n, p), b in zip(aux, before):
                if p._data._data is not b:
                    p._data._rebind(b)
                    moved.append(n)
            if moved:
                raise ValueError(
                    "gluon.block.loop: the body moved auxiliary state (%s); a "
                    "grad_req='null' parameter cannot be updated inside the "
                    "compiled loop: update it outside, or run the passes as "
                    "a Python loop" % ", ".join(moved))
            return (tuple(c._data for c in new),
                    tuple(o._data for o in outs))

        vals, outs = jax.lax.scan(body, tuple(a._data for a in carry), None,
                                  length=times)
        return [NDArray(v) for v in vals], [NDArray(o) for o in outs]

    return wrapped


class SymbolBlock(HybridBlock):
    """Wrap a Symbol as a Block (reference gluon/block.py:937) — used to load
    exported models back into gluon."""

    def __init__(self, outputs, inputs, params=None):
        # empty prefix: parameters must keep the wrapped symbol's argument
        # names so imports()+load() can match them (reference resets the
        # prefix for SymbolBlock for the same reason).  A caller-supplied
        # `params` dict is shared, so existing initialized Parameters are
        # reused rather than shadowed by fresh deferred ones.
        super().__init__(prefix="", params=params)
        from ..symbol.symbol import Symbol
        from .. import symbol as sym_mod

        if isinstance(inputs, Symbol):
            inputs = [inputs]
        if isinstance(outputs, (list, tuple)):
            outputs = sym_mod.Group(list(outputs))
        self._sym_inputs = inputs
        self._sym_output = outputs
        input_names = {i.name for i in inputs}
        # every non-input argument becomes a Parameter
        for name in outputs.list_arguments():
            if name not in input_names:
                self.params.get(name, allow_deferred_init=True, grad_req="write")
        for name in outputs.list_auxiliary_states():
            self.params.get(name, allow_deferred_init=True, grad_req="null")
        self._cached_graph = inputs, outputs

    @staticmethod
    def imports(symbol_file, input_names, param_file=None, ctx=None):
        from .. import symbol as sym_mod

        sym = sym_mod.load(symbol_file)
        if isinstance(input_names, str):
            input_names = [input_names]
        inputs = [sym_mod.var(n) for n in input_names]
        block = SymbolBlock(sym, inputs)
        if param_file is not None:
            block.collect_params().load(param_file, ctx=ctx, allow_missing=False, ignore_extra=True)
        return block

    def forward(self, *args):
        from ..executor import Executor

        arg_dict = {}
        for i, a in zip(self._sym_inputs, args):
            arg_dict[i.name] = a
        aux_dict = {}
        for name, p in self.collect_params().items():
            if p.grad_req == "null":
                aux_dict[name] = p.data()
            else:
                arg_dict[name] = p.data()
        exe = Executor(self._sym_output, args=arg_dict, aux_states=aux_dict or None, grad_req="null")
        outs = exe.forward(is_train=autograd.is_training())
        return outs[0] if len(outs) == 1 else outs

    def hybrid_forward(self, F, *args, **kwargs):
        raise NotImplementedError
