"""Executor — compiled runtime for Symbol graphs.

TPU-native replacement for the reference GraphExecutor
(``src/executor/graph_executor.cc:513``): instead of NNVM passes + engine
scheduling, ``bind`` composes the registry's pure functions over the DAG and
hands the whole thing to ``jax.jit``.  XLA performs memory planning
(PlanMemory), op fusion (bulking), and scheduling; gradients come from
``jax.vjp`` (the nnvm::Gradient pass).  Aux states (BatchNorm moving stats)
are extra functional outputs folded back after each training forward —
replacing the reference's in-place aux mutation.
"""
from __future__ import annotations

import numpy as np

from .base import MXNetError
from .ndarray.ndarray import NDArray, _wrap, array

__all__ = ["Executor"]


class Executor:
    """Compiled forward/backward runner (reference include/mxnet/executor.h:53)."""

    def __init__(self, symbol, ctx=None, args=None, args_grad=None, grad_req="write", aux_states=None):
        self._symbol = symbol
        self._ctx = ctx
        self._arg_names = symbol.list_arguments()
        self._aux_names = symbol.list_auxiliary_states()
        self._out_names = symbol.list_outputs()
        self._arg_dict = self._to_dict(args, self._arg_names, "args")
        self._aux_dict = self._to_dict(aux_states, self._aux_names, "aux_states")
        self._grad_dict = self._to_dict(args_grad, self._arg_names, "args_grad", allow_none=True) or {}
        # whoever holds newer values of these arrays than the arrays do (a
        # Module's FusedStepper keeps them packed between steps): every
        # read of the dicts asks it to write them back first
        self._owner = None
        if isinstance(grad_req, str):
            self._grad_req = {n: grad_req for n in self._arg_names}
        elif isinstance(grad_req, (list, tuple)):
            self._grad_req = dict(zip(self._arg_names, grad_req))
        else:
            self._grad_req = dict(grad_req or {})
        self.outputs = []
        self._monitor = None
        self._monitor_all = False
        self._fwd_cache = {}
        self._bwd_cache = {}  # (diff_names, ones_ct, mode) -> jitted bwd
        from . import graph_passes

        # gate snapshot at bind time: one executor never mixes optimized
        # and raw plans even if MXNET_GRAPH_PASSES flips mid-process (a
        # re-bind — Module.reshape, Predictor.with_shapes — re-reads it)
        self._graph_passes = graph_passes.enabled()
        # precision tier snapshot (ISSUE 15): MXNET_PRECISION_TIER at bind
        # time, overridable via set_precision_tier (Predictor.with_precision
        # builds explicit twins that way).  Rides on the pass layer — with
        # MXNET_GRAPH_PASSES=0 the tier is inert and the plan stays raw.
        self._precision_tier = graph_passes.precision.tier() \
            if self._graph_passes else None
        self._calibration = None  # int8 tier's CalibrationTable, if any
        self._opt_cache = {}     # is_train -> FINAL (plan, heads, const_env)
        self._struct_cache = {}  # is_train -> structural (pre-tier) triple
        self._pass_stats = {}  # "train"/"eval" -> graph_passes.optimize stats
        self._tier_stats = None  # tier-pass rows of the lowered eval plan
        self._int8_sites = {}  # int8_rewrite's drift-baseline export
        self._plan = self._make_plan()

    # -- array plumbing -----------------------------------------------------
    def _to_dict(self, arrays, names, what, allow_none=False):
        if arrays is None:
            if allow_none:
                return None
            return {}
        if isinstance(arrays, dict):
            return dict(arrays)
        if isinstance(arrays, (list, tuple)):
            if len(arrays) != len(names):
                raise MXNetError(
                    "%s length %d != expected %d (%s)" % (what, len(arrays), len(names), names)
                )
            return {n: a for n, a in zip(names, arrays) if a is not None}
        raise TypeError(type(arrays))

    @property
    def arg_dict(self):
        if self._owner is not None:
            self._owner.materialize()
        return self._arg_dict

    @property
    def aux_dict(self):
        if self._owner is not None:
            self._owner.materialize()
        return self._aux_dict

    @property
    def grad_dict(self):
        if self._owner is not None:
            self._owner.materialize()
        return self._grad_dict

    @property
    def arg_arrays(self):
        return [self.arg_dict.get(n) for n in self._arg_names]

    @property
    def grad_arrays(self):
        return [self.grad_dict.get(n) for n in self._arg_names]

    @property
    def aux_arrays(self):
        return [self.aux_dict.get(n) for n in self._aux_names]

    # -- plan ---------------------------------------------------------------
    def _make_plan(self):
        """Static execution plan: topological node list with resolved input
        slots, random-key folding per stochastic node, aux-update metadata.
        Capture itself lives in ``graph_passes.capture`` (shared with the
        standalone node-count surface); the pass pipeline runs lazily per
        mode in :meth:`_opt_plan`."""
        from .graph_passes import capture

        plan, self._head_names = capture(self._symbol)
        return plan

    def _structural_plan(self, is_train):
        """The STANDARD pipeline's result for ``is_train`` — ``(plan,
        head_names, const_env)`` before any precision-tier rewrite.  This
        is the plan ``precision_plan()`` describes (the CastPlan contract
        is defined over the fp32 graph the tier rewrites) and the plan
        :func:`graph_passes.precision.calibrate` replays.

        With ``MXNET_GRAPH_PASSES`` off (snapshot at bind) this returns the
        raw captured plan untouched — byte-identical lowering to a build
        without the pass layer."""
        is_train = bool(is_train)
        hit = self._struct_cache.get(is_train)
        if hit is None:
            if not self._graph_passes:
                hit = (self._plan, self._head_names, None)
            else:
                from . import graph_passes, telemetry

                g, stats = graph_passes.optimize(
                    self._plan, self._head_names, is_train)
                self._pass_stats[stats["mode"]] = stats
                telemetry.note_graph_passes(
                    stats["nodes_pre"], stats["nodes_post"],
                    stats["seconds"], mode=stats["mode"])
                hit = (list(g.entries), list(g.heads),
                       g.constants or None)
            self._struct_cache[is_train] = hit
        return hit

    def _opt_plan(self, is_train):
        """The plan :meth:`_graph_fn` evaluates for ``is_train`` —
        ``(plan, head_names, const_env)``, where ``const_env`` seeds the
        evaluation env with pass-baked constants (None when nothing baked).

        = :meth:`_structural_plan`, plus — on EVAL plans of an executor
        whose precision tier is set (ISSUE 15) — the tier pass list
        (``graph_passes.precision``): the CastPlan-driven bf16 rewrite or
        the calibration-based int8 rewrite, with BN-affine weight folding
        ahead of either.  Tier unset ⇒ the structural triple verbatim
        (byte-identical plans, the PR 7 off-path contract); train plans are
        never tier-rewritten.  Tier pass stats append to
        :meth:`pass_stats`'s eval row."""
        is_train = bool(is_train)
        hit = self._opt_cache.get(is_train)
        if hit is None:
            hit = self._structural_plan(is_train)
            if self._precision_tier and not is_train:
                from . import graph_passes

                tctx = self._tier_context()
                if tctx is None:
                    import warnings

                    warnings.warn(
                        "MXNET_PRECISION_TIER=%s set but this executor has "
                        "unbound inputs — no cast plan, precision tier "
                        "skipped for this plan" % self._precision_tier)
                else:
                    g = graph_passes.Graph(hit[0], hit[1], hit[2])
                    g, rows = graph_passes.precision.apply(
                        g, self._precision_tier, tctx)
                    # kept SEPARATE from the cached structural stats (a
                    # struct-cache hit would otherwise re-append on every
                    # tier change); pass_stats() composes the two
                    self._tier_stats = {"passes": rows,
                                        "nodes_post": g.n_nodes}
                    # quality plane's drift baseline: the sites this
                    # twin actually quantized, keyed to the calibration
                    # table the executable was built from
                    self._int8_sites = dict(tctx.int8_sites)
                    hit = (list(g.entries), list(g.heads),
                           g.constants or None)
            self._opt_cache[is_train] = hit
        return hit

    def _tier_context(self):
        """Build the :class:`graph_passes.precision.TierContext` the tier
        passes consume — the structural-plan CastPlan (the exact artifact
        ``precision_plan(is_train=False)`` returns), bound avals/values,
        and the int8 calibration table.  None when inputs are unbound (a
        cast plan over unknown dtypes would be a guess)."""
        from . import analysis
        from .analysis import numerics as _numerics
        from .graph_passes import precision as _precision

        ctx = analysis.executor_context(self, is_train=False,
                                        plan="structural")
        if not ctx.has_avals:
            return None
        cast_plan = _numerics.precision_plan(ctx)
        return _precision.TierContext(
            cast_plan=cast_plan,
            arg_names=self._arg_names, aux_names=self._aux_names,
            arg_avals=ctx.arg_avals, aux_avals=ctx.aux_avals,
            arg_values={n: a._data for n, a in self.arg_dict.items()},
            aux_values={n: a._data for n, a in self.aux_dict.items()},
            calibration=self._calibration)

    @property
    def precision_tier(self):
        """This executor's precision tier label: ``"bf16"``/``"int8"``, or
        ``"fp32"`` when no tier is active — the warmup-row /
        ``Engine.stats()`` discriminator (ISSUE 15)."""
        return self._precision_tier or "fp32"

    def set_precision_tier(self, tier, calibration=None):
        """Override the bind-time ``MXNET_PRECISION_TIER`` snapshot —
        how ``Predictor.with_precision`` builds explicit twins without
        touching the process environment.  ``tier`` is ``"bf16"``,
        ``"int8"``, or None/``"fp32"`` (clear); ``calibration`` is the
        int8 tier's :class:`~.graph_passes.precision.CalibrationTable`.
        Resets the plan/executable caches, so call it before (or instead
        of re-doing) the first forward."""
        from .graph_passes import precision as _precision

        if tier in (None, "fp32"):
            tier = None
        elif tier not in _precision.VALID_TIERS:
            raise ValueError("unknown precision tier %r (valid: %s)"
                             % (tier, list(_precision.VALID_TIERS)))
        if tier and not self._graph_passes:
            raise ValueError(
                "precision tiers ride on the graph-pass layer — "
                "MXNET_GRAPH_PASSES=0 executors cannot host a %r twin"
                % tier)
        self._precision_tier = tier
        self._calibration = calibration
        self._tier_stats = None
        self._int8_sites = {}  # re-stashed at next lowering (new table)
        self._opt_cache.clear()
        self._fwd_cache.clear()
        self._bwd_cache.clear()

    def pass_stats(self):
        """Per-mode graph-pass results (``{"train"/"eval": stats}``) for
        the modes this executor has lowered so far; empty with passes off.
        On an eval plan a precision tier rewrote (ISSUE 15), the tier
        passes append to the eval row's ``passes`` list and
        ``nodes_post``/``seconds`` reflect the final plan — composed here
        so the cached structural stats are never mutated."""
        out = {m: dict(s) for m, s in self._pass_stats.items()}
        tier = self._tier_stats
        if tier is not None and "eval" in out:
            ev = out["eval"]
            ev["passes"] = list(ev["passes"]) + list(tier["passes"])
            ev["nodes_post"] = tier["nodes_post"]
            ev["seconds"] = round(
                ev["seconds"] + sum(r["seconds"] for r in tier["passes"]), 6)
        return out

    def check(self, is_train=False):
        """Run the registered graph-IR analyzers (``mxnet_tpu.analysis``,
        ISSUE 8) over the plan this executor lowers for ``is_train`` ->
        sorted ``[Diagnostic]`` (most severe first; empty = clean).  Static
        contract checking only — PRNG-stream safety, abstract shape/dtype
        walk, dead inputs/aux — no device work and no compile.  Calling it
        is the opt-in; the ``MXNET_GRAPH_ANALYZERS`` gate only controls the
        automatic serving-warmup surface."""
        from . import analysis

        return analysis.check_executor(self, bool(is_train))

    def precision_plan(self, is_train=False):
        """The fingerprinted cast-plan artifact (ISSUE 11) for the plan
        this executor lowers for ``is_train`` — one ``bf16_safe |
        fp32_accum | fp32_only`` verdict per plan node, from the numerics
        analyzer's dtype-flow + interval + sensitivity analysis
        (``analysis.numerics``; docs/ANALYSIS.md has the verdict table).
        This is the exact contract the precision-tier passes consume
        (``graph_passes/precision.py``, ISSUE 15), so the verdicts are
        computed over the STRUCTURAL plan — the fp32 graph the tier
        rewrites — even on an executor whose tier is active; its
        ``fingerprint()`` changes when and only when the plan or the
        sensitivity/analyzer registry versions change.
        Static (``jax.eval_shape``) — no compile, no device work; raises
        ``ValueError`` on an executor with unbound inputs."""
        from . import analysis

        return analysis.precision_plan_executor(self, bool(is_train))

    def _graph_fn(self, is_train, monitor=None):
        """Pure fn (arg_vals, aux_vals, key) -> (head_vals, new_aux_vals).

        ``monitor``: optional callback(name, jax_value) invoked per node output
        — only used on the un-jitted path (reference ExecuteMonCallback,
        graph_executor.cc:1562).  The monitor path always evaluates the RAW
        captured plan: a debugging hook must see every captured node, not
        the pass-optimized subset.
        """
        from .graph_passes.ir import node_call_attrs
        from .symbol.symbol import _node_input_names

        if monitor is not None:
            plan, heads, const_env = self._plan, self._head_names, None
        else:
            plan, heads, const_env = self._opt_plan(is_train)
        aux_names = list(self._aux_names)
        arg_names = list(self._arg_names)
        # locals only: the returned fn must NOT close over the Executor —
        # the Module fused stepper keeps it across re-binds, and an
        # executor reference would pin the old buffers after reshape
        head_names = list(heads)
        # each node's ops trace inside jax.named_scope(node.name), so a
        # profiler trace (the xplane's tf_op stat) and the HLO's op_name
        # attribute device time back to symbolic node names.  Pure
        # trace-time metadata: the jaxpr is unchanged, zero retraces
        # (tested), and JAX's persistent-cache key leaves it out.  Every
        # caller but the monitor's traces this fn under jit.
        import jax as _jax

        from . import compile_cache

        traced = monitor is None

        def run_node(node, args, attrs):
            with _jax.named_scope(node.name):
                if traced:
                    compile_cache.note_op_traced()
                return node.op.fn(*args, **attrs)

        def fn(arg_vals, aux_vals, key):  # mxlint: traced
            env = dict(const_env) if const_env else {}
            env.update(zip(arg_names, arg_vals))
            env.update(zip(aux_names, aux_vals))
            new_aux = dict(zip(aux_names, aux_vals))
            for node, in_names in plan:
                attrs = node_call_attrs(node, key, is_train)
                args = [env[n] for n in in_names]
                res = run_node(node, args, attrs)
                outs = res if isinstance(res, tuple) else (res,)
                if is_train and node.op.aux_update is not None:
                    by_arg = dict(zip(_node_input_names(node), node.inputs))
                    aux_in = {
                        a: new_aux[by_arg[a].name]
                        for a in node.op.aux
                        if a in by_arg and by_arg[a].is_var and by_arg[a].name in new_aux
                    }
                    updated = node.op.aux_update(attrs, res, aux_in)
                    for a, v in updated.items():
                        new_aux[by_arg[a].name] = v
                if len(outs) > 1 and node.num_outputs == 1:
                    outs = outs[:1]  # hidden outputs (e.g. BatchNorm stats)
                for i, o in enumerate(outs):
                    nm = (
                        "%s_output%d" % (node.name, i)
                        if node.num_outputs > 1
                        else "%s_output" % node.name
                    )
                    env[nm] = o
                    if monitor is not None:
                        monitor(nm, o)
            heads = [env[h] for h in head_names]
            return heads, [new_aux[n] for n in aux_names]

        return fn

    def _tier_key_parts(self, is_train):
        """Extra AOT logical-key parts for an active precision tier (ISSUE
        15): the tier fingerprint (pass names:versions + numerics contract
        versions) and, for calibrated int8 twins, the calibration-table
        fingerprint — so two twins of one checkpoint, or one twin across a
        re-calibration, can never share an executable.  Empty (keys
        byte-identical to pre-tier builds) when no tier is active or for
        train plans, which the tier never rewrites."""
        if not self._precision_tier or is_train:
            return ()
        from .graph_passes import precision as _precision

        parts = ("precision_tier",
                 _precision.tier_fingerprint(self._precision_tier))
        if self._calibration is not None:
            parts += (self._calibration.fingerprint(),)
        return (parts,)

    def _compiled(self, is_train):
        import jax

        if is_train not in self._fwd_cache:
            fn = jax.jit(self._graph_fn(is_train))
            from . import compile_cache

            if compile_cache.active():
                # persistent AOT executable cache (ISSUE 6): per shape
                # signature the forward restores from MXNET_AOT_CACHE
                # instead of trace+lower+XLA-compile; gate off ⇒ the plain
                # jit above, byte-identical to before.  passes_on pins the
                # bind-time graph-pass snapshot into the logical key, so
                # this executor's entries always describe the plan it
                # actually lowered (ISSUE 7).
                fn = compile_cache.CachedFunction(
                    fn,
                    ("executor_fwd",
                     compile_cache.symbol_fingerprint(self._symbol),
                     bool(is_train)) + self._tier_key_parts(is_train),
                    name="executor_fwd", passes_on=self._graph_passes)
            else:
                from .telemetry import costplane

                if costplane.enabled():
                    # compile plane (ISSUE 13): without the AOT cache the
                    # forward is a plain jit whose compiles XLA pays
                    # invisibly — the instrumented split records one
                    # ledger row per shape signature.  Gate off keeps the
                    # plain jit (one env read).
                    fn = costplane.instrument_jit(
                        fn, "executor_fwd",
                        ("executor_fwd",
                         compile_cache.symbol_fingerprint(self._symbol),
                         bool(is_train), self._graph_passes)
                        + self._tier_key_parts(is_train))
            self._fwd_cache[is_train] = fn
        return self._fwd_cache[is_train]

    # -- AOT warmup surface (compile_cache.py, ISSUE 6) ----------------------
    def _aot_example_args(self):
        import jax

        arg_vals = [self.arg_dict[n]._data for n in self._arg_names]
        aux_vals = [self.aux_dict[n]._data for n in self._aux_names]
        # same aval as random.next_key()'s split keys: raw uint32[2]
        return arg_vals, aux_vals, jax.random.PRNGKey(0)

    def aot_lower(self, is_train=False):
        """Stage 1 of the warmup compile split: disk-restore or trace+lower
        this executor's forward for its bound shapes.  Pure host work — safe
        concurrently and off a serving device loop.  → handle for
        :meth:`aot_finalize`, or None when ``MXNET_AOT_CACHE`` is off (or an
        input is unbound; warmup then falls back to first-forward compile)."""
        from . import compile_cache

        fn = self._compiled(bool(is_train))
        if not isinstance(fn, compile_cache.CachedFunction):
            return None
        try:
            args = self._aot_example_args()
        except KeyError:
            return None
        return fn.lower_prepare(*args)

    def aot_finalize(self, handle, is_train=False):
        """Stage 2: XLA-compile (or pass through a disk-restored) handle and
        install the executable, so the next forward on these shapes
        dispatches without compiling.  → the finalize row."""
        return self._compiled(bool(is_train)).finalize(handle)

    # -- API ----------------------------------------------------------------
    def forward(self, is_train=False, **kwargs):
        """Run forward (reference GraphExecutor::Forward → RunOps)."""
        from . import random as _rnd

        for k, v in kwargs.items():
            if k not in self._arg_names:
                raise MXNetError(
                    "forward() got unknown argument %r; expected one of %s" % (k, self._arg_names)
                )
            self.arg_dict[k] = v if isinstance(v, NDArray) else array(v)
        missing = [n for n in self._arg_names if n not in self.arg_dict]
        if missing:
            raise MXNetError("forward() missing bound values for arguments: %s" % missing)
        arg_vals = [self.arg_dict[n]._data for n in self._arg_names]
        aux_vals = [self.aux_dict[n]._data for n in self._aux_names]
        key = _rnd.next_key()
        from . import profiler as _prof

        _pt0 = _prof._now_us() if _prof._symbolic_profiling_active() else None
        if self._monitor is not None:
            cb = self._monitor
            if self._monitor_all:
                # reference monitor_all=True also reports every node INPUT
                # (graph_executor.cc ExecuteMonCallback input loop) — for a
                # flat executor that is the arg/aux arrays themselves
                for n in self._arg_names:
                    cb(n, self.arg_dict[n])
                for n in self._aux_names:
                    cb(n, self.aux_dict[n])
            heads, new_aux = self._graph_fn(
                bool(is_train), monitor=lambda n, v: cb(n, _wrap(v))
            )(arg_vals, aux_vals, key)
        else:
            heads, new_aux = self._compiled(bool(is_train))(arg_vals, aux_vals, key)
        for n, v in zip(self._aux_names, new_aux):
            self.aux_dict[n]._rebind(v)
        self.outputs = [_wrap(h) for h in heads]
        self._last_key = key
        self._last_is_train = bool(is_train)
        if self._last_is_train:
            # train-step dispatch accounting (ISSUE 3 regression surface):
            # counted here at the dispatch site so manual loops and
            # BucketingModule report the same 2+P as Module.forward_backward
            from .telemetry import tracing

            tracing.count("dispatch", path="legacy")
        if _pt0 is not None:
            # duration = trace+enqueue (async dispatch), same caveat as the
            # eager per-op events; the XLA device timeline is use_xla_trace
            _prof._emit_op("Executor::Forward", _pt0, _prof._now_us() - _pt0)
        return self.outputs

    def backward(self, out_grads=None, is_train=None):
        """Gradients into grad arrays per grad_req (reference
        GraphExecutor::Backward; the Gradient pass is jax.vjp here).

        ``is_train=None`` (default) differentiates in the mode the last
        forward ran in; passing an explicit bool overrides it."""
        import jax
        import jax.numpy as jnp

        diff_names = tuple(
            n for n in self._arg_names if self._grad_req.get(n, "null") != "null" and n in self.grad_dict
        )
        if not diff_names:
            return
        from . import profiler as _prof

        _pt0 = _prof._now_us() if _prof._symbolic_profiling_active() else None
        aux_vals = [self.aux_dict[n]._data for n in self._aux_names]
        key = getattr(self, "_last_key", None)
        if key is None:
            from . import random as _rnd

            key = _rnd.next_key()
        arg_vals = [self.arg_dict[n]._data for n in self._arg_names]
        ones_ct = out_grads is None
        if not ones_ct:
            if isinstance(out_grads, (NDArray, np.ndarray)):
                out_grads = [out_grads]
            cts_in = [g._data if isinstance(g, NDArray) else jnp.asarray(g) for g in out_grads]
        # differentiate in the mode the last forward actually ran in — a
        # backward after forward(is_train=False) must see eval-mode
        # BatchNorm/Dropout, not a silently re-traced train graph
        if is_train is None:
            mode = getattr(self, "_last_is_train", True)
        else:
            mode = bool(is_train)
        cache_key = (diff_names, ones_ct, mode)
        bwd_fn = self._bwd_cache.get(cache_key)
        if bwd_fn is None:
            fn = self._graph_fn(mode)
            arg_names = list(self._arg_names)
            dset = set(diff_names)
            const_names = [n for n in arg_names if n not in dset]

            def bwd(diff_vals, const_vals, aux_v, k, cts):
                def f(dvals):
                    merged = dict(zip(const_names, const_vals))
                    merged.update(zip(diff_names, dvals))
                    heads, _ = fn([merged[n] for n in arg_names], aux_v, k)
                    return heads

                heads, vjp_fn = jax.vjp(f, diff_vals)
                c = [jnp.ones_like(h) for h in heads] if ones_ct else cts
                (grads,) = vjp_fn(c)
                return grads

            bwd_fn = self._bwd_cache[cache_key] = jax.jit(bwd)
        dset = set(diff_names)
        grads = bwd_fn(
            [self.arg_dict[n]._data for n in diff_names],
            [v for n, v in zip(self._arg_names, arg_vals) if n not in dset],
            aux_vals,
            key,
            [] if ones_ct else cts_in,
        )
        for n, g in zip(diff_names, grads):
            req = self._grad_req.get(n, "write")
            tgt = self.grad_dict.get(n)
            if tgt is None:
                continue
            if req == "add":
                tgt._rebind(tgt._data + g)
            else:
                tgt._rebind(g)
        from .telemetry import tracing

        tracing.count("dispatch", path="legacy")
        if _pt0 is not None:
            _prof._emit_op("Executor::Backward", _pt0,
                           _prof._now_us() - _pt0)

    def reshape(self, partial_shaping=False, allow_up_sizing=False, **kwargs):
        """Re-bind with new shapes (reference GraphExecutor::Reshape:1053).

        jit recompiles per shape signature automatically; only arrays need
        re-allocation here.
        """
        from .ndarray import zeros as nd_zeros

        arg_shapes, _, aux_shapes = self._symbol.infer_shape(**kwargs)
        new_args = {}
        for n, s in zip(self._arg_names, arg_shapes):
            old = self.arg_dict.get(n)
            if old is not None and tuple(old.shape) == tuple(s):
                new_args[n] = old
            else:
                new_args[n] = nd_zeros(s, ctx=self._ctx)
        new_grads = None
        if self.grad_dict:
            new_grads = {}
            for n, s in zip(self._arg_names, arg_shapes):
                if n in self.grad_dict:
                    old = self.grad_dict[n]
                    new_grads[n] = old if tuple(old.shape) == tuple(s) else nd_zeros(s, ctx=self._ctx)
        new_aux = {}
        for n, s in zip(self._aux_names, aux_shapes):
            old = self.aux_dict.get(n)
            new_aux[n] = old if old is not None and tuple(old.shape) == tuple(s) else nd_zeros(s, ctx=self._ctx)
        return Executor(self._symbol, self._ctx, new_args, new_grads, self._grad_req, new_aux)

    def copy_params_from(self, arg_params, aux_params=None, allow_extra_params=False):
        for k, v in (arg_params or {}).items():
            if k in self.arg_dict:
                self.arg_dict[k]._rebind(v._data if isinstance(v, NDArray) else array(v)._data)
            elif not allow_extra_params:
                raise MXNetError("unknown arg %s" % k)
        for k, v in (aux_params or {}).items():
            if k in self.aux_dict:
                self.aux_dict[k]._rebind(v._data if isinstance(v, NDArray) else array(v)._data)
            elif not allow_extra_params:
                raise MXNetError("unknown aux %s" % k)

    def set_monitor_callback(self, callback, monitor_all=False):
        """Install per-output inspection (reference executor.h:172 monitor).
        Forward runs un-jitted — and over the RAW captured plan, graph
        passes bypassed — while a monitor is installed, so the callback
        sees every captured node; monitor_all additionally reports node
        inputs (args/aux — weights included)."""
        self._monitor = callback
        self._monitor_all = bool(monitor_all)

    @property
    def output_dict(self):
        return dict(zip(self._out_names, self.outputs))

    def debug_str(self):
        return self._symbol.debug_str()


def _simple_bind_for_test(sym, locations, aux_states=None, ctx=None, grad_req="null"):
    """Bind with concrete numpy/NDArray inputs (test_utils helper)."""
    args = {}
    if isinstance(locations, dict):
        for k, v in locations.items():
            args[k] = v if isinstance(v, NDArray) else array(v)
    else:
        for n, v in zip(sym.list_arguments(), locations):
            args[n] = v if isinstance(v, NDArray) else array(v)
    aux = {}
    if aux_states:
        if isinstance(aux_states, dict):
            aux = {k: (v if isinstance(v, NDArray) else array(v)) for k, v in aux_states.items()}
        else:
            aux = {
                n: (v if isinstance(v, NDArray) else array(v))
                for n, v in zip(sym.list_auxiliary_states(), aux_states)
            }
    # fill any remaining args (params) with zeros via shape inference
    known = {k: tuple(v.shape) for k, v in args.items()}
    try:
        arg_shapes, _, aux_shapes = sym.infer_shape(**known)
        from .ndarray import zeros as nd_zeros

        for n, s in zip(sym.list_arguments(), arg_shapes):
            if n not in args and s is not None:
                args[n] = nd_zeros(s, ctx=ctx)
        for n, s in zip(sym.list_auxiliary_states(), aux_shapes):
            if n not in aux and s is not None:
                aux[n] = nd_zeros(s, ctx=ctx)
    except MXNetError:
        pass
    grads = {n: None for n in args}
    if grad_req != "null":
        from .ndarray import zeros as nd_zeros

        grads = {n: nd_zeros(a.shape, ctx=ctx) for n, a in args.items()}
    return Executor(sym, ctx, args, grads if grad_req != "null" else None, grad_req, aux)
