"""Fused train-step executor for the symbolic Module stack (ISSUE 3).

The legacy Module step runs the forward graph TWICE (``Executor.forward``
dispatches it, ``Executor.backward`` re-traces it inside ``jax.vjp``) and
then issues a per-parameter storm of tiny eager optimizer dispatches
(``model._update_params``), with zero buffer donation.  This module collapses
the whole training step into ONE donated jit dispatch — the whole-graph
fusion win TVM/Relay demonstrate, and the idiom the gluon path already
proves in ``gluon.functional.make_train_step``:

    (params, grads_in, opt_state, aux, data, key, lr, wd)
        -> (new_params, new_opt_state, new_aux, outputs, grads)

- loss heads AND gradients come from a single ``jax.vjp`` pass over the
  executor's graph function (no duplicated forward);
- the optimizer update is folded into the same graph through the pure
  kernels in ``ops.optimizer_ops`` (``fused_update``), with per-parameter
  lr/wd (schedulers, ``lr_mult``/``wd_mult``) arriving as TRACED vectors so
  decays cost zero recompiles;
- BatchNorm aux statistics fold back functionally, exactly like the legacy
  forward;
- param / grad / optimizer-state / aux buffers are donated, so steady-state
  HBM traffic matches an in-place engine;
- jax.jit caches per shape signature: ``Module.reshape`` costs exactly one
  retrace, switching back costs none.

``Module.forward_backward`` stages the batch, ``Module.update`` dispatches;
eligibility and the ``MXNET_MODULE_FUSED_STEP`` escape hatch live here (see
``fused_ineligible_reason`` and docs/PERF_NOTES.md "Fused Module train
step").  Fallbacks route through the untouched legacy path and are counted
in the telemetry registry (``module_fused_fallback_total{reason}``).

**Sharded (mesh) fused step — ISSUE 5.**  A ``mesh=`` Module (dp-sharded
batch feed, ``parallel.mesh``) used to hard-fall-back to the legacy path;
now an eligible mesh-fed Module runs the whole multi-chip step as the same
ONE donated jit, sharding-annotated: the batch enters dp-sharded (staged by
``Module._stage_batch`` / the prefetch path), params/aux/grads are pinned
replicated via ``out_shardings``, and GSPMD derives the dp gradient psum
*inside* the compiled step — the collective overlaps compute on ICI instead
of serializing at the Python boundary.  Opt-in ``MXNET_FUSED_ZERO=1``
switches the optimizer state (and the returned grads) to the ZeRO-1 layout
(``parallel.zero_shard_spec``): GSPMD reduce-scatters grads over dp, each
device updates its 1/dp state shard, and the updated params allgather back
to replicated — all in the same XLA module.
"""
from __future__ import annotations

import numpy as np

from .. import telemetry
from ..base import MXNetError, env_flag
from ..telemetry import tracing
from ..ndarray.ndarray import NDArray, _wrap

__all__ = ["FusedStepper", "fused_enabled", "fused_ineligible_reason",
           "fused_zero_enabled"]

_DP_AXIS = "dp"  # the mesh axis the Module batch feed shards over


def fused_enabled():
    """``MXNET_MODULE_FUSED_STEP`` gate (docs/ENV_VARS.md) — default ON."""
    return env_flag("MXNET_MODULE_FUSED_STEP", default="1")


def fused_zero_enabled():
    """``MXNET_FUSED_ZERO`` gate (docs/ENV_VARS.md) — default OFF.  Only
    consulted on the mesh path: ZeRO-1 sharding of optimizer state over dp."""
    return env_flag("MXNET_FUSED_ZERO")


def fused_donate_enabled():
    """``MXNET_FUSED_DONATE`` gate (docs/ENV_VARS.md) — default ON.

    ``0`` builds the fused step WITHOUT donated operands.  The use case:
    restored *donated* executables are skipped on the CPU backend
    (the donation hazard, ``compile_cache.py`` docstring), so a CPU pod
    restart re-pays the train-step compile even with ``MXNET_AOT_CACHE``
    set.  Turning donation off makes the disk restore legal again — the
    warm-restart CI (``ci/check_pod_train.py``) runs its second launch this
    way to prove every rank restores the identical executable.  Costs the
    donation's buffer recycling (params/grads/state copies per step), so
    keep the default on TPU."""
    return env_flag("MXNET_FUSED_DONATE", default="1")


def fused_ineligible_reason(module):
    """None when the fused path can take this Module's next train step, else
    a short tag naming why not (doubles as the fallback-counter label).

    The conditions mirror what the fused graph cannot express: a monitor
    needs un-jitted per-node callbacks, ``grad_req`` mixes ("add"/"null")
    need the executor's accumulate-into-buffer semantics, dist kvstores
    aggregate across processes outside the step, and optimizers without a
    ``fused_step_kind`` carry host-side state.  A mesh feed is fused when
    the mesh carries the ``dp`` batch axis (the in-step psum replaces the
    legacy sharded forward); a local-family kvstore under such a mesh folds
    into that psum (``KVStore.folds_into_fused_step``) instead of forcing
    the eager push/pull loop.  Mesh-*unsupported-feature* steps surface the
    feature's own reason (``monitor``/``grad_req``/``optimizer``/...), not
    the old blanket ``"mesh"``; a mesh without a dp axis is ``mesh_no_dp``.
    Explicit ``backward(out_grads=...)`` calls never reach here — only
    ``forward_backward`` stages fused steps, so user-supplied head
    cotangents always take the legacy path by construction.
    """
    if not fused_enabled():
        return "disabled"
    if not module.optimizer_initialized:
        return "no_optimizer"
    if module._exec is None or module._exec._monitor is not None:
        return "monitor"
    if module._kvstore is not None or module._update_on_kvstore:
        kv = module._kvstore
        folds = (module._mesh is not None and kv is not None
                 and not module._update_on_kvstore
                 and kv.folds_into_fused_step(module._mesh))
        if not folds:
            if kv is not None and kv._is_dist:
                # dist store over a single-host mesh: the cross-process DCN
                # aggregation happens outside the local step.  (Under a
                # PROCESS-SPANNING mesh dist stores fold — GSPMD's in-step
                # psum over the host-crossing dp axis is that aggregation.)
                return "kvstore_dist"
            return "kvstore"
        # store folded under the dp mesh: its per-key aggregation IS the
        # in-step psum (ICI single-host, DCN when dp spans processes) —
        # fused path proceeds, the store stays idle
    if module._updater is None:
        return "no_optimizer"
    if module.inputs_need_grad:
        return "inputs_need_grad"
    req = module._exec._grad_req
    for n in module._param_names:
        if req.get(n, "null") != "write":
            return "grad_req"
        if module._exec.grad_dict.get(n) is None:
            return "grad_req"
    opt = module._optimizer
    if opt is None or opt.fused_step_kind() is None:
        return "optimizer"
    if module._mesh is not None and _DP_AXIS not in module._mesh.axis_names:
        return "mesh_no_dp"
    return None


def _hp_signature(opt):
    """The optimizer hyperparams the fused graph folds in as constants
    (lr/wd stay live — they enter as traced vectors every step).  The
    Module rebuilds the stepper when this changes, so mutating e.g.
    ``rescale_grad`` or ``momentum`` mid-run behaves like the legacy path
    instead of silently using stale values."""
    kind = opt.fused_step_kind()
    sig = (kind, float(opt.rescale_grad),
           None if opt.clip_gradient is None else float(opt.clip_gradient))
    if kind == "sgd":
        sig += (float(opt.momentum),)
    elif kind == "adam":
        sig += (float(opt.beta1), float(opt.beta2), float(opt.epsilon))
    return sig


def _state_leaves(state):
    """Flatten one Updater state slot (None | NDArray | tuple) to a list of
    jax arrays for the jitted step."""
    if state is None:
        return []
    if isinstance(state, NDArray):
        return [state._data]
    return [s._data for s in state]


def _commit_state(state, new_leaves):
    """Write the fused step's returned state leaves back into the Updater's
    NDArrays (keeps save/load_optimizer_states working unchanged)."""
    if state is None:
        assert not new_leaves
        return
    if isinstance(state, NDArray):
        state._rebind(new_leaves[0])
        return
    for s, v in zip(state, new_leaves):
        s._rebind(v)


def _build_step_fn(graph_fn, arg_names, diff_names, const_names, kind, hp,
                   nancheck=False, health_groups=None):
    """The pure fused step: one vjp over the executor graph + the in-graph
    optimizer fold.  Closed over only static structure (names, kind, static
    hyperparams, the nancheck/health flags) so one jitted instance survives
    re-binds of the same symbol and re-traces only on new shape signatures.

    With ``nancheck`` the step also returns a scalar ``finite`` flag —
    ``all(isfinite(heads)) & all(isfinite(grads))`` reduced INSIDE the same
    donated jit, so the check adds no dispatch and no sync (the caller reads
    the flag one step later, when it has materialized for free).

    With ``health_groups`` (ISSUE 12, ``MXNET_TRAINHEALTH`` or an in-graph
    monitor) the step additionally returns the trainhealth stats pytree —
    global/per-group grad norms, param norms, update-to-weight ratios and
    per-group non-finite flags, reduced by
    ``telemetry.trainhealth.compute_step_stats`` inside the same donated
    jit: observing the step costs zero extra dispatches.  Both extras
    append to the output tuple (finite flag first), so the gate-off output
    structure stays byte-identical to a build without either feature."""
    import jax
    import jax.numpy as jnp

    from ..ops.optimizer_ops import fused_update

    def step(diff_vals, grads_in, opt_state, aux_vals, const_vals, key,
             lr_vec, wd_vec):
        # grads_in is donated purely so XLA can recycle the standing grad
        # buffers for the returned gradients
        del grads_in

        def f(dvals):
            env = dict(zip(const_names, const_vals))
            env.update(zip(diff_names, dvals))
            return graph_fn([env[n] for n in arg_names], aux_vals, key)

        heads, vjp_fn, new_aux = jax.vjp(f, diff_vals, has_aux=True)
        (grads,) = vjp_fn([jnp.ones_like(h) for h in heads])
        new_params, new_state = [], []
        with jax.named_scope("optimizer"):
            for i, (w, g) in enumerate(zip(diff_vals, grads)):
                st = tuple(opt_state[i])
                # like sgd_rule: a parameter updates with momentum iff it
                # HAS a momentum slot (created when the optimizer's momentum
                # was set), so mid-run momentum edits behave exactly like
                # the legacy path
                k = ("sgd_mom" if st else "sgd") if kind == "sgd" else kind
                new_w, new_st = fused_update(k, w, g, st, lr=lr_vec[i],
                                             wd=wd_vec[i], **hp)
                new_params.append(new_w)
                new_state.append(list(new_st))
        out = (new_params, new_state, new_aux, heads, grads)
        if nancheck:
            finite = jnp.bool_(True)
            for h in heads:
                finite = jnp.logical_and(finite, jnp.all(jnp.isfinite(h)))
            for g in grads:
                finite = jnp.logical_and(finite, jnp.all(jnp.isfinite(g)))
            out = out + (finite,)
        if health_groups is not None:
            from ..telemetry import trainhealth

            out = out + (trainhealth.compute_step_stats(
                heads, grads, diff_vals, new_params, health_groups),)
        return out

    return step


class FusedStepper:
    """Per-Module fused-step cache: builds the jitted step once (per
    optimizer configuration) and re-dispatches it for every eligible step;
    jax.jit's executable cache provides the per-shape-signature caching.

    With a mesh the same jit is built sharding-annotated (``out_shardings``
    pinned so params/state keep their layout across donated steps, GSPMD
    inserting the dp collectives); the jit construction is deferred to the
    first ``run`` because the ZeRO-1 ``out_shardings`` pytree needs the
    optimizer-state leaf structure, which ``Updater.states`` materializes
    lazily."""

    def __init__(self, module):
        exec_ = module._exec
        opt = module._optimizer
        self._opt = opt
        self._kind = opt.fused_step_kind()
        assert self._kind is not None
        self._hp_sig = _hp_signature(opt)
        self._arg_names = list(exec_._arg_names)
        self._aux_names = list(exec_._aux_names)
        self._diff_names = list(module._param_names)
        dset = set(self._diff_names)
        self._const_names = [n for n in self._arg_names if n not in dset]
        hp = {"rescale_grad": float(opt.rescale_grad),
              "clip_gradient": (-1.0 if opt.clip_gradient is None
                                else float(opt.clip_gradient))}
        if self._kind == "sgd":
            hp["momentum"] = float(opt.momentum)
        elif self._kind == "adam":
            hp.update(beta1=float(opt.beta1), beta2=float(opt.beta2),
                      epsilon=float(opt.epsilon))
        self._nancheck = env_flag("MXNET_NANCHECK")
        # trainhealth (ISSUE 12): in-graph stats ride the same donated jit
        # when the env gate is on OR a pattern-filtered Monitor is routed
        # onto the fused step (Module.install_monitor).  Both flip the
        # output structure, so both are stepper identity (stale() rebuilds
        # on a change) and the AOT key gains a marker — the gate-off key
        # stays byte-identical to a build without the feature.
        from ..telemetry import trainhealth

        self._health_env = trainhealth.enabled()
        self._monitor_attached = \
            getattr(module, "_stat_monitor", None) is not None
        self._health_groups = None
        self._health_verdicts = None
        if self._health_env or self._monitor_attached:
            self._health_groups = trainhealth.param_groups(self._diff_names)
            self._health_verdicts = trainhealth.group_verdict_classes(
                module, self._diff_names, self._health_groups)
        self._last_health = None  # (step number, device stats pytree)
        self._mesh = module._mesh
        self._zero = self._mesh is not None and fused_zero_enabled()
        self._donate = fused_donate_enabled()
        # the executor's bind-time graph-pass snapshot (ISSUE 7): the
        # stepper's step fn closes over the (possibly pass-optimized) train
        # plan, so the snapshot is program identity — it keys the AOT cache
        # entry and, via stale(), forces a rebuild when a re-bind (reshape)
        # lands on an executor with a different snapshot
        self._passes_on = exec_._graph_passes
        # persistent AOT executable cache (compile_cache.py, ISSUE 6): the
        # logical key is everything folded into the compiled step besides
        # argument shapes (those join at prepare time) and the environment
        # (verified inside the cache entry — incl. the mesh descriptor, so
        # a restart onto a different topology misses cleanly)
        from .. import compile_cache

        self._aot_key = None
        if compile_cache.active():
            # mesh PRESENCE is program identity (out_shardings, in-step
            # psum); mesh SHAPE lives in the verified environment
            # fingerprint, so a restart onto a different topology is a
            # clean miss + recompile rather than a different entry
            self._aot_key = (
                "fused_step",
                compile_cache.symbol_fingerprint(module._symbol),
                tuple(self._diff_names), tuple(self._const_names),
                tuple(self._aux_names), self._hp_sig, self._nancheck,
                self._zero, self._mesh is not None,
                "donate:0123" if self._donate else "donate:none")
            if self._health_groups is not None:
                # appended (not an always-present flag) so gate-off keys
                # stay byte-identical to pre-trainhealth entries
                self._aot_key = self._aot_key + ("trainhealth",)
        # symbol kept for the compile plane's logical row key (ISSUE 13) —
        # a Symbol, not an executor: no buffer pinning across re-binds
        self._symbol_ref = module._symbol
        self._nsteps = 0
        self._pending_flag = None  # (finite device scalar, step number)
        self._fn = _build_step_fn(exec_._graph_fn(True), self._arg_names,
                                  self._diff_names, self._const_names,
                                  self._kind, hp, nancheck=self._nancheck,
                                  health_groups=self._health_groups)
        self._jit = None
        self._step = None
        # mesh-path sharding cache, filled on first run (needs the state
        # leaf structure): (repl, [grad/param spec]*P, [[state leaf spec]])
        # — static for the stepper's lifetime (param shapes survive
        # retraces), so run() never rebuilds NamedShardings per step
        self._shardings = None

    @property
    def mesh(self):
        return self._mesh

    @property
    def zero(self):
        """True when this stepper runs in ZeRO-1 mode (sharded opt state)."""
        return self._zero

    # -- mesh shardings ------------------------------------------------------
    def _repl(self):
        from ..parallel import named_sharding

        return named_sharding(self._mesh)

    def _shard_spec(self, v):
        """Layout for grads and optimizer-state leaves on the mesh path:
        ZeRO-1 partitions them over dp (``parallel.zero_shard_spec``), the
        replicated mode keeps them whole on every device."""
        if not self._zero:
            return self._repl()
        from ..parallel import zero_shard_spec

        return zero_shard_spec(v, self._mesh, _DP_AXIS)

    @staticmethod
    def _place(v, sharding):
        """Commit ``v`` to ``sharding`` if it is not already there — a no-op
        from the second step on (the pinned out_shardings hand back buffers
        already in layout, so donation recycles them in place)."""
        from ..parallel import place_committed

        return place_committed(v, sharding)

    def _ensure_jit(self, diff_vals, leaves):
        """Build the jitted step on first dispatch.  Mesh path: pin
        ``out_shardings`` (params/aux replicated; grads and state leaves per
        ``_shard_spec``; heads and the nancheck flag compiler-chosen) so the
        layout survives every donated step, and declare the GSPMD-derived
        collectives to telemetry once per build."""
        import jax

        if self._step is not None:
            return
        donate = (0, 1, 2, 3) if self._donate else ()
        if self._mesh is None:
            self._jit = jax.jit(self._fn, donate_argnums=donate)
        else:
            from ..parallel import note_derived

            repl, grad_sh, state_sh = self._shardings
            out_sh = ([repl] * len(diff_vals), state_sh,
                      [repl] * len(self._aux_names), None, grad_sh)
            if self._nancheck:
                out_sh = out_sh + (None,)
            if self._health_groups is not None:
                out_sh = out_sh + (None,)  # stats pytree: compiler-chosen
            self._jit = jax.jit(self._fn, donate_argnums=donate,
                                out_shardings=out_sh)
            # declared ONCE per stepper build (not per retrace like the
            # explicit lax collectives — a reshape re-specializes the same
            # logical collectives, so one declaration per layout is honest).
            # mesh= buckets the same bytes by slowest link crossed: dcn when
            # the dp axis spans processes (pod), ici on a single host.
            if self._zero:
                # only leaves zero_shard_spec actually splits ride the
                # reduce-scatter/allgather; non-divisible leaves stay
                # replicated and their grads are a plain psum
                split = [v for v, s in zip(diff_vals, grad_sh) if s != repl]
                whole = [v for v, s in zip(diff_vals, grad_sh) if s == repl]
                note_derived("reduce_scatter", split,
                             mesh=self._mesh, axis=_DP_AXIS)
                note_derived("allgather", split,
                             mesh=self._mesh, axis=_DP_AXIS)
                note_derived("psum_grads", whole,
                             mesh=self._mesh, axis=_DP_AXIS)
            else:
                note_derived("psum_grads", diff_vals,
                             mesh=self._mesh, axis=_DP_AXIS)
        if self._aot_key is not None:
            from .. import compile_cache

            # donated=True: on the CPU backend the disk tier is skipped
            # entirely — restored donated executables compute wrong
            # trajectories there (the donation hazard, compile_cache.py
            # docstring) — so a CPU restart re-pays this compile; TPU-class
            # backends restore normally.  MXNET_FUSED_DONATE=0 makes the
            # restore legal everywhere.  Cache off ⇒ the plain jit above.
            self._jit = compile_cache.CachedFunction(
                self._jit, self._aot_key, name="fused_step",
                mesh_desc=compile_cache.mesh_descriptor(self._mesh),
                donated=self._donate, passes_on=self._passes_on)
        else:
            from ..telemetry import costplane

            if costplane.enabled():
                # compile plane (ISSUE 13): without the AOT cache the
                # donated train-step jit still records one ledger row per
                # shape signature.  donated=True: a dispatch failure
                # re-raises instead of re-invoking the jit on consumed
                # buffers (compile_cache's donation stance).
                from .. import compile_cache

                self._jit = costplane.instrument_jit(
                    self._jit, "fused_step",
                    ("fused_step",
                     compile_cache.symbol_fingerprint(self._symbol_ref),
                     tuple(self._diff_names), self._hp_sig, self._nancheck,
                     self._zero, self._mesh is not None, self._passes_on,
                     self._health_groups is not None),
                    donated=self._donate)
        # compile/steady-state accounting (identity when telemetry is off)
        self._step = telemetry.instrument_step(self._jit,
                                               name="module_fused_step")

    def cache_size(self):
        """Number of compiled executables (one per shape signature)."""
        size = getattr(self._jit, "_cache_size", None)
        return size() if size is not None else None

    def stale(self, module):
        """True when the Module's optimizer (or a folded-in hyperparam, the
        MXNET_NANCHECK gate — it changes the step's output structure — the
        MXNET_TRAINHEALTH gate / in-graph monitor attachment — same reason
        — or the MXNET_FUSED_ZERO gate — it changes the state layout)
        changed since this stepper was built — caller rebuilds."""
        from ..telemetry import trainhealth

        return (module._optimizer is not self._opt
                or _hp_signature(module._optimizer) != self._hp_sig
                or env_flag("MXNET_NANCHECK") != self._nancheck
                or trainhealth.enabled() != self._health_env
                or (getattr(module, "_stat_monitor", None) is not None)
                != self._monitor_attached
                or (module._mesh is not None
                    and fused_zero_enabled() != self._zero)
                # donation is executable identity (argnums + AOT key)
                or fused_donate_enabled() != self._donate
                # a re-bind whose executor snapshotted a different
                # MXNET_GRAPH_PASSES state: the cached step fn closes over
                # the other plan flavor — rebuild instead of mixing
                or module._exec._graph_passes != self._passes_on)

    def check_nonfinite(self):
        """Raise if the PREVIOUS step's folded isfinite flag tripped.

        The flag is an output of the fused jit, so reading it right after
        dispatch would add the per-step sync the fold exists to avoid;
        instead ``run`` checks it just before dispatching the next step, by
        which point it is long materialized (the next step consumes the
        previous outputs anyway).  The error therefore surfaces one update()
        late but NAMES the offending step."""
        if self._pending_flag is None:
            return
        flag, stepno = self._pending_flag
        self._pending_flag = None
        if not bool(flag):
            telemetry.note_nonfinite("fused")
            # black box first (ISSUE 12 satellite): the raise below ends
            # the run, so the flight recorder dumps NOW — step timeline
            # plus the last trainhealth rows, when either plane is live
            telemetry.trainhealth.note_nonfinite_trip("fused", stepno)
            raise MXNetError(
                "MXNET_NANCHECK: non-finite loss/gradient in fused train "
                "step %d (detected before step %d: the flag is folded into "
                "the fused dispatch and read one step later to avoid a "
                "per-step sync)" % (stepno, stepno + 1))

    # -- trainhealth surfaces (ISSUE 12) -------------------------------------
    def pop_health(self):
        """(step number, device stats pytree) of the last dispatched step,
        or None — consumed by ``telemetry.trainhealth.HealthPlane.drain``
        (one drain per step; a second pop returns None)."""
        h, self._last_health = self._last_health, None
        return h

    def feed_monitor(self, mon):
        """Feed an activated in-graph :class:`~mxnet_tpu.monitor.Monitor`
        the last step's stats as ``(name, value)`` rows —
        ``<group>:grad_norm`` / ``:param_norm`` / ``:update_ratio`` plus
        ``global:grad_norm`` and ``loss`` — pattern-filtered by the
        monitor itself.  Reads device scalars (a sync), but only on
        monitor-activated interval batches."""
        h = self._last_health
        if h is None or self._health_groups is None:
            return
        _stepno, stats = h
        gn = np.asarray(stats["grad_norm"])
        pn = np.asarray(stats["param_norm"])
        ur = np.asarray(stats["update_ratio"])
        for i, (group, _idxs) in enumerate(self._health_groups):
            mon.observe("%s:grad_norm" % group, gn[i])
            mon.observe("%s:param_norm" % group, pn[i])
            mon.observe("%s:update_ratio" % group, ur[i])
        mon.observe("global:grad_norm",
                    np.asarray(stats["global_grad_norm"]))
        mon.observe("loss", np.asarray(stats["loss"]))

    def run(self, module):
        """Dispatch ONE fused step over the feed already staged in the
        executor's arg buffers, then commit params / optimizer state / aux /
        outputs / grads.  Consumes exactly one RNG key (like the legacy
        forward), so seeded runs stay reproducible across paths.

        Three child spans of the caller's ``update`` split the host's time
        (telemetry/tracing.py; a profiler session or ``MXNET_TRACE``):
        ``fused.prepare`` everything before the launch, ``fused.dispatch``
        the jitted call alone until it returns, ``fused.commit`` rebinding
        what came back."""
        from .. import random as _rnd

        with tracing.span("fused.prepare"):
            exec_ = module._exec
            opt = self._opt
            updater = module._updater
            diff_vals = [exec_.arg_dict[n]._data for n in self._diff_names]
            grads_in = [exec_.grad_dict[n]._data for n in self._diff_names]
            const_vals = [exec_.arg_dict[n]._data for n in self._const_names]
            aux_vals = [exec_.aux_dict[n]._data for n in self._aux_names]
            states, leaves = [], []
            for i, n in enumerate(self._diff_names):
                if i not in updater.states:
                    updater.states[i] = opt.create_state(i, exec_.arg_dict[n])
                    updater.states_synced[i] = True
                states.append(updater.states[i])
                leaves.append(_state_leaves(updater.states[i]))
            if self._mesh is not None:
                # commit every donated operand to its pinned layout
                # (params/aux replicated over the mesh, grads + opt state
                # per _shard_spec — 1/dp shards in ZeRO-1 mode).  Only the
                # FIRST step actually moves bytes; afterwards the step's
                # out_shardings return buffers already in layout and _place
                # is a sharding == check.  The batch feed itself is already
                # dp-sharded by _stage_batch.
                if self._shardings is None:
                    self._shardings = (
                        self._repl(),
                        [self._shard_spec(v) for v in diff_vals],
                        [[self._shard_spec(v) for v in lv] for lv in leaves])
                repl, grad_sh, state_sh = self._shardings
                diff_vals = [self._place(v, repl) for v in diff_vals]
                aux_vals = [self._place(v, repl) for v in aux_vals]
                grads_in = [self._place(g, s)
                            for g, s in zip(grads_in, grad_sh)]
                leaves = [[self._place(v, s) for v, s in zip(lv, shl)]
                          for lv, shl in zip(leaves, state_sh)]
            self._ensure_jit(diff_vals, leaves)
            # host-side hyperparam prep, O(P) python and zero dispatches:
            # update counts first (the legacy Updater order), then read
            # lr/wd through the optimizer's scheduler/multiplier logic;
            # adam's bias correction folds into lr so the in-graph kernel
            # stays schedule-free
            for i in range(len(self._diff_names)):
                opt._update_count(i)
            lrs, wds = [], []
            from ..ops.optimizer_ops import adam_bias_corrected_lr

            for i in range(len(self._diff_names)):
                lr, wd = opt._get_lr(i), opt._get_wd(i)
                if self._kind == "adam":
                    lr = adam_bias_corrected_lr(lr, opt._index_update_count[i],
                                                opt.beta1, opt.beta2)
                lrs.append(lr)
                wds.append(wd)
            lrs = np.asarray(lrs, np.float32)
            wds = np.asarray(wds, np.float32)
            key = _rnd.next_key()
            if self._nancheck:
                self.check_nonfinite()
        with tracing.span("fused.dispatch"):
            out = self._step(diff_vals, grads_in, leaves, aux_vals,
                             const_vals, key, lrs, wds)
        with tracing.span("fused.commit"):
            new_params, new_state, new_aux, heads, grads = out[:5]
            extra = list(out[5:])
            self._nsteps += 1
            if self._nancheck:
                self._pending_flag = (extra.pop(0), self._nsteps)
            if self._health_groups is not None:
                # device arrays, NOT read here (that would add the per-step
                # sync the in-graph fold avoids): the fit loop drains them
                # after its metric read has already synced this dispatch
                self._last_health = (self._nsteps, extra.pop(0))
            for n, v in zip(self._diff_names, new_params):
                exec_.arg_dict[n]._rebind(v)
            for n, g in zip(self._diff_names, grads):
                exec_.grad_dict[n]._rebind(g)
            for n, v in zip(self._aux_names, new_aux):
                exec_.aux_dict[n]._rebind(v)
            for st, new_leaves in zip(states, new_state):
                _commit_state(st, new_leaves)
            exec_.outputs = [_wrap(h) for h in heads]
            exec_._last_key = key
            exec_._last_is_train = True
