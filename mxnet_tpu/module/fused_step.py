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

**Packed boundary.**  Without a mesh the carried state crosses the jit
boundary packed: one flat buffer per role and dtype (parameters, gradients,
optimizer-state slots, auxiliary states), each leaf a segment starting on a
whole (8, 128) tile, so a ResNet-50 launch moves 14 buffers where one array
per parameter moved 1152.  Inside, the forward pass reads the
parameters sliced out at static offsets, and the optimizer writes the new
parameters and state into the donated buffers in place: one elementwise
update over each buffer whose leaves share an update kind, a
``dynamic_update_slice`` a leaf elsewhere (the per-leaf step's operations,
in its order, on every element).  The stepper owns
the packed buffers from step to step; the Module's per-name NDArrays go
stale and are brought up to date (one jitted unpack, then ``_rebind``) only
when something reads them: ``Executor.arg_dict`` / ``grad_dict`` /
``aux_dict`` and ``Updater.states`` ask their ``_owner`` to
``materialize`` first.  Before a launch the stepper repacks only if an
array was rebound since it last synced (a write through any NDArray
rebinds it).  A mesh keeps one array per leaf (ZeRO-1 shards per leaf), and
so do Modules that share their arrays with another Module (bucketing,
``shared_module``): another executor's reads would not ask this owner.

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

import operator

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
        if module._exec._grad_dict.get(n) is None:
            return "grad_req"
    opt = module._optimizer
    if opt is None or opt.fused_step_kind() is None:
        return "optimizer"
    if module._mesh is not None and _DP_AXIS not in module._mesh.axis_names:
        return "mesh_no_dp"
    return None


def _packs(module):
    """True when the Module's fused step carries its state packed: no mesh
    (ZeRO-1 decides each leaf's sharding) and arrays no other Module's
    executor reads (``Module.bind(shared_module=)``)."""
    return module._mesh is None and not module._params_shared


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


def _state_arrays(state):
    """Flatten one Updater state slot (None | NDArray | tuple) to its list
    of NDArrays."""
    if state is None:
        return []
    if isinstance(state, NDArray):
        return [state]
    return list(state)


def _state_leaves(state):
    """Flatten one Updater state slot to a list of jax arrays for the
    jitted step."""
    return [s._data for s in _state_arrays(state)]


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


def _step_parts(graph_fn, arg_names, diff_names, const_names, kind, hp,
                nancheck=False, health_groups=None):
    """The pure fused step in three parts, which the per-leaf step
    (``_build_step_fn``) and the packed one (``_packed_step_fn``) put
    together each in its own way.  Closed over only static structure
    (names, kind, static hyperparams, the nancheck/health flags) so one
    jitted instance survives re-binds of the same symbol and re-traces only
    on new shape signatures:

    - ``forward(diff_vals, aux_vals, const_vals, key) -> (heads, grads,
      new_aux)``: one vjp over the executor graph;
    - ``update(w, g, state, lr, wd) -> (w', state')``: the in-graph
      optimizer fold.  Every kernel is elementwise, so ``w`` may be one leaf
      with scalar ``lr`` / ``wd`` or a flat buffer of leaves of one kind
      with per-element vectors;
    - ``extras(heads, grads, params, new_params)``: what the step appends
      to its outputs.

    With ``nancheck`` the extras hold a scalar ``finite`` flag —
    ``all(isfinite(heads)) & all(isfinite(grads))`` reduced INSIDE the same
    donated jit, so the check adds no dispatch and no sync (the caller reads
    the flag one step later, when it has materialized for free).

    With ``health_groups`` (ISSUE 12, ``MXNET_TRAINHEALTH`` or an in-graph
    monitor) the extras also hold the trainhealth stats pytree —
    global/per-group grad norms, param norms, update-to-weight ratios and
    per-group non-finite flags, reduced by
    ``telemetry.trainhealth.compute_step_stats`` inside the same donated
    jit: observing the step costs zero extra dispatches.  Both extras
    append to the output tuple (finite flag first), so the gate-off output
    structure stays byte-identical to a build without either feature."""
    import jax
    import jax.numpy as jnp

    from ..ops.optimizer_ops import fused_update

    def forward(diff_vals, aux_vals, const_vals, key):
        def f(dvals):
            env = dict(zip(const_names, const_vals))
            env.update(zip(diff_names, dvals))
            return graph_fn([env[n] for n in arg_names], aux_vals, key)

        heads, vjp_fn, new_aux = jax.vjp(f, diff_vals, has_aux=True)
        (grads,) = vjp_fn([jnp.ones_like(h) for h in heads])
        return heads, grads, new_aux

    def update(w, g, state, lr, wd):
        # like sgd_rule: a parameter updates with momentum iff it HAS a
        # momentum slot (created when the optimizer's momentum was set), so
        # mid-run momentum edits behave exactly like the legacy path
        k = ("sgd_mom" if state else "sgd") if kind == "sgd" else kind
        return fused_update(k, w, g, state, lr=lr, wd=wd, **hp)

    def extras(heads, grads, params, new_params):
        out = ()
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
                heads, grads, params, new_params, health_groups),)
        return out

    return forward, update, extras


def _build_step_fn(parts):
    """The per-leaf fused step over ``_step_parts``: one array per leaf in
    and out, each leaf updated on its own."""
    import jax

    forward, update, extras = parts

    def step(diff_vals, grads_in, opt_state, aux_vals, const_vals, key,
             lr_vec, wd_vec):
        # grads_in is donated purely so XLA can recycle the standing grad
        # buffers for the returned gradients
        del grads_in
        heads, grads, new_aux = forward(diff_vals, aux_vals, const_vals, key)
        new_params, new_state = [], []
        with jax.named_scope("optimizer"):
            for i, (w, g) in enumerate(zip(diff_vals, grads)):
                new_w, new_st = update(w, g, tuple(opt_state[i]), lr_vec[i],
                                       wd_vec[i])
                new_params.append(new_w)
                new_state.append(list(new_st))
        return (new_params, new_state, new_aux, heads, grads) + extras(
            heads, grads, diff_vals, new_params)

    return step


_ALIGN = 1024  # elements: a segment starts on a whole (8, 128) f32 tile


def _major_to_minor(v):
    """The order in which the device lays out ``v``'s axes, outermost
    first (row-major where the array does not say)."""
    fmt = getattr(v, "format", None)
    order = getattr(getattr(fmt, "layout", None), "major_to_minor", None)
    return tuple(order) if order is not None else tuple(range(v.ndim))


class _Layout:
    """Where each leaf of one role lives in that role's flat buffers: one
    buffer per group and dtype (in order of first appearance), each leaf a
    segment padded to a multiple of ``_ALIGN`` elements and holding the
    leaf's elements in the order the device lays its axes out (a 3x3
    convolution's filter on a TPU: taps outermost), so slicing a leaf out
    and putting it back move dense rows and never a padded tile.  The
    optimizer state groups its leaves by slot, so where every parameter of
    a buffer has the slot, that slot's buffer is laid out segment for
    segment as the parameters' is.  Static: offsets and axis orders are
    Python ints the compiler sees through."""

    def __init__(self, leaves, groups=None):
        leaves = list(leaves)
        if groups is None:
            groups = [0] * len(leaves)
        self.avals = tuple((tuple(v.shape), str(v.dtype), _major_to_minor(v))
                           for v in leaves)
        keys = []
        self.dtypes = []
        self.sizes = []
        self.slots = []  # (buffer index, offset, size, shape, axis order)
        for (shape, dt, order), g in zip(self.avals, groups):
            if (g, dt) not in keys:
                keys.append((g, dt))
                self.dtypes.append(dt)
                self.sizes.append(0)
            b = keys.index((g, dt))
            n = int(np.prod(shape, dtype=np.int64))
            self.slots.append((b, self.sizes[b], n, shape, order))
            self.sizes[b] += -(-n // _ALIGN) * _ALIGN

    def leaf(self, bufs, k):
        """Leaf ``k`` sliced out of its buffer."""
        from jax import lax

        b, o, n, shape, order = self.slots[k]
        seg = lax.slice(bufs[b], (o,), (o + n,))
        seg = seg.reshape(tuple(shape[d] for d in order))
        if order != tuple(range(len(order))):
            seg = seg.transpose(np.argsort(order))
        return seg

    def unpack(self, bufs):
        return [self.leaf(bufs, k) for k in range(len(self.slots))]

    def _flat(self, k, v):
        """Leaf ``k``'s value as its segment holds it; a value of another
        dtype is cast as ``NDArray._rebind`` would cast it."""
        import jax.numpy as jnp

        b, _o, _n, _shape, order = self.slots[k]
        if order != tuple(range(len(order))):
            v = v.transpose(order)
        return jnp.ravel(v).astype(self.dtypes[b])

    def pack(self, leaves):
        """Leaves -> flat buffers; the padding is zeros."""
        import jax.numpy as jnp

        parts = [[] for _ in self.dtypes]
        for k, v in enumerate(leaves):
            b, _o, n, _shape, _order = self.slots[k]
            parts[b].append(self._flat(k, v))
            if n % _ALIGN:
                parts[b].append(jnp.zeros((-n % _ALIGN,), self.dtypes[b]))
        return [jnp.concatenate(p) for p in parts]

    def put(self, bufs, k, v):
        """``bufs`` with leaf ``k`` written over its segment: a
        ``dynamic_update_slice`` at a static offset, in place in a donated
        buffer."""
        from jax import lax

        b, o = self.slots[k][:2]
        bufs = list(bufs)
        bufs[b] = lax.dynamic_update_slice(bufs[b], self._flat(k, v), (o,))
        return bufs

    def spread(self, b, vec):
        """Buffer ``b``'s elements each given its leaf's entry of ``vec``
        (one scalar a leaf, padding included).  A segment is whole rows of
        ``_ALIGN`` elements, so the entries are gathered one a row, by a
        constant index, and broadcast over (8, 128) tiles: a flat buffer is
        stored as such tiles, so the compiler fuses the broadcast into the
        elementwise op that reads it.  Broadcast over rows of 1024, or
        concatenated a segment at a time, the spread is built in memory."""
        import jax.numpy as jnp

        rows = np.concatenate([
            np.full(-(-n // _ALIGN), k, np.int32)
            for k, (bb, _o, n, _shape, _order) in enumerate(self.slots)
            if bb == b])
        return jnp.broadcast_to(jnp.take(vec, rows)[:, None, None],
                                (len(rows), 8, _ALIGN // 8)).reshape(-1)


def _flat_plan(lay_params, lay_state, state_counts):
    """``{parameter buffer: [state buffer of each slot]}`` for every
    parameter buffer that can be updated whole: its leaves share one update
    kind (the same number of state slots), and each slot's buffer holds
    exactly those leaves' slot, at the parameters' offsets, in their dtype.
    The other parameters update leaf by leaf."""
    owners = [i for i, c in enumerate(state_counts) for _ in range(c)]
    first = np.cumsum([0] + list(state_counts))
    by_buf = {}
    for i, (b, *seg) in zip(owners, lay_state.slots):
        by_buf.setdefault(b, []).append((i, *seg))
    plan = {}
    for b, dt in enumerate(lay_params.dtypes):
        mine = [(i, *seg) for i, (bb, *seg) in enumerate(lay_params.slots)
                if bb == b]
        counts = {state_counts[i] for i, *_ in mine}
        if len(counts) != 1:
            continue
        sbs = [lay_state.slots[first[mine[0][0]] + j][0]
               for j in range(counts.pop())]
        if all(by_buf[s] == mine and lay_state.dtypes[s] == dt for s in sbs):
            plan[b] = sbs
    return plan


def _packed_step_fn(parts, lay_params, lay_state, lay_aux, state_counts):
    """The packed twin of the per-leaf step, and the number of leaves it
    updates on whole buffers.  The carried state enters and leaves as flat
    buffers (``_Layout``).  The forward pass reads the
    parameters sliced out behind an ``optimization_barrier``, which makes
    each a buffer of its own, as an argument was (a convolution whose
    weights are a view into a 100 MB buffer cannot prefetch them alone and
    runs dearer); the auxiliary states are sliced out as they are.  The
    gradients are packed, since the executor materializes them on read.
    The update then writes the donated buffers in place: once over each
    parameter buffer of one kind (``_flat_plan``), with ``lr`` / ``wd``
    spread over its elements, and leaf by leaf, at each leaf's offset, over
    the rest.  The optimizer state never leaves its buffers, and no
    parameter or state buffer is concatenated anew.  The operations on each
    element and their order are the per-leaf step's.  ``grad_bufs`` are
    the previous step's gradients, donated so the new ones are written into
    them."""
    forward, update, extras = parts
    plan = _flat_plan(lay_params, lay_state, state_counts)
    first = np.cumsum([0] + list(state_counts))

    def packed(param_bufs, grad_bufs, state_bufs, aux_bufs, const_vals,
               key, lr_vec, wd_vec):
        import jax

        del grad_bufs
        params = jax.lax.optimization_barrier(lay_params.unpack(param_bufs))
        heads, grads, new_aux = forward(params, lay_aux.unpack(aux_bufs),
                                        const_vals, key)
        grad_bufs = lay_params.pack(grads)
        new_params, new_state = list(param_bufs), list(state_bufs)
        with jax.named_scope("optimizer"):
            for b, sbs in plan.items():
                w, st = update(param_bufs[b], grad_bufs[b],
                               tuple(state_bufs[s] for s in sbs),
                               lay_params.spread(b, lr_vec),
                               lay_params.spread(b, wd_vec))
                new_params[b] = w.astype(param_bufs[b].dtype)
                for s, v in zip(sbs, st):
                    new_state[s] = v.astype(state_bufs[s].dtype)
            for i, slot in enumerate(lay_params.slots):
                if slot[0] in plan:
                    continue
                ks = range(first[i], first[i + 1])
                w, st = update(params[i], grads[i],
                               tuple(lay_state.leaf(state_bufs, k)
                                     for k in ks), lr_vec[i], wd_vec[i])
                new_params = lay_params.put(new_params, i, w)
                for k, v in zip(ks, st):
                    new_state = lay_state.put(new_state, k, v)
        return (new_params, new_state, lay_aux.pack(new_aux), heads,
                grad_bufs) + extras(heads, grads, params,
                                    lay_params.unpack(new_params))

    return packed, sum(b in plan for b, *_ in lay_params.slots)


class _Hold:
    """What a packed stepper last synced with: the executor and Updater it
    owns, their NDArrays, and the jax array each held right after the sync
    (``seen``): an array whose ``_data`` is no longer that one was written."""

    def __init__(self, exec_, updater, params, grads, slots, states, aux):
        self.exec_ = exec_
        self.updater = updater
        self.params = params
        self.grads = grads
        self.slots = slots
        self.states = states
        self.aux = aux
        self.arrays = params + states + aux  # what the step reads
        self.seen = [a._data for a in self.arrays]


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
        self._packed = _packs(module)
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
            if self._packed:
                # the packed boundary is another program over the same
                # symbol; the per-leaf key stays as it was
                self._aot_key = self._aot_key + ("packed",)
            if self._health_groups is not None:
                # appended (not an always-present flag) so gate-off keys
                # stay byte-identical to pre-trainhealth entries
                self._aot_key = self._aot_key + ("trainhealth",)
        # symbol kept for the compile plane's logical row key (ISSUE 13) —
        # a Symbol, not an executor: no buffer pinning across re-binds
        self._symbol_ref = module._symbol
        self._nsteps = 0
        self._pending_flag = None  # (finite device scalar, step number)
        self._parts = _step_parts(exec_._graph_fn(True), self._arg_names,
                                  self._diff_names, self._const_names,
                                  self._kind, hp, nancheck=self._nancheck,
                                  health_groups=self._health_groups)
        self._fn = _build_step_fn(self._parts)
        self._jit = None
        self._step = None
        # packed path: the buffers the stepper owns between steps (params,
        # grads, state slots, aux: a list of flat buffers each), whether
        # they are newer than the Module's NDArrays, what they were last
        # synced with (_Hold), the jitted (step, pack, unpack) of each
        # layout, the buffers a launch moves (the ``buffers`` counter) and
        # the leaves its step updates on whole buffers
        # (``flat_update_leaves``)
        self._bufs = None
        self._newer = False
        self._hold = None
        self._fns = None
        self._layouts = {}
        self._nbuf = None
        self._nflat = 0
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
        if self._step is not None:
            return
        if self._mesh is None:
            self._wrap(self._fn)
        else:
            from ..parallel import note_derived

            repl, grad_sh, state_sh = self._shardings
            out_sh = ([repl] * len(diff_vals), state_sh,
                      [repl] * len(self._aux_names), None, grad_sh)
            if self._nancheck:
                out_sh = out_sh + (None,)
            if self._health_groups is not None:
                out_sh = out_sh + (None,)  # stats pytree: compiler-chosen
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
            self._wrap(self._fn, out_shardings=out_sh)

    def _wrap(self, fn, layout=(), **jit_kw):
        """Jit a step (its four state arguments donated) and put the AOT
        cache or the compile plane, and the step accounting, around it;
        ``layout`` (the packed path's leaf shapes) joins the logical key.
        Sets ``_jit`` and ``_step``, returns ``_step``."""
        import jax

        donate = (0, 1, 2, 3) if self._donate else ()
        self._jit = jax.jit(fn, donate_argnums=donate, **jit_kw)
        if self._aot_key is not None:
            from .. import compile_cache

            # donated=True: on the CPU backend the disk tier is skipped
            # entirely — restored donated executables compute wrong
            # trajectories there (the donation hazard, compile_cache.py
            # docstring) — so a CPU restart re-pays this compile; TPU-class
            # backends restore normally.  MXNET_FUSED_DONATE=0 makes the
            # restore legal everywhere.  Cache off ⇒ the plain jit above.
            self._jit = compile_cache.CachedFunction(
                self._jit, self._aot_key + layout, name="fused_step",
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
                     self._health_groups is not None) + layout,
                    donated=self._donate)
        # compile/steady-state accounting (identity when telemetry is off)
        self._step = telemetry.instrument_step(self._jit,
                                               name="module_fused_step")
        return self._step

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
                or module._exec._graph_passes != self._passes_on
                # the Module began sharing its arrays: per leaf from now on
                or _packs(module) != self._packed)

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

    # -- packed state (no mesh) ----------------------------------------------
    def _holds(self, exec_, updater):
        """True while the packed buffers stand for this executor's and
        Updater's arrays: the same objects as at the last sync, and none
        rebound since (a write through an NDArray rebinds it).  Reads the
        raw storage, so it never materializes."""
        h = self._hold
        if h is None or h.exec_ is not exec_ or h.updater is not updater:
            return False
        arg, aux, slots = exec_._arg_dict, exec_._aux_dict, updater._states
        is_ = operator.is_
        return (all(map(is_, [arg.get(n) for n in self._diff_names],
                        h.params))
                and all(map(is_, [aux.get(n) for n in self._aux_names],
                            h.aux))
                and all(map(is_, [slots.get(i) for i in range(len(h.params))],
                            h.slots))
                and all(map(is_, [a._data for a in h.arrays], h.seen)))

    def _sync(self, exec_, updater):
        """Pack the Module's arrays into fresh buffers and own them: at the
        first launch, and at one after an array was rebound (which keeps
        what was written into it: ``materialize``)."""
        if self._hold is not None:
            self.materialize()
            self._let_go()
        arg = exec_._arg_dict
        params = [arg[n] for n in self._diff_names]
        slots = updater._states
        for i, w in enumerate(params):
            if i not in slots:
                slots[i] = self._opt.create_state(i, w)
                updater.states_synced[i] = True
        slots = [slots[i] for i in range(len(params))]
        per_param = [_state_arrays(st) for st in slots]
        states = [a for lv in per_param for a in lv]
        aux = [exec_._aux_dict[n] for n in self._aux_names]
        grads = [exec_._grad_dict[n] for n in self._diff_names]
        data = [[a._data for a in arrs] for arrs in (params, grads, states,
                                                     aux)]
        counts = tuple(len(lv) for lv in per_param)
        # a state leaf's group is its slot (momentum 0; adam's mean 0 and
        # variance 1), so each slot's buffer is laid out as the parameters'
        self._fns = self._packed_fns(
            _Layout(data[0]),
            _Layout(data[2], [j for c in counts for j in range(c)]),
            _Layout(data[3]), counts)
        self._bufs = self._fns[1](*data)
        tracing.count("dispatch")
        self._hold = _Hold(exec_, updater, params, grads, slots, states, aux)
        self._newer = False
        self._nbuf = None
        exec_._owner = self
        updater._owner = self

    def _packed_fns(self, lay_params, lay_state, lay_aux, state_counts):
        """(step, pack, unpack, the step's jit, the leaves the step updates
        on whole buffers) of one layout, built once.  Pack and unpack move
        every role in one program each: the Module's arrays into buffers,
        and buffers back into arrays."""
        import jax

        key = (lay_params.avals, lay_state.avals, lay_aux.avals, state_counts)
        fns = self._layouts.get(key)
        if fns is None:
            fn, nflat = _packed_step_fn(self._parts, lay_params, lay_state,
                                        lay_aux, state_counts)
            step = self._wrap(fn, (("layout",) + key,))

            def pack(params, grads, states, aux):
                return (lay_params.pack(params), lay_params.pack(grads),
                        lay_state.pack(states), lay_aux.pack(aux))

            def unpack(params, grads, states, aux):
                return (lay_params.unpack(params), lay_params.unpack(grads),
                        lay_state.unpack(states), lay_aux.unpack(aux))

            fns = self._layouts[key] = (step, jax.jit(pack), jax.jit(unpack),
                                        self._jit, nflat)
        self._step, self._jit, self._nflat = fns[0], fns[3], fns[4]
        return fns

    def materialize(self):
        """Write the packed buffers back into the Module's NDArrays when
        they are newer: one jitted unpack, then ``_rebind`` of each array.
        An array rebound since the last sync keeps what was written into
        it, the newer value, and the next launch repacks.  The executor's
        dicts and the Updater's ``states`` call this before every read."""
        if not self._newer:
            return
        self._newer = False
        h = self._hold
        params, grads, states, aux = self._fns[2](*self._bufs)
        tracing.count("dispatch")
        for a, v in zip(h.grads, grads):
            a._rebind(v)
        for i, (a, v) in enumerate(zip(h.arrays, params + states + aux)):
            if a._data is h.seen[i]:
                a._rebind(v)
                h.seen[i] = a._data

    def _let_go(self):
        h, self._hold = self._hold, None
        self._bufs = None
        if h is not None:
            if h.exec_._owner is self:
                h.exec_._owner = None
            if h.updater._owner is self:
                h.updater._owner = None

    def release(self):
        """Hand the state back to the Module's NDArrays and stop owning
        them: before the Module drops this stepper, re-binds, or lets
        another Module share its arrays."""
        self.materialize()
        self._let_go()

    # -- the launch ----------------------------------------------------------
    def _leaf_args(self, exec_, updater):
        """The per-leaf step's state arguments (params, grads, state slots,
        aux) from the Module's arrays, committed to their mesh layout, and
        the Updater's slots the step's new state goes back into."""
        arg = exec_._arg_dict
        diff_vals = [arg[n]._data for n in self._diff_names]
        grads_in = [exec_._grad_dict[n]._data for n in self._diff_names]
        aux_vals = [exec_._aux_dict[n]._data for n in self._aux_names]
        states, leaves = [], []
        for i, n in enumerate(self._diff_names):
            if i not in updater._states:
                updater._states[i] = self._opt.create_state(i, arg[n])
                updater.states_synced[i] = True
            states.append(updater._states[i])
            leaves.append(_state_leaves(updater._states[i]))
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
        return (diff_vals, grads_in, leaves, aux_vals), states

    def _hyperparams(self):
        """Host-side hyperparam prep, O(P) python and zero dispatches:
        update counts first (the legacy Updater order), then lr/wd read
        through the optimizer's scheduler/multiplier logic; adam's bias
        correction folds into lr so the in-graph kernel stays
        schedule-free."""
        from ..ops.optimizer_ops import adam_bias_corrected_lr

        opt = self._opt
        for i in range(len(self._diff_names)):
            opt._update_count(i)
        lrs, wds = [], []
        for i in range(len(self._diff_names)):
            lr, wd = opt._get_lr(i), opt._get_wd(i)
            if self._kind == "adam":
                lr = adam_bias_corrected_lr(lr, opt._index_update_count[i],
                                            opt.beta1, opt.beta2)
            lrs.append(lr)
            wds.append(wd)
        return np.asarray(lrs, np.float32), np.asarray(wds, np.float32)

    def run(self, module):
        """Dispatch ONE fused step over the feed already staged in the
        executor's arg buffers, then commit params / optimizer state / aux /
        outputs / grads: packed, the new buffers replace the stepper's own
        and the Module's arrays wait for a reader (``materialize``); per
        leaf, each array is rebound.  Consumes exactly one RNG key (like the
        legacy forward), so seeded runs stay reproducible across paths.

        Three child spans of the caller's ``update`` split the host's time
        (telemetry/tracing.py; a profiler session or ``MXNET_TRACE``):
        ``fused.prepare`` everything before the launch, ``fused.dispatch``
        the jitted call alone until it returns (its counters ``buffers``:
        the arrays the call takes and returns, and ``flat_update_leaves``:
        the leaves its step updates on whole buffers, 0 per leaf),
        ``fused.commit`` taking in what came back."""
        from .. import random as _rnd

        with tracing.span("fused.prepare"):
            exec_ = module._exec
            updater = module._updater
            if self._packed:
                if not self._holds(exec_, updater):
                    self._sync(exec_, updater)
                state_args = self._bufs
            else:
                state_args, states = self._leaf_args(exec_, updater)
            const_vals = [exec_._arg_dict[n]._data for n in self._const_names]
            lrs, wds = self._hyperparams()
            key = _rnd.next_key()
            if self._nancheck:
                self.check_nonfinite()
        with tracing.span("fused.dispatch"):
            out = self._step(*state_args, const_vals, key, lrs, wds)
            if self._nbuf is None:
                import jax

                self._nbuf = len(jax.tree_util.tree_leaves(
                    (state_args, const_vals, key, lrs, wds, out)))
            tracing.count("buffers", self._nbuf)
            tracing.count("flat_update_leaves", self._nflat)
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
            if self._packed:
                self._bufs = (new_params, grads, new_state, new_aux)
                self._newer = True
            else:
                arg, aux = exec_._arg_dict, exec_._aux_dict
                for n, v in zip(self._diff_names, new_params):
                    arg[n]._rebind(v)
                for n, g in zip(self._diff_names, grads):
                    exec_._grad_dict[n]._rebind(g)
                for n, v in zip(self._aux_names, new_aux):
                    aux[n]._rebind(v)
                for st, new_leaves in zip(states, new_state):
                    _commit_state(st, new_leaves)
            exec_.outputs = [_wrap(h) for h in heads]
            exec_._last_key = key
            exec_._last_is_train = True
