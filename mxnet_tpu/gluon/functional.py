"""Functionalize a gluon Block: explicit-parameter pure functions.

The reference trains gluon nets through the autograd tape + Trainer
(``python/mxnet/gluon/trainer.py:27``); the TPU-performance path is a single
jitted train step where parameters are explicit pytree inputs so jax.grad /
pjit / donation all apply.  This module converts any initialized Block into
that form — the same param-swap trace technique HybridBlock's CachedOp uses
(``mxnet_tpu/gluon/block.py:_build_cached_op``), exposed as a public utility.
"""
from __future__ import annotations

import numpy as np

from .. import random as _rnd
from ..ndarray.ndarray import NDArray
from ..ops.optimizer_ops import adam_bias_corrected_lr, adam_update
from ..telemetry import tracing
from .block import Block, _swap_trace_call

__all__ = ["functionalize", "merge_params", "param_names", "build_train_step",
           "make_train_step", "count_step"]


def _ordered_params(net):
    """→ (sorted (name, Parameter) items, names, aux names): the one place
    that fixes the order of a functional value list and what counts as
    auxiliary state (grad_req='null')."""
    params = sorted(net.collect_params().items())
    return (params, [n for n, _ in params],
            [n for n, p in params if p.grad_req == "null"])


def functionalize(net, train=False):
    """→ (apply, param_names, param_vals, aux_names)

    ``apply(param_vals, x, key) -> (outputs, new_aux_vals)`` is pure and
    jittable: ``param_vals`` is a list of jax arrays ordered like
    ``param_names``; ``new_aux_vals`` carries mutated auxiliary state
    (BatchNorm running stats) for names in ``aux_names`` (a subset of
    ``param_names`` with grad_req='null').
    """
    params, names, aux_names = _ordered_params(net)
    for _, p in params:
        p.data()  # raise early (with a clear message) if uninitialized
    param_vals = [p._data._data for _, p in params]
    aux_set = set(aux_names)
    aux_idx = [i for i, n in enumerate(names) if n in aux_set]

    def apply(vals, x, key=None):
        if key is None:
            key = _rnd.next_key()

        def call():
            xs = x if isinstance(x, (list, tuple)) else (x,)
            nd_in = [v if isinstance(v, NDArray) else NDArray(v) for v in xs]
            return Block.__call__(net, *nd_in)

        out, post = _swap_trace_call(params, vals, call, key, train)
        outs = out if isinstance(out, (list, tuple)) else (out,)
        out_vals = tuple(o._data for o in outs)
        new_aux = [post[i] for i in aux_idx]
        return out_vals if len(out_vals) > 1 else out_vals[0], new_aux

    return apply, names, param_vals, aux_names


def merge_params(names, aux_names, learn, aux):
    """Reassemble ``functionalize``'s ordered value list from a train-step
    state's (learn, aux) split — the eval-side inverse of the learn/aux
    partition every make_*_train_step performs."""
    aux_set = set(aux_names)
    merged, li, ai = [], 0, 0
    for n in names:
        if n in aux_set:
            merged.append(aux[ai]); ai += 1
        else:
            merged.append(learn[li]); li += 1
    return merged


def param_names(net):
    """→ (param_names, aux_names) in :func:`functionalize`'s order: what
    :func:`merge_params` needs to put a train-step state back together."""
    return _ordered_params(net)[1:]


def build_train_step(net, forward_loss, learning_rate=0.01, momentum=0.0,
                     compute_dtype=None, optimizer="sgd", beta1=0.9,
                     beta2=0.999, epsilon=1e-8):
    """The one place a functional train step is assembled: parameters split
    into learnables and auxiliary state, the learnables cast to
    ``compute_dtype`` (fp32 masters; aux stays fp32), ``jax.value_and_grad``
    of the caller's loss, and the update rule.

    ``forward_loss(run, batch, key) -> (loss, extra)`` is the model's part:
    ``run(inputs, key)`` applies the net with the step's parameters (and
    keeps the new auxiliary values for the state), ``batch`` is whatever
    pytree the step is called with, ``loss`` a scalar, ``extra`` any pytree
    of further device values (None for none).

    → (step, state, (names, learn_idx, aux_idx)) with ``state = (learn,
    mom, aux)`` and ``step(state, batch, key, lr=learning_rate) -> (state,
    loss, extra)``, jittable, the state donate-able.  ``lr`` is the baked
    constant by default; a schedule passes it per step as a traced scalar,
    so a decay costs no recompile (for Adam it is the rate that
    ``adam_bias_corrected_lr`` corrects).  ``optimizer="sgd"``: ``mom`` is
    the momentum list (empty without momentum); ``"adam"``: ``{"mean",
    "var", "t"}``.
    """
    import jax
    import jax.numpy as jnp

    if optimizer not in ("sgd", "adam"):
        raise ValueError("optimizer %r: make_train_step knows 'sgd' and "
                         "'adam'" % (optimizer,))
    apply, names, vals, aux_names = functionalize(net, train=True)
    aux_set = set(aux_names)
    aux_idx = [i for i, n in enumerate(names) if n in aux_set]
    learn_idx = [i for i, n in enumerate(names) if n not in aux_set]
    cdtype = jnp.dtype(compute_dtype) if compute_dtype is not None else None

    def compute_loss(learn_vals, aux_vals, batch, key):
        merged = [None] * len(names)
        for i, v in zip(learn_idx, learn_vals):
            merged[i] = v.astype(cdtype) if cdtype is not None else v
        for i, v in zip(aux_idx, aux_vals):
            merged[i] = v  # BN stats stay fp32
        new_aux = []

        def run(inputs, key):
            out, new_aux[:] = apply(merged, inputs, key)
            return out

        loss, extra = forward_loss(run, batch, key)
        if cdtype is not None:
            new_aux[:] = [a.astype(jnp.float32) for a in new_aux]
        return loss, (new_aux, extra)

    grad_fn = jax.value_and_grad(compute_loss, has_aux=True)

    def step(state, batch, key, lr=learning_rate):
        learn_vals, mom_vals, aux_vals = state
        (loss, (new_aux, extra)), grads = grad_fn(
            learn_vals, aux_vals, batch, key)
        with jax.named_scope("optimizer"):
            if optimizer == "adam":
                t = mom_vals["t"] + 1
                lr_t = adam_bias_corrected_lr(
                    lr, t.astype(jnp.float32), beta1, beta2)
                new = [adam_update(p, g, m, v, lr=lr_t, beta1=beta1,
                                   beta2=beta2, epsilon=epsilon)
                       for p, g, m, v in zip(learn_vals, grads,
                                             mom_vals["mean"],
                                             mom_vals["var"])]
                learn_vals, mean, var = (list(c) for c in zip(*new))
                mom_vals = {"mean": mean, "var": var, "t": t}
            else:
                if momentum:
                    mom_vals = [momentum * m + g
                                for m, g in zip(mom_vals, grads)]
                    upd = mom_vals
                else:
                    upd = grads
                learn_vals = [p - lr * g for p, g in zip(learn_vals, upd)]
        return (learn_vals, mom_vals, new_aux), loss, extra

    learn_vals = [vals[i] for i in learn_idx]
    aux_vals = [vals[i] for i in aux_idx]
    # zeros_like on the jax arrays: shapes/dtypes only, no D2H transfer
    if optimizer == "adam":
        mom_vals = {"mean": [jnp.zeros_like(v) for v in learn_vals],
                    "var": [jnp.zeros_like(v) for v in learn_vals],
                    "t": jnp.zeros((), jnp.int32)}
    else:
        mom_vals = [jnp.zeros_like(v) for v in learn_vals] if momentum else []
    return step, (learn_vals, mom_vals, aux_vals), (names, learn_idx, aux_idx)


def make_train_step(net, loss_fn, learning_rate=0.01, momentum=0.0,
                    compute_dtype=None, mesh=None, data_axis="dp",
                    shard_optimizer_states=False, optimizer="sgd",
                    beta1=0.9, beta2=0.999, epsilon=1e-8):
    """Build a fully-jittable train step for an initialized Block.

    → (step, state) where ``state = (param_vals, momentum_vals, aux_vals)``
    pytrees and ``step(state, x, y, key) -> (state, loss)``.  All compute —
    forward, backward, BN-stat update, optimizer — lands in ONE XLA module,
    which is what lets the compiler fuse and overlap (the reference needed
    engine bulking + fused optimizer kernels for the same effect,
    ``src/executor/graph_executor.cc:1454``, ``src/operator/optimizer_op.cc``).
    ``step`` takes an optional trailing ``lr``: a schedule passes the rate
    per step as a device scalar and the step is compiled once
    (:func:`build_train_step`, which assembles the step; this function adds
    the standard forward around ``loss_fn``).

    ``compute_dtype='bfloat16'`` enables mixed precision: fp32 master
    parameters and optimizer state, forward/backward in bf16 (halved HBM
    traffic, native MXU dtype; the reference's fp16 multi-precision mode,
    ``optimizer_op.cc mp_sgd_mom_update``, with bf16's range so no loss
    scaling is needed), loss and BN statistics in fp32.

    **Data-parallel + ZeRO**: pass ``mesh`` (a ``jax.sharding.Mesh`` with a
    ``data_axis`` axis) and the returned ``step`` comes back **already
    jitted** (donated state, pinned output shardings, replicated by
    default) ready for SPMD data parallelism — shard the batch over
    ``data_axis`` and GSPMD derives the gradient collectives from the loss
    mean (the reference's KVStore allreduce, ``src/kvstore/comm.h:451``,
    collapses into the jitted step).  With
    ``shard_optimizer_states=True`` the returned state additionally has
    parameters and momentum partitioned over ``data_axis`` (ZeRO/FSDP
    style: each array's first divisible axis is split; aux/BN stats stay
    replicated) and the returned ``step`` is **already jitted** with
    donation + pinned output shardings, so the partition survives every
    step without hand-written ``device_put`` specs.  GSPMD inserts the
    forward all-gathers and update reduce-scatters; per-device optimizer
    bytes drop ~axis-size×, which is what frees HBM for activations at
    north-star scale (the ``__graft_entry__`` ZeRO phase measures 50 MB vs
    399 MB at ResNet-101 scale).

    ``optimizer="adam"`` replaces SGD's rule by ``ops.optimizer_ops.
    adam_update`` (``beta1`` / ``beta2`` / ``epsilon``; no decay): the
    state's second entry is then ``{"mean": [...], "var": [...], "t": step
    count}``, all donated with the rest, and the bias correction is folded
    into the learning rate from ``t`` inside the step
    (``adam_bias_corrected_lr``).

    A net with several outputs hands ``loss_fn`` a list.  ``loss_fn`` may
    return ``(loss, aux)``, ``aux`` a dict of further device values (the
    loss's terms apart, counters the model computed): the step then returns
    ``(state, loss, aux)``; :func:`count_step` records the counters among
    them on the host's open span.
    """
    import jax
    import jax.numpy as jnp

    cdtype = jnp.dtype(compute_dtype) if compute_dtype is not None else None

    def forward_loss(run, batch, key):
        x, y = batch
        if cdtype is not None:
            # only float leaves change dtype: token ids / masks stay integral
            x = jax.tree_util.tree_map(
                lambda a: a.astype(cdtype)
                if jnp.issubdtype(jnp.asarray(a).dtype, jnp.floating) else a,
                x,
            )
        out = run(x, key)
        if cdtype is not None:
            # integer outputs (counters, choices) keep their type
            out = jax.tree_util.tree_map(
                lambda a: a.astype(jnp.float32)
                if jnp.issubdtype(a.dtype, jnp.floating) else a, out)
        with jax.named_scope("loss"):
            loss = loss_fn([NDArray(o) for o in out] if isinstance(out, tuple)
                           else NDArray(out), NDArray(y))
            extra = None
            if isinstance(loss, tuple):
                loss, extra = loss
                extra = {k: v._data if isinstance(v, NDArray) else v
                         for k, v in extra.items()}
            return jnp.mean(loss._data), extra

    core, state, meta = build_train_step(
        net, forward_loss, learning_rate, momentum, compute_dtype, optimizer,
        beta1, beta2, epsilon)

    def step(state, x, y, key, lr=learning_rate):
        state, loss, extra = core(state, (x, y), key, lr)
        if extra is None:
            return state, loss
        if mesh is not None:
            raise ValueError("a loss_fn that returns (loss, aux) is not "
                             "supported with mesh=: the jitted step pins "
                             "the shardings of (state, loss) only")
        return state, loss, extra

    if shard_optimizer_states and mesh is None:
        raise ValueError(
            "shard_optimizer_states=True needs a mesh with a '%s' axis "
            "(parallel.make_mesh({'%s': n}))" % (data_axis, data_axis))
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ..parallel import zero_shard_spec

        learn_vals, mom_vals, aux_vals = state
        repl = NamedSharding(mesh, P())
        spec = ((lambda v: zero_shard_spec(v, mesh, data_axis))
                if shard_optimizer_states else (lambda v: repl))
        state = ([jax.device_put(v, spec(v)) for v in learn_vals],
                 jax.tree_util.tree_map(
                     lambda v: jax.device_put(v, spec(v)), mom_vals),
                 [jax.device_put(v, repl) for v in aux_vals])
        state_sh = jax.tree_util.tree_map(lambda v: v.sharding, state)
        step = jax.jit(step, donate_argnums=(0,),
                       out_shardings=(state_sh, repl))
        # telemetry (identity when MXNET_TELEMETRY is off — the jitted step
        # object comes back untouched): compile count/seconds + step counters
        from .. import telemetry

        step = telemetry.instrument_step(step, name="gluon_train_step")

    return step, state, meta


def count_step(aux, counters):
    """Record a finished step's device counters on the host's innermost
    open span (``telemetry.tracing.count``, so only while a profiler session
    or ``MXNET_TRACE`` is live): ``counters`` names the entries of the
    step's ``aux`` to record, each summed over its array.  Reading them
    waits for the step, so call it where the step's loss has come back."""
    if tracing.current() is None:
        return
    for name in counters:
        tracing.count(name, int(np.asarray(aux[name]).sum()))
