"""Pipeline parallelism over a ``pp`` mesh axis — GPipe-style microbatching.

The reference's only "pipeline" story is manual per-layer device placement
(`group2ctx` → `nnvm::pass::PlaceDevice`, `src/executor/graph_executor.cc:407`,
with `_CrossDeviceCopy` hops and NO overlap: one device computes while the
others idle).  The TPU-native version is a real pipeline: each device owns
one stage's weights, M microbatches stream through, and at steady state all
stages compute concurrently while `lax.ppermute` moves activations over ICI
— the schedule the reference could not express.

Constraints (the standard SPMD pipeline contract): stages are uniform — one
``stage_fn`` applied S times with per-stage parameters whose leading axis is
sharded over ``pp`` — and every microbatch has the same shape.  Transformer /
MLP stacks fit this naturally.  The whole schedule is differentiable
(``ppermute`` has a transpose rule), so ``jax.grad`` through ``gpipe`` trains
the pipeline without any extra machinery.
"""
from __future__ import annotations

import functools

__all__ = ["gpipe", "stack_stage_params"]


def stack_stage_params(params_list):
    """Stack a list of S identical-structure pytrees along a new leading
    axis (stage axis) — shard that axis over ``pp`` with
    ``shard(x, P('pp', ...))`` so each device holds its own stage."""
    import jax
    import jax.numpy as jnp

    return jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves), *params_list)


def gpipe(stage_fn, stacked_params, microbatches, *, mesh, axis="pp"):
    """Run ``S`` pipeline stages over ``M`` microbatches.

    Parameters
    ----------
    stage_fn : callable ``(stage_params, x) -> y`` with ``y.shape == x.shape``
        (uniform stages; compose shape changes into stage 0/embedding outside).
    stacked_params : pytree with leading dim ``S = mesh.shape[axis]``
        (stage-stacked, e.g. from :func:`stack_stage_params`); sharded or
        replicated — the shard_map slices each device's stage.
    microbatches : array ``(M, mb, ...)`` — the global batch split into M
        equal microbatches (replicated across ``pp``).
    mesh : the device mesh holding ``axis``.

    Returns ``(M, mb, ...)`` outputs after all S stages, replicated.

    Schedule: ``M + S - 1`` ticks; on tick ``t`` device ``d`` processes
    microbatch ``t - d`` (when valid), then activations ppermute one hop
    right.  Bubble fraction is ``(S-1)/(M+S-1)`` — pick ``M >= 4*S`` for
    >75% steady-state utilization.
    """
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    S = mesh.shape[axis]
    M = microbatches.shape[0]
    for leaf in jax.tree_util.tree_leaves(stacked_params):
        if leaf.shape[0] != S:
            raise ValueError(
                "stacked_params leading dim %d != %d pipeline stages (mesh "
                "axis %r); one stage per device" % (leaf.shape[0], S, axis))

    p_specs = jax.tree_util.tree_map(
        lambda _: P(axis), stacked_params)

    def per_device(p_stacked, xs):
        # p_stacked leaves: (1, ...) — this device's stage slice
        p = jax.tree_util.tree_map(lambda a: a[0], p_stacked)
        d = jax.lax.axis_index(axis)
        # the carries differ per stage — mark them axis-varying so the
        # fori_loop carry types line up under shard_map
        state = jax.lax.pcast(jnp.zeros_like(xs[0]), (axis,), to="varying")
        outs = jax.lax.pcast(jnp.zeros_like(xs), (axis,), to="varying")

        def tick(t, carry):
            state, outs = carry
            # stage 0 ingests microbatch t (clamped; masked out when t >= M)
            feed = jax.lax.dynamic_index_in_dim(
                xs, jnp.clip(t, 0, M - 1), axis=0, keepdims=False)
            x_in = jnp.where(d == 0, feed, state)
            y = stage_fn(p, x_in)
            # last stage banks microbatch t - (S-1) when in range
            widx = jnp.clip(t - (S - 1), 0, M - 1)
            bank = (d == S - 1) & (t >= S - 1)
            outs = jax.lax.dynamic_update_index_in_dim(
                outs,
                jnp.where(bank, y, jax.lax.dynamic_index_in_dim(
                    outs, widx, axis=0, keepdims=False)),
                widx, axis=0)
            # activations hop one stage right over ICI
            state = jax.lax.ppermute(
                y, axis, [(i, i + 1) for i in range(S - 1)])
            return state, outs

        state, outs = jax.lax.fori_loop(0, M + S - 1, tick, (state, outs))
        # replicate the last stage's bank to every device
        mask = (d == S - 1).astype(outs.dtype)
        return jax.lax.psum(outs * mask, axis)

    fn = shard_map(per_device, mesh=mesh,
                   in_specs=(p_specs, P()), out_specs=P())
    return fn(stacked_params, microbatches)
