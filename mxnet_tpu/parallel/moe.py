"""Expert parallelism — Mixture-of-Experts dispatch over an ``ep`` mesh axis.

Absent from the reference (SURVEY §2.2: EP/MoE "out of scope"); provided
here because expert parallelism is a first-class TPU distribution strategy:
each device owns one expert's FFN weights, tokens are routed top-1
(Switch-Transformer style) with fixed capacity, and two ``all_to_all``
collectives over ICI move token buffers to their experts and back — the
GShard dispatch/combine einsum formulation, which keeps everything dense,
static-shaped, and MXU-friendly (no gather/scatter of ragged groups).

Routing contract: ``n_experts == mesh.shape[axis]``; tokens beyond an
expert's capacity are dropped (output 0 for that token — standard Switch
behavior); the router is differentiable through the combine weights.
"""
from __future__ import annotations

__all__ = ["moe_ffn", "stack_expert_params"]


from .pipeline import stack_stage_params as stack_expert_params  # same op


def moe_ffn(x, gate_w, expert_params, expert_fn, *, mesh, axis="ep",
            capacity_factor=1.25):
    """Top-1 routed MoE layer over the ``axis`` mesh dimension.

    Parameters
    ----------
    x : (T, D) global tokens; the token axis is sharded over ``axis``
        (data parallel and expert parallel share the mesh axis, the usual
        MoE layout) — each device routes its ``T/E`` local tokens.
    gate_w : (D, E) router weights (replicated).
    expert_params : pytree with leading dim ``E = mesh.shape[axis]``
        (stacked experts; the shard_map slices one expert per device).
    expert_fn : ``(params_slice, tokens) -> tokens`` applied by each device
        to the tokens routed to its expert (it sees ``E*C`` tokens: ``C``
        slots from every source device).
    capacity_factor : buffer size multiplier; per-source capacity
        ``C = ceil(T/E / E * capacity_factor)``.

    Returns (T, D) outputs sharded like ``x``: gate-prob-weighted expert
    outputs (zero for capacity-dropped tokens).
    """
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    E = mesh.shape[axis]
    for leaf in jax.tree_util.tree_leaves(expert_params):
        if leaf.shape[0] != E:
            raise ValueError(
                "expert_params leading dim %d != %d experts (mesh axis %r)"
                % (leaf.shape[0], E, axis))
    T = x.shape[0]
    if T % E:
        raise ValueError("token count %d must divide over %d devices" % (T, E))
    if gate_w.shape[-1] != E:
        raise ValueError(
            "gate_w routes to %d experts but mesh axis %r has %d devices"
            % (gate_w.shape[-1], axis, E))
    C = max(1, int(-(-(T // E) * capacity_factor // E)))  # ceil
    p_specs = jax.tree_util.tree_map(lambda _: P(axis), expert_params)

    def per_device(x_loc, gw, p_stacked):
        p = jax.tree_util.tree_map(lambda a: a[0], p_stacked)
        logits = x_loc @ gw                           # (T, E)
        probs = jax.nn.softmax(logits, axis=-1)
        expert = jnp.argmax(probs, axis=-1)           # (T,)
        gate = jnp.take_along_axis(probs, expert[:, None], axis=1)[:, 0]
        # slot counting in int32: token dtype may be bf16, whose integers
        # stop being exact at 256 — silent slot collisions otherwise
        onehot = jax.nn.one_hot(expert, E, dtype=jnp.int32)    # (T, E)
        pos = jnp.cumsum(onehot, axis=0) * onehot - 1          # (T, E)
        keep = (pos < C) & (onehot > 0)
        posc = jnp.clip(pos, 0, C - 1)
        # dispatch tensor (T, E, C): 1 where token t sits in slot c of e
        disp = (jax.nn.one_hot(posc, C, dtype=x_loc.dtype)
                * keep[..., None].astype(x_loc.dtype))
        buffers = jnp.einsum("tec,td->ecd", disp, x_loc)       # (E, C, D)
        # ship each expert's buffer to its device; receive (E, C, D) where
        # leading dim indexes SOURCE device after the exchange
        inbox = jax.lax.all_to_all(buffers, axis, split_axis=0,
                                   concat_axis=0, tiled=True)
        y = expert_fn(p, inbox.reshape(E * C, -1)).reshape(E, C, -1)
        outbox = jax.lax.all_to_all(y, axis, split_axis=0,
                                    concat_axis=0, tiled=True)
        combine = disp * gate[:, None, None]                   # (T, E, C)
        return jnp.einsum("tec,ecd->td", combine, outbox)

    fn = shard_map(per_device, mesh=mesh,
                   in_specs=(P(axis), P(), p_specs), out_specs=P(axis))
    return fn(x, gate_w, expert_params)
