"""Ring attention — sequence/context parallelism over a mesh axis.

The reference has NO long-context machinery (SURVEY §5.7: bucketing + memory
mirror only); this subsystem is the TPU-native upgrade that makes sequence
length a first-class sharded dimension.  Design: blockwise attention with
online softmax, K/V blocks rotated around the ``sp`` mesh axis with
``lax.ppermute`` so each step overlaps compute with ICI transfer
(Liu et al., Ring Attention; see PAPERS.md).

Use :func:`ring_attention` inside an existing ``shard_map``, or
:func:`ring_self_attention` as a standalone entry that builds the shard_map
over the current mesh.
"""
from __future__ import annotations

from functools import partial

__all__ = ["ring_attention", "ring_self_attention"]


def ring_attention(q, k, v, axis_name="sp", causal=False, scale=None):
    """Blockwise attention with K/V ring rotation.  Call inside shard_map.

    q: [B, H, Sq, D] local query block; k, v: [B, H, Skv, D] local key/value
    blocks (sequence dimension sharded over ``axis_name``).  Returns the
    attention output for the local query block: [B, H, Sq, D].
    """
    import jax
    import jax.numpy as jnp

    n = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    B, H, Sq, D = q.shape
    Skv = k.shape[2]
    if scale is None:
        scale = 1.0 / (D ** 0.5)

    q32 = (q * scale).astype(jnp.float32)
    perm = [(i, (i + 1) % n) for i in range(n)]

    neg_inf = jnp.finfo(jnp.float32).min

    def accumulate(o, m, l, k_blk, v_blk, t):
        # block currently held arrived from device (my_idx - t) mod n
        src = (my_idx - t) % n
        s = jnp.einsum("bhqd,bhkd->bhqk", q32, k_blk.astype(jnp.float32))
        if causal:
            qpos = my_idx * Sq + jnp.arange(Sq)
            kpos = src * Skv + jnp.arange(Skv)
            mask = qpos[:, None] >= kpos[None, :]
            s = jnp.where(mask[None, None], s, neg_inf)
        m_blk = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m, m_blk)
        # guard fully-masked rows: exp(neg_inf - neg_inf) otherwise NaNs
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - m_safe)
        if causal:
            p = jnp.where(jnp.isfinite(m_new), p, 0.0)
        corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
        l_new = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        o_new = o * corr + jnp.einsum("bhqk,bhkd->bhqd", p, v_blk.astype(jnp.float32))
        return o_new, m_new, l_new

    def step(carry, t):
        o, m, l, k_blk, v_blk = carry
        o, m, l = accumulate(o, m, l, k_blk, v_blk, t)
        # rotate K/V to the next device; overlaps with the next step's einsum
        k_next = jax.lax.ppermute(k_blk, axis_name, perm)
        v_next = jax.lax.ppermute(v_blk, axis_name, perm)
        return (o, m, l, k_next, v_next), None

    o0 = jnp.zeros((B, H, Sq, D), jnp.float32)
    m0 = jnp.full((B, H, Sq, 1), neg_inf, jnp.float32)
    l0 = jnp.zeros((B, H, Sq, 1), jnp.float32)
    # mark accumulators device-varying so the scan carry type matches
    # (shard_map VMA checking)
    o0, m0, l0 = (jax.lax.pcast(x, (axis_name,), to="varying")
                  for x in (o0, m0, l0))
    # scan n-1 rotate-steps, then consume the final block without rotating —
    # otherwise the last ppermute ships a full K+V block nobody reads
    if n > 1:
        (o, m, l, k_last, v_last), _ = jax.lax.scan(
            step, (o0, m0, l0, k, v), jnp.arange(n - 1)
        )
    else:
        o, m, l, k_last, v_last = o0, m0, l0, k, v
    o, m, l = accumulate(o, m, l, k_last, v_last, n - 1)
    out = o / jnp.maximum(l, 1e-30)
    return out.astype(q.dtype)


def ring_self_attention(q, k, v, mesh=None, axis_name="sp", causal=False, scale=None):
    """Standalone ring attention: shards the sequence axis of [B, H, S, D]
    inputs over ``axis_name`` of ``mesh`` and runs :func:`ring_attention`."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from .mesh import current_mesh

    mesh = mesh or current_mesh()
    if axis_name not in mesh.axis_names:
        raise ValueError(f"mesh has no axis {axis_name!r}: {mesh.axis_names}")
    spec = P(None, None, axis_name, None)
    fn = shard_map(
        partial(ring_attention, axis_name=axis_name, causal=causal, scale=scale),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
    )
    return fn(q, k, v)
