"""Expert parallelism — a Mixture-of-Experts layer that is told which
experts it holds.

Absent from the reference (SURVEY §2.2: EP/MoE "out of scope"); provided
here because expert parallelism is a first-class TPU distribution strategy.
The router always has its published width (every expert of the layer); a
chip holds a contiguous share of the experts and computes its own experts'
part of the result:

* :func:`route` — scores over all experts in float32 (a softmax, or
  independent sigmoids with a selection bias that chooses and never gates,
  DeepSeek-V3's form), top-k, gates (normalised over the k chosen experts,
  held here or not, and scaled);
* :func:`held_experts_ffn` — the (token, expert) pairs routed to the experts
  held here, sorted by expert, through grouped matrix products
  (``jax.lax.ragged_dot``: on a TPU a grouped Mosaic product, one pass over
  the pairs, no expert computed densely) and summed back per token.  No pair
  is dropped: the buffer holds ``capacity`` pairs (default: every pair, the
  worst case) and the layer counts what did not fit, so a caller that sizes
  it tighter can fail the step on overflow;
* :func:`moe_layer` — the two together, with the load-balance term (over the
  batch, or sequence-wise over normalised scores) and, where the layer has
  one, the shared expert every token passes.  On one chip it runs without an
  exchange, and nothing stands in for absent chips;
* :func:`moe_ffn` — the same routing over an ``ep`` mesh axis, one expert
  per device, with the two ``all_to_all`` collectives that move fixed-size
  token buffers to their experts and back (GShard dispatch/combine einsums;
  tokens beyond a buffer's capacity are dropped there, Switch style).
"""
from __future__ import annotations

import functools

__all__ = ["route", "held_experts_ffn", "moe_layer", "moe_ffn",
           "stack_expert_params"]


from .pipeline import stack_stage_params as stack_expert_params  # same op


def route(x, router_w, top_k, normalize=True, scoring="softmax", bias=None,
          scale=1.0):
    """Router of a MoE layer.  ``x`` (T, D), ``router_w`` (E, D).
    -> scores (T, E) float32 over all E experts, choice (T, k) int32, gates
    (T, k) float32.  ``scoring="softmax"`` (the default): the scores are a
    softmax over the experts, the choice their top-k, the gates the chosen
    scores, divided by their sum when ``normalize``.  ``"sigmoid"``: each
    expert's score is its own sigmoid.  ``bias`` (E,) is added to the scores
    for the choice alone: the gates are the unbiased scores of the chosen
    (normalised over the chosen plus 1e-20 when ``normalize``), times
    ``scale``.  The product runs in float32 at the highest precision: a
    top-k choice flips on the last bits."""
    import jax
    import jax.numpy as jnp

    if scoring not in ("softmax", "sigmoid"):
        raise ValueError("scoring %r: 'softmax' or 'sigmoid'" % (scoring,))
    logits = jnp.einsum("td,ed->te", x.astype(jnp.float32),
                        router_w.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    if scoring == "softmax":
        probs = jax.nn.softmax(logits, axis=-1)
    else:
        probs = jax.nn.sigmoid(logits)
    if bias is None:
        gates, choice = jax.lax.top_k(probs, top_k)
    else:
        _, choice = jax.lax.top_k(
            probs + jax.lax.stop_gradient(bias.astype(jnp.float32)), top_k)
        gates = jnp.take_along_axis(probs, choice, axis=1)
    if normalize:
        total = jnp.sum(gates, axis=-1, keepdims=True)
        # sigmoids can all underflow; the softmax form stays bit for bit
        gates = gates / (total if scoring == "softmax" else total + 1e-20)
    if scale != 1.0:
        gates = gates * scale
    return probs, choice.astype(jnp.int32), gates


def _gather_rows(src, idx):
    import jax.numpy as jnp

    return jnp.take(src, idx, axis=0, mode="clip")


def _dispatch_combine():
    """``dispatch(x, tok)``: rows of ``x`` (T, D) for each buffered pair ->
    (C, D).  ``combine(y, pos)``: buffered rows ``y`` (C, D) summed back per
    token through ``pos`` (T, k), the pair's row in the buffer or C for a
    pair not held -> (T, D).  ``pos`` and ``tok`` describe one permutation,
    so each is the other's transpose: both passes are gathers, never a
    scatter-add over colliding rows."""
    import jax
    import jax.numpy as jnp

    def combine_rows(y, pos):
        padded = jnp.concatenate([y, jnp.zeros((1,) + y.shape[1:], y.dtype)])
        return jnp.sum(_gather_rows(padded, pos).astype(jnp.float32),
                       axis=1).astype(y.dtype)

    def dispatch_rows(x, tok, valid):
        return jnp.where(valid[:, None], _gather_rows(x, tok), 0)

    @jax.custom_vjp
    def dispatch(x, tok, valid, pos):
        return dispatch_rows(x, tok, valid)

    def dispatch_fwd(x, tok, valid, pos):
        return dispatch_rows(x, tok, valid), pos

    def dispatch_bwd(pos, g):
        return combine_rows(g, pos), None, None, None

    dispatch.defvjp(dispatch_fwd, dispatch_bwd)

    @jax.custom_vjp
    def combine(y, pos, tok, valid):
        return combine_rows(y, pos)

    def combine_fwd(y, pos, tok, valid):
        return combine_rows(y, pos), (tok, valid)

    def combine_bwd(res, g):
        tok, valid = res
        return dispatch_rows(g, tok, valid), None, None, None

    combine.defvjp(combine_fwd, combine_bwd)
    return dispatch, combine


def held_experts_ffn(x, choice, gates, gate_w, up_w, down_w, first_expert=0,
                     capacity=None):
    """The held experts' part of a MoE layer's result.

    ``x`` (T, D) tokens; ``choice`` / ``gates`` (T, k) from :func:`route`;
    ``gate_w`` / ``up_w`` (H, D, F) and ``down_w`` (H, F, D): the H experts
    ``first_expert .. first_expert + H - 1`` of the layer, each a gated
    feed-forward ``(silu(x Wg) * (x Wu)) Wd``.  ``capacity``: rows of the
    buffer of held pairs (default ``T * k``, every pair).
    -> y (T, D): sum over the pairs routed to a held expert of gate x
    expert(x); pairs (H,) int32: pairs routed to each held expert; dropped
    () int32: held pairs that did not fit the buffer (0 at the default)."""
    import jax
    import jax.numpy as jnp

    T, k = choice.shape
    H = gate_w.shape[0]
    C = T * k if capacity is None else min(int(capacity), T * k)
    local = choice - first_expert
    held = (local >= 0) & (local < H)
    flat = jnp.where(held, local, H).reshape(-1)              # (T*k,)
    order = jnp.argsort(flat, stable=True).astype(jnp.int32)  # by expert
    pairs = jnp.sum(jax.nn.one_hot(flat, H, dtype=jnp.int32), axis=0)
    starts = jnp.cumsum(pairs) - pairs
    sizes = jnp.clip(jnp.minimum(pairs, C - starts), 0)       # what fits
    n_fit = jnp.sum(sizes)
    dropped = jnp.sum(pairs) - n_fit
    rows = order[:C]                                          # pair of row c
    valid = jnp.arange(C) < n_fit
    tok = rows // k
    # where each pair sits in the buffer (C: nowhere)
    pos = jnp.full((T * k,), C, jnp.int32).at[rows].set(
        jnp.where(valid, jnp.arange(C, dtype=jnp.int32), C)).reshape(T, k)
    dispatch, combine = _dispatch_combine()
    xg = dispatch(x, tok, valid, pos)                         # (C, D)
    dot = functools.partial(jax.lax.ragged_dot, group_sizes=sizes,
                            preferred_element_type=x.dtype)
    h = jax.nn.silu(dot(xg, gate_w)) * dot(xg, up_w)
    g = jnp.where(valid, gates.reshape(-1)[rows], 0.0)
    out = jnp.where(valid[:, None],
                    dot(h, down_w) * g[:, None].astype(x.dtype), 0)
    return combine(out, pos, tok, valid), pairs, dropped


def moe_layer(x, router_w, gate_w, up_w, down_w, *, top_k, first_expert=0,
              normalize=True, capacity=None, scoring="softmax",
              router_bias=None, routed_scale=1.0, shared=None,
              sequences=None):
    """Router + held experts (+ the shared expert).  ``scoring``,
    ``router_bias`` and ``routed_scale`` are :func:`route`'s.  ``shared``:
    (gate (Fs, D), up (Fs, D), down (D, Fs)) of a gated feed-forward every
    token passes, added to the held experts' part (each chip of the group
    computes it alike).  -> (y, aux) with ``aux``: ``balance``, ``pairs``
    (H,), ``dropped`` (), ``choice`` / ``gates`` (T, k), ``router_pairs``
    (E,) float32: the pairs routed to every expert of the layer.  ``balance`` is ``E *
    sum_e frac_e * mean_t scores[t, e]``, ``frac_e`` the pairs routed to
    expert e over T (Switch / Qwen3-MoE's form, all E experts); with
    ``sequences`` = B the tokens are B documents of T / B and it is
    DeepSeek-V3's sequence-wise term, the mean over the documents of ``sum_e
    f_e P_e``, ``f_e = E / (k S) x`` the document's pairs to e, ``P_e`` the
    document's mean of ``scores[t, e] / sum_j scores[t, j]``."""
    import jax
    import jax.numpy as jnp

    E = router_w.shape[0]
    with jax.named_scope("moe.route"):
        probs, choice, gates = route(x, router_w, top_k, normalize, scoring,
                                     router_bias, routed_scale)
        if sequences is None:
            router_pairs = jnp.sum(jax.nn.one_hot(
                choice.reshape(-1), E, dtype=jnp.float32), axis=0)
            frac = router_pairs / x.shape[0]
            balance = E * jnp.sum(jax.lax.stop_gradient(frac)
                                  * jnp.mean(probs, axis=0))
        else:
            per_doc = jnp.sum(jax.nn.one_hot(
                choice.reshape(sequences, -1), E, dtype=jnp.float32), axis=1)
            S = x.shape[0] // sequences
            share = (probs / jnp.sum(probs, axis=-1, keepdims=True)).reshape(
                sequences, S, E)
            balance = jnp.mean(jnp.sum(
                jax.lax.stop_gradient(per_doc * (E / (top_k * S)))
                * jnp.mean(share, axis=1), axis=-1))
            router_pairs = jnp.sum(per_doc, axis=0)
    with jax.named_scope("moe.experts"):
        y, pairs, dropped = held_experts_ffn(
            x, choice, gates, gate_w, up_w, down_w, first_expert, capacity)
    if shared is not None:
        from ..ops.transformer import gated_ffn

        with jax.named_scope("moe.shared"):
            y = y + gated_ffn(x, *shared)
    return y, {"balance": balance, "pairs": pairs, "dropped": dropped,
               "choice": choice, "router_pairs": router_pairs, "gates": gates}


def moe_ffn(x, gate_w, expert_params, expert_fn, *, mesh, axis="ep",
            capacity_factor=1.25):
    """Top-1 routed MoE layer over the ``axis`` mesh dimension: one expert
    a device, :func:`route` at ``top_k=1`` with the gate left unnormalised
    (Switch style), fixed-capacity buffers through two ``all_to_all``s.

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
        _, choice, gates = route(x_loc, gw.T, 1, normalize=False)
        expert = choice[:, 0]                         # (T,)
        gate = gates[:, 0].astype(x_loc.dtype)        # its probability
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
