"""Transformer operators: RMSNorm, rotary embedding with sections, the gated
feed-forward, indexer-selected sparse attention, dense causal attention, the
latent (compressed key-value) attention around it, the expert layer and the
head's log-probabilities in row blocks.

None has a counterpart in the reference (its attention lived in user code
over ``batch_dot`` + ``softmax``).  Layouts are token-major: activations
``(S, D)``, heads ``(S, heads, head_dim)``, one sequence, or with a leading
axis of documents that never see each other (``CausalAttention``,
``LatentAttention``); weights are ``(out, in)`` like ``FullyConnected``'s.
Statistics, softmaxes, index scores and the router run in float32 whatever
the activations' type.

``IndexerSparseAttention`` is DeepSeek-V3.2-Exp's sparse attention as a
training operator: a light indexer scores every causal key for every query,
an exact threshold keeps the ``topk`` best, attention reads only those, and a
KL term teaches the indexer the attention's own distribution.  It walks the
queries in blocks (no ``(heads, S, S)`` array exists), skips the key blocks
above the diagonal by spans, and saves each query's threshold for the
backward pass, which recomputes scores but never the selection.  A block's
attention over its selected keys runs on one of two paths: lowered for a TPU
at shapes the kernels take, ``ops/pallas_attention.py``'s pair
(``sparse_attn_pallas_fwd`` / ``_bwd``: the block's (heads, block, keys)
weights never leave VMEM); anywhere else (a CPU, toy shapes) the XLA walk
(``_attend_walk``), which is also the kernels' oracle in the tests.

``CausalAttention`` is dense causal attention on one of two paths.  Lowered
for a TPU at shapes ``pallas_attention.causal_attn_supported`` takes, the
pair ``causal_attn_pallas_fwd`` / ``_bwd``: blocks of queries against tiles
of keys of one (document, head), the block's weights, their gradient and
the float32 ``dk`` / ``dv`` / ``dq`` sums all in VMEM; only q, k, v, o, do,
the three gradients and each row's log-sum cross.  Anywhere else (a CPU, toy
shapes) the same walk as above with every causal key selected and nothing to
index (``_causal_forward`` / ``_causal_backward``), the pair's oracle.
``LatentAttention`` (DeepSeek-V2's multi-head latent attention, training
form) rebuilds every head's keys and values from one narrow normed latent a
token plus one rotary key all heads share, and hands them to either path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .registry import register


@register("RMSNorm")
def rms_norm(data, gamma, *, eps=1e-6):
    """``x / sqrt(mean(x^2) + eps) * gamma`` over the last axis, statistics
    in float32."""
    x = data.astype(jnp.float32)
    y = x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (y * gamma.astype(jnp.float32)).astype(data.dtype)


@register("RotaryEmbedding")
def rotary_embedding(data, positions, *, theta=10000.0, sections=()):
    """Rotary position embedding, half-rotation form, on ``data`` (S, heads,
    d).  ``d / 2`` frequency pairs ``theta^(-2i/d)``.  ``positions`` is (S,)
    for 1-D rotary, or (len(sections), S) with ``sections`` the number of
    pairs that read each row of ids (M-RoPE: ``sum(sections) == d / 2``;
    pair i reads the ids of the section it falls in)."""
    half = data.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    pos = positions.astype(jnp.float32)
    if pos.ndim == 2:
        if sum(sections) != half or len(sections) != pos.shape[0]:
            raise ValueError(
                "sections %r must be one per row of positions %r and sum to "
                "%d frequency pairs" % (sections, pos.shape, half))
        which = np.repeat(np.arange(len(sections)), sections)
        pos = pos[which].T                                   # (S, half)
    else:
        pos = pos[:, None]
    ang = pos * inv_freq                                     # (S, half)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x = data.astype(jnp.float32)
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(data.dtype)


@register("GatedFFN")
def gated_ffn(data, gate_weight, up_weight, down_weight):
    """SwiGLU feed-forward ``(silu(x Wg^T) * (x Wu^T)) Wd^T`` over the last
    axis; ``Wg`` / ``Wu`` (F, D), ``Wd`` (D, F)."""
    g = jnp.einsum("...d,fd->...f", data, gate_weight)
    u = jnp.einsum("...d,fd->...f", data, up_weight)
    return jnp.einsum("...f,df->...d", jax.nn.silu(g) * u, down_weight)


# -- indexer-selected sparse attention -----------------------------------------
def _sortable(x):
    """float32 -> uint32 with the same order (and -0.0 == +0.0)."""
    b = lax.bitcast_convert_type(jnp.where(x == 0, 0.0, x), jnp.uint32)
    return jnp.where(b >> 31 == 1, ~b, b | jnp.uint32(0x80000000))


def _kth_largest(keys, k):
    """``keys`` (R, N) uint32 -> (R,) the k-th largest of each row: the
    largest T with ``count(keys >= T) >= k``, by 32 halvings of the key
    range, each one compare-and-count pass.  Exact, no sort."""
    def halve(i, prefix):
        cand = prefix | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        n = jnp.sum(keys >= cand[:, None], axis=1, dtype=jnp.int32)
        return jnp.where(n >= k, cand, prefix)

    return lax.fori_loop(0, 32, halve,
                         jnp.zeros(keys.shape[0], jnp.uint32))


def _pack_bits(mask, width):
    """(R, N) bool -> (R, width // 32) uint32, bit j of word i = column
    32 i + j; columns from N to ``width`` read 0."""
    r, n = mask.shape
    words = jnp.sum(mask.reshape(r, n // 32, 32).astype(jnp.uint32)
                    << jnp.arange(32, dtype=jnp.uint32), axis=-1,
                    dtype=jnp.uint32)
    return jnp.pad(words, ((0, 0), (0, (width - n) // 32)))


def _index_scores(iq, ik, iw, with_vjp=False):
    """iq (B, HI, dI), ik (Sk, dI), iw (B, HI) -> (B, Sk) float32: ``sum_j
    w[., j] relu(qI[., j] . kI[s]) / sqrt(HI dI)``.  ``with_vjp``: also the
    function from the scores' cotangent to (diq, dik, diw), its (B, HI, Sk)
    array held in the compute type like the attention's."""
    dots = jnp.einsum("qjd,kd->qjk", iq, ik,
                      preferred_element_type=jnp.float32)
    scale = (iq.shape[1] * iq.shape[2]) ** -0.5
    w = iw.astype(jnp.float32)[:, :, None]
    scores = jnp.sum(jax.nn.relu(dots) * w, axis=1) * scale
    if not with_vjp:
        return scores

    def vjp(g):
        g = g[:, None, :] * scale
        diw = jnp.sum(g * jax.nn.relu(dots), axis=2).astype(iw.dtype)
        dd = jnp.where(dots > 0, g * w, 0.0).astype(ik.dtype)
        return (jnp.einsum("qjk,kd->qjd", dd, ik),
                jnp.einsum("qjk,qjd->kd", dd, iq), diw)

    return scores, vjp


def _select(scores, t, topk, tau=None):
    """Index scores (B, Sk) of the queries at positions ``t`` -> (sel (B, Sk)
    bool, tau (B,) uint32, causal): the keys at or above each row's
    ``topk``-th largest causal score.  Given ``tau`` nothing is searched."""
    causal = jnp.arange(scores.shape[1])[None, :] <= t[:, None]
    keys = jnp.where(causal, _sortable(scores), 0)
    if tau is None:
        tau = _kth_largest(keys, topk)
    return (keys >= tau[:, None]) & causal, tau, causal


def _weights(qg, k, sel):
    """qg (B, h, g, d), k (Sk, h, d), sel (B, Sk) -> e (h, g, B, Sk) the
    unnormalised attention weights in the compute type, z (h, g, B) their
    float32 sums.  Softmax is shift-invariant: the shift is an upper bound of
    the row's scores (|q . k| <= |q| max_s |k_s|), which costs no pass over
    the (heads, B, Sk) scores where their maximum costs two."""
    d = qg.shape[-1]
    norm = lambda x: jnp.sqrt(jnp.sum(jnp.square(  # noqa: E731
        x.astype(jnp.float32)), -1))
    bound = (norm(qg) * jnp.max(norm(k), 0)[None, :, None]).transpose(1, 2, 0)
    s = jnp.einsum("qhgd,khd->hgqk", qg, k,
                   preferred_element_type=jnp.float32)
    e = jnp.where(sel, jnp.exp((s - bound[..., None]) * d ** -0.5),
                  0.0).astype(k.dtype)
    return e, jnp.sum(e.astype(jnp.float32), axis=-1)


def _target(e, z):
    """Mean over the heads of the attention probabilities -> (B, Sk)."""
    return jnp.mean(e.astype(jnp.float32) / z[..., None], axis=(0, 1))


def _attend_walk(q, k, v, sel):
    """The XLA walk's block: q (B, Hq, d), k / v (Sk, Hkv, d), sel (B, Sk)
    -> o (B, Hq, d), z (Hkv, g, B) float32, target (B, Sk) float32."""
    B, Hq, d = q.shape
    Hkv = k.shape[1]
    e, z = _weights(q.reshape(B, Hkv, Hq // Hkv, d), k, sel)
    o = (jnp.einsum("hgqk,khd->qhgd", e, v,
                    preferred_element_type=jnp.float32)
         / z.transpose(2, 0, 1)[..., None]).astype(v.dtype)
    return o.reshape(B, Hq, d), z, _target(e, z)


def _attend_walk_bwd(q, k, v, sel, o, do):
    """The block's weights recomputed through HBM -> dq, dk, dv, target."""
    B, Hq, d = q.shape
    Hkv = k.shape[1]
    heads = (B, Hkv, Hq // Hkv, d)
    qg = q.reshape(heads)
    e, z = _weights(qg, k, sel)
    zt = z.transpose(2, 0, 1)[..., None]                         # (B, h, g, 1)
    dog = do.reshape(heads).astype(jnp.float32)
    # o = (e v) / z:  de = (do . v - do . o) / z,  ds = e de / sqrt(d)
    dov = (dog / zt).astype(v.dtype)
    shift = (jnp.sum(dog * o.reshape(heads).astype(jnp.float32), -1,
                     keepdims=True) / zt).transpose(1, 2, 0, 3)
    ds = (e.astype(jnp.float32) * d ** -0.5
          * (jnp.einsum("qhgd,khd->hgqk", dov, v,
                        preferred_element_type=jnp.float32)
             - shift)).astype(k.dtype)
    dv = jnp.einsum("hgqk,qhgd->khd", e, dov)
    dq = jnp.einsum("hgqk,khd->qhgd", ds, k).reshape(q.shape)
    dk = jnp.einsum("hgqk,qhgd->khd", ds, qg)
    return dq, dk, dv, _target(e, z)


def _head_rows(x, Hkv):
    """(B, Hq, d) -> (Hkv, g B, d), the kernels' layout: a key-value head's
    query heads as one block of rows."""
    B, Hq, d = x.shape
    return x.reshape(B, Hkv, Hq // Hkv, d).transpose(1, 2, 0, 3).reshape(
        Hkv, Hq // Hkv * B, d)


def _head_cols(x, B):
    """:func:`_head_rows` back: (Hkv, g B, d) -> (B, Hq, d)."""
    Hkv, R, d = x.shape
    return x.reshape(Hkv, R // B, B, d).transpose(2, 0, 1, 3).reshape(
        B, Hkv * (R // B), d)


def _heads_of(x, d):
    """The kernels' (keys, Hkv d) view of keys or values -> (keys, Hkv, d)."""
    return x.reshape(x.shape[0], x.shape[1] // d, d)


def _attend_kernel(interpret, tile, walked, q, k, v, kmax, sel, t):
    """:func:`_attend_walk` through ``sparse_attn_pallas_fwd``: k / v in the
    kernels' view (Sk, Hkv d), ``kmax`` (Hkv,) the largest key norm a head;
    in place of z the rows' log-sums (shift included), which is what its
    backward reads."""
    from .pallas_attention import sparse_attn_fwd

    B, Hq, d = q.shape
    Hkv = k.shape[1] // d
    o, lse, target = sparse_attn_fwd(
        _head_rows(q, Hkv), k, v, sel.astype(jnp.int8), kmax, t[-1],
        tile=tile, walked=walked, interpret=interpret)
    return _head_cols(o, B), lse.reshape(Hkv, Hq // Hkv, B), target


def _attend_kernel_bwd(interpret, tile, walked, q, k, v, sel, t, lse, o, do):
    from .pallas_attention import sparse_attn_bwd

    B, Hq, d = q.shape
    Hkv = k.shape[1] // d
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), -1)
    dq, dk, dv, target = sparse_attn_bwd(
        _head_rows(q, Hkv), k, v, sel.astype(jnp.int8), lse.reshape(Hkv, -1),
        delta.reshape(B, Hkv, Hq // Hkv).transpose(1, 2, 0).reshape(Hkv, -1),
        _head_rows(do, Hkv), t[-1], tile=tile, walked=walked,
        interpret=interpret)
    return _head_cols(dq, B), dk, dv, target


def _walk_in_kernel_view(q, k, v, kmax, sel, t):
    d = q.shape[-1]
    return _attend_walk(q, _heads_of(k, d), _heads_of(v, d), sel)


def _walk_bwd_in_kernel_view(q, k, v, sel, t, stat, o, do):
    d = q.shape[-1]
    dq, dk, dv, target = _attend_walk_bwd(q, _heads_of(k, d), _heads_of(v, d),
                                          sel, o, do)
    return dq, dk.reshape(k.shape), dv.reshape(v.shape), target


def _kernel_or_walk(kernel, kernel_fn, walk_fn, *args):
    """``kernel = (mode, *what the kernels are priced by)``, the selected-key
    pair's ``(mode, tile, walked)``: the kernel pair interpreted where
    ``mode`` says so, else whatever the step is lowered for decides: Mosaic
    on a TPU, the XLA walk anywhere else."""
    mode, *priced = kernel
    if mode == "interpret":
        return kernel_fn(True, *priced, *args)
    return lax.platform_dependent(
        *args, tpu=functools.partial(kernel_fn, False, *priced),
        default=walk_fn)


def _block_forward(topk, emit_width, kernel, index_scores, k, v, ik, kmax,
                   blk):
    q, iq, iw, t = blk
    with jax.named_scope("sparse_attention.indexer"):
        scores = index_scores(iq, ik, iw)
    with jax.named_scope("sparse_attention.select"):
        sel, tau, causal = _select(scores, t, topk)
    with jax.named_scope("sparse_attention.attend"):
        if kernel is None:
            o, stat, target = _attend_walk(q, k, v, sel)
        else:
            o, stat, target = _kernel_or_walk(
                kernel, _attend_kernel, _walk_in_kernel_view,
                q, k, v, kmax, sel, t)
    with jax.named_scope("sparse_attention.indexer"):
        logp = jax.nn.log_softmax(jnp.where(sel, scores, -jnp.inf), axis=-1)
        kl = jnp.sum(jnp.where(
            target > 0,
            target * (jnp.log(jnp.where(target > 0, target, 1.0))
                      - jnp.where(sel, logp, 0.0)), 0.0))
    out = (o, kl, jnp.sum(sel, dtype=jnp.int32),
           jnp.sum(causal, dtype=jnp.int32))
    if emit_width:
        out += (_pack_bits(sel, emit_width),)
    return out, (tau, None if kernel is None else stat)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _attend_block(topk, emit_width, kernel, index_scores, k, v, ik, kmax,
                  blk):
    """One block of queries against the keys ``[0, Sk)``.  ``blk``: q (B,
    Hq, d), iq (B, HI, dI), iw (B, HI), t (B,) the queries' positions.
    -> (o (B, Hq, d), kl, selected, causal[, packed selection]).

    The backward pass is written out: the block's thresholds are saved and
    its scores and weights recomputed, so nothing of (heads, B, Sk) outlives
    the block.  ``kernel`` None: the XLA walk, every array of that size held
    in the compute type (the float32 scores and their gradient exist only
    inside the fusions that make them).  ``kernel = (mode, tile, walked)``:
    ``ops/pallas_attention.py``'s pair, in which no such array leaves VMEM;
    k and v then come in the kernels' view (Sk, Hkv d), relaid once a layer
    and not once a block, with ``kmax`` (Hkv,) the largest key norm a head
    (None on the walk, which finds it itself); each row's log-sum is saved
    besides.  ``mode`` ``"interpret"`` runs the pair interpreted anywhere;
    ``"auto"`` runs it where the step is lowered for a TPU and the walk
    everywhere else."""
    return _block_forward(topk, emit_width, kernel, index_scores, k, v, ik,
                          kmax, blk)[0]


def _attend_block_fwd(topk, emit_width, kernel, index_scores, k, v, ik, kmax,
                      blk):
    out, (tau, stat) = _block_forward(topk, emit_width, kernel, index_scores,
                                      k, v, ik, kmax, blk)
    return out, (k, v, ik, kmax, blk, tau, stat, out[0])


def _attend_block_bwd(topk, emit_width, kernel, index_scores, res, cts):
    k, v, ik, kmax, (q, iq, iw, t), tau, stat, o = res
    do, dkl = cts[0], cts[1]
    with jax.named_scope("sparse_attention.indexer"):
        scores, index_vjp = index_scores(iq, ik, iw, with_vjp=True)
    with jax.named_scope("sparse_attention.select"):
        sel, _, _ = _select(scores, t, topk, tau)
    with jax.named_scope("sparse_attention.attend"):
        if kernel is None:
            dq, dk, dv, target = _attend_walk_bwd(q, k, v, sel, o, do)
        else:
            dq, dk, dv, target = _kernel_or_walk(
                kernel, _attend_kernel_bwd, _walk_bwd_in_kernel_view,
                q, k, v, sel, t, stat, o, do)
    with jax.named_scope("sparse_attention.indexer"):
        # d KL(target || softmax over S_t of I) / dI = softmax - target
        p = jax.nn.softmax(jnp.where(sel, scores, -jnp.inf), axis=-1)
        diq, dik, diw = index_vjp(dkl * jnp.where(sel, p - target, 0.0))
    return (dk, dv, dik, None if kmax is None else jnp.zeros_like(kmax),
            (dq, diq, diw, None))


_attend_block.defvjp(_attend_block_fwd, _attend_block_bwd)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _attend_span(topk, emit_width, kernel, index_scores, k, v, ik, kmax,
                 blocks):
    """A span's blocks, one after the other.  Jitted so that a model's layers
    share the trace, its linearisation and its lowering of each span's shape:
    they are paid on every run.  ``index_scores`` (:func:`_index_scores`)
    rides in the static arguments, so that a cached span is never another
    scoring function's."""
    return lax.map(functools.partial(_attend_block, topk, emit_width, kernel,
                                     index_scores, k, v, ik, kmax), blocks)


@register("IndexerSparseAttention")
def indexer_sparse_attention(query, key, value, index_query, index_key,
                             index_weight, *, topk, block=256, span=2048,
                             emit_selection=False):
    """Causal attention of one sequence over the keys an indexer selects.

    ``query`` (S, Hq, d); ``key`` / ``value`` (S, Hkv, d), ``Hq % Hkv == 0``;
    ``index_query`` (S, HI, dI), ``index_key`` (S, dI), ``index_weight``
    (S, HI).  For query t and key s <= t the index score is ``I[t, s] =
    sum_j w[t, j] relu(qI[t, j] . kI[s]) / sqrt(HI dI)`` in float32;
    ``tau[t]`` is the ``topk``-th largest of ``I[t, :t+1]`` (none while
    ``t < topk``), ``S_t = {s <= t : I[t, s] >= tau[t]}``, and the output is
    softmax attention over ``S_t`` (scale ``d^-1/2``).  The selection carries
    no gradient.  The softmax is shifted by ``|q| max_s |k_s| d^-1/2`` and not
    by the row's maximum: the same result while that bound stays under about
    40 (per-head-normalised queries and keys of 128 dims read 11 to 17), after
    which ``exp`` underflows.

    -> out (S, Hq, d); kl (): ``sum_t KL(sg(mean over heads of the attention
    probabilities) || softmax over S_t of I[t, .])``, whose gradient reaches
    only the index inputs; selected (): ``sum_t |S_t|``; causal (): ``S (S +
    1) / 2``; with ``emit_selection`` also bits (S, S / 32) uint32, bit
    ``s % 32`` of word ``s // 32`` of row t set where ``s in S_t``.

    ``block`` queries are scored at a time against the keys up to the end
    of their ``span`` of queries (tiles: they change no result).  Each
    block's thresholds are saved for the backward pass, which recomputes
    the block's scores but not its selection.

    Which path attends: where the step is lowered for a TPU and the shapes
    are the kernels' (``pallas_attention.sparse_attn_supported``: head size a
    multiple of 128, the block whole sublane tiles, the span whole key tiles,
    float32 or bfloat16), each block runs ``sparse_attn_pallas_fwd`` / ``_bwd``
    and no (heads, block, keys) array reaches HBM; everywhere else (a CPU,
    toy shapes) the XLA walk, which is also the tests' oracle.  Indexer and
    selection are XLA's on both.
    """
    return _sparse_attention(query, key, value, index_query, index_key,
                             index_weight, topk, block, span, emit_selection,
                             "auto")


def _sparse_attention(query, key, value, index_query, index_key,
                      index_weight, topk, block, span, emit_selection, mode):
    """``mode`` ``"auto"`` (the operator's), ``"interpret"`` (the kernel pair
    interpreted, for tests on a CPU: shapes it cannot take still walk) or
    ``"xla"`` (the walk alone)."""
    from .pallas_attention import (sparse_attn_supported, sparse_attn_tile,
                                   sparse_attn_walked)

    S, Hq, d = query.shape
    span = min(span, S)
    block = min(block, span)
    if S % span or span % block or (emit_selection and span % 32):
        raise ValueError("sequence %d, span %d and block %d must divide "
                         "(and the span by 32 to emit the selection)"
                         % (S, span, block))
    takes = mode != "xla" and sparse_attn_supported(
        block, Hq, key.shape[1], d, span, query.dtype)
    if takes:
        # once a layer, not once a block: each head's key norms, and keys
        # and values in the kernels' view (on the chip that reshape moves
        # the heads from the sublanes to the lanes of a tiled array: a copy)
        norms = lax.stop_gradient(jnp.sqrt(jnp.sum(jnp.square(
            key.astype(jnp.float32)), -1)))                        # (S, Hkv)
        key, value = (a.reshape(S, -1) for a in (key, value))
    t_all = jnp.arange(S, dtype=jnp.int32)
    outs = []
    for end in range(span, S + 1, span):
        rows = slice(end - span, end)
        kernel = (mode, sparse_attn_tile(span),
                  sparse_attn_walked(end, span, block)) if takes else None
        outs.append(_attend_span(
            topk, S if emit_selection else 0, kernel, _index_scores,
            key[:end], value[:end], index_key[:end],
            jnp.max(norms[:end], 0) if takes else None,
            tuple(a[rows].reshape((span // block, block) + a.shape[1:])
                  for a in (query, index_query, index_weight, t_all))))
    o, kl, selected, causal = (jnp.concatenate([r[i] for r in outs])
                               for i in range(4))
    res = (o.reshape(query.shape), jnp.sum(kl), jnp.sum(selected),
           jnp.sum(causal))
    if emit_selection:
        res += (jnp.concatenate([r[4] for r in outs]).reshape(S, S // 32),)
    return res


# -- dense causal attention and the latent projections around it ---------------
def _causal_block(k, v, q, t):
    """One block of one document's queries against its keys ``[0, Sk)``: q
    (B, Hq, d) at positions t (B,), k (Sk, Hkv, d), v (Sk, Hkv, dv).
    -> o (B, Hq, dv).  :func:`_attend_block` with every causal key selected."""
    B, Hq, d = q.shape
    Hkv = k.shape[1]
    causal = jnp.arange(k.shape[0])[None, :] <= t[:, None]
    e, z = _weights(q.reshape(B, Hkv, Hq // Hkv, d), k, causal)
    o = (jnp.einsum("hgqk,khd->qhgd", e, v,
                    preferred_element_type=jnp.float32)
         / z.transpose(2, 0, 1)[..., None]).astype(v.dtype)
    return o.reshape(B, Hq, v.shape[-1])


def _causal_block_bwd(k, v, q, t, o, do):
    """The block's weights recomputed -> (dq (B, Hq, d), dk, dv in float32:
    the caller sums them over the blocks).  What it holds of (heads, B, Sk)
    is in the compute type."""
    B, Hq, d = q.shape
    Hkv = k.shape[1]
    qg = q.reshape(B, Hkv, Hq // Hkv, d)
    heads = (B, Hkv, Hq // Hkv, v.shape[-1])
    causal = jnp.arange(k.shape[0])[None, :] <= t[:, None]
    e, z = _weights(qg, k, causal)
    zt = z.transpose(2, 0, 1)[..., None]                         # (B, h, g, 1)
    dog = do.reshape(heads).astype(jnp.float32)
    # o = (e v) / z:  de = (do . v - do . o) / z,  ds = e de / sqrt(d)
    dov = (dog / zt).astype(v.dtype)
    shift = (jnp.sum(dog * o.reshape(heads).astype(jnp.float32), -1,
                     keepdims=True) / zt).transpose(1, 2, 0, 3)
    ds = (e.astype(jnp.float32) * d ** -0.5
          * (jnp.einsum("qhgd,khd->hgqk", dov, v,
                        preferred_element_type=jnp.float32)
             - shift)).astype(k.dtype)
    dv = jnp.einsum("hgqk,qhgd->khd", e, dov,
                    preferred_element_type=jnp.float32)
    dq = jnp.einsum("hgqk,khd->qhgd", ds, k).reshape(q.shape)
    dk = jnp.einsum("hgqk,qhgd->khd", ds, qg,
                    preferred_element_type=jnp.float32)
    return dq, dk, dv


def _tiles(S, block, span):
    span = min(span, S)
    block = min(block, span)
    if S % span or span % block:
        raise ValueError("sequence %d, span %d and block %d must divide"
                         % (S, span, block))
    return block, span


def _blocks(x, rows, block):
    """(N, S, ..) -> the rows' blocks first: (blocks, N, block, ..)."""
    x = x[:, rows]
    return x.reshape((x.shape[0], -1, block) + x.shape[2:]).swapaxes(0, 1)


def _causal_forward(q, k, v, block, span):
    """q, k (N, S, H., d), v (N, S, Hkv, dv) -> o (N, S, Hq, dv): ``block``
    queries of every document at a time against the keys up to the end of
    their ``span`` of queries."""
    N, S = q.shape[:2]
    block, span = _tiles(S, block, span)
    one_block = jax.vmap(_causal_block, in_axes=(0, 0, 0, None))
    t_all = jnp.arange(S, dtype=jnp.int32)
    outs = []
    for end in range(span, S + 1, span):
        rows = slice(end - span, end)
        outs.append(lax.map(
            lambda blk, end=end: one_block(k[:, :end], v[:, :end], *blk),
            (_blocks(q, rows, block), t_all[rows].reshape(-1, block))))
    o = jnp.concatenate(outs)                        # (S / block, N, block, ..)
    return o.swapaxes(0, 1).reshape((N, S) + o.shape[3:])


def _causal_backward(q, k, v, o, do, block, span):
    """-> (dq, dk, dv): every block's weights recomputed from q, k, v, the
    keys' and values' gradients summed over the blocks in float32."""
    N, S = q.shape[:2]
    block, span = _tiles(S, block, span)
    one_block = jax.vmap(_causal_block_bwd, in_axes=(0, 0, 0, None, 0, 0))
    t_all = jnp.arange(S, dtype=jnp.int32)
    dk = jnp.zeros(k.shape, jnp.float32)
    dv = jnp.zeros(v.shape, jnp.float32)
    dqs = []
    for end in range(span, S + 1, span):
        rows = slice(end - span, end)

        def body(acc, blk, end=end):
            dq_b, dk_b, dv_b = one_block(k[:, :end], v[:, :end], *blk)
            return (acc[0] + dk_b, acc[1] + dv_b), dq_b

        (dk_s, dv_s), dq = lax.scan(
            body, (jnp.zeros_like(dk[:, :end]), jnp.zeros_like(dv[:, :end])),
            (_blocks(q, rows, block), t_all[rows].reshape(-1, block),
             _blocks(o, rows, block), _blocks(do, rows, block)))
        dk = dk.at[:, :end].add(dk_s)
        dv = dv.at[:, :end].add(dv_s)
        dqs.append(dq)
    dq = jnp.concatenate(dqs).swapaxes(0, 1).reshape(q.shape)
    return dq, dk.astype(k.dtype), dv.astype(v.dtype)


def _heads_major(x):
    """(N, S, H, d) <-> (N, H, S, d), the causal kernels' layout."""
    return x.transpose(0, 2, 1, 3)


def _causal_kernel(interpret, q, k, v):
    """:func:`_causal_forward` through ``causal_attn_pallas_fwd`` -> (o, lse
    (N, Hq, S) float32, the rows' log-sums with their shift, which is what
    its backward reads)."""
    from .pallas_attention import causal_attn_fwd

    kmax = jnp.max(jnp.sqrt(jnp.sum(jnp.square(k.astype(jnp.float32)), -1)),
                   axis=1)                                       # (N, Hkv)
    o, lse = causal_attn_fwd(_heads_major(q), _heads_major(k),
                             _heads_major(v), kmax, interpret=interpret)
    return _heads_major(o), lse


def _causal_kernel_bwd(interpret, q, k, v, o, lse, do):
    from .pallas_attention import causal_attn_bwd

    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), -1)
    return tuple(_heads_major(g) for g in causal_attn_bwd(
        _heads_major(q), _heads_major(k), _heads_major(v), lse,
        delta.transpose(0, 2, 1), _heads_major(do), interpret=interpret))


def _causal_takes(q, k, v, mode):
    from .pallas_attention import causal_attn_supported

    return mode != "xla" and causal_attn_supported(
        q.shape[1], q.shape[2], k.shape[2], q.shape[3], v.shape[3], q.dtype)


def _causal_attend(q, k, v, block, span, mode):
    """Dense causal attention on one of two paths -> (o, lse).  At shapes
    ``pallas_attention.causal_attn_supported`` takes: the kernel pair where
    the step is lowered for a TPU (``mode`` ``"auto"``, the operators') or
    interpreted anywhere (``"interpret"``, the CPU tests'), with each row's
    log-sum for the backward.  Everywhere else, and under ``"xla"``, the walk
    (:func:`_causal_forward`), whose backward recomputes its own sums: its
    ``lse`` is a placeholder of the kernels' shape, or None."""
    if not _causal_takes(q, k, v, mode):
        return _causal_forward(q, k, v, block, span), None
    return _causal_either(_causal_forward, block, span, mode, q, k, v)


def _causal_attend_bwd(q, k, v, o, lse, do, block, span, mode):
    """-> (dq, dk, dv) on the path :func:`_causal_attend` took."""
    if not _causal_takes(q, k, v, mode):
        return _causal_backward(q, k, v, o, do, block, span)
    return _causal_either_bwd(_causal_backward, block, span, mode,
                              q, k, v, o, lse, do)


# Jitted so that a model's layers share one trace of both branches (the walk
# is traced, and dropped, wherever the step is lowered for a TPU) and one
# lowering: trace + lower are paid on every run.  The walk rides in the
# static arguments, so that a cached trace is never another walk's.
@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _causal_either(walk, block, span, mode, q, k, v):
    return _kernel_or_walk(
        (mode,), _causal_kernel,
        lambda q, k, v: (
            walk(q, k, v, block, span),
            jnp.zeros((q.shape[0], q.shape[2], q.shape[1]), jnp.float32)),
        q, k, v)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _causal_either_bwd(walk_bwd, block, span, mode, q, k, v, o, lse, do):
    return _kernel_or_walk(
        (mode,), _causal_kernel_bwd,
        lambda q, k, v, o, lse, do: walk_bwd(q, k, v, o, do, block, span),
        q, k, v, o, lse, do)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _causal_attention(q, k, v, block, span, mode):
    return _causal_attend(q, k, v, block, span, mode)[0]


def _causal_attention_fwd(q, k, v, block, span, mode):
    o, lse = _causal_attend(q, k, v, block, span, mode)
    return o, (q, k, v, o, lse)


def _causal_attention_bwd(block, span, mode, res, do):
    return _causal_attend_bwd(*res, do, block, span, mode)


_causal_attention.defvjp(_causal_attention_fwd, _causal_attention_bwd)


@register("CausalAttention")
def causal_attention(query, key, value, *, block=256, span=2048):
    """Dense causal softmax attention, each document alone.

    ``query`` (N, S, Hq, d), ``key`` (N, S, Hkv, d), ``value`` (N, S, Hkv,
    dv), ``Hq % Hkv == 0``, ``dv`` free of ``d``; or one document without
    the leading axis.  Query t of document n reads that document's keys
    ``s <= t`` (scale ``d^-1/2``) and no other document's.  -> (N, S, Hq, dv).

    Which path attends: where the step is lowered for a TPU and
    ``pallas_attention.causal_attn_supported`` holds (float32 or bfloat16,
    ``dv`` a multiple of 128, ``d`` of 64, ``S`` of 128, one head's float32
    ``dq`` within the kernels' VMEM), the pair ``causal_attn_pallas_fwd`` /
    ``_bwd`` (``ops/pallas_attention.py``).  They choose their own tiles
    (``block`` and ``span`` are the walk's), skip the key tiles above a
    block's last query, form the mask from positions in VMEM, and keep every
    block's weights and the float32 sums of ``dk``, ``dv`` and ``dq`` in
    VMEM: q, k, v, o, do, dq, dk, dv and each row's log-sum (N, Hq, S)
    float32 are all that crosses.  Everywhere else (a CPU, toy shapes,
    float16) the XLA walk, which is also the pair's oracle in the tests:
    ``block`` queries of every document at a time against the keys up to the
    end of their ``span`` of queries (tiles: they change no result), the
    block's weights in the compute type through HBM, ``dk`` / ``dv`` summed
    over the blocks in float32.  No (heads, S, S) array exists on either.
    Both shift the softmax by ``|q| max_s |k_s| d^-1/2`` like
    ``IndexerSparseAttention``: the same result while that bound stays under
    about 40; both round the weights to the compute type before the value
    product and ``dk`` / ``dv`` once at the end.

    Two blocks stand on it: ``nn.SelfAttention`` calls this operator after
    its projections and rotary embedding (its vjp keeps q, k, v, o and the
    rows' log-sums), and ``LatentAttention`` runs the same forward and
    backward (:func:`_causal_attend` / :func:`_causal_attend_bwd`) inside its
    own vjp, which keeps only the layer's input, o and the log-sums.
    """
    if query.ndim == 3:
        return _causal_attention(query[None], key[None], value[None],
                                 block, span, "auto")[0]
    return _causal_attention(query, key, value, block, span, "auto")


def _latent_project(sizes, data, positions, q_weight, kv_a_weight,
                    kv_norm_gamma, kv_b_weight):
    """-> q, k (N, S, H, nope + rope), v (N, S, H, v_dim)."""
    H, nope, rope, v_dim, theta, latent_eps = sizes
    N, S, _ = data.shape
    rotary = lambda x: rotary_embedding(x, positions, theta=theta)  # noqa: E731
    q = jnp.einsum("nsd,od->nso", data, q_weight).reshape(N, S, H, nope + rope)
    ckr = jnp.einsum("nsd,od->nso", data, kv_a_weight)
    latent = kv_b_weight.shape[1]
    c = rms_norm(ckr[..., :latent], kv_norm_gamma, eps=latent_eps)
    kr = rotary(ckr[..., latent:].reshape(N, S, 1, rope))
    kv = jnp.einsum("nsc,oc->nso", c, kv_b_weight).reshape(
        N, S, H, nope + v_dim)
    q = jnp.concatenate([q[..., :nope], rotary(q[..., nope:])], -1)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(kr, (N, S, H, rope))], -1)
    return q, k, kv[..., nope:]


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _latent_attention(sizes, block, span, mode, data, positions, *weights):
    return _latent_attention_fwd(sizes, block, span, mode, data, positions,
                                 *weights)[0]


def _latent_attention_fwd(sizes, block, span, mode, data, positions,
                          *weights):
    with jax.named_scope("latent_attention.project"):
        q, k, v = _latent_project(sizes, data, positions, *weights)
    with jax.named_scope("latent_attention.attend"):
        o, lse = _causal_attend(q, k, v, block, span, mode)
    return o, (data, positions, weights, o, lse)


def _latent_attention_bwd(sizes, block, span, mode, res, do):
    data, positions, weights, o, lse = res
    with jax.named_scope("latent_attention.project"):
        (q, k, v), project_vjp = jax.vjp(
            lambda data, *w: _latent_project(sizes, data, positions, *w),
            data, *weights)
    with jax.named_scope("latent_attention.attend"):
        cts = _causal_attend_bwd(q, k, v, o, lse, do, block, span, mode)
    with jax.named_scope("latent_attention.project"):
        d_data, *d_weights = project_vjp(cts)
    return (d_data, None) + tuple(d_weights)


_latent_attention.defvjp(_latent_attention_fwd, _latent_attention_bwd)


@register("LatentAttention")
def latent_attention(data, positions, q_weight, kv_a_weight, kv_norm_gamma,
                     kv_b_weight, *, num_heads, qk_nope_dim, qk_rope_dim,
                     v_dim, theta=10000.0, latent_eps=1e-6, block=256,
                     span=2048):
    """Multi-head latent attention (DeepSeek-V2), training form, without a
    query latent.

    ``data`` (N, S, D) the normed input of N documents (or (S, D), one);
    ``positions`` (S,).  ``q = data Wq`` gives ``num_heads`` heads of
    ``qk_nope_dim + qk_rope_dim``; ``[c | kr] = data Wkv_a`` a latent ``c``
    (``Wkv_b``'s input width) and ONE rotary key ``kr`` of ``qk_rope_dim``
    for all heads; ``[k_nope | v] = RMSNorm(c; kv_norm_gamma, latent_eps)
    Wkv_b`` gives each head ``qk_nope_dim + v_dim``.  Rotary embedding
    (half-rotation form) on each head's last ``qk_rope_dim`` query dims and
    on ``kr``; a head's key is ``[k_nope | rope(kr)]``.  Dense causal
    attention (``CausalAttention``: scale ``(qk_nope_dim + qk_rope_dim)^-1/2``)
    -> (N, S, num_heads x v_dim), before the output projection.

    The forward pass keeps its input, its output and each row's log-sum
    ((N, heads, S) float32; a placeholder where the walk ran): the backward
    pass rebuilds queries, keys and values from the input (the latent's
    point: it is what is cheap to hold) and recomputes each block's weights.
    Attention itself runs on ``CausalAttention``'s two paths: the Pallas pair
    where the step is lowered for a TPU (its 128 + 64-wide scores are one
    192-wide contraction in the kernels), the XLA walk elsewhere."""
    sizes = (num_heads, qk_nope_dim, qk_rope_dim, v_dim, theta, latent_eps)
    one = data.ndim == 2
    o = _latent_attention(sizes, block, span, "auto",
                          data[None] if one else data, positions, q_weight,
                          kv_a_weight, kv_norm_gamma, kv_b_weight)
    o = o.reshape(o.shape[:2] + (num_heads * v_dim,))
    return o[0] if one else o


@register("LMHeadLogProb")
def lm_head_log_prob(data, weight, labels, *, block=2048):
    """Log-probability of each position's label under the head's softmax:
    ``log_softmax(data W^T)[label]`` in float32, 0 where the label is
    negative (no label).  ``data`` (.., D), ``weight`` (V, D), ``labels`` (..)
    integer.  -> (..) float32.  The rows are walked ``block`` at a time and
    each block's logits recomputed in the backward pass: no (rows, V) array
    outlives a block (at 16 384 rows of 20 480 the float32 logits and their
    gradient are 1.3 GB each)."""
    D = data.shape[-1]
    flat, lab = data.reshape(-1, D), labels.reshape(-1)
    T = flat.shape[0]
    block = min(block, T)
    if T % block:
        raise ValueError("%d rows do not divide into blocks of %d"
                         % (T, block))

    @jax.checkpoint
    def rows(blk):
        x, y = blk
        logits = jnp.einsum("td,vd->tv", x, weight,
                            preferred_element_type=jnp.float32)
        picked = jnp.take_along_axis(logits, jnp.maximum(y, 0)[:, None], 1)
        return jnp.where(y >= 0, picked[:, 0]
                         - jax.nn.logsumexp(logits, axis=-1), 0.0)

    out = lax.map(rows, (flat.reshape(-1, block, D), lab.reshape(-1, block)))
    return out.reshape(labels.shape)


@register("MoEExperts")
def moe_experts(data, router_weight, gate_weight, up_weight, down_weight,
                router_bias=None, shared_gate_weight=None,
                shared_up_weight=None, shared_down_weight=None, *, top_k,
                first_expert=0, norm_topk_prob=True, capacity=None,
                scoring="softmax", routed_scale=1.0, sequences=0):
    """The held experts' part of a top-k mixture-of-experts layer
    (``parallel.moe.moe_layer``).  ``data`` (T, D), ``router_weight`` (E, D)
    over all E experts, ``gate_weight`` / ``up_weight`` (H, D, F) and
    ``down_weight`` (H, F, D) the H experts held, from ``first_expert``.
    ``scoring`` ``"sigmoid"``, ``router_bias`` (E,) (chooses, never gates)
    and ``routed_scale`` are the router's other form (``parallel.moe.route``);
    ``shared_*_weight`` ((Fs, D), (Fs, D), (D, Fs)) a gated feed-forward every
    token passes, added to the result; ``sequences`` > 0: the tokens are that
    many documents of equal length and the balance term is sequence-wise.
    -> out (T, D), balance (), pairs (H,), dropped (), choice (T, top_k) and,
    given ``router_bias``, router_pairs (E,) int32: the pairs these tokens
    sent to every expert of the layer, which the bias update reads, and gates
    (T, top_k) float32: the chosen experts' gates (that no bias entered them
    is checked on these)."""
    from ..parallel.moe import moe_layer

    shared = None
    if shared_gate_weight is not None:
        shared = (shared_gate_weight, shared_up_weight, shared_down_weight)
    y, aux = moe_layer(data, router_weight, gate_weight, up_weight,
                       down_weight, top_k=top_k, first_expert=first_expert,
                       normalize=norm_topk_prob, capacity=capacity,
                       scoring=scoring, router_bias=router_bias,
                       routed_scale=routed_scale, shared=shared,
                       sequences=sequences or None)
    out = (y, aux["balance"], aux["pairs"], aux["dropped"], aux["choice"])
    if router_bias is not None:
        out += (aux["router_pairs"].astype(jnp.int32),
                lax.stop_gradient(aux["gates"]))
    return out
