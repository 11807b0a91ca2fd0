"""Transformer layers over ``ops/transformer.py``: RMSNorm, rotary embedding
with sections, the gated feed-forward, a plain causal self-attention block,
an attention block whose keys an indexer selects, a latent (compressed
key-value) attention block, and a mixture-of-experts block that holds a share
of its layer's experts.  Token-major: activations are ``(S, units)``, one
sequence, or ``(N, S, units)``, N documents that never see each other
(``SelfAttention``, ``LatentAttention``, ``SparseMoE``).
"""
from __future__ import annotations

import math

import jax

from ... import autograd
from ..block import HybridBlock, remat
from .basic_layers import Dense, LayerNorm

__all__ = ["RMSNorm", "RotaryEmbedding", "GatedFFN", "SelfAttention",
           "IndexerSparseAttention", "LatentAttention", "SparseMoE"]


class RMSNorm(HybridBlock):
    """``x / sqrt(mean(x^2) + epsilon) * gamma`` over the last axis."""

    def __init__(self, in_channels, epsilon=1e-6, gamma_initializer="ones",
                 prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._epsilon = epsilon
        with self.name_scope():
            self.gamma = self.params.get("gamma", shape=(in_channels,),
                                         init=gamma_initializer)

    def hybrid_forward(self, F, x, gamma):
        return F.RMSNorm(x, gamma, eps=self._epsilon)


class RotaryEmbedding(HybridBlock):
    """Rotary position embedding of ``(S, heads, d)`` by ``positions``: (S,)
    ids, or (len(sections), S) with ``sections`` frequency pairs each
    (M-RoPE)."""

    def __init__(self, theta=10000.0, sections=(), prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._theta = theta
        self._sections = tuple(sections)

    def hybrid_forward(self, F, x, positions):
        return F.RotaryEmbedding(x, positions, theta=self._theta,
                                 sections=self._sections)


class GatedFFN(HybridBlock):
    """SwiGLU feed-forward ``(silu(x Wg^T) * (x Wu^T)) Wd^T``."""

    def __init__(self, units, hidden_units, weight_initializer=None,
                 prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        with self.name_scope():
            for name, shape in (("gate", (hidden_units, units)),
                                ("up", (hidden_units, units)),
                                ("down", (units, hidden_units))):
                setattr(self, name + "_weight", self.params.get(
                    name + "_weight", shape=shape, init=weight_initializer))

    def hybrid_forward(self, F, x, gate_weight, up_weight, down_weight):
        return F.GatedFFN(x, gate_weight, up_weight, down_weight)


def _proj(units, in_units, init, prefix):
    return Dense(units, use_bias=False, flatten=False, in_units=in_units,
                 weight_initializer=init, prefix=prefix)


class SelfAttention(HybridBlock):
    """Plain multi-head causal self-attention: query, key, value and output
    projections without bias, rotary embedding over the whole head, dense
    causal attention within each document (``ops.CausalAttention``: blocks of
    queries, no (heads, S, S) array in either pass).  ``num_kv_heads`` <
    ``num_heads`` shares each key-value head among a group of query heads.
    ``forward(a, positions)``: ``a`` (N, S, units) the normed input of N
    documents (or (S, units)), ``positions`` (S,).  -> the shape of ``a``."""

    def __init__(self, units, num_heads, num_kv_heads, head_dim,
                 theta=10000.0, block=256, span=2048, weight_initializer=None,
                 prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._heads = (num_heads, num_kv_heads, head_dim)
        self._op = {"block": block, "span": span}
        init = weight_initializer
        with self.name_scope():
            self.q = _proj(num_heads * head_dim, units, init, "q_")
            self.k = _proj(num_kv_heads * head_dim, units, init, "k_")
            self.v = _proj(num_kv_heads * head_dim, units, init, "v_")
            self.o = _proj(units, num_heads * head_dim, init, "o_")
            self.rope = RotaryEmbedding(theta, prefix="rope_")

    def hybrid_forward(self, F, a, positions):
        nq, nkv, d = self._heads
        lead = a.shape[:-1]
        with jax.named_scope("self_attention.project"):
            q = self.rope(self.q(a).reshape(lead + (nq, d)), positions)
            k = self.rope(self.k(a).reshape(lead + (nkv, d)), positions)
            v = self.v(a).reshape(lead + (nkv, d))
        with jax.named_scope("self_attention.attend"):
            out = F.CausalAttention(q, k, v, **self._op)
        with jax.named_scope("self_attention.project"):
            return self.o(out.reshape(lead + (nq * d,)))


class IndexerSparseAttention(HybridBlock):
    """Grouped-query causal attention over the ``topk`` keys per query that
    a light indexer selects (DeepSeek sparse attention, training form).

    ``forward(a, positions, a_detached)``: ``a`` (S, units) the normed
    input, ``a_detached`` the same without gradient (the indexer's input).
    Queries and keys get a per-head RMSNorm and rotary embedding; the
    indexer has ``index_heads`` query heads of ``index_dim``, one key head
    under LayerNorm, and a weight per query head.  The projections are
    recomputed in the backward pass; the attention operator saves its own
    selection.  -> [out (S, units), kl, selected, causal] and, with
    ``emit_selection``, the packed selection (``ops.IndexerSparseAttention``).
    """

    def __init__(self, units, num_heads, num_kv_heads, head_dim, index_heads,
                 index_dim, topk, theta=10000.0, sections=(), epsilon=1e-6,
                 block=256, span=2048, emit_selection=False,
                 weight_initializer=None, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._heads = (num_heads, num_kv_heads, head_dim)
        self._index = (index_heads, index_dim)
        self._op = {"topk": topk, "block": block, "span": span,
                    "emit_selection": emit_selection}
        init = weight_initializer
        with self.name_scope():
            self.q = _proj(num_heads * head_dim, units, init, "q_")
            self.k = _proj(num_kv_heads * head_dim, units, init, "k_")
            self.v = _proj(num_kv_heads * head_dim, units, init, "v_")
            self.o = _proj(units, num_heads * head_dim, init, "o_")
            self.q_norm = RMSNorm(head_dim, epsilon, prefix="q_norm_")
            self.k_norm = RMSNorm(head_dim, epsilon, prefix="k_norm_")
            self.rope = RotaryEmbedding(theta, sections, prefix="rope_")
            self.idx_q = _proj(index_heads * index_dim, units, init, "idx_q_")
            self.idx_k = _proj(index_dim, units, init, "idx_k_")
            self.idx_w = _proj(index_heads, units, init, "idx_w_")
            self.idx_k_norm = LayerNorm(epsilon=epsilon, in_channels=index_dim,
                                        prefix="idx_k_norm_")
            # the indexer's heads are narrower: each section keeps its share
            ends = [sum(sections[:i + 1]) * index_dim // head_dim
                    for i in range(len(sections))]
            self.idx_rope = RotaryEmbedding(
                theta, tuple(e - b for b, e in zip([0] + ends, ends)),
                prefix="idx_rope_")

    def _project(self, a, positions, a_detached):
        nq, nkv, d = self._heads
        ni, di = self._index
        q = self.rope(self.q_norm(self.q(a).reshape((-1, nq, d))), positions)
        k = self.rope(self.k_norm(self.k(a).reshape((-1, nkv, d))), positions)
        v = self.v(a).reshape((-1, nkv, d))
        iq = self.idx_rope(self.idx_q(a_detached).reshape((-1, ni, di)),
                           positions)
        ik = self.idx_rope(self.idx_k_norm(self.idx_k(a_detached))
                           .reshape((-1, 1, di)), positions).reshape((-1, di))
        return q, k, v, iq, ik, self.idx_w(a_detached)

    def hybrid_forward(self, F, a, positions, a_detached):
        q, k, v, iq, ik, iw = remat(self._project)(a, positions, a_detached)
        out = F.IndexerSparseAttention(q, k, v, iq, ik, iw, **self._op)
        nq, _, d = self._heads
        return [self.o(out[0].reshape((-1, nq * d)))] + list(out[1:])


class LatentAttention(HybridBlock):
    """Multi-head latent attention (DeepSeek-V2), training form, no query
    latent: keys and values are rebuilt from a ``latent``-wide normed vector
    a token plus one rotary key of ``qk_rope_dim`` that all heads share;
    query and key heads are ``qk_nope_dim + qk_rope_dim`` wide, value heads
    ``v_dim``.  ``forward(a, positions)``: ``a`` (N, S, units) the normed
    input of N documents (or (S, units)), ``positions`` (S,).  Dense causal
    attention within each document, the output projection after it
    (``ops.LatentAttention``).  -> the same shape as ``a``."""

    def __init__(self, units, num_heads, latent, qk_nope_dim, qk_rope_dim,
                 v_dim, theta=10000.0, latent_epsilon=1e-6, block=256,
                 span=2048, weight_initializer=None, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._op = {"num_heads": num_heads, "qk_nope_dim": qk_nope_dim,
                    "qk_rope_dim": qk_rope_dim, "v_dim": v_dim,
                    "theta": theta, "latent_eps": latent_epsilon,
                    "block": block, "span": span}
        init = weight_initializer
        with self.name_scope():
            self.q_weight = self.params.get(
                "q_weight", init=init,
                shape=(num_heads * (qk_nope_dim + qk_rope_dim), units))
            self.kv_a_weight = self.params.get(
                "kv_a_weight", shape=(latent + qk_rope_dim, units), init=init)
            self.kv_norm_gamma = self.params.get(
                "kv_norm_gamma", shape=(latent,), init="ones")
            self.kv_b_weight = self.params.get(
                "kv_b_weight", init=init,
                shape=(num_heads * (qk_nope_dim + v_dim), latent))
            self.o = _proj(units, num_heads * v_dim, init, "o_")

    def hybrid_forward(self, F, a, positions, q_weight, kv_a_weight,
                       kv_norm_gamma, kv_b_weight):
        return self.o(F.LatentAttention(a, positions, q_weight, kv_a_weight,
                                        kv_norm_gamma, kv_b_weight,
                                        **self._op))


class SparseMoE(HybridBlock):
    """A top-k mixture-of-experts layer that holds ``num_held`` of its
    ``num_experts`` experts, from ``first_expert`` (expert parallelism: the
    other chips of the group hold the rest).  The router has its full
    width; the result is the held experts' part, gates normalised over all
    ``top_k`` chosen experts when ``norm_topk_prob``.  ``capacity_factor``
    sizes the buffer of held (token, expert) pairs as a multiple of the
    expected count (None: every pair, the worst case); pairs that do not
    fit are counted in ``dropped``, never silently lost.  The expert
    products are recomputed in the backward pass.

    The router's other form (``parallel.moe.route``): ``scoring="sigmoid"``,
    gates times ``routed_scale``, and with ``bias_update_rate`` a selection
    bias ``router_bias`` (E,) that chooses and never gates.  The bias is
    auxiliary state (``grad_req='null'``): under training the forward pass
    moves it by ``rate x sign(mean load - load)`` over the pairs this call's
    tokens sent to each of the layer's experts, and a functional train step
    carries it in ``state[2]``.  ``shared_units`` > 0 adds a gated
    feed-forward of that width every token passes.  ``sequence_balance``:
    the balance term is sequence-wise, over the N documents of an ``(N, S,
    units)`` input (one for ``(T, units)``).
    -> [out, balance, pairs (num_held,), dropped, choice (T, top_k)] and,
    with the bias, router_pairs (num_experts,) and gates (T, top_k)."""

    def __init__(self, units, hidden_units, num_experts, top_k, num_held=None,
                 first_expert=0, norm_topk_prob=True, capacity_factor=None,
                 scoring="softmax", routed_scale=1.0, bias_update_rate=None,
                 shared_units=0, sequence_balance=False,
                 weight_initializer=None, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        held = num_experts if num_held is None else num_held
        if not 0 <= first_expert <= num_experts - held:
            raise ValueError("experts %d..%d are not among the layer's %d"
                             % (first_expert, first_expert + held - 1,
                                num_experts))
        self._held_share = held * top_k / num_experts
        self._capacity_factor = capacity_factor
        self._bias_update_rate = bias_update_rate
        self._sequence_balance = sequence_balance
        self._op = {"top_k": top_k, "first_expert": first_expert,
                    "norm_topk_prob": norm_topk_prob, "scoring": scoring,
                    "routed_scale": routed_scale}
        init = weight_initializer
        with self.name_scope():
            self.router_weight = self.params.get(
                "router_weight", shape=(num_experts, units), init=init)
            self.gate_weight = self.params.get(
                "gate_weight", shape=(held, units, hidden_units), init=init)
            self.up_weight = self.params.get(
                "up_weight", shape=(held, units, hidden_units), init=init)
            self.down_weight = self.params.get(
                "down_weight", shape=(held, hidden_units, units), init=init)
            if bias_update_rate is not None:
                self.router_bias = self.params.get(
                    "router_bias", shape=(num_experts,), init="zeros",
                    grad_req="null", differentiable=False)
            if shared_units:
                for name, shape in (("gate", (shared_units, units)),
                                    ("up", (shared_units, units)),
                                    ("down", (units, shared_units))):
                    setattr(self, "shared_%s_weight" % name, self.params.get(
                        "shared_%s_weight" % name, shape=shape, init=init))

    def hybrid_forward(self, F, x, router_weight, gate_weight, up_weight,
                       down_weight, router_bias=None, shared_gate_weight=None,
                       shared_up_weight=None, shared_down_weight=None):
        shape = x.shape
        tokens = x.reshape((-1, shape[-1]))
        op = dict(self._op, capacity=None, sequences=0)
        if self._capacity_factor is not None:
            op["capacity"] = math.ceil(tokens.shape[0] * self._held_share
                                       * self._capacity_factor)
        if self._sequence_balance:
            op["sequences"] = shape[0] if len(shape) == 3 else 1
        optional = {k: v for k, v in (
            ("router_bias", router_bias),
            ("shared_gate_weight", shared_gate_weight),
            ("shared_up_weight", shared_up_weight),
            ("shared_down_weight", shared_down_weight)) if v is not None}
        out = remat(lambda *a: F.MoEExperts(
            *a[:5], **dict(zip(optional, a[5:])), **op))(
            tokens, router_weight, gate_weight, up_weight, down_weight,
            *optional.values())
        if router_bias is not None and autograd.is_training():
            with autograd.pause(), jax.named_scope("moe.bias_update"):
                load = out[5].astype("float32")
                self.router_bias.data()._rebind(
                    (router_bias + self._bias_update_rate
                     * F.sign(F.mean(load) - load))._data)
        return [out[0].reshape(shape)] + list(out[1:])
