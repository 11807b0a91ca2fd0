"""Moonshot AI's Moonlight-16B-A3B (``model_type: deepseek_v3``): a decoder
whose every layer has multi-head latent attention, whose first layer has a
dense gated feed-forward and whose other layers have a mixture of experts
with sigmoid scores, a selection bias that balances the load without a
gradient, a shared expert and a sequence-wise balance term, as one chip of an
expert-parallel group trains it: the chip holds a share of each layer's
routed experts and a slice of the vocabulary, and computes its own part of
each layer's result (attention, the shared expert and the router are every
chip's alike).

A batch of documents ``(N, S)`` that never attend to each other, positions
``0 .. S-1`` in each.  What the published ``config.json`` does not state
follows the DeepSeek-V3 report: the balance term's form and the bias update
(``moe.bias_update``: after each step ``b += rate x sign(mean load -
load)``, the bias a ``grad_req='null'`` parameter that a functional train
step carries as auxiliary state).
"""
from __future__ import annotations

import jax

from .... import initializer
from ... import nn
from ...block import HybridBlock, remat

__all__ = ["MoonlightLM", "MoonlightLMLoss"]


class _DecoderLayer(HybridBlock):
    """Latent attention, then the dense feed-forward (``dense``) or the
    expert layer.  -> [x] or [x, balance, pairs, dropped, choice,
    router_pairs, gates]."""

    def __init__(self, m, dense, prefix):
        super().__init__(prefix=prefix)
        init = m["init"]
        with self.name_scope():
            self.attn_norm = nn.RMSNorm(m["units"], m["eps"],
                                        prefix="attn_norm_")
            self.attn = nn.LatentAttention(
                m["units"], m["heads"], m["latent"], m["qk_nope_dim"],
                m["qk_rope_dim"], m["v_dim"], m["theta"], m["latent_eps"],
                m["attn_block"], m["attn_span"], weight_initializer=init,
                prefix="attn_")
            self.ffn_norm = nn.RMSNorm(m["units"], m["eps"],
                                       prefix="ffn_norm_")
            if dense:
                self.ffn = nn.GatedFFN(m["units"], m["dense_units"], init,
                                       prefix="ffn_")
            else:
                self.moe = nn.SparseMoE(
                    m["units"], m["expert_units"], m["experts"], m["top_k"],
                    m["experts_held"], m["first_expert"], m["norm_topk_prob"],
                    m["capacity_factor"], scoring=m["scoring"],
                    routed_scale=m["routed_scale"],
                    bias_update_rate=m["bias_update_rate"],
                    shared_units=m["shared_units"], sequence_balance=True,
                    weight_initializer=init, prefix="moe_")
        self._dense = dense

    def hybrid_forward(self, F, x, positions):
        y = x + self.attn(self.attn_norm(x), positions)
        b = self.ffn_norm(y)
        if not self._dense:
            moe = self.moe(b)
            return [y + moe[0]] + moe[1:]
        with jax.named_scope("dense_ffn"):
            return [y + remat(self.ffn)(b)]


class MoonlightLM(HybridBlock):
    """``forward(tokens[, labels])``: ``tokens`` (N, S) ids of N documents
    from the vocabulary slice held here; ``labels`` (N, S) the next ids
    (negative: no label).  Returns a list (L: the expert layers):

    0. logits (N, S, vocab) over the slice or, given ``labels``, the
       log-probability (N, S) float32 of each position's label, computed
       ``loss_block`` rows at a time so that no (N x S, vocab) array exists
       (``ops.LMHeadLogProb``);
    1. balance: sum over the expert layers of the sequence-wise balance term;
    2. pairs (L, experts_held): (token, expert) pairs to each held expert;
    3. dropped (L,): held pairs that did not fit their buffer;
    4. router_pairs (L, experts): pairs these tokens sent to every expert;
    5. choice, 6. gates (N x S, top_k): the first expert layer's chosen
       experts and their gates.
    """

    def __init__(self, vocab, units, layers, dense_layers, heads, latent,
                 qk_nope_dim, qk_rope_dim, v_dim, dense_units, expert_units,
                 experts, top_k, shared_units, experts_held=None,
                 first_expert=0, norm_topk_prob=True, scoring="sigmoid",
                 routed_scale=1.0, bias_update_rate=0.001, theta=10000.0,
                 eps=1e-6, latent_eps=1e-6, capacity_factor=None,
                 attn_block=256, attn_span=2048, loss_block=2048,
                 weight_initializer=None, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._loss_block = loss_block
        m = dict(units=units, heads=heads, latent=latent,
                 qk_nope_dim=qk_nope_dim, qk_rope_dim=qk_rope_dim,
                 v_dim=v_dim, dense_units=dense_units,
                 expert_units=expert_units, experts=experts, top_k=top_k,
                 shared_units=shared_units, experts_held=experts_held,
                 first_expert=first_expert, norm_topk_prob=norm_topk_prob,
                 scoring=scoring, routed_scale=routed_scale,
                 bias_update_rate=bias_update_rate, theta=theta, eps=eps,
                 latent_eps=latent_eps, capacity_factor=capacity_factor,
                 attn_block=attn_block, attn_span=attn_span,
                 init=weight_initializer or initializer.Normal(0.02))
        if not 0 <= dense_layers < layers:
            raise ValueError("%d leading dense layers of %d leave no expert "
                             "layer" % (dense_layers, layers))
        with self.name_scope():
            self.embed = nn.Embedding(vocab, units, weight_initializer=m["init"],
                                      prefix="embed_")
            self.layers = []
            for i in range(layers):
                layer = _DecoderLayer(m, dense=i < dense_layers,
                                      prefix="l%d_" % i)
                self.register_child(layer)
                self.layers.append(layer)
            self.final_norm = nn.RMSNorm(units, eps, prefix="final_norm_")
            self.head = nn.Dense(vocab, use_bias=False, flatten=False,
                                 in_units=units, weight_initializer=m["init"],
                                 prefix="head_")

    @classmethod
    def from_config(cls, cfg, **kwargs):
        """From the keys of the published ``config.json`` (as
        ``benchmark/configs/moonlight_16b_a3b_ep8.json`` holds them):
        ``n_routed_experts`` is the experts held here, the router's width is
        ``deployment.published.n_routed_experts`` (the same without a
        deployment) and ``deployment.first_expert`` the first one held."""
        if cfg.get("q_lora_rank") is not None:
            raise ValueError("a query latent (q_lora_rank) is not built")
        if (cfg["n_group"], cfg["topk_group"]) != (1, 1):
            raise ValueError("group-limited routing (n_group %r, topk_group "
                             "%r) is not built" % (cfg["n_group"],
                                                   cfg["topk_group"]))
        if cfg["moe_layer_freq"] != 1 or cfg["topk_method"] != "noaux_tc":
            raise ValueError("every layer after the dense ones is an expert "
                             "layer with a selection bias (noaux_tc)")
        deployment = cfg.get("deployment", {})
        experts = deployment.get("published", cfg)["n_routed_experts"]
        return cls(
            vocab=cfg["vocab_size"], units=cfg["hidden_size"],
            layers=cfg["num_hidden_layers"],
            dense_layers=cfg["first_k_dense_replace"],
            heads=cfg["num_attention_heads"], latent=cfg["kv_lora_rank"],
            qk_nope_dim=cfg["qk_nope_head_dim"],
            qk_rope_dim=cfg["qk_rope_head_dim"], v_dim=cfg["v_head_dim"],
            dense_units=cfg["intermediate_size"],
            expert_units=cfg["moe_intermediate_size"], experts=experts,
            top_k=cfg["num_experts_per_tok"],
            shared_units=cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
            experts_held=cfg["n_routed_experts"],
            first_expert=deployment.get("first_expert", 0),
            norm_topk_prob=cfg["norm_topk_prob"], scoring=cfg["scoring_func"],
            routed_scale=cfg["routed_scaling_factor"],
            theta=float(cfg["rope_theta"]), eps=cfg["rms_norm_eps"], **kwargs)

    def hybrid_forward(self, F, tokens, labels=None):
        positions = F.arange(tokens.shape[1])
        x = self.embed(tokens)
        per_layer = []
        for layer in self.layers:
            out = layer(x, positions)
            x = out[0]
            if len(out) > 1:
                per_layer.append(out[1:])
        with jax.named_scope("lm_head"):
            x = self.final_norm(x)
            first = self.head(x) if labels is None else F.LMHeadLogProb(
                x, self.head.weight.data(), labels, block=self._loss_block)
        balance, pairs, dropped, choice, router_pairs, gates = zip(*per_layer)
        return [first, F.add_n(*balance), F.stack(*pairs), F.stack(*dropped),
                F.stack(*router_pairs), choice[0], gates[0]]


class MoonlightLMLoss:
    """Loss of :class:`MoonlightLM`'s outputs against next-token ``labels``
    (N, S) (a negative label is no label): mean cross-entropy over the
    vocabulary slice + ``aux_loss_alpha`` x the sequence-wise balance term.
    The first output is the logits, or the labels' log-probabilities where
    the model was given the labels.
    -> (loss, aux): the two terms apart and the step's device counters."""

    def __init__(self, aux_loss_alpha=0.001):
        self.aux_loss_alpha = aux_loss_alpha

    def __call__(self, out, labels):
        from .... import ndarray as F

        picked, balance, pairs, dropped, router_pairs, choice, gates = out
        valid = labels >= 0
        if len(picked.shape) == 3:
            picked = F.pick(F.log_softmax(picked, axis=-1),
                            F.maximum(labels, 0), axis=-1) * valid
        lm = -F.sum(picked) / F.sum(valid)
        return lm + self.aux_loss_alpha * balance, {
            "lm_loss": lm, "balance_loss": balance, "expert_pairs": pairs,
            "expert_pairs_max": F.max(pairs, axis=1),
            "moe_dropped_pairs": dropped, "router_pairs": router_pairs,
            "router_pairs_max": F.max(router_pairs, axis=1), "choice": choice,
            "gates": gates}
