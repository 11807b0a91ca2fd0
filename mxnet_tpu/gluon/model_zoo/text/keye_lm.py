"""The language model of Kwai-Keye's Keye-VL-2.0-30B-A3B: a decoder whose
every layer pairs an indexer-selected sparse attention (DeepSeek sparse
attention over grouped-query heads with M-RoPE) with a top-k
mixture-of-experts feed-forward, as one chip of an expert-parallel group
trains it: the chip holds a share of each layer's experts and a slice of the
vocabulary, and computes its own part of each layer's result.

Text only (the vision tower is not here): one sequence of token ids, the
three M-RoPE position ids equal unless given.  What the published
``config.json`` does not state follows its family: per-head RMSNorm on
queries and keys and the load-balance term (the Qwen3-MoE decoder whose
sizes the config repeats); the indexer's form, the token-level ``topk`` and
its KL objective on a detached input (DeepSeek-V3.2-Exp's report).
"""
from __future__ import annotations

import jax

from .... import initializer
from ... import nn
from ...block import HybridBlock

__all__ = ["KeyeLM", "KeyeLMLoss"]


class _DecoderLayer(HybridBlock):
    def __init__(self, m, emit, prefix):
        super().__init__(prefix=prefix)
        init = m["init"]
        with self.name_scope():
            self.attn_norm = nn.RMSNorm(m["units"], m["eps"],
                                        prefix="attn_norm_")
            self.attn = nn.IndexerSparseAttention(
                m["units"], m["heads"], m["kv_heads"], m["head_dim"],
                m["index_heads"], m["index_dim"], m["topk"], m["theta"],
                m["sections"], m["eps"], m["attn_block"], m["attn_span"],
                emit_selection=emit, weight_initializer=init, prefix="attn_")
            self.moe_norm = nn.RMSNorm(m["units"], m["eps"],
                                       prefix="moe_norm_")
            self.moe = nn.SparseMoE(
                m["units"], m["expert_units"], m["experts"], m["top_k"],
                m["experts_held"], m["first_expert"], m["norm_topk_prob"],
                m["capacity_factor"], weight_initializer=init, prefix="moe_")

    def hybrid_forward(self, F, x, positions):
        a = self.attn_norm(x)
        attn = self.attn(a, positions, F.BlockGrad(a))
        y = x + attn[0]
        moe = self.moe(self.moe_norm(y))
        # [x, kl, selected, causal, balance, pairs, dropped, choice(, bits)]
        return [y + moe[0]] + attn[1:4] + moe[1:] + attn[4:]


class KeyeLM(HybridBlock):
    """``forward(tokens[, positions])``: ``tokens`` (S,) ids of one sequence
    from the vocabulary slice held here, ``positions`` (3, S) M-RoPE ids
    (default: text, all three ``0 .. S-1``).  Returns a list:

    0. logits (S, vocab) over the slice;
    1. balance: sum over layers of the load-balance term;
    2. indexer_kl: sum over layers of the mean over tokens of the indexer's KL;
    3. selected (L,), 4. causal (L,): keys the attention read, and could have;
    5. pairs (L, experts_held): (token, expert) pairs to each held expert;
    6. dropped (L,): held pairs that did not fit their buffer;
    7. selection (S, S/32) uint32: the first layer's selected keys, packed;
    8. choice (S, top_k): the first layer's chosen experts.
    """

    def __init__(self, vocab, units, layers, heads, kv_heads, head_dim,
                 expert_units, experts, top_k, index_heads, index_dim, topk,
                 experts_held=None, first_expert=0, norm_topk_prob=True,
                 theta=10000000.0, sections=(16, 24, 24), eps=1e-6,
                 capacity_factor=None, attn_block=256, attn_span=2048,
                 weight_initializer=None, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        m = dict(units=units, heads=heads, kv_heads=kv_heads,
                 head_dim=head_dim, expert_units=expert_units,
                 experts=experts, top_k=top_k, index_heads=index_heads,
                 index_dim=index_dim, topk=topk, experts_held=experts_held,
                 first_expert=first_expert, norm_topk_prob=norm_topk_prob,
                 theta=theta, sections=tuple(sections), eps=eps,
                 capacity_factor=capacity_factor, attn_block=attn_block,
                 attn_span=attn_span,
                 init=weight_initializer or initializer.Normal(0.02))
        self._sections = len(m["sections"])
        with self.name_scope():
            self.embed = nn.Embedding(vocab, units, weight_initializer=m["init"],
                                      prefix="embed_")
            self.layers = []
            for i in range(layers):
                layer = _DecoderLayer(m, emit=i == 0, prefix="l%d_" % i)
                self.register_child(layer)
                self.layers.append(layer)
            self.final_norm = nn.RMSNorm(units, eps, prefix="final_norm_")
            self.head = nn.Dense(vocab, use_bias=False, flatten=False,
                                 in_units=units, weight_initializer=m["init"],
                                 prefix="head_")

    @classmethod
    def from_config(cls, cfg, **kwargs):
        """From the keys of the published ``config.json`` (as
        ``benchmark/configs/keye_vl2_30b_a3b_lm_ep8.json`` holds them):
        ``num_experts`` is the experts held here, ``num_local_experts`` the
        router's width, ``deployment.first_expert`` the first one held."""
        sa = cfg["sa_config"]
        return cls(
            vocab=cfg["vocab_size"], units=cfg["hidden_size"],
            layers=cfg["num_hidden_layers"], heads=cfg["num_attention_heads"],
            kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
            expert_units=cfg["moe_intermediate_size"],
            experts=cfg["num_local_experts"], top_k=cfg["num_experts_per_tok"],
            experts_held=cfg["num_experts"],
            first_expert=cfg.get("deployment", {}).get("first_expert", 0),
            norm_topk_prob=cfg["norm_topk_prob"],
            index_heads=sa["indexer_num_heads"], index_dim=sa["indexer_head_dim"],
            topk=sa["topk"], theta=float(cfg["rope_theta"]),
            sections=cfg["rope_scaling"]["mrope_section"],
            eps=cfg["rms_norm_eps"], **kwargs)

    def hybrid_forward(self, F, tokens, positions=None):
        if positions is None:
            positions = F.tile(F.arange(tokens.shape[0]).reshape((1, -1)),
                               reps=(self._sections, 1))
        x = self.embed(tokens)
        per_layer = []
        for layer in self.layers:
            out = layer(x, positions)
            x = out[0]
            per_layer.append(out[1:])
        with jax.named_scope("lm_head"):
            logits = self.head(self.final_norm(x))
        kl, selected, causal, balance, pairs, dropped, choice = zip(
            *[p[:7] for p in per_layer])
        return [logits, F.add_n(*balance),
                F.add_n(*kl) / tokens.shape[0],
                F.stack(*selected), F.stack(*causal), F.stack(*pairs),
                F.stack(*dropped), per_layer[0][7], choice[0]]


class KeyeLMLoss:
    """Loss of :class:`KeyeLM`'s outputs against next-token ``labels`` (S,)
    (a negative label is no label): mean cross-entropy over the vocabulary
    slice + ``balance_coef`` x the load-balance term + the indexer's KL.
    -> (loss, aux): the three terms apart and the step's device counters."""

    def __init__(self, balance_coef=0.001):
        self.balance_coef = balance_coef

    def __call__(self, out, labels):
        from .... import ndarray as F

        logits, balance, kl, selected, causal, pairs, dropped, bits, choice = out
        valid = labels >= 0
        picked = F.pick(F.log_softmax(logits, axis=-1),
                        F.maximum(labels, 0), axis=-1)
        lm = -F.sum(picked * valid) / F.sum(valid)
        loss = lm + self.balance_coef * balance + kl
        return loss, {"lm_loss": lm, "balance_loss": balance,
                      "indexer_kl": kl, "selected_keys": selected,
                      "causal_keys": causal, "expert_pairs": pairs,
                      "expert_pairs_max": F.max(pairs, axis=1),
                      "moe_dropped_pairs": dropped, "selection": bits,
                      "choice": choice}
