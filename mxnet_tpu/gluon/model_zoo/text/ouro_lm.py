"""ByteDance's Ouro (``model_type: ouro``, "Scaling Latent Reasoning via
Looped Language Models"): a dense decoder whose stack of layers is applied
``passes`` times with the SAME weights, an exit gate after each pass, and a
training loss that is an expectation over the exits.

A layer has four norms (one before and one after each sub-layer): ``y = x +
norm(attention(norm(x)))``, ``x = y + norm(ffn(norm(y)))``; plain multi-head
causal attention with rotary embedding over the whole head.  After every pass
the final norm's result ``h_t`` is what the next pass starts from, what the
gate reads (``lambda_t = sigmoid(h_t . w + b)``, float32) and what the head
reads.  The passes are one :func:`gluon.block.loop`: under a jitted train
step one ``lax.scan`` whose body (the layers, the final norm, the gate) is
compiled once; each layer application is recomputed in the backward pass
(:func:`gluon.block.remat`), so a pass keeps one hidden state a layer.  The
head walks the passes' stacked states once, after the loop.

A batch of documents ``(N, S)`` that never attend to each other, positions
``0 .. S-1`` in each, the same in every pass.  What the published
``config.json`` does not state follows the family's modeling file and paper:
the norms' places, the final norm inside the loop, the gate's form, and the
entropy-regularised objective with a uniform prior over the exits
(:class:`OuroLMLoss`).  Leaving the loop early at inference
(``early_exit_threshold``) is not built.
"""
from __future__ import annotations

import jax

from .... import initializer
from ... import nn
from ...block import HybridBlock, loop, remat

__all__ = ["OuroLM", "OuroLMLoss"]


class _LoopLayer(HybridBlock):
    """Self-attention and the gated feed-forward, each between two norms."""

    def __init__(self, m, prefix):
        super().__init__(prefix=prefix)
        norm = lambda name: nn.RMSNorm(m["units"], m["eps"],     # noqa: E731
                                       prefix=name + "_")
        with self.name_scope():
            self.attn_norm = norm("attn_norm")
            self.attn = nn.SelfAttention(
                m["units"], m["heads"], m["kv_heads"], m["head_dim"],
                m["theta"], m["attn_block"], m["attn_span"],
                weight_initializer=m["init"], prefix="attn_")
            self.attn_post_norm = norm("attn_post_norm")
            self.ffn_norm = norm("ffn_norm")
            self.ffn = nn.GatedFFN(m["units"], m["ffn_units"], m["init"],
                                   prefix="ffn_")
            self.ffn_post_norm = norm("ffn_post_norm")

    def hybrid_forward(self, F, x, positions):
        y = x + self.attn_post_norm(self.attn(self.attn_norm(x), positions))
        with jax.named_scope("dense_ffn"):
            return y + self.ffn_post_norm(self.ffn(self.ffn_norm(y)))


class OuroLM(HybridBlock):
    """``forward(tokens[, labels])``: ``tokens`` (N, S) ids of N documents;
    ``labels`` (N, S) the next ids (negative: no label).  Returns a list
    (T: the passes):

    0. logits (T, N, S, vocab) of every exit or, given ``labels``, the
       log-probability (T, N, S) float32 of each position's label at every
       exit, computed ``loss_block`` rows at a time (``ops.LMHeadLogProb``);
    1. gate (T, N, S) float32: ``lambda_t``, the share of what has not left
       yet that leaves after pass t;
    2. layer_applications (1,) int32: a counter every layer application adds
       one to on the device (``passes x layers`` when every pass ran).
    """

    def __init__(self, vocab, units, layers, passes, heads, head_dim,
                 ffn_units, kv_heads=None, theta=10000.0, eps=1e-6,
                 attn_block=256, attn_span=2048, loss_block=2048,
                 weight_initializer=None, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._passes = passes
        self._loss_block = loss_block
        init = weight_initializer or initializer.Normal(0.02)
        m = dict(units=units, heads=heads, kv_heads=kv_heads or heads,
                 head_dim=head_dim, ffn_units=ffn_units, theta=theta,
                 eps=eps, attn_block=attn_block, attn_span=attn_span,
                 init=init)
        with self.name_scope():
            self.embed = nn.Embedding(vocab, units, weight_initializer=init,
                                      prefix="embed_")
            self.layers = []
            for i in range(layers):
                layer = _LoopLayer(m, prefix="l%d_" % i)
                self.register_child(layer)
                self.layers.append(layer)
            self.final_norm = nn.RMSNorm(units, eps, prefix="final_norm_")
            self.gate_weight = self.params.get("gate_weight",
                                               shape=(1, units), init=init)
            self.gate_bias = self.params.get("gate_bias", shape=(1,),
                                             init="zeros")
            self.head = nn.Dense(vocab, use_bias=False, flatten=False,
                                 in_units=units, weight_initializer=init,
                                 prefix="head_")

    @classmethod
    def from_config(cls, cfg, **kwargs):
        """From the keys of the published ``config.json`` (as
        ``benchmark/configs/ouro_2_6b_loop4.json`` holds them)."""
        kinds = cfg.get("layer_types", [])[:cfg["num_hidden_layers"]]
        if (cfg.get("use_sliding_window") or cfg.get("rope_scaling")
                or any(k != "full_attention" for k in kinds)):
            raise ValueError("sliding-window layers and rotary scaling are "
                             "not built")
        if cfg.get("tie_word_embeddings"):
            raise ValueError("a head tied to the embedding is not built")
        return cls(
            vocab=cfg["vocab_size"], units=cfg["hidden_size"],
            layers=cfg["num_hidden_layers"], passes=cfg["total_ut_steps"],
            heads=cfg["num_attention_heads"], head_dim=cfg["head_dim"],
            ffn_units=cfg["intermediate_size"],
            kv_heads=cfg["num_key_value_heads"],
            theta=float(cfg["rope_theta"]), eps=cfg["rms_norm_eps"], **kwargs)

    def hybrid_forward(self, F, tokens, labels=None, gate_weight=None,
                       gate_bias=None):
        positions = F.arange(tokens.shape[1])

        def one_pass(x, applied):
            with jax.named_scope("loop.pass"):
                for layer in self.layers:
                    x = remat(layer)(x, positions)
                    applied = applied + 1
                h = self.final_norm(x)
                with jax.named_scope("exit_gate"):
                    lam = F.sigmoid(
                        F.sum(F.cast(h, dtype="float32")
                              * F.cast(gate_weight, dtype="float32"), axis=-1)
                        + F.cast(gate_bias, dtype="float32"))
            return [h, applied], [h, lam]

        (_, applied), (exits, lam) = loop(one_pass, self._passes)(
            self.embed(tokens), F.zeros((1,), dtype="int32"))
        # one walk of the head over the passes' stacked states: a walk a
        # pass inside the loop declares 1.3 GB more at the benchmark's size
        # and is no faster (632.8 against 629.8 ms a step on a v5e)
        with jax.named_scope("lm_head"):
            first = self.head(exits) if labels is None else F.LMHeadLogProb(
                exits, self.head.weight.data(),
                F.stack(*[labels] * self._passes, axis=0),
                block=self._loss_block)
        return [first, lam, applied]


class OuroLMLoss:
    """Loss of :class:`OuroLM`'s outputs against next-token ``labels`` (N, S)
    (a negative label is no label).  With ``l_t`` a position's cross-entropy
    at exit t and ``p_t = lambda_t prod_{j<t} (1 - lambda_j)`` the share that
    leaves there (the last exit takes what is left): the mean over the
    labelled positions of ``sum_t p_t l_t - entropy_beta x H(p)``, ``H(p) =
    -sum_t p_t log p_t``: the expected loss over the exits, with the entropy
    of the exit distribution rewarded (a uniform prior over the exits).  The
    first output is every exit's logits, or the labels' log-probabilities
    where the model was given the labels.
    -> (loss, aux): the two terms apart, each exit's own mean loss
    (``lm_loss_exits`` (T,)) and mean share (``exit_mass`` (T,)), and the
    step's device counters ``exit_step_milli`` (the rounded sum over all
    positions of 1000 x the expected exit ``sum_t t p_t``), ``gate_tokens``
    and ``layer_applications``."""

    def __init__(self, entropy_beta=0.1):
        self.entropy_beta = entropy_beta

    @staticmethod
    def exit_shares(lam):
        """(T, ..) gates -> [p_1 .. p_T]."""
        from .... import ndarray as F

        shares, stay = [], F.ones_like(lam[0])
        for t in range(lam.shape[0] - 1):
            shares.append(lam[t] * stay)
            stay = stay * (1.0 - lam[t])
        return shares + [stay]

    def __call__(self, out, labels):
        from .... import ndarray as F

        picked, lam, applied = out
        valid = labels >= 0
        if len(picked.shape) == 4:
            picked = F.pick(F.log_softmax(picked, axis=-1),
                            F.stack(*[F.maximum(labels, 0)] * picked.shape[0],
                                    axis=0), axis=-1) * valid
        count = F.sum(valid)
        shares = self.exit_shares(lam)
        expected = entropy = steps = 0.0
        for t, p in enumerate(shares):
            expected = expected - p * picked[t]
            entropy = entropy - p * F.log(F.maximum(p, 1e-30))
            steps = steps + (t + 1.0) * p
        expected = F.sum(expected * valid) / count
        entropy = F.sum(entropy * valid) / count
        return expected - self.entropy_beta * entropy, {
            "expected_lm_loss": expected, "exit_entropy": entropy,
            "lm_loss_exits": F.stack(*[-F.sum(picked[t]) / count
                                       for t in range(len(shares))]),
            "exit_mass": F.stack(*[F.sum(p * valid) / count for p in shares]),
            "exit_step_milli": F.cast(F.round(F.sum(steps) * 1000.0),
                                      dtype="int32"),
            "gate_tokens": steps.size,
            "layer_applications": applied}
