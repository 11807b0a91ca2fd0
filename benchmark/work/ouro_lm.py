"""Ouro-2.6B, one pipeline stage's layers looped: every product of a training
step with its multiply-adds **a token**, at the mathematics' size.

What is counted is what the equations need (benchmark/reference/ouro_lm.py's
four steps), whatever computes it: every pass's application of every held
layer (the four projections, attention scores and values over a query's
causal keys, ``(S + 1) / 2`` of them averaged over a document, the gated
feed-forward), and after every pass the exit gate and the head over the whole
vocabulary.  A layer application recomputed in the backward pass is not
counted twice, and an attention computed over masked keys above the diagonal
reads as the lower share of the peak it is.  Every product is trained.
"""


def causal_keys_mean(cfg):
    return (cfg["seq_len"] + 1) / 2


def layers(cfg):
    """[{name, macs a token, trained}] of the step: ``p<pass>_l<layer>_*``
    and ``p<pass>_exit_*``."""
    D, H, d = cfg["hidden_size"], cfg["num_attention_heads"], cfg["head_dim"]
    Hkv = cfg["num_key_value_heads"]
    causal = causal_keys_mean(cfg)
    application = [
        ("attn_q", D * H * d), ("attn_k", D * Hkv * d),
        ("attn_v", D * Hkv * d), ("attn_o", H * d * D),
        ("attn_scores_causal", causal * H * d),
        ("attn_values_causal", causal * H * d),
        ("dense_ffn", 3 * D * cfg["intermediate_size"]),
    ]
    out = []
    for t in range(1, cfg["total_ut_steps"] + 1):
        out += [{"name": "p%d_l%d_%s" % (t, l, n), "macs": m, "trained": True}
                for l in range(cfg["num_hidden_layers"])
                for n, m in application]
        out += [{"name": "p%d_exit_gate" % t, "macs": D, "trained": True},
                {"name": "p%d_exit_lm_head" % t,
                 "macs": D * cfg["vocab_size"], "trained": True}]
    return out
