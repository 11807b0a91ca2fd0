"""Keye-VL-2.0-30B-A3B's language model, one chip's share: every product of a
training step with its multiply-adds **a token**, at the mathematics' size.

What is counted is what the equations need (benchmark/reference/keye_lm.py's
six steps), whatever computes it: index scores over a query's causal keys,
attention over the keys selected (``min(t + 1, topk)``, averaged over the
sequence), the held experts' expected share of a token's ``top_k`` pairs
(``top_k x held / experts``), the head over the vocabulary slice.  So an
attention computed densely under a mask reads as the low share of the peak it
is.  A backward pass goes through every product; the index scores' backward
needs only the selected keys (the KL term is over them), so it is counted
there and the scores over all causal keys count forward only.
"""


def selected_keys_mean(cfg):
    """Mean over the queries t = 0 .. S-1 of ``min(t + 1, topk)``."""
    S, k = cfg["seq_len"], min(cfg["sa_config"]["topk"], cfg["seq_len"])
    return (k * (k + 1) / 2 + (S - k) * k) / S


def causal_keys_mean(cfg):
    return (cfg["seq_len"] + 1) / 2


def selected_share(cfg):
    """Selected over causal keys, sum over sum (23.4 % at 16384 / 2048)."""
    return selected_keys_mean(cfg) / causal_keys_mean(cfg)


def layers(cfg):
    """[{name, macs a token, trained}] of the share's step."""
    H, d = cfg["hidden_size"], cfg["head_dim"]
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    sa = cfg["sa_config"]
    ni, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    F, E = cfg["moe_intermediate_size"], cfg["num_local_experts"]
    held_pairs = cfg["num_experts_per_tok"] * cfg["num_experts"] / E
    sel, causal = selected_keys_mean(cfg), causal_keys_mean(cfg)
    one = [
        ("attn_qkv", H * (nq + 2 * nkv) * d, True),
        ("attn_o", nq * d * H, True),
        ("index_proj", H * (ni * di + di + ni), True),
        ("index_scores_causal", causal * ni * di, False),
        # the scores' backward, two products over the selected keys only
        ("index_scores_selected_bwd", 2 * sel * ni * di, False),
        ("attn_scores_selected", sel * nq * d, True),
        ("attn_values_selected", sel * nq * d, True),
        ("moe_router", H * E, True),
        ("moe_experts_held", held_pairs * 3 * H * F, True),
    ]
    out = [{"name": "l%d_%s" % (l, n), "macs": m, "trained": t}
           for l in range(cfg["num_hidden_layers"]) for n, m, t in one]
    out.append({"name": "lm_head", "macs": H * cfg["vocab_size"],
                "trained": True})
    return out
