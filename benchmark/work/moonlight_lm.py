"""Moonlight-16B-A3B, one chip's share: every product of a training step
with its multiply-adds **a token**, at the mathematics' size.

What is counted is what the equations need (benchmark/reference/
moonlight_lm.py's six steps), whatever computes it: the latent projections,
attention scores and values over a query's causal keys (``(S + 1) / 2`` of
them, averaged over a document), the shared expert, the held experts'
expected share of a token's pairs (``top_k x held / experts``), the router at
its full width, the dense layers' feed-forward, the head over the vocabulary
slice.  So an attention computed over masked keys above the diagonal reads
as the lower share of the peak it is.  Every product is trained.
"""


def causal_keys_mean(cfg):
    return (cfg["seq_len"] + 1) / 2


def layers(cfg):
    """[{name, macs a token, trained}] of the share's step."""
    D, H = cfg["hidden_size"], cfg["num_attention_heads"]
    L, dn = cfg["kv_lora_rank"], cfg["qk_nope_head_dim"]
    dr, dv = cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    F = cfg["moe_intermediate_size"]
    E = cfg.get("deployment", {}).get("published", cfg)["n_routed_experts"]
    held_pairs = cfg["num_experts_per_tok"] * cfg["n_routed_experts"] / E
    causal = causal_keys_mean(cfg)
    attention = [
        ("attn_q", D * H * (dn + dr)),
        ("attn_kv_a", D * (L + dr)),
        ("attn_kv_b", L * H * (dn + dv)),
        ("attn_o", H * dv * D),
        ("attn_scores_causal", causal * H * (dn + dr)),
        ("attn_values_causal", causal * H * dv),
    ]
    dense = [("dense_ffn", 3 * D * cfg["intermediate_size"])]
    experts = [
        ("moe_router", D * E),
        ("moe_shared", 3 * D * cfg["n_shared_experts"] * F),
        ("moe_experts_held", held_pairs * 3 * D * F),
    ]
    out = [{"name": "l%d_%s" % (l, n), "macs": m, "trained": True}
           for l in range(cfg["num_hidden_layers"])
           for n, m in attention + (dense if l < cfg["first_k_dense_replace"]
                                    else experts)]
    out.append({"name": "lm_head", "macs": D * cfg["vocab_size"],
                "trained": True})
    return out
