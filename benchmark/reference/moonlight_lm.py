"""Plain reference of Moonlight-16B-A3B (``model_type: deepseek_v3``) as one
chip of an 8-way expert-parallel group trains it: forward, the two loss
terms, gradients, Adam and the selection bias's update, in ``jax.numpy``
float32 with every product at ``Precision.HIGHEST``.  Imports nothing of
``mxnet_tpu``.

One layer, x (S, hidden) of one document, ``sg`` = stop-gradient
(configuration keys in brackets):

1. ``a = RMSNorm(x)`` [rms_norm_eps]; ``q = a Wq`` -> [num_attention_heads]
   heads of [qk_nope_head_dim] + [qk_rope_head_dim] ([q_lora_rank] null: no
   query latent); ``[c | kr] = a Wkv_a``, ``c`` [kv_lora_rank] wide, ``kr``
   one rotary key for all heads; ``[k_nope | v] = RMSNorm(c) Wkv_b``
   [latent_norm_eps] -> heads of [qk_nope_head_dim] + [v_head_dim]; rotary
   embedding [rope_theta], half-rotation form, on each head's rope dims of
   ``q`` and on ``kr``; ``k_h = [k_nope_h | rope(kr)]``.
2. ``o_h[t] = softmax over s <= t of (q_h[t] . k_h[s] / sqrt(nope + rope))
   v_h``; ``y = x + concat(o) Wo``.
3. The first [first_k_dense_replace] layers: ``z = y + (silu(b Wg) * (b Wu))
   Wd``, ``b = RMSNorm(y)``, [intermediate_size] wide.
4. The others: ``s = sigmoid(b Wr)`` over all the layer's experts
   [scoring_func]; ``T_t`` = the top [num_experts_per_tok] of ``s + bias``
   ([topk_method] noaux_tc; [n_group] = [topk_group] = 1: no group limit);
   gates ``s[T_t] / (sum over T_t of s + 1e-20) x`` [routed_scaling_factor]
   ([norm_topk_prob]; the bias chooses, never gates); ``z = y +`` the shared
   gated feed-forward of ``b`` ([n_shared_experts] x [moe_intermediate_size]
   wide) ``+ sum over the chosen experts held here of gate x (silu(b Wg_e) *
   (b Wu_e)) Wd_e``.
5. Final RMSNorm, head over the vocabulary slice.  Loss = mean next-token
   cross-entropy + [aux_loss_alpha] x sum over the expert layers of the
   sequence-wise balance term: per document ``f_e = E / (k S) x #{t : e in
   T_t}``, ``P_e = mean_t s[t, e] / sum_j s[t, j]``, term ``sum_e sg(f_e)
   P_e``, mean over the step's documents.
6. After the step's gradients (none reaches the bias): ``bias_e +=``
   [bias_update_rate] ``x sign(mean_e' load - load_e)``, ``load_e`` the
   (token, expert) pairs the step's tokens sent to expert e of that layer,
   over all the layer's experts.

Departures and readings, each also under ``assumed`` in the configuration's
file: rotary in half-rotation form (the published interleaved form with the
rope rows of ``Wq`` / ``Wkv_a`` permuted); the last position of a document
has no label; Adam is MXNet's ``adam_update`` (benchmark/reference/
keye_lm.py).  The share: experts ``first_expert .. first_expert +
n_routed_experts - 1`` and the vocabulary slice are all this reference is
given; what absent experts would add is left out.

Memory: each layer is recomputed in the backward pass, attention walks each
document's queries in blocks of ``block`` rows against all its keys under a
mask, and the held experts are walked one at a time over all tokens under
their gate (zero where not routed): nothing is gathered, sorted or grouped.
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.reference import precision
from benchmark.reference.keye_lm import adam, rms_norm, rope


def _sizes(c):
    return (c["hidden_size"], c["num_attention_heads"], c["kv_lora_rank"],
            c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"])


def router_width(cfg):
    """Experts of a layer: the published count where this is a share."""
    return cfg.get("deployment", {}).get("published", cfg)["n_routed_experts"]


def _attention_leaves(c):
    D, H, L, dn, dr, dv = _sizes(c)
    return [("attn_norm_gamma", (D,), "gamma"),
            ("attn_q_weight", (H * (dn + dr), D), "head"),
            ("attn_kv_a_weight", (L + dr, D), "head"),
            ("attn_kv_norm_gamma", (L,), "gamma"),
            ("attn_kv_b_weight", (H * (dn + dv), L), "head"),
            ("attn_o_weight", (D, H * dv), "head"),
            ("ffn_norm_gamma", (D,), "gamma")]


def _dense_leaves(c):
    D, F = c["hidden_size"], c["intermediate_size"]
    return [("ffn_gate_weight", (F, D), "head"),
            ("ffn_up_weight", (F, D), "head"),
            ("ffn_down_weight", (D, F), "head")]


def _expert_leaves(c):
    D, F = c["hidden_size"], c["moe_intermediate_size"]
    held, Fs = c["n_routed_experts"], c["n_shared_experts"] * F
    return [("moe_router_weight", (router_width(c), D), "head"),
            ("moe_gate_weight", (held, D, F), "head"),
            ("moe_up_weight", (held, D, F), "head"),
            ("moe_down_weight", (held, F, D), "head"),
            ("moe_shared_gate_weight", (Fs, D), "head"),
            ("moe_shared_up_weight", (Fs, D), "head"),
            ("moe_shared_down_weight", (D, Fs), "head")]


def expert_layers(cfg):
    return range(cfg["first_k_dense_replace"], cfg["num_hidden_layers"])


def param_spec(cfg):
    """[(name, shape, kind)]: every trained leaf of the share, ``kind`` one
    of benchmark/seeded.py's.  Dense weights are (out, in); the experts held
    are stacked, (held, in, out)."""
    spec = [("embed_weight", (cfg["vocab_size"], cfg["hidden_size"]), "head")]
    for l in range(cfg["num_hidden_layers"]):
        leaves = _attention_leaves(cfg) + (
            _expert_leaves(cfg) if l in expert_layers(cfg)
            else _dense_leaves(cfg))
        spec += [("l%d_%s" % (l, n), s, k) for n, s, k in leaves]
    spec += [("final_norm_gamma", (cfg["hidden_size"],), "gamma"),
             ("head_weight", (cfg["vocab_size"], cfg["hidden_size"]), "head")]
    return spec


def bias_spec(cfg):
    """The state no gradient trains: each expert layer's selection bias."""
    return [("l%d_moe_router_bias" % l, (router_width(cfg),), "bias")
            for l in expert_layers(cfg)]


def latent_attention(a, g, cfg, prec, block):
    """Steps 1 and 2 of one document after the norm, before ``Wo``:
    a (S, D) -> (S, H x v)."""
    _, H, L, dn, dr, dv = _sizes(cfg)
    S = a.shape[0]
    theta = float(cfg["rope_theta"])
    pos = jnp.arange(S)[None]
    mm = lambda x, w: precision.einsum("td,od->to", x, w, prec)   # noqa: E731
    rot = lambda x: rope(x, pos, theta, [dr // 2])                # noqa: E731
    q = mm(a, g("attn_q_weight")).reshape(S, H, dn + dr)
    ckr = mm(a, g("attn_kv_a_weight"))
    c = rms_norm(ckr[:, :L], g("attn_kv_norm_gamma"), cfg["latent_norm_eps"])
    kr = rot(ckr[:, None, L:])
    kv = mm(c, g("attn_kv_b_weight")).reshape(S, H, dn + dv)
    q = jnp.concatenate([q[..., :dn], rot(q[..., dn:])], -1)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(kr, (S, H, dr))], -1)
    v = kv[..., dn:]
    block = min(block, S)
    nb = S // block
    s_all = jnp.arange(S)

    @jax.checkpoint
    def attend(blk):
        q_b, t_b = blk
        s = precision.einsum("qhd,khd->hqk", q_b, k, prec) * (dn + dr) ** -0.5
        p = jax.nn.softmax(jnp.where(s_all[None, :] <= t_b[:, None], s,
                                     -jnp.inf), -1)
        return precision.einsum("hqk,khd->qhd", p, v, prec)

    o = lax.map(attend, (q.reshape(nb, block, H, dn + dr),
                         s_all.reshape(nb, block)))
    return o.reshape(S, H * dv)


def gated_ffn(b, wg, wu, wd, prec):
    """``(silu(b Wg^T) * (b Wu^T)) Wd^T``, weights (out, in)."""
    h = jax.nn.silu(precision.einsum("td,fd->tf", b, wg, prec)) \
        * precision.einsum("td,fd->tf", b, wu, prec)
    return precision.einsum("tf,df->td", h, wd, prec)


def expert_layer(b, g, bias, cfg, prec, first, docs):
    """Step 4 after the norm.  b (T, D), the tokens of ``docs`` documents of
    equal length.  -> (the shared expert's and the held experts' part,
    balance term, (choice, gates) (T, k), pairs to each held expert, load
    (E,): pairs to every expert of the layer)."""
    wg, wu, wd = (g("moe_%s_weight" % n) for n in ("gate", "up", "down"))
    E, k = g("moe_router_weight").shape[0], cfg["num_experts_per_tok"]
    if (cfg["scoring_func"], cfg["topk_method"], cfg["n_group"],
            cfg["topk_group"]) != ("sigmoid", "noaux_tc", 1, 1):
        raise ValueError("sigmoid scores, a selection bias, no group limit")
    s = jax.nn.sigmoid(precision.einsum("td,ed->te", b,
                                        g("moe_router_weight"), prec))
    _, choice = lax.top_k(s + lax.stop_gradient(bias), k)
    top = jnp.take_along_axis(s, choice, 1)
    if cfg["norm_topk_prob"]:
        top = top / (jnp.sum(top, -1, keepdims=True) + 1e-20)
    gates = top * cfg["routed_scaling_factor"]
    hot = jax.nn.one_hot(choice, E, dtype=jnp.float32)            # (T, k, E)
    dense_gate = jnp.einsum("tke,tk->te", hot, gates)
    S = b.shape[0] // docs
    f = lax.stop_gradient(jnp.sum(hot.reshape(docs, S * k, E), 1)) \
        * (E / (k * S))
    share = (s / jnp.sum(s, -1, keepdims=True)).reshape(docs, S, E)
    balance = jnp.mean(jnp.sum(f * jnp.mean(share, 1), -1))
    held = wg.shape[0]

    @jax.checkpoint
    def one(carry, e):
        h = jax.nn.silu(precision.einsum("td,df->tf", b, wg[e], prec)) \
            * precision.einsum("td,df->tf", b, wu[e], prec)
        y = precision.einsum("tf,fd->td", h, wd[e], prec)
        gate = lax.dynamic_index_in_dim(dense_gate, first + e, 1,
                                        keepdims=True)
        return carry + gate * y, None

    shared = jax.checkpoint(gated_ffn, static_argnums=(4,))(
        b, g("moe_shared_gate_weight"), g("moe_shared_up_weight"),
        g("moe_shared_down_weight"), prec)
    y, _ = lax.scan(one, shared, jnp.arange(held))
    load = jnp.sum(hot, (0, 1))
    return (y, balance, (choice, gates),
            load[first:first + held].astype(jnp.int32), load)


def forward(p, bias, tokens, cfg, prec="float32", block=128):
    """-> (logits (N, S, vocab), balance, facts) for ``tokens`` (N, S);
    ``bias``: {leaf of :func:`bias_spec`: (E,)}."""
    eps = cfg["rms_norm_eps"]
    first = cfg.get("deployment", {}).get("first_expert", 0)
    N, S = tokens.shape
    mm = lambda x, w: precision.einsum("td,od->to", x, w, prec)   # noqa: E731

    @functools.partial(jax.checkpoint, static_argnums=(3,))
    def layer(x, w, bias_l, l):
        g = lambda n: w["l%d_%s" % (l, n)]                        # noqa: E731
        a = rms_norm(x, g("attn_norm_gamma"), eps)
        o = jnp.concatenate([latent_attention(a[n * S:(n + 1) * S], g, cfg,
                                              prec, block)
                             for n in range(N)])
        y = x + mm(o, g("attn_o_weight"))
        b = rms_norm(y, g("ffn_norm_gamma"), eps)
        if l not in expert_layers(cfg):
            return y + gated_ffn(b, g("ffn_gate_weight"), g("ffn_up_weight"),
                                 g("ffn_down_weight"), prec), None
        m, *rest = expert_layer(b, g, bias_l, cfg, prec, first, N)
        return y + m, rest

    x = p["embed_weight"][tokens.reshape(-1)]
    balance = 0.0
    facts = {"expert_pairs": [], "load": []}
    for l in range(cfg["num_hidden_layers"]):
        x, rest = layer(x, p, bias.get("l%d_moe_router_bias" % l), l)
        if rest is not None:
            bal_l, chosen, pairs, load = rest
            balance = balance + bal_l
            facts["expert_pairs"].append(pairs)
            facts["load"].append(load)
            facts.setdefault("choice", chosen[0])
            facts.setdefault("gates", chosen[1])
    logits = mm(rms_norm(x, p["final_norm_gamma"], eps), p["head_weight"])
    return logits.reshape(N, S, -1), balance, facts


def loss_terms(p, bias, tokens, cfg, prec="float32", block=128):
    """-> (loss, (parts, facts)); labels are the ids shifted by one within
    each document."""
    logits, balance, facts = forward(p, bias, tokens, cfg, prec, block)
    logp = jax.nn.log_softmax(logits[:, :-1], -1)
    lm = -jnp.mean(jnp.take_along_axis(logp, tokens[:, 1:, None], 2))
    loss = lm + cfg["aux_loss_alpha"] * balance
    return loss, ({"lm_loss": lm, "balance_loss": balance}, facts)


def update_bias(bias, load, rate):
    """Step 6 for one layer."""
    return bias + rate * jnp.sign(jnp.mean(load) - load)


class Reference:
    """The training state of the share and its step."""

    def __init__(self, cfg, weights, prec="float32", block=128):
        self.cfg = cfg
        self.names = [n for n, _, _ in param_spec(cfg)]
        self.p = {n: jnp.array(weights[n], jnp.float32, copy=True)
                  for n in self.names}
        self.bias = {n: jnp.array(weights[n], jnp.float32, copy=True)
                     for n, _, _ in bias_spec(cfg)}
        self.m = {n: jnp.zeros_like(w) for n, w in self.p.items()}
        self.v = {n: jnp.zeros_like(w) for n, w in self.p.items()}
        self.t = 0

        def step(p, m, v, bias, t, tokens):
            (loss, (parts, facts)), g = jax.value_and_grad(
                loss_terms, has_aux=True)(p, bias, tokens, cfg, prec, block)
            new = {n: adam(p[n], g[n], m[n], v[n], t, cfg) for n in p}
            bias = {n: update_bias(bias[n], load, cfg["bias_update_rate"])
                    for (n, _, _), load in zip(bias_spec(cfg),
                                               facts["load"])}
            return ({n: c[0] for n, c in new.items()},
                    {n: c[1] for n, c in new.items()},
                    {n: c[2] for n, c in new.items()}, bias, loss, parts,
                    facts)

        self._step = jax.jit(step, donate_argnums=(0, 1, 2))

    def step(self, tokens):
        """One Adam step.  -> (loss, the two terms, facts of the step)."""
        self.t += 1
        with jax.default_matmul_precision("highest"):
            (self.p, self.m, self.v, self.bias, loss, parts,
             facts) = self._step(self.p, self.m, self.v, self.bias,
                                 jnp.float32(self.t), tokens)
        return loss, parts, facts
