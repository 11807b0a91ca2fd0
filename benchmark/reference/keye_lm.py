"""Plain reference of the Keye-VL-2.0-30B-A3B language model as one chip of an
8-way expert-parallel group trains it: forward, the three loss terms,
gradients and Adam, in ``jax.numpy`` float32 with every product at
``Precision.HIGHEST``.  Imports nothing of ``mxnet_tpu``.

One layer, x (S, hidden), ``sg`` = stop-gradient (configuration keys in
brackets):

1. ``a = RMSNorm(x)``; ``q = a Wq`` [num_attention_heads x head_dim], ``k = a
   Wk``, ``v = a Wv`` [num_key_value_heads x head_dim]; RMSNorm over each
   head's dims on q and k; M-RoPE on q and k: head_dim / 2 frequency pairs at
   [rope_theta], split by [mrope_section] over three position ids,
   half-rotation form.
2. Indexer on ``sg(a)``: ``qI = sg(a) WqI`` [indexer_num_heads x
   indexer_head_dim], ``kI = LayerNorm(sg(a) WkI)``, ``w = sg(a) Ww``, rotary
   on qI and kI with the same ids; ``I[t, s] = sum_j w[t, j] relu(qI[t, j] .
   kI[s]) / sqrt(heads x dim)`` for s <= t.
3. ``tau[t]`` = the [topk]-th largest of ``I[t, :t+1]`` (minus infinity
   while t < topk); ``S_t = {s <= t : I[t, s] >= tau[t]}``.
4. ``o[t, h] = softmax over S_t of (q[t, h] . k[s, g(h)] / sqrt(head_dim))``
   applied to v; ``y = x + concat(o) Wo``.
5. ``b = RMSNorm(y)``; ``p = softmax(b Wr)`` over all [num_local_experts];
   ``T_t`` its top [num_experts_per_tok]; gates ``p / sum over T_t`` (over
   all chosen experts, held or not); ``z = y + sum over the chosen experts
   held here of gate x (silu(b Wg_e) * (b Wu_e)) Wd_e``.
6. Final RMSNorm, head over the vocabulary slice.  Loss = mean next-token
   cross-entropy + balance_coef x sum over layers of ``E sum_e frac_e mean_t
   p[t, e]`` + sum over layers of ``mean_t KL(sg(mean over heads of step 4's
   probabilities) || softmax over S_t of I[t, .])``.

Departures and readings, each also under ``assumed`` in the configuration's
file: ``frac_e`` counts the (token, expert) pairs routed to e over the
tokens (it sums to the experts per token: the Hugging Face
``load_balancing_loss_func`` form); the indexer's narrower heads split the
M-RoPE sections in proportion (text ids are equal, so no result depends on
it); the last position has no label; Adam is MXNet's ``adam_update`` with
the bias correction folded into the rate (``lr sqrt(1 - b2^t) / (1 - b1^t)``,
epsilon outside the correction).  The share: experts ``first_expert ..
first_expert + num_experts - 1`` and the vocabulary slice are all this
reference is given; what absent experts would add is left out.

Memory: each layer is recomputed in the backward pass, attention walks the
queries in blocks of ``block`` rows against all keys (thresholds by
``lax.top_k`` in a first pass without gradient), and the held experts are
walked one at a time over all tokens under their gate (zero where not
routed): nothing is gathered, sorted or grouped.
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.reference import precision

LAYER_LEAVES = (
    ("attn_norm_gamma", lambda c: (c["hidden_size"],), "gamma"),
    ("attn_q_weight", lambda c: (c["num_attention_heads"] * c["head_dim"],
                                 c["hidden_size"]), "head"),
    ("attn_k_weight", lambda c: (c["num_key_value_heads"] * c["head_dim"],
                                 c["hidden_size"]), "head"),
    ("attn_v_weight", lambda c: (c["num_key_value_heads"] * c["head_dim"],
                                 c["hidden_size"]), "head"),
    ("attn_o_weight", lambda c: (c["hidden_size"],
                                 c["num_attention_heads"] * c["head_dim"]),
     "head"),
    ("attn_q_norm_gamma", lambda c: (c["head_dim"],), "gamma"),
    ("attn_k_norm_gamma", lambda c: (c["head_dim"],), "gamma"),
    ("attn_idx_q_weight", lambda c: (
        c["sa_config"]["indexer_num_heads"] * c["sa_config"]["indexer_head_dim"],
        c["hidden_size"]), "head"),
    ("attn_idx_k_weight", lambda c: (c["sa_config"]["indexer_head_dim"],
                                     c["hidden_size"]), "head"),
    ("attn_idx_w_weight", lambda c: (c["sa_config"]["indexer_num_heads"],
                                     c["hidden_size"]), "head"),
    ("attn_idx_k_norm_gamma", lambda c: (c["sa_config"]["indexer_head_dim"],),
     "gamma"),
    ("attn_idx_k_norm_beta", lambda c: (c["sa_config"]["indexer_head_dim"],),
     "beta"),
    ("moe_norm_gamma", lambda c: (c["hidden_size"],), "gamma"),
    ("moe_router_weight", lambda c: (c["num_local_experts"],
                                     c["hidden_size"]), "head"),
    ("moe_gate_weight", lambda c: (c["num_experts"], c["hidden_size"],
                                   c["moe_intermediate_size"]), "head"),
    ("moe_up_weight", lambda c: (c["num_experts"], c["hidden_size"],
                                 c["moe_intermediate_size"]), "head"),
    ("moe_down_weight", lambda c: (c["num_experts"],
                                   c["moe_intermediate_size"],
                                   c["hidden_size"]), "head"),
)


def param_spec(cfg):
    """[(name, shape, kind)]: every leaf of the share, ``kind`` one of
    benchmark/seeded.py's.  Dense weights are (out, in); the experts held
    are stacked, (held, in, out)."""
    spec = [("embed_weight", (cfg["vocab_size"], cfg["hidden_size"]), "head")]
    for l in range(cfg["num_hidden_layers"]):
        spec += [("l%d_%s" % (l, n), shape(cfg), kind)
                 for n, shape, kind in LAYER_LEAVES]
    spec += [("final_norm_gamma", (cfg["hidden_size"],), "gamma"),
             ("head_weight", (cfg["vocab_size"], cfg["hidden_size"]), "head")]
    return spec


def is_indexer(name):
    """The leaves only the KL term trains."""
    return "_idx_" in name


def rms_norm(x, gamma, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gamma


def layer_norm(x, gamma, beta, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * lax.rsqrt(var + eps) * gamma + beta


def split_sections(sections, pairs):
    """``sections`` (of head_dim / 2 pairs) cut in proportion to ``pairs``."""
    total, ends = sum(sections), []
    for i in range(len(sections)):
        ends.append(sum(sections[:i + 1]) * pairs // total)
    return [e - b for b, e in zip([0] + ends, ends)]


def rope(x, positions, theta, sections):
    """x (S, heads, d); positions (3, S); half-rotation form."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    which = jnp.concatenate([jnp.full((n,), i, jnp.int32)
                             for i, n in enumerate(split_sections(sections, half))])
    ang = positions.astype(jnp.float32)[which].T * inv_freq      # (S, half)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def index_scores(iq, ik, iw, prec):
    """iq (B, HI, dI), ik (S, dI), iw (B, HI) -> I (B, S)."""
    dots = precision.einsum("qjd,kd->qjk", iq, ik, prec)
    return jnp.sum(jax.nn.relu(dots) * iw[:, :, None], 1) \
        * (iq.shape[1] * iq.shape[2]) ** -0.5


def pack_bits(mask):
    r, n = mask.shape
    return jnp.sum(mask.reshape(r, n // 32, 32).astype(jnp.uint32)
                   << jnp.arange(32, dtype=jnp.uint32), -1, dtype=jnp.uint32)


def sparse_attention(q, k, v, iq, ik, iw, topk, block, prec):
    """Steps 2-4 after the projections.  -> o (S, Hq, d), kl sum, selected,
    bits (S, S/32): bit ``s % 32`` of word ``s // 32`` of row t set where s
    is in S_t."""
    S, Hq, d = q.shape
    Hkv = k.shape[1]
    nb = S // block
    t = jnp.arange(S).reshape(nb, block)
    s_all = jnp.arange(S)

    def threshold(blk):
        iq_b, iw_b, t_b = blk
        causal = s_all[None, :] <= t_b[:, None]
        I = jnp.where(causal, index_scores(iq_b, ik, iw_b, prec), -jnp.inf)
        return lax.top_k(I, topk)[0][:, -1]

    blocks = lambda a: a.reshape((nb, block) + a.shape[1:])   # noqa: E731
    tau = lax.map(threshold, jax.tree_util.tree_map(
        lax.stop_gradient, (blocks(iq), blocks(iw), t)))

    @jax.checkpoint
    def attend(blk):
        q_b, iq_b, iw_b, t_b, tau_b = blk
        causal = s_all[None, :] <= t_b[:, None]
        I = index_scores(iq_b, ik, iw_b, prec)
        sel = (I >= tau_b[:, None]) & causal
        qg = q_b.reshape(block, Hkv, Hq // Hkv, d)
        s = precision.einsum("qhgd,khd->hgqk", qg, k, prec) * d ** -0.5
        p = jax.nn.softmax(jnp.where(sel, s, -jnp.inf), -1)
        o = precision.einsum("hgqk,khd->qhgd", p, v, prec)
        target = lax.stop_gradient(jnp.mean(p, (0, 1)))
        logp = jax.nn.log_softmax(jnp.where(sel, I, -jnp.inf), -1)
        safe = jnp.where(target > 0, target, 1.0)
        kl = jnp.sum(jnp.where(target > 0, target * (
            jnp.log(safe) - jnp.where(sel, logp, 0.0)), 0.0))
        return (o.reshape(block, Hq, d), kl, jnp.sum(sel), pack_bits(sel))

    o, kl, n_sel, bits = lax.map(attend, (blocks(q), blocks(iq), blocks(iw),
                                          t, lax.stop_gradient(tau)))
    return (o.reshape(S, Hq, d), jnp.sum(kl), jnp.sum(n_sel),
            bits.reshape(S, S // 32))


def experts(b, p_router, cfg, wg, wu, wd, prec, first):
    """Step 5 after the norm.  -> (held experts' part, balance term, choice
    (S, k), pairs to each held expert)."""
    E, k = cfg["num_local_experts"], cfg["num_experts_per_tok"]
    probs = jax.nn.softmax(p_router, -1)
    top, choice = lax.top_k(probs, k)
    gates = top / jnp.sum(top, -1, keepdims=True) \
        if cfg["norm_topk_prob"] else top
    hot = jax.nn.one_hot(choice, E, dtype=jnp.float32)            # (S, k, E)
    dense_gate = jnp.einsum("ske,sk->se", hot, gates)             # (S, E)
    frac = lax.stop_gradient(jnp.sum(hot, (0, 1)) / b.shape[0])
    balance = E * jnp.sum(frac * jnp.mean(probs, 0))
    held = wg.shape[0]

    @jax.checkpoint
    def one(carry, e):
        h = jax.nn.silu(precision.einsum("sd,df->sf", b, wg[e], prec)) \
            * precision.einsum("sd,df->sf", b, wu[e], prec)
        y = precision.einsum("sf,fd->sd", h, wd[e], prec)
        g = lax.dynamic_index_in_dim(dense_gate, first + e, 1, keepdims=True)
        return carry + g * y, None

    y, _ = lax.scan(one, jnp.zeros_like(b), jnp.arange(held))
    pairs = jnp.sum(hot, (0, 1))[first:first + held].astype(jnp.int32)
    return y, balance, choice, pairs


def forward(p, tokens, cfg, prec="float32", block=128):
    """-> (logits (S, vocab), balance, kl, facts) for ``tokens`` (S,), text
    positions."""
    eps, d = cfg["rms_norm_eps"], cfg["head_dim"]
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    sa = cfg["sa_config"]
    ni, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    sections = cfg["rope_scaling"]["mrope_section"]
    theta = float(cfg["rope_theta"])
    first = cfg.get("deployment", {}).get("first_expert", 0)
    S = tokens.shape[0]
    pos = jnp.tile(jnp.arange(S)[None], (len(sections), 1))
    mm = lambda x, w: precision.einsum("td,od->to", x, w, prec)  # noqa: E731

    @functools.partial(jax.checkpoint, static_argnums=(2,))
    def layer(x, w, l):
        g = lambda n: w["l%d_%s" % (l, n)]                       # noqa: E731
        a = rms_norm(x, g("attn_norm_gamma"), eps)
        q = rope(rms_norm(mm(a, g("attn_q_weight")).reshape(S, nq, d),
                          g("attn_q_norm_gamma"), eps), pos, theta, sections)
        k = rope(rms_norm(mm(a, g("attn_k_weight")).reshape(S, nkv, d),
                          g("attn_k_norm_gamma"), eps), pos, theta, sections)
        v = mm(a, g("attn_v_weight")).reshape(S, nkv, d)
        a_sg = lax.stop_gradient(a)
        iq = rope(mm(a_sg, g("attn_idx_q_weight")).reshape(S, ni, di),
                  pos, theta, sections)
        ik = rope(layer_norm(mm(a_sg, g("attn_idx_k_weight")),
                             g("attn_idx_k_norm_gamma"),
                             g("attn_idx_k_norm_beta"), eps)[:, None],
                  pos, theta, sections)[:, 0]
        iw = mm(a_sg, g("attn_idx_w_weight"))
        o, kl, n_sel, bits = sparse_attention(
            q, k, v, iq, ik, iw, sa["topk"], min(block, S), prec)
        y = x + mm(o.reshape(S, nq * d), g("attn_o_weight"))
        b = rms_norm(y, g("moe_norm_gamma"), eps)
        m, balance, choice, pairs = experts(
            b, precision.einsum("td,ed->te", b, g("moe_router_weight"), prec),
            cfg, g("moe_gate_weight"), g("moe_up_weight"),
            g("moe_down_weight"), prec, first)
        return y + m, (kl / S, balance, n_sel, bits, choice, pairs)

    x = p["embed_weight"][tokens]
    kl = balance = 0.0
    facts = {"selected_keys": [], "expert_pairs": []}
    for l in range(cfg["num_hidden_layers"]):
        x, (kl_l, bal_l, n_sel, bits, choice, pairs) = layer(x, p, l)
        kl, balance = kl + kl_l, balance + bal_l
        facts["selected_keys"].append(n_sel)
        facts["expert_pairs"].append(pairs)
        if l == 0:
            facts["selection"], facts["choice"] = bits, choice
    logits = mm(rms_norm(x, p["final_norm_gamma"], eps), p["head_weight"])
    return logits, balance, kl, facts


def loss_terms(p, tokens, cfg, prec="float32", block=128):
    """-> (loss, (parts, facts)); labels are the ids shifted by one."""
    logits, balance, kl, facts = forward(p, tokens, cfg, prec, block)
    logp = jax.nn.log_softmax(logits[:-1], -1)
    lm = -jnp.mean(jnp.take_along_axis(logp, tokens[1:, None], 1))
    loss = lm + cfg["balance_coef"] * balance + kl
    return loss, ({"lm_loss": lm, "balance_loss": balance,
                   "indexer_kl": kl}, facts)


def adam(p, g, m, v, t, cfg):
    """MXNet's adam_update at step ``t`` (1-based), no decay."""
    b1, b2, eps = cfg["beta1"], cfg["beta2"], cfg["epsilon"]
    lr = cfg["learning_rate"] * (1 - b2 ** t) ** 0.5 / (1 - b1 ** t)
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    return p - lr * m / (jnp.sqrt(v) + eps), m, v


class Reference:
    """The training state of the share and its step."""

    def __init__(self, cfg, weights, prec="float32", block=128):
        self.cfg = cfg
        self.names = [n for n, _, _ in param_spec(cfg)]
        self.p = {n: jnp.array(weights[n], jnp.float32, copy=True)
                  for n in self.names}
        self.m = {n: jnp.zeros_like(w) for n, w in self.p.items()}
        self.v = {n: jnp.zeros_like(w) for n, w in self.p.items()}
        self.t = 0

        def step(p, m, v, t, tokens):
            (loss, (parts, facts)), g = jax.value_and_grad(
                loss_terms, has_aux=True)(p, tokens, cfg, prec, block)
            new = {n: adam(p[n], g[n], m[n], v[n], t, cfg) for n in p}
            return ({n: c[0] for n, c in new.items()},
                    {n: c[1] for n, c in new.items()},
                    {n: c[2] for n, c in new.items()}, loss, parts, facts)

        self._step = jax.jit(step, donate_argnums=(0, 1, 2))

    def step(self, tokens):
        """One Adam step.  -> (loss, the three terms, facts of the step)."""
        self.t += 1
        with jax.default_matmul_precision("highest"):
            self.p, self.m, self.v, loss, parts, facts = self._step(
                self.p, self.m, self.v, jnp.float32(self.t), tokens)
        return loss, parts, facts
