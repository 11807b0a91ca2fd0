"""Plain reference of Ouro-2.6B (``model_type: ouro``, a looped language
model) as one pipeline stage's layers are trained: forward over every pass,
the expected loss over the exits with its entropy term, gradients and Adam,
in ``jax.numpy`` float32 with every product at ``Precision.HIGHEST``.  A
Python loop over the passes and the layers.  Imports nothing of
``mxnet_tpu``.

Tokens ``(N, S)``, documents apart (configuration keys in brackets):

1. ``x = Embedding(tokens)`` [vocab_size, hidden_size].
2. For pass ``t = 1 .. T`` [total_ut_steps], for layer ``l = 1 .. L``
   [num_hidden_layers], THE SAME WEIGHTS in every pass:
   ``a = RMSNorm_{l,1}(x)`` [rms_norm_eps]; ``q, k, v = a Wq, a Wk, a Wv`` ->
   [num_attention_heads] = [num_key_value_heads] heads of [head_dim]; rotary
   embedding [rope_theta], half-rotation form, over the whole head of ``q``
   and ``k``, positions ``0 .. S-1``; ``o_h[t'] = softmax over s <= t' of
   (q_h[t'] . k_h[s] / sqrt(head_dim)) v_h`` (every layer full attention);
   ``y = x + RMSNorm_{l,2}(concat(o) Wo)``;
   ``b = RMSNorm_{l,3}(y)``; ``x = y + RMSNorm_{l,4}((silu(b Wg) * (b Wu))
   Wd)`` [intermediate_size, hidden_act].
3. After each pass: ``h_t = RMSNorm_final(x)``; ``x <- h_t``;
   ``lambda_t = sigmoid(h_t . w_gate + b_gate)``; ``l_t = -log softmax(h_t
   W_head^T)[label]`` [tie_word_embeddings false].
4. ``p_t = lambda_t prod_{j<t} (1 - lambda_j)`` for ``t < T``, ``p_T =
   prod_{j<T} (1 - lambda_j)``.  Loss = mean over the labelled positions of
   ``sum_t p_t l_t -`` [entropy_beta] ``x H(p)``, ``H(p) = -sum_t p_t log
   max(p_t, 1e-30)``.

Departures and readings, each also under ``assumed`` in the configuration's
file: the norms' places, the final norm inside the loop and the gate's form
(the family's modeling file); the objective (the paper's first stage, a
uniform prior over the exits); rotary in half-rotation form; the last
position of a document has no label; Adam is MXNet's ``adam_update``
(benchmark/reference/keye_lm.py).

Memory: each layer application and each exit is recomputed in the backward
pass, attention walks a document's queries in blocks of ``block`` rows
against all its keys under a mask, and the head walks its rows ``8 x block``
at a time, keeping of each block only the labels' log-probabilities (whole,
one exit's float32 log-softmax and its gradient are 0.8 GB each at the
benchmark's size): none changes a result.
"""
import jax
import jax.numpy as jnp
from jax import lax

from benchmark.reference import precision
from benchmark.reference.keye_lm import adam, rms_norm, rope

LAYER_LEAVES = ("attn_norm_gamma", "attn_q_weight", "attn_k_weight",
                "attn_v_weight", "attn_o_weight", "attn_post_norm_gamma",
                "ffn_norm_gamma", "ffn_gate_weight", "ffn_up_weight",
                "ffn_down_weight", "ffn_post_norm_gamma")


def param_spec(cfg):
    """[(name, shape, kind)]: every trained leaf, ``kind`` one of
    benchmark/seeded.py's.  Dense weights are (out, in)."""
    D, F, V = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    H, Hkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    if H != Hkv:
        raise ValueError("every query head has its own key-value head")
    shapes = {"attn_q_weight": (H * d, D), "attn_k_weight": (Hkv * d, D),
              "attn_v_weight": (Hkv * d, D), "attn_o_weight": (D, H * d),
              "ffn_gate_weight": (F, D), "ffn_up_weight": (F, D),
              "ffn_down_weight": (D, F)}
    spec = [("embed_weight", (V, D), "head")]
    for l in range(cfg["num_hidden_layers"]):
        spec += [("l%d_%s" % (l, n), shapes.get(n, (D,)),
                  "head" if n in shapes else "gamma") for n in LAYER_LEAVES]
    return spec + [("final_norm_gamma", (D,), "gamma"),
                   ("gate_weight", (1, D), "head"), ("gate_bias", (1,), "bias"),
                   ("head_weight", (V, D), "head")]


def attention(a, g, cfg, prec, block):
    """Step 2's attention of one document after the norm, before ``Wo``:
    a (S, D) -> (S, H x d)."""
    H, d = cfg["num_attention_heads"], cfg["head_dim"]
    S = a.shape[0]
    pos = jnp.arange(S)[None]
    mm = lambda x, w: precision.einsum("td,od->to", x, w, prec)   # noqa: E731
    rot = lambda x: rope(x.reshape(S, H, d), pos,                 # noqa: E731
                         float(cfg["rope_theta"]), [d // 2])
    q, k = rot(mm(a, g("attn_q_weight"))), rot(mm(a, g("attn_k_weight")))
    v = mm(a, g("attn_v_weight")).reshape(S, H, d)
    block = min(block, S)
    s_all = jnp.arange(S)

    @jax.checkpoint
    def attend(blk):
        q_b, t_b = blk
        s = precision.einsum("qhd,khd->hqk", q_b, k, prec) * d ** -0.5
        p = jax.nn.softmax(jnp.where(s_all[None, :] <= t_b[:, None], s,
                                     -jnp.inf), -1)
        return precision.einsum("hqk,khd->qhd", p, v, prec)

    o = lax.map(attend, (q.reshape(S // block, block, H, d),
                         s_all.reshape(S // block, block)))
    return o.reshape(S, H * d)


def gated_ffn(b, wg, wu, wd, prec):
    """``(silu(b Wg^T) * (b Wu^T)) Wd^T``, weights (out, in)."""
    h = jax.nn.silu(precision.einsum("td,fd->tf", b, wg, prec)) \
        * precision.einsum("td,fd->tf", b, wu, prec)
    return precision.einsum("tf,df->td", h, wd, prec)


def layer(x, w, l, docs, cfg, prec, block):
    """One application of layer ``l`` to x (docs x S, D)."""
    eps = cfg["rms_norm_eps"]
    g = lambda n: w["l%d_%s" % (l, n)]                            # noqa: E731
    S = x.shape[0] // docs
    a = rms_norm(x, g("attn_norm_gamma"), eps)
    o = jnp.concatenate([attention(a[n * S:(n + 1) * S], g, cfg, prec, block)
                         for n in range(docs)])
    y = x + rms_norm(precision.einsum("td,od->to", o, g("attn_o_weight"),
                                      prec), g("attn_post_norm_gamma"), eps)
    b = rms_norm(y, g("ffn_norm_gamma"), eps)
    return y + rms_norm(gated_ffn(b, g("ffn_gate_weight"), g("ffn_up_weight"),
                                  g("ffn_down_weight"), prec),
                        g("ffn_post_norm_gamma"), eps)


def log_probs(h, w, prec, rows, labels=None):
    """h (T, D) -> log softmax(h W^T) (T, V), ``rows`` rows at a time; given
    ``labels`` (T,), each row's log-probability of its label (T,) instead, 0
    where the label is negative, and no (T, V) array outlives a block."""
    rows = min(rows, h.shape[0])

    def block(blk):
        x, y = blk
        logp = jax.nn.log_softmax(
            precision.einsum("td,vd->tv", x, w, prec), -1)
        if labels is None:
            return logp
        picked = jnp.take_along_axis(logp, jnp.maximum(y, 0)[:, None], 1)
        return jnp.where(y >= 0, picked[:, 0], 0.0)

    y = jnp.zeros(h.shape[:1], jnp.int32) if labels is None else labels
    out = lax.map(jax.checkpoint(block), (h.reshape(-1, rows, h.shape[1]),
                                          y.reshape(-1, rows)))
    return out.reshape((h.shape[0],) + out.shape[2:])


def passes(p, tokens, cfg, prec, block, read):
    """Steps 1-3.  ``read(h (N x S, D))`` is what is kept of each exit.
    -> ([read(h_t)], gates (T, N, S), layer applications made)."""
    N, S = tokens.shape
    one = jax.checkpoint(layer, static_argnums=(2, 3, 4, 5, 6))
    x = p["embed_weight"][tokens.reshape(-1)]
    kept, gates, applied = [], [], 0
    for _ in range(cfg["total_ut_steps"]):
        for l in range(cfg["num_hidden_layers"]):
            x = one(x, p, l, N, cfg, prec, block)
            applied += 1
        x = rms_norm(x, p["final_norm_gamma"], cfg["rms_norm_eps"])
        gates.append(jax.nn.sigmoid(
            jnp.sum(x * p["gate_weight"][0], -1) + p["gate_bias"][0]))
        kept.append(read(x))
    return kept, jnp.stack(gates).reshape(-1, N, S), applied


def forward(p, tokens, cfg, prec="float32", block=128):
    """-> (every exit's log-probabilities (T, N, S, vocab), gates (T, N, S),
    layer applications)."""
    N, S = tokens.shape
    logp, gates, applied = passes(
        p, tokens, cfg, prec, block,
        lambda h: log_probs(h, p["head_weight"], prec, 8 * block))
    return jnp.stack(logp).reshape(len(logp), N, S, -1), gates, applied


def exit_shares(gates):
    """Step 4: (T, ..) gates -> (T, ..) shares that leave at each exit."""
    shares, stay = [], 1.0
    for lam in gates[:-1]:
        shares.append(lam * stay)
        stay = stay * (1.0 - lam)
    return jnp.stack(shares + [stay])


def loss_terms(p, tokens, cfg, prec="float32", block=128):
    """-> (loss, (parts, facts)); labels are the ids shifted by one within
    each document, its last position without one."""
    N, S = tokens.shape
    labels = jnp.concatenate(
        [tokens[:, 1:], jnp.full((N, 1), -1, tokens.dtype)], 1)
    valid = labels >= 0
    count = jnp.sum(valid)
    picked = jax.checkpoint(lambda h: log_probs(
        h, p["head_weight"], prec, 8 * block, labels.reshape(-1)))
    logp, gates, applied = passes(p, tokens, cfg, prec, block, picked)
    nll = -jnp.stack(logp).reshape(-1, N, S)          # 0 without a label
    shares = exit_shares(gates)
    mean = lambda x: jnp.sum(x * valid, (-2, -1)) / count        # noqa: E731
    expected = mean(jnp.sum(shares * nll, 0))
    entropy = mean(-jnp.sum(shares * jnp.log(jnp.maximum(shares, 1e-30)), 0))
    loss = expected - cfg["entropy_beta"] * entropy
    exits = jnp.arange(1, shares.shape[0] + 1, dtype=jnp.float32)
    facts = {"lm_loss_exits": mean(nll), "exit_mass": mean(shares),
             "expected_exit_step": jnp.mean(jnp.tensordot(exits, shares, 1)),
             "layer_applications": applied}
    return loss, ({"expected_lm_loss": expected, "exit_entropy": entropy},
                  facts)


class Reference:
    """The training state and its step."""

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
        """One Adam step.  -> (loss, the two terms, facts of the step)."""
        self.t += 1
        with jax.default_matmul_precision("highest"):
            self.p, self.m, self.v, loss, parts, facts = self._step(
                self.p, self.m, self.v, jnp.float32(self.t), tokens)
        return loss, parts, facts
