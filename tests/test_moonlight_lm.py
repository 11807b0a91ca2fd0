"""Dense causal attention, latent attention, the sigmoid / bias router, the
shared expert and the zoo's Moonlight language model against the plain
reference (benchmark/reference/moonlight_lm.py) at a toy size: 1 dense + 2
expert layers, hidden 64, 4 heads of 16 + 8 / 12 over a latent of 20, 8
experts top-2 (4 held, from the third), 2 documents of 32; seeded weights.
"""
import os
import re
import sys

import numpy as np
import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, REPO)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu.gluon.functional import functionalize, make_train_step  # noqa: E402
from mxnet_tpu.gluon.model_zoo.text import MoonlightLM, MoonlightLMLoss  # noqa: E402
from mxnet_tpu.ops import transformer  # noqa: E402
from mxnet_tpu.parallel import moe  # noqa: E402

from benchmark import seeded  # noqa: E402
from benchmark.reference import moonlight_lm as ref  # noqa: E402

N, S = 2, 32
CFG = {"hidden_size": 64, "num_hidden_layers": 3, "first_k_dense_replace": 1,
       "num_attention_heads": 4, "kv_lora_rank": 20, "qk_nope_head_dim": 16,
       "qk_rope_head_dim": 8, "v_head_dim": 12, "q_lora_rank": None,
       "intermediate_size": 96, "moe_intermediate_size": 48,
       "n_routed_experts": 4, "n_shared_experts": 2, "num_experts_per_tok": 2,
       "norm_topk_prob": True, "routed_scaling_factor": 2.446,
       "scoring_func": "sigmoid", "topk_method": "noaux_tc", "n_group": 1,
       "topk_group": 1, "moe_layer_freq": 1, "seq_aux": True,
       "rms_norm_eps": 1e-5, "latent_norm_eps": 1e-6, "rope_theta": 50000,
       "vocab_size": 96,
       "deployment": {"first_expert": 2, "published": {"n_routed_experts": 8}},
       "learning_rate": 1e-3, "beta1": 0.9, "beta2": 0.95, "epsilon": 1e-8,
       "aux_loss_alpha": 0.001, "bias_update_rate": 0.001}
BIAS = [n for n, _, _ in ref.bias_spec(CFG)]


@pytest.fixture(scope="module")
def toy():
    """The program's step and state on seeded weights, the reference on the
    same, and the token ids."""
    net = MoonlightLM.from_config(CFG, attn_block=8, attn_span=16,
                                  bias_update_rate=CFG["bias_update_rate"])
    net.initialize()
    weights = seeded.make_weights(ref.param_spec(CFG) + ref.bias_spec(CFG), 5)
    step, state, (names, learn_idx, aux_idx) = make_train_step(
        net, MoonlightLMLoss(CFG["aux_loss_alpha"]), learning_rate=1e-3,
        optimizer="adam", beta1=0.9, beta2=0.95)
    short = [n[len(net.prefix):] for n in names]
    learn = [short[i] for i in learn_idx]
    assert {n: tuple(v.shape) for n, v in zip(learn, state[0])} \
        == {n: tuple(s) for n, s, _ in ref.param_spec(CFG)}
    # the selection bias is the model's auxiliary state, in the state's order
    assert [short[i] for i in aux_idx] == BIAS
    state = ([jnp.array(weights[n]) for n in learn], state[1],
             [jnp.array(weights[n]) for n in BIAS])
    tokens = jax.random.randint(jax.random.PRNGKey(1), (N, S), 0, 96)
    labels = jnp.concatenate(
        [tokens[:, 1:], jnp.full((N, 1), -1, jnp.int32)], 1)
    return net, learn, weights, step, state, tokens, labels


def test_forward_and_loss_terms_match_the_reference(toy):
    net, _, weights, _, _, tokens, labels = toy
    apply, order, _, _ = functionalize(net, train=True)
    vals = [weights[n[len(net.prefix):]] for n in order]
    out, new_bias = jax.jit(
        lambda v, t: apply(v, t, jax.random.PRNGKey(0)))(vals, tokens)
    bias = {n: weights[n] for n in BIAS}
    logits, balance, facts = ref.forward(weights, bias, tokens, CFG, block=8)
    np.testing.assert_allclose(out[0], logits, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(out[1], balance, rtol=1e-5)
    np.testing.assert_array_equal(out[2], np.stack(facts["expert_pairs"]))
    assert np.asarray(out[3]).tolist() == [0, 0]
    np.testing.assert_array_equal(out[4], np.stack(facts["load"]))
    assert np.asarray(out[4]).sum(1).tolist() == [N * S * 2] * 2
    order = np.argsort(out[5], 1), np.argsort(facts["choice"], 1)
    np.testing.assert_array_equal(np.take_along_axis(out[5], order[0], 1),
                                  np.take_along_axis(facts["choice"], order[1], 1))
    np.testing.assert_allclose(np.take_along_axis(out[6], order[0], 1),
                               np.take_along_axis(facts["gates"], order[1], 1),
                               rtol=1e-5)
    # under training the forward pass hands back the moved bias
    for n, load, got in zip(BIAS, facts["load"], new_bias):
        np.testing.assert_array_equal(got, ref.update_bias(bias[n], load, 1e-3))
    loss, aux = MoonlightLMLoss(0.001)([mx.nd.NDArray(o) for o in out],
                                       mx.nd.NDArray(labels))
    want, (parts, _) = ref.loss_terms(weights, bias, tokens, CFG, block=8)
    np.testing.assert_allclose(loss.asnumpy(), want, rtol=1e-5)
    for k in ("lm_loss", "balance_loss"):
        np.testing.assert_allclose(aux[k].asnumpy(), parts[k], rtol=1e-5)
    assert aux["router_pairs_max"].asnumpy().tolist() \
        == np.stack(facts["load"]).max(1).tolist()


def test_three_adam_steps_every_gradient_and_the_bias_match_the_reference(toy):
    """The step as the cell runs it: the model fed the labels too, the head's
    log-probabilities in row blocks inside it."""
    _, names, weights, step, state, tokens, labels = toy
    jstep = jax.jit(step)
    model = ref.Reference(CFG, weights, block=8)
    _, by_logits, _ = jstep(state, tokens, labels, jax.random.PRNGKey(0))
    for i in range(3):
        state, loss, aux = jstep(state, (tokens, labels), labels,
                                 jax.random.PRNGKey(0))
        if i == 0:
            np.testing.assert_allclose(loss, by_logits, rtol=1e-6)
        want, parts, _ = model.step(tokens)
        np.testing.assert_allclose(loss, want, rtol=1e-5)
        np.testing.assert_allclose(aux["balance_loss"], parts["balance_loss"],
                                   rtol=1e-5)
        for n, b in zip(BIAS, state[2]):        # the bias after each step
            np.testing.assert_array_equal(b, model.bias[n], err_msg=n)
            assert b.dtype == jnp.float32
        if i == 0:      # Adam's first moment is a tenth of the first gradient
            for n, m in zip(names, state[1]["mean"]):
                g = np.asarray(model.m[n])
                np.testing.assert_allclose(m, g, rtol=2e-3,
                                           atol=1e-5 * np.abs(g).max(), err_msg=n)
    assert int(state[1]["t"]) == 3
    for n, p in zip(names, state[0]):
        np.testing.assert_allclose(p, model.p[n], atol=2e-5, err_msg=n)
        assert float(jnp.abs(p - weights[n]).max()) > 1e-4, n   # every leaf moved
    for n, b in zip(BIAS, state[2]):
        moved = np.abs(np.asarray(b - weights[n])) / 1e-3
        assert set(np.round(moved).tolist()) <= {0.0, 1.0, 2.0, 3.0}
        assert moved.max() > 0.5


def _plain_attention(q, k, v):
    """(N, S, H, d) masked softmax over a (N, H, S, S) array."""
    s = jnp.einsum("nqhd,nkhd->nhqk", q, k) * q.shape[-1] ** -0.5
    mask = jnp.arange(q.shape[1])[:, None] >= jnp.arange(q.shape[1])[None, :]
    return jnp.einsum("nhqk,nkhd->nqhd",
                      jax.nn.softmax(jnp.where(mask, s, -jnp.inf), -1), v)


def _qkv(key, hkv=4):
    kq, kk, kv = jax.random.split(key, 3)
    return (jax.random.normal(kq, (N, S, 4, 24)),
            jax.random.normal(kk, (N, S, hkv, 24)),
            jax.random.normal(kv, (N, S, hkv, 12)))


@pytest.mark.parametrize("block,span", [(8, 16), (32, 32), (4, 8)])
def test_causal_attention_is_the_plain_masked_softmax(block, span):
    q, k, v = _qkv(jax.random.PRNGKey(3))
    want = _plain_attention(q, k, v)
    got = transformer.causal_attention(q, k, v, block=block, span=span)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    w = jax.random.normal(jax.random.PRNGKey(4), want.shape)
    grads = [jax.jit(jax.grad(lambda q, k, v, f=f: jnp.sum(f(q, k, v) * w),
                              argnums=(0, 1, 2)))(q, k, v)
             for f in (lambda *a: transformer.causal_attention(
                 *a, block=block, span=span), _plain_attention)]
    for g, want_g in zip(*grads):
        np.testing.assert_allclose(g, want_g, rtol=1e-3, atol=1e-4)
    # one document without the leading axis is the same document
    np.testing.assert_array_equal(
        transformer.causal_attention(q[1], k[1], v[1], block=block, span=span),
        got[1])


def test_causal_attention_shares_key_heads_and_checks_its_tiles():
    q, k, v = _qkv(jax.random.PRNGKey(5), hkv=2)
    want = _plain_attention(q, jnp.repeat(k, 2, 2), jnp.repeat(v, 2, 2))
    np.testing.assert_allclose(
        transformer.causal_attention(q, k, v, block=8, span=16), want,
        rtol=1e-4, atol=1e-5)
    with pytest.raises(ValueError, match="must divide"):
        transformer.causal_attention(q, k, v, block=6, span=16)


def test_two_documents_do_not_see_each_other(toy):
    """Causal attention, the latent attention around it and the whole model:
    a document's result does not move with the other document."""
    q, k, v = _qkv(jax.random.PRNGKey(6))
    both = transformer.causal_attention(q, k, v, block=8, span=16)
    other = transformer.causal_attention(
        q, k.at[0].set(-k[0]), v.at[0].set(0.0), block=8, span=16)
    np.testing.assert_array_equal(both[1], other[1])
    assert float(jnp.abs(both[0] - other[0]).max()) > 0.1
    # and a query does not see later keys of its own document
    late = transformer.causal_attention(
        q, k.at[:, S // 2:].set(0.0), v.at[:, S // 2:].set(9.0),
        block=8, span=16)
    np.testing.assert_array_equal(both[:, :S // 2], late[:, :S // 2])
    net, _, weights, _, _, tokens, _ = toy
    apply, order, _, _ = functionalize(net, train=False)
    vals = [weights[n[len(net.prefix):]] for n in order]
    run = jax.jit(lambda t: apply(vals, t, jax.random.PRNGKey(0))[0][0])
    swapped = run(tokens.at[0].set((tokens[0] + 1) % 96))
    np.testing.assert_array_equal(run(tokens)[1], swapped[1])


def test_no_heads_by_sequence_by_sequence_array_in_the_lowered_step(toy):
    _, _, _, step, state, tokens, labels = toy
    text = jax.jit(step).lower(state, tokens, labels,
                               jax.random.PRNGKey(0)).as_text()
    shapes = [tuple(int(d) for d in m.split("x")[:-1]) for m in
              re.findall(r"tensor<((?:\d+x)+[a-z]\w*)>", text)]
    assert any(s.count(S) == 1 and 8 in s for s in shapes)   # a block's weights
    assert not [s for s in shapes if s.count(S) >= 2]
    # the plain form does hold one
    q, k, v = _qkv(jax.random.PRNGKey(3))
    plain = jax.jit(_plain_attention).lower(q, k, v).as_text()
    assert "x%dx%dx" % (S, S) in plain


def test_head_log_probabilities_in_row_blocks_are_the_plain_log_softmax():
    ks = jax.random.split(jax.random.PRNGKey(12), 3)
    x = jax.random.normal(ks[0], (N, S, 24))
    w = jax.random.normal(ks[1], (96, 24)) * 0.5
    y = jax.random.randint(ks[2], (N, S), -1, 96)
    assert int((y < 0).sum()) > 0

    def plain(x, w):
        logp = jax.nn.log_softmax(jnp.einsum("nsd,vd->nsv", x, w), -1)
        return jnp.take_along_axis(logp, jnp.maximum(y, 0)[..., None],
                                   -1)[..., 0] * (y >= 0)

    blocks = lambda x, w: transformer.lm_head_log_prob(x, w, y, block=16)  # noqa: E731
    np.testing.assert_allclose(blocks(x, w), plain(x, w), rtol=1e-5, atol=1e-6)
    c = jax.random.normal(jax.random.PRNGKey(13), (N, S))
    for got, want in zip(*[jax.grad(lambda x, w, f=f: jnp.sum(f(x, w) * c),
                                    argnums=(0, 1))(x, w)
                           for f in (blocks, plain)]):
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    text = jax.jit(jax.grad(lambda x, w: jnp.sum(blocks(x, w)))).lower(
        x, w).as_text()
    assert "x%dx96x" % (N * S) not in text and "16x96x" in text
    with pytest.raises(ValueError, match="do not divide"):
        transformer.lm_head_log_prob(x, w, y, block=24)


def test_latent_attention_is_the_references():
    D, H, L, dn, dr, dv = 64, 4, 20, 16, 8, 12
    ks = jax.random.split(jax.random.PRNGKey(7), 6)
    a = jax.random.normal(ks[0], (N, S, D))
    w = {"attn_q_weight": jax.random.normal(ks[1], (H * (dn + dr), D)) * 0.2,
         "attn_kv_a_weight": jax.random.normal(ks[2], (L + dr, D)) * 0.2,
         "attn_kv_norm_gamma": 1 + 0.1 * jax.random.normal(ks[3], (L,)),
         "attn_kv_b_weight": jax.random.normal(ks[4], (H * (dn + dv), L)) * 0.3}
    op = dict(num_heads=H, qk_nope_dim=dn, qk_rope_dim=dr, v_dim=dv,
              theta=50000.0, block=8, span=16)
    got = transformer.latent_attention(a, jnp.arange(S), *w.values(), **op)
    want = jnp.stack([ref.latent_attention(a[n], w.__getitem__, CFG,
                                           "float32", 8) for n in range(N)])
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    # the rotary key is one for all heads: a key's rope dims are equal
    _, k, _ = transformer._latent_project(
        (H, dn, dr, dv, 50000.0, 1e-6), a, jnp.arange(S), *w.values())
    np.testing.assert_array_equal(k[:, :, 0, dn:], k[:, :, 3, dn:])
    assert float(jnp.abs(k[:, :, 0, :dn] - k[:, :, 3, :dn]).max()) > 0.1


def _moe_weights(key, T=N * S, E=8, D=64, F=32):
    ks = jax.random.split(key, 9)
    n = jax.random.normal
    return {"x": n(ks[0], (T, D)), "wr": n(ks[1], (E, D)) * 0.3,
            "wg": n(ks[2], (E, D, F)) * 0.2, "wu": n(ks[3], (E, D, F)) * 0.2,
            "wd": n(ks[4], (E, F, D)) * 0.2,
            "shared": (n(ks[5], (2 * F, D)) * 0.2, n(ks[6], (2 * F, D)) * 0.2,
                       n(ks[7], (D, 2 * F)) * 0.2),
            "bias": n(ks[8], (E,)) * 0.3}


def _reference_layer(m, first, held):
    """The reference's expert layer over experts first .. first + held - 1."""
    w = {"moe_router_weight": m["wr"], "moe_gate_weight": m["wg"][first:first + held],
         "moe_up_weight": m["wu"][first:first + held],
         "moe_down_weight": m["wd"][first:first + held],
         "moe_shared_gate_weight": m["shared"][0],
         "moe_shared_up_weight": m["shared"][1],
         "moe_shared_down_weight": m["shared"][2]}
    return ref.expert_layer(m["x"], w.__getitem__, m["bias"], CFG, "float32",
                            first, N)


def test_the_eight_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    """Guide section 4: each share told its own held expert; the held parts
    and what every chip computes alike, the shared expert, counted once, add
    up to the uncut reference's layer."""
    m = _moe_weights(jax.random.PRNGKey(2))
    whole, balance, (choice, gates), pairs, load = _reference_layer(m, 0, 8)
    shared = ref.gated_ffn(m["x"], *m["shared"], "float32")
    held_parts, n_pairs = 0.0, []
    for first in range(8):
        y, aux = moe.moe_layer(
            m["x"], m["wr"], m["wg"][first:first + 1], m["wu"][first:first + 1],
            m["wd"][first:first + 1], top_k=2, first_expert=first,
            scoring="sigmoid", router_bias=m["bias"], routed_scale=2.446,
            shared=m["shared"], sequences=N)
        assert int(aux["dropped"]) == 0
        np.testing.assert_allclose(aux["balance"], balance, rtol=1e-5)
        np.testing.assert_array_equal(aux["router_pairs"], load)
        np.testing.assert_array_equal(np.sort(aux["choice"], 1),
                                      np.sort(choice, 1))
        np.testing.assert_allclose(np.sort(aux["gates"], 1), np.sort(gates, 1),
                                   rtol=1e-5)
        np.testing.assert_allclose(y, _reference_layer(m, first, 1)[0],
                                   atol=2e-5)
        held_parts = held_parts + (y - shared)
        n_pairs += list(np.asarray(aux["pairs"]))
    np.testing.assert_allclose(held_parts + shared, whole, atol=5e-5)
    assert n_pairs == list(np.asarray(pairs)) and sum(n_pairs) == 2 * N * S
    assert float(jnp.abs(shared).mean()) > 0.1 * float(jnp.abs(whole).mean())


def test_the_bias_changes_the_choice_and_never_the_gate():
    m = _moe_weights(jax.random.PRNGKey(8))
    x, wr = m["x"], m["wr"]
    scores, plain, gates = moe.route(x, wr, 2, scoring="sigmoid", scale=2.446)
    # a bias that lifts expert 5 above every score: chosen by every token
    lifted = jnp.zeros(8).at[5].set(2.0)
    s2, choice, g2 = moe.route(x, wr, 2, scoring="sigmoid", bias=lifted,
                               scale=2.446)
    np.testing.assert_array_equal(scores, s2)
    assert (np.asarray(choice) == 5).any(1).all()
    assert not (np.asarray(plain) == 5).any(1).all()
    # gates are the unbiased scores of the chosen, normalised and scaled
    top = jnp.take_along_axis(scores, choice, 1)
    np.testing.assert_allclose(g2, top / top.sum(1, keepdims=True) * 2.446,
                               rtol=1e-6)
    np.testing.assert_allclose(g2.sum(1), 2.446, rtol=1e-5)
    assert float(jnp.max(g2)) < 2.446           # the 2.0 never entered a gate
    # a constant bias changes nothing; no gradient reaches the bias
    _, same, g3 = moe.route(x, wr, 2, scoring="sigmoid", bias=jnp.full(8, 0.7),
                            scale=2.446)
    np.testing.assert_array_equal(np.sort(same, 1), np.sort(plain, 1))
    np.testing.assert_allclose(np.sort(g3, 1), np.sort(gates, 1), rtol=1e-6)
    d_bias = jax.grad(lambda b: jnp.sum(moe.route(
        x, wr, 2, scoring="sigmoid", bias=b)[2] ** 2))(lifted)
    assert float(jnp.abs(d_bias).max()) == 0.0
    with pytest.raises(ValueError, match="'softmax' or 'sigmoid'"):
        moe.route(x, wr, 2, scoring="tanh")


def test_routes_softmax_default_is_what_it_was():
    """The Keye cell runs the same function: probabilities, choice and gates
    of the default form, bit for bit, as the formula before the other form
    was an argument."""
    m = _moe_weights(jax.random.PRNGKey(9))
    x, wr = m["x"], m["wr"]
    logits = jnp.einsum("td,ed->te", x, wr, precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, choice = jax.lax.top_k(probs, 2)
    for normalize, want in ((True, gates / gates.sum(-1, keepdims=True)),
                            (False, gates)):
        got = moe.route(x, wr, 2, normalize)
        np.testing.assert_array_equal(got[0], probs)
        np.testing.assert_array_equal(got[1], choice)
        np.testing.assert_array_equal(got[2], want)
    text = [jax.jit(lambda x, wr, f=f: f(x, wr, 2)).lower(x, wr).as_text()
            for f in (moe.route, lambda x, wr, k: moe.route(
                x, wr, k, True, "softmax", None, 1.0))]
    assert text[0] == text[1] and "logistic" not in text[0]
    y, aux = moe.moe_layer(x, wr, m["wg"][2:6], m["wu"][2:6], m["wd"][2:6],
                           top_k=2, first_expert=2)
    frac = jnp.sum(jax.nn.one_hot(choice.reshape(-1), 8), 0) / x.shape[0]
    np.testing.assert_array_equal(
        aux["balance"], 8 * jnp.sum(frac * jnp.mean(probs, axis=0)))


def test_the_block_and_the_operators_run_eagerly_through_nd():
    """``mx.nd.CausalAttention`` / ``LatentAttention`` and ``MoEExperts``
    with its optional inputs by name; the bias moves only under training."""
    from mxnet_tpu import autograd
    from mxnet_tpu.gluon import nn

    q, k, v = _qkv(jax.random.PRNGKey(10))
    out = mx.nd.CausalAttention(*(mx.nd.NDArray(a) for a in (q, k, v)),
                                block=8, span=16)
    np.testing.assert_allclose(out.asnumpy(), _plain_attention(q, k, v),
                               rtol=1e-4, atol=1e-5)
    x, w = q[..., 0, :], jax.random.normal(jax.random.PRNGKey(14), (96, 24))
    y = jax.random.randint(jax.random.PRNGKey(15), (N, S), -1, 96)
    np.testing.assert_array_equal(
        mx.nd.LMHeadLogProb(*(mx.nd.NDArray(a) for a in (x, w, y)),
                            block=16).asnumpy(),
        transformer.lm_head_log_prob(x, w, y, block=16))
    layer = nn.SparseMoE(64, 32, num_experts=8, top_k=2, num_held=4,
                         first_expert=2, scoring="sigmoid", routed_scale=2.446,
                         bias_update_rate=0.01, shared_units=64,
                         sequence_balance=True,
                         weight_initializer=mx.init.Normal(0.2))
    layer.initialize()
    x = mx.nd.NDArray(jax.random.normal(jax.random.PRNGKey(11), (N, S, 64)))
    out = layer(x)
    assert out[0].shape == (N, S, 64) and len(out) == 7
    np.testing.assert_allclose(out[6].asnumpy().sum(1), 2.446, rtol=1e-5)
    assert out[5].asnumpy().sum() == 2 * N * S
    assert float(np.abs(layer.router_bias.data().asnumpy()).max()) == 0.0
    with autograd.record():
        layer(x)
    load = out[5].asnumpy()
    np.testing.assert_allclose(layer.router_bias.data().asnumpy(),
                               0.01 * np.sign(load.mean() - load), rtol=1e-6)
    attn = nn.LatentAttention(64, 4, 20, 16, 8, 12, theta=50000.0, block=8,
                              span=16, weight_initializer=mx.init.Normal(0.2))
    attn.initialize()
    assert attn(x, mx.nd.arange(S)).shape == (N, S, 64)
    assert sorted(p[len(attn.prefix):] for p in attn.collect_params()) == [
        "kv_a_weight", "kv_b_weight", "kv_norm_gamma", "o_weight", "q_weight"]
    with pytest.raises(ValueError, match="group-limited"):
        MoonlightLM.from_config(dict(CFG, n_group=8, topk_group=4))
    with pytest.raises(ValueError, match="query latent"):
        MoonlightLM.from_config(dict(CFG, q_lora_rank=1536))
