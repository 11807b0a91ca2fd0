"""The transformer operators, blocks and the zoo's language model against the
plain reference (benchmark/reference/keye_lm.py) at a toy size: 2 layers,
hidden 64, 8 experts top-2, 16 indexer-selected keys of 64; seeded weights.
"""
import os
import sys

import numpy as np
import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, REPO)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu.gluon import nn  # noqa: E402
from mxnet_tpu.gluon.functional import functionalize, make_train_step  # noqa: E402
from mxnet_tpu.gluon.model_zoo.text import KeyeLM, KeyeLMLoss  # noqa: E402
from mxnet_tpu.ops import transformer  # noqa: E402
from mxnet_tpu.parallel import moe  # noqa: E402

from benchmark import seeded  # noqa: E402
from benchmark.reference import keye_lm as ref  # noqa: E402

S = 64
CFG = {"hidden_size": 64, "num_hidden_layers": 2, "num_attention_heads": 4,
       "num_key_value_heads": 2, "head_dim": 16, "moe_intermediate_size": 32,
       "num_local_experts": 8, "num_experts": 4, "num_experts_per_tok": 2,
       "norm_topk_prob": True, "rms_norm_eps": 1e-6, "rope_theta": 1e7,
       "rope_scaling": {"mrope_section": [2, 3, 3]}, "vocab_size": 96,
       "sa_config": {"indexer_head_dim": 8, "indexer_num_heads": 4, "topk": 16},
       "deployment": {"first_expert": 2}, "learning_rate": 1e-3, "beta1": 0.9,
       "beta2": 0.95, "epsilon": 1e-8, "balance_coef": 0.001}


@pytest.fixture(scope="module")
def toy():
    """The program's step and state on seeded weights, the reference on the
    same, and the token ids."""
    net = KeyeLM.from_config(CFG, attn_block=16, attn_span=32)
    net.initialize()
    weights = seeded.make_weights(ref.param_spec(CFG), 5)
    step, state, (names, learn_idx, _) = make_train_step(
        net, KeyeLMLoss(CFG["balance_coef"]), learning_rate=1e-3,
        optimizer="adam", beta1=0.9, beta2=0.95)
    names = [names[i][len(net.prefix):] for i in learn_idx]
    assert {n: tuple(v.shape) for n, v in zip(names, state[0])} \
        == {n: tuple(s) for n, s, _ in ref.param_spec(CFG)}
    state = ([jnp.array(weights[n]) for n in names],) + tuple(state[1:])
    tokens = jax.random.randint(jax.random.PRNGKey(1), (S,), 0, 96)
    labels = jnp.concatenate([tokens[1:], jnp.array([-1], jnp.int32)])
    return net, names, weights, step, state, tokens, labels


def test_forward_and_loss_terms_match_the_reference(toy):
    net, names, weights, _, state, tokens, labels = toy
    apply, order, _, _ = functionalize(net, train=True)
    vals = [weights[n[len(net.prefix):]] for n in order]
    out, _ = jax.jit(lambda v, t: apply(v, t, jax.random.PRNGKey(0)))(vals, tokens)
    logits, balance, kl, facts = ref.forward(weights, tokens, CFG, block=16)
    np.testing.assert_allclose(out[0], logits, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(out[1], balance, rtol=1e-5)
    np.testing.assert_allclose(out[2], kl, rtol=1e-4)
    assert [int(x) for x in out[3]] == [int(x) for x in facts["selected_keys"]]
    assert np.asarray(out[4]).tolist() == [S * (S + 1) // 2] * 2
    np.testing.assert_array_equal(out[5], np.stack(facts["expert_pairs"]))
    assert np.asarray(out[6]).tolist() == [0, 0]
    np.testing.assert_array_equal(out[7], facts["selection"])
    np.testing.assert_array_equal(np.sort(out[8], 1),
                                  np.sort(facts["choice"], 1))
    loss, aux = KeyeLMLoss(0.001)([mx.nd.NDArray(o) for o in out],
                                  mx.nd.NDArray(labels))
    want, (parts, _) = ref.loss_terms(weights, tokens, CFG, block=16)
    np.testing.assert_allclose(loss.asnumpy(), want, rtol=1e-5)
    for k in ("lm_loss", "balance_loss", "indexer_kl"):
        np.testing.assert_allclose(aux[k].asnumpy(), parts[k], rtol=1e-4)


def test_three_adam_steps_and_every_gradient_match_the_reference(toy):
    _, names, weights, step, state, tokens, labels = toy
    jstep = jax.jit(step)
    model = ref.Reference(CFG, weights, block=16)
    for i in range(3):
        state, loss, aux = jstep(state, tokens, labels, jax.random.PRNGKey(0))
        want, parts, _ = model.step(tokens)
        np.testing.assert_allclose(loss, want, rtol=1e-5)
        np.testing.assert_allclose(aux["indexer_kl"], parts["indexer_kl"],
                                   rtol=1e-4)
        if i == 0:      # Adam's first moment is a tenth of the first gradient
            for n, m in zip(names, state[1]["mean"]):
                g = np.asarray(model.m[n])
                np.testing.assert_allclose(m, g, rtol=2e-3,
                                           atol=1e-5 * np.abs(g).max(), err_msg=n)
    assert int(state[1]["t"]) == 3
    for n, p in zip(names, state[0]):
        np.testing.assert_allclose(p, model.p[n], atol=2e-5, err_msg=n)
        assert float(jnp.abs(p - weights[n]).max()) > 1e-4, n   # every leaf moved


def test_indexer_learns_from_the_kl_term_alone(toy):
    net, names, weights, _, _, tokens, labels = toy
    apply, order, _, _ = functionalize(net, train=True)
    short = [n[len(net.prefix):] for n in order]

    def terms(vals):
        out, _ = apply(vals, tokens, jax.random.PRNGKey(0))
        _, aux = KeyeLMLoss(1.0)([mx.nd.NDArray(o) for o in out],
                                 mx.nd.NDArray(labels))
        return jnp.stack([aux["lm_loss"]._data + aux["balance_loss"]._data,
                          aux["indexer_kl"]._data])

    vals = [weights[n] for n in short]
    g_rest, g_kl = (jax.jit(jax.grad(lambda v, i=i: terms(v)[i]))(vals)
                    for i in (0, 1))
    for n, a, b in zip(short, g_rest, g_kl):
        rest, kl = float(jnp.abs(a).max()), float(jnp.abs(b).max())
        if ref.is_indexer(n):
            assert rest == 0.0 and kl > 0.0, n
        else:
            assert rest > 0.0 and kl == 0.0, n


def test_equal_ids_mrope_is_one_dimensional_rotary():
    x = jax.random.normal(jax.random.PRNGKey(0), (S, 3, 16))
    pos = jnp.arange(S)
    one = transformer.rotary_embedding(x, pos, theta=1e7)
    three = transformer.rotary_embedding(x, jnp.tile(pos[None], (3, 1)),
                                         theta=1e7, sections=(2, 3, 3))
    np.testing.assert_array_equal(one, three)
    # and different ids are different rotations, section by section
    ids = jnp.stack([pos, pos * 0, pos * 0])
    mixed = transformer.rotary_embedding(x, ids, theta=1e7, sections=(2, 3, 3))
    np.testing.assert_array_equal(mixed[..., :2], one[..., :2])
    np.testing.assert_array_equal(mixed[..., 2:8], x[..., 2:8])
    np.testing.assert_allclose(one, ref.rope(x, jnp.tile(pos[None], (3, 1)),
                                             1e7, [2, 3, 3]), atol=1e-6)
    with pytest.raises(ValueError, match="sections"):
        transformer.rotary_embedding(x, ids, theta=1e7, sections=(2, 3))


def test_threshold_selection_is_top_k_where_no_scores_tie():
    scores = jax.random.normal(jax.random.PRNGKey(3), (32, 256))
    scores = scores.at[0, :5].set(jnp.array([0.0, -0.0, 1e-40, -1e-40, 3.0]))
    keys = transformer._sortable(scores)
    order = np.argsort(np.asarray(scores), axis=1, kind="stable")
    assert (np.diff(np.take_along_axis(np.asarray(keys).astype(np.int64),
                                       order, 1), axis=1) >= 0).all()
    for k in (1, 7, 64, 256):
        tau = transformer._kth_largest(keys, k)
        want = jax.lax.top_k(scores, k)[0][:, -1]
        np.testing.assert_array_equal(tau, transformer._sortable(want))
        assert ((keys >= tau[:, None]).sum(1) == k).all()
    tied = jnp.zeros((2, 64)).at[:, :3].set(1.0)       # ties keep them all
    tau = transformer._kth_largest(transformer._sortable(tied), 8)
    assert ((transformer._sortable(tied) >= tau[:, None]).sum(1) == 64).all()


def _moe_weights(key, E=8, D=64, F=32):
    ks = jax.random.split(key, 5)
    return (jax.random.normal(ks[0], (S, D)),
            jax.random.normal(ks[1], (E, D)) * 0.3,
            jax.random.normal(ks[2], (E, D, F)) * 0.2,
            jax.random.normal(ks[3], (E, D, F)) * 0.2,
            jax.random.normal(ks[4], (E, F, D)) * 0.2)


def test_the_shares_add_up_to_the_uncut_layer():
    """Guide section 4: each share told its own held experts; their parts
    add up to the uncut reference's layer."""
    x, wr, wg, wu, wd = _moe_weights(jax.random.PRNGKey(2))
    cfg = dict(CFG, num_experts=8)
    whole, balance, choice, pairs = ref.experts(
        x, x @ wr.T, cfg, wg, wu, wd, "float32", 0)
    parts, n_pairs = 0.0, []
    for first in (0, 2, 4, 6):
        y, aux = moe.moe_layer(x, wr, wg[first:first + 2], wu[first:first + 2],
                               wd[first:first + 2], top_k=2, first_expert=first)
        assert int(aux["dropped"]) == 0
        np.testing.assert_allclose(aux["balance"], balance, rtol=1e-5)
        np.testing.assert_array_equal(np.sort(aux["choice"], 1),
                                      np.sort(choice, 1))
        mine, _, _, mine_pairs = ref.experts(
            x, x @ wr.T, dict(CFG, num_experts=2), wg[first:first + 2],
            wu[first:first + 2], wd[first:first + 2], "float32", first)
        np.testing.assert_allclose(y, mine, atol=1e-5)
        np.testing.assert_array_equal(aux["pairs"], mine_pairs)
        parts = parts + y
        n_pairs += list(np.asarray(aux["pairs"]))
    np.testing.assert_allclose(parts, whole, atol=2e-5)
    assert n_pairs == list(np.asarray(pairs)) and sum(n_pairs) == 2 * S


def test_no_pair_is_dropped_when_every_token_goes_to_one_held_expert():
    x, wr, wg, wu, wd = _moe_weights(jax.random.PRNGKey(4))
    wr = wr.at[3].set(0.0)
    x = jnp.abs(x)
    wr = wr.at[:3].set(-1.0).at[4:].set(-1.0)      # expert 3 wins every token
    y, aux = jax.jit(lambda *a: moe.moe_layer(
        *a, top_k=2, first_expert=2))(x, wr, wg[2:4], wu[2:4], wd[2:4])
    assert int(aux["pairs"][1]) == S and int(aux["dropped"]) == 0
    want, _, _, _ = ref.experts(x, x @ wr.T, dict(CFG, num_experts=2),
                                wg[2:4], wu[2:4], wd[2:4], "float32", 2)
    np.testing.assert_allclose(y, want, atol=1e-5)
    assert float(jnp.abs(y).sum(1).min()) > 0      # every token came out
    # a buffer sized tighter than the load counts what it leaves out
    _, aux = moe.moe_layer(x, wr, wg[2:4], wu[2:4], wd[2:4], top_k=2,
                           first_expert=2, capacity=S // 2)
    assert int(aux["dropped"]) == int(aux["pairs"].sum()) - S // 2


def test_moe_gradients_are_the_dense_oracles():
    x, wr, wg, wu, wd = _moe_weights(jax.random.PRNGKey(6))

    def mine(x, wr, wg, wu, wd):
        y, aux = moe.moe_layer(x, wr, wg, wu, wd, top_k=2, first_expert=2)
        return jnp.sum(y * y) + aux["balance"]

    def oracle(x, wr, wg, wu, wd):
        y, balance, _, _ = ref.experts(x, x @ wr.T, dict(CFG, num_experts=4),
                                       wg, wu, wd, "float32", 2)
        return jnp.sum(y * y) + balance

    args = (x, wr, wg[2:6], wu[2:6], wd[2:6])
    got = jax.jit(jax.grad(mine, argnums=(0, 1, 2, 3, 4)))(*args)
    want = jax.jit(jax.grad(oracle, argnums=(0, 1, 2, 3, 4)))(*args)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=1e-3, atol=1e-4)


def test_blocks_norm_and_gated_ffn():
    x = mx.nd.array(np.random.RandomState(0).randn(5, 8).astype(np.float32))
    norm = nn.RMSNorm(8)
    norm.initialize()
    xn = x.asnumpy()
    np.testing.assert_allclose(
        norm(x).asnumpy(), xn / np.sqrt((xn ** 2).mean(-1, keepdims=True) + 1e-6),
        rtol=1e-5)
    ffn = nn.GatedFFN(8, 12, weight_initializer=mx.init.Normal(0.5))
    ffn.initialize()
    g, u, d = (p.data().asnumpy() for p in
               (ffn.gate_weight, ffn.up_weight, ffn.down_weight))
    a = xn @ g.T
    np.testing.assert_allclose(ffn(x).asnumpy(),
                               (a / (1 + np.exp(-a)) * (xn @ u.T)) @ d.T,
                               rtol=1e-4, atol=1e-5)
    with pytest.raises(ValueError, match="not among"):
        nn.SparseMoE(8, 12, num_experts=8, top_k=2, num_held=4, first_expert=6)


def test_make_train_step_rejects_an_unknown_optimizer_and_keeps_sgd():
    net = nn.Dense(3, in_units=4)
    net.initialize()
    loss = mx.gluon.loss.L2Loss()
    with pytest.raises(ValueError, match="knows 'sgd' and 'adam'"):
        make_train_step(net, loss, optimizer="lamb")
    step, state, _ = make_train_step(net, loss, learning_rate=0.1, momentum=0.9)
    assert isinstance(state[1], list) and len(state[1]) == 2
    x, y = jnp.ones((2, 4)), jnp.zeros((2, 3))
    out = jax.jit(step)(state, x, y, jax.random.PRNGKey(0))
    assert len(out) == 2                                # (state, loss)


def test_the_registered_operators_run_eagerly_through_nd():
    """``mx.nd.RotaryEmbedding`` / ``IndexerSparseAttention`` / ``MoEExperts``
    on NDArrays, outside any trace, against the reference's functions."""
    key = jax.random.PRNGKey(7)
    ks = jax.random.split(key, 6)
    q = jax.random.normal(ks[0], (S, 4, 16))
    k = jax.random.normal(ks[1], (S, 2, 16))
    v = jax.random.normal(ks[2], (S, 2, 16))
    iq = jax.random.normal(ks[3], (S, 4, 8))
    ik = jax.random.normal(ks[4], (S, 8))
    iw = jax.random.normal(ks[5], (S, 4))
    nd = lambda *a: [mx.nd.NDArray(x) for x in a]                # noqa: E731
    pos = jnp.tile(jnp.arange(S)[None], (3, 1))
    got = mx.nd.RotaryEmbedding(*nd(q, pos), theta=1e7, sections=(2, 3, 3))
    np.testing.assert_allclose(got.asnumpy(), ref.rope(q, pos, 1e7, [2, 3, 3]),
                               atol=1e-5)
    out = mx.nd.IndexerSparseAttention(*nd(q, k, v, iq, ik, iw), topk=16,
                                       block=16, span=32, emit_selection=True)
    o, kl, n_sel, bits = ref.sparse_attention(q, k, v, iq, ik, iw, 16, 16,
                                              "float32")
    np.testing.assert_allclose(out[0].asnumpy(), o, atol=2e-5)
    np.testing.assert_allclose(out[1].asnumpy(), kl, rtol=1e-4)
    assert int(out[2].asnumpy()) == int(n_sel)
    assert int(out[3].asnumpy()) == S * (S + 1) // 2
    np.testing.assert_array_equal(out[4].asnumpy(), bits)
    with pytest.raises(ValueError, match="must divide"):
        mx.nd.IndexerSparseAttention(*nd(q, k, v, iq, ik, iw), topk=16,
                                     block=24, span=32)
    x, wr, wg, wu, wd = _moe_weights(jax.random.PRNGKey(8))
    y, balance, pairs, dropped, choice = mx.nd.MoEExperts(
        *nd(x, wr, wg[2:6], wu[2:6], wd[2:6]), top_k=2, first_expert=2)
    want, bal, _, n = ref.experts(x, x @ wr.T, dict(CFG, num_experts=4),
                                  wg[2:6], wu[2:6], wd[2:6], "float32", 2)
    np.testing.assert_allclose(y.asnumpy(), want, atol=1e-5)
    np.testing.assert_allclose(balance.asnumpy(), bal, rtol=1e-5)
    np.testing.assert_array_equal(pairs.asnumpy(), n)
    assert int(dropped.asnumpy()) == 0 and choice.shape == (S, 2)
