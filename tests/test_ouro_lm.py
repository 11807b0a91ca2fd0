"""The loop helper (``gluon.block.loop``), the plain self-attention block and
the zoo's looped language model against the plain reference
(benchmark/reference/ouro_lm.py) at a toy size: 2 layers applied 4 times,
hidden 64, 4 heads of 16, feed-forward 96, vocabulary 96, 2 documents of 32;
seeded weights.
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
from mxnet_tpu import autograd  # noqa: E402
from mxnet_tpu.gluon import nn  # noqa: E402
from mxnet_tpu.gluon.block import HybridBlock, loop, remat  # noqa: E402
from mxnet_tpu.gluon.functional import functionalize, make_train_step  # noqa: E402
from mxnet_tpu.gluon.model_zoo.text import OuroLM, OuroLMLoss, ouro_lm  # noqa: E402

from benchmark import seeded  # noqa: E402
from benchmark.reference import ouro_lm as ref  # noqa: E402

N, S = 2, 32
CFG = {"hidden_size": 64, "num_hidden_layers": 2, "total_ut_steps": 4,
       "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 16,
       "intermediate_size": 96, "vocab_size": 96, "rms_norm_eps": 1e-6,
       "rope_theta": 1000000, "rope_scaling": None,
       "use_sliding_window": False, "tie_word_embeddings": False,
       "layer_types": ["full_attention"] * 48, "entropy_beta": 0.1,
       "learning_rate": 1e-3, "beta1": 0.9, "beta2": 0.95, "epsilon": 1e-8}
KEY = jax.random.PRNGKey(0)


def _python_loop(fn, times):
    """What ``loop`` is outside a trace, whatever the arguments are."""
    def run(*carry):
        passes = []
        for _ in range(times):
            carry, outs = fn(*carry)
            passes.append(outs)
        return list(carry), [mx.nd.stack(*each, axis=0)
                             for each in zip(*passes)]
    return run


def _train_step(cfg=CFG, **kwargs):
    net = OuroLM.from_config(cfg, attn_block=8, attn_span=16, loss_block=16,
                             **kwargs)
    net.initialize()
    step, state, (names, learn_idx, aux_idx) = make_train_step(
        net, OuroLMLoss(cfg["entropy_beta"]), learning_rate=1e-3,
        optimizer="adam", beta1=0.9, beta2=0.95)
    assert not aux_idx
    return net, step, state, [names[i][len(net.prefix):] for i in learn_idx]


@pytest.fixture(scope="module")
def toy():
    """The program's step and state on seeded weights and the token ids."""
    net, step, state, learn = _train_step()
    weights = seeded.make_weights(ref.param_spec(CFG), 5)
    assert {n: tuple(v.shape) for n, v in zip(learn, state[0])} \
        == {n: tuple(s) for n, s, _ in ref.param_spec(CFG)}
    state = ([jnp.array(weights[n]) for n in learn], state[1], [])
    tokens = jax.random.randint(jax.random.PRNGKey(1), (N, S), 0, 96)
    labels = jnp.concatenate(
        [tokens[:, 1:], jnp.full((N, 1), -1, jnp.int32)], 1)
    return net, learn, weights, step, state, tokens, labels


def test_every_exit_and_both_loss_terms_match_the_reference(toy):
    net, _, weights, _, _, tokens, labels = toy
    apply, order, _, _ = functionalize(net, train=True)
    vals = [weights[n[len(net.prefix):]] for n in order]
    out, _ = jax.jit(lambda v, t: apply(v, t, KEY))(vals, tokens)
    logp, gates, applied = ref.forward(weights, tokens, CFG, block=8)
    assert out[0].shape == (4, N, S, 96) and applied == 8
    np.testing.assert_allclose(jax.nn.log_softmax(out[0], -1), logp,
                               rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(out[1], gates, rtol=1e-5)
    assert np.asarray(out[2]).tolist() == [8]
    # the exits differ: every pass moved the state
    assert float(jnp.abs(out[0][3] - out[0][0]).max()) > 0.1
    loss, aux = OuroLMLoss(0.1)([mx.nd.NDArray(o) for o in out],
                                mx.nd.NDArray(labels))
    want, (parts, facts) = ref.loss_terms(weights, tokens, CFG, block=8)
    np.testing.assert_allclose(loss.asnumpy(), want, rtol=1e-5)
    for k in ("expected_lm_loss", "exit_entropy"):
        np.testing.assert_allclose(aux[k].asnumpy(), parts[k], rtol=1e-5)
    for k in ("lm_loss_exits", "exit_mass"):
        np.testing.assert_allclose(aux[k].asnumpy(), facts[k], rtol=1e-5)
    np.testing.assert_allclose(aux["exit_mass"].asnumpy().sum(), 1, rtol=1e-6)
    assert aux["gate_tokens"] == N * S
    np.testing.assert_allclose(
        int(aux["exit_step_milli"].asnumpy()) / (N * S) / 1000,
        facts["expected_exit_step"], rtol=1e-4)
    # an undecided gate over four passes leaves after 1.875 in the mean
    half = mx.nd.NDArray(jnp.full((4, N, S), 0.5))
    shares = [p.asnumpy() for p in OuroLMLoss.exit_shares(half)]
    assert [float(p[0, 0]) for p in shares] == [0.5, 0.25, 0.125, 0.125]
    assert sum((t + 1) * float(p[0, 0]) for t, p in enumerate(shares)) == 1.875


def test_three_adam_steps_and_every_gradient_match_the_reference(toy):
    """The step as the cell runs it: the model fed the labels too, every
    exit's log-probabilities in row blocks inside the loop."""
    _, names, weights, step, state, tokens, labels = toy
    jstep = jax.jit(step)
    model = ref.Reference(CFG, weights, block=8)
    _, by_logits, _ = jstep(state, tokens, labels, KEY)
    for i in range(3):
        state, loss, aux = jstep(state, (tokens, labels), labels, KEY)
        if i == 0:
            np.testing.assert_allclose(loss, by_logits, rtol=1e-6)
        want, parts, facts = model.step(tokens)
        np.testing.assert_allclose(loss, want, rtol=1e-5)
        np.testing.assert_allclose(aux["exit_entropy"], parts["exit_entropy"],
                                   rtol=1e-5)
        np.testing.assert_allclose(aux["exit_mass"], facts["exit_mass"],
                                   rtol=1e-4)
        assert np.asarray(aux["layer_applications"]).tolist() == [8]
        if i == 0:      # Adam's first moment is a tenth of the first gradient
            for n, m in zip(names, state[1]["mean"]):
                g = np.asarray(model.m[n])
                np.testing.assert_allclose(m, g, rtol=2e-3,
                                           atol=1e-5 * np.abs(g).max(), err_msg=n)
    assert int(state[1]["t"]) == 3
    for n, p in zip(names, state[0]):
        np.testing.assert_allclose(p, model.p[n], atol=2e-5, err_msg=n)
        assert float(jnp.abs(p - weights[n]).max()) > 1e-4, n   # every leaf moved


def _count(text, what="stablehlo.dot_general"):
    return text.count(what)


def test_the_lowered_step_holds_each_layers_products_once(toy, monkeypatch):
    """The passes are one compiled loop: as many products in the step's
    StableHLO at four passes as at one, where the unrolled step has four
    times the loop's."""
    _, _, _, step, state, tokens, labels = toy
    args = (state, (tokens, labels), labels, KEY)
    looped = jax.jit(step).lower(*args).as_text()
    assert "stablehlo.while" in looped
    _, once, state1, _ = _train_step(dict(CFG, total_ut_steps=1))
    one = _count(jax.jit(once).lower(state1, *args[1:]).as_text())
    assert _count(looped) == one
    # the feed-forward's gate product (rows of 96 from 64) per layer: one
    # forward, one recomputed, and in the backward pass
    gate_products = len(re.findall(
        r"dot_general.*\(tensor<2x32x64xf32>, tensor<96x64xf32>\)", looped))
    monkeypatch.setattr(ouro_lm, "loop", _python_loop)
    _, unrolled_step, state4, _ = _train_step()
    unrolled = jax.jit(unrolled_step).lower(state4, *args[1:]).as_text()
    # every pass's body again; the head's one walk is outside the loop
    assert 3.5 * _count(looped) < _count(unrolled) < 4 * _count(looped)
    assert len(re.findall(
        r"dot_general.*\(tensor<2x32x64xf32>, tensor<96x64xf32>\)",
        unrolled)) == 4 * gate_products > 0
    # the weights' bfloat16 copies are made once a step, not once a pass: as
    # many converts of a weight's shape at four passes as at one
    monkeypatch.undo()
    casts = []
    for passes in (4, 1):
        net = OuroLM.from_config(dict(CFG, total_ut_steps=passes),
                                 attn_block=8, attn_span=16, loss_block=16)
        net.initialize()
        half, half_state, _ = make_train_step(
            net, OuroLMLoss(0.1), learning_rate=1e-3, optimizer="adam",
            compute_dtype="bfloat16")
        casts.append(len(re.findall(
            r"convert %\S+ : \(tensor<96x64xf32>\) -> tensor<96x64xbf16>",
            jax.jit(half).lower(half_state, *args[1:]).as_text())))
    assert casts[0] == casts[1] >= 6            # embed, head, 2 x (gate, up)
    # the two are the same step
    a, b = (jax.jit(s)(st, *args[1:]) for s, st in
            ((step, state), (unrolled_step, state)))
    np.testing.assert_allclose(a[1], b[1], rtol=1e-6)
    for x, y in zip(a[0][0], b[0][0]):
        np.testing.assert_allclose(x, y, atol=5e-5)


def test_no_heads_by_sequence_by_sequence_array_and_every_scope(toy):
    _, _, _, step, state, tokens, labels = toy
    lowered = jax.jit(step).lower(state, (tokens, labels), labels, KEY)
    shapes = [tuple(int(d) for d in m.split("x")[:-1]) for m in
              re.findall(r"tensor<((?:\d+x)+[a-z]\w*)>", lowered.as_text())]
    assert any(s.count(S) == 1 and 8 in s for s in shapes)   # a block's weights
    assert not [s for s in shapes if s.count(S) >= 2]
    named = lowered.as_text(debug_info=True)
    for scope in ("loop.pass", "self_attention.project",
                  "self_attention.attend", "dense_ffn", "exit_gate",
                  "lm_head", "loss", "optimizer"):
        assert scope in named, scope


# -- the loop helper -----------------------------------------------------------
class _Looped(HybridBlock):
    """``times`` passes over two dense layers, the second rematted; each pass
    hands out its state's row sums."""

    def __init__(self, times, tied=True, helper=loop):
        super().__init__(prefix="looped_")
        self._times, self._helper = times, helper
        with self.name_scope():
            self.cells = []
            for t in range(1 if tied else times):
                cell = (nn.Dense(8, flatten=False, in_units=8, prefix="a%d_" % t),
                        nn.Dense(8, flatten=False, in_units=8, prefix="b%d_" % t))
                for c in cell:
                    self.register_child(c)
                self.cells.append(cell)

    def hybrid_forward(self, F, x):
        if len(self.cells) == 1:
            a, b = self.cells[0]

            def body(x, n):
                x = x + remat(b)(F.tanh(a(x)))
                return [x, n + 1], [F.sum(x, axis=-1)]

            (x, n), (sums,) = self._helper(body, self._times)(
                x, F.zeros((1,), dtype="int32"))
            return [x, sums, n]
        sums = []
        for a, b in self.cells:
            x = x + b(F.tanh(a(x)))
            sums.append(F.sum(x, axis=-1))
        return [x, F.stack(*sums, axis=0)]


def _values_and_grads(net, x):
    """-> (outputs, {leaf: gradient of a scalar of both outputs}) through the
    functional form, jitted."""
    apply, names, vals, _ = functionalize(net, train=True)

    def scalar(vals, x):
        out, _ = apply(vals, x, KEY)
        return jnp.sum(out[0] ** 2) + jnp.sum(jnp.sin(out[1])), out

    (_, out), grads = jax.jit(jax.value_and_grad(scalar, has_aux=True))(vals, x)
    return out, dict(zip([n[len(net.prefix):] for n in names], grads))


@pytest.fixture(scope="module")
def looped():
    net = _Looped(4)
    net.initialize(mx.init.Normal(0.3))
    x = jax.random.normal(jax.random.PRNGKey(2), (5, 8))
    return net, x


def test_loop_is_the_python_loop_jitted_values_and_gradients(looped):
    net, x = looped
    out, grads = _values_and_grads(net, x)
    assert out[1].shape == (4, 5) and np.asarray(out[2]).tolist() == [4]
    plain = _Looped(4, helper=_python_loop)
    plain.initialize()
    for n, p in plain.collect_params().items():
        p.set_data(net.collect_params()[n].data())
    want, want_grads = _values_and_grads(plain, x)
    for a, b in zip(out, want):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
    assert set(grads) == set(want_grads) and len(grads) == 4
    for n in grads:
        np.testing.assert_allclose(grads[n], want_grads[n], rtol=1e-4,
                                   atol=1e-6, err_msg=n)
    # one compiled loop, the body once
    apply, _, vals, _ = functionalize(net, train=True)
    text = jax.jit(lambda v, x: apply(v, x, KEY)[0]).lower(vals, x).as_text()
    assert "stablehlo.while" in text and _count(text) == 2


def test_loop_runs_eagerly_and_under_the_tape(looped):
    net, x = looped
    _, grads = _values_and_grads(net, x)
    out = net(mx.nd.NDArray(x))                  # eager: a Python loop
    want, _ = _values_and_grads(net, x)
    np.testing.assert_allclose(out[1].asnumpy(), want[1], rtol=1e-5, atol=1e-6)
    params = net.collect_params()
    with autograd.record():
        y, sums, _ = net(mx.nd.NDArray(x))
        scalar = mx.nd.sum(y * y) + mx.nd.sum(mx.nd.sin(sums))
    scalar.backward()
    for n, p in params.items():
        np.testing.assert_allclose(p.grad().asnumpy(),
                                   grads[n[len(net.prefix):]], rtol=1e-4,
                                   atol=1e-6, err_msg=n)
    # hybridized, the cached op holds the compiled loop
    net.hybridize()
    try:
        np.testing.assert_allclose(net(mx.nd.NDArray(x))[1].asnumpy(),
                                   want[1], rtol=1e-5, atol=1e-6)
    finally:
        net.hybridize(False)


def test_a_shared_weights_gradient_is_the_sum_over_four_untied_copies(looped):
    net, x = looped
    _, tied = _values_and_grads(net, x)
    untied = _Looped(4, tied=False)
    untied.initialize()
    shared = {n[len(net.prefix):]: p.data()
              for n, p in net.collect_params().items()}
    for n, p in untied.collect_params().items():
        short = n[len(untied.prefix):]
        p.set_data(shared[re.sub(r"\d_", "0_", short)])
    out, grads = _values_and_grads(untied, x)
    np.testing.assert_allclose(out[1], _values_and_grads(net, x)[0][1],
                               rtol=1e-5, atol=1e-6)
    assert len(grads) == 16
    for n, g in tied.items():
        each = [grads[n.replace("0_", "%d_" % t)] for t in range(4)]
        np.testing.assert_allclose(g, sum(each), rtol=1e-4, atol=1e-6,
                                   err_msg=n)
        assert float(jnp.abs(each[0] - each[3]).max()) > 1e-4   # passes differ


def test_loop_refuses_auxiliary_state_moved_inside_it():
    class Counting(HybridBlock):
        def __init__(self):
            super().__init__(prefix="counting_")
            with self.name_scope():
                self.norm = nn.BatchNorm(in_channels=8, prefix="bn_")

        def hybrid_forward(self, F, x):
            (x,), _ = loop(lambda x: ([self.norm(x)], []), 3)(x)
            return x

    net = Counting()
    net.initialize()
    x = jax.random.normal(jax.random.PRNGKey(3), (4, 8))
    apply, _, vals, _ = functionalize(net, train=True)
    with pytest.raises(ValueError, match="auxiliary state.*bn_running"):
        jax.jit(lambda v, x: apply(v, x, KEY))(vals, x)
    # in inference nothing moves, and eagerly a Python loop moves it freely
    infer, _, vals, _ = functionalize(net, train=False)
    assert jax.jit(lambda v, x: infer(v, x, KEY)[0])(vals, x).shape == (4, 8)
    before = net.norm.running_mean.data().asnumpy().copy()
    with autograd.record():
        net(mx.nd.NDArray(x))
    assert np.abs(net.norm.running_mean.data().asnumpy() - before).max() > 0


def test_loop_is_a_python_loop_over_symbols():
    x = mx.sym.var("x")
    (y,), (halves,) = loop(lambda x: ([x * 2.0], [x * 0.5]), 3)(x)
    exe = mx.sym.Group([y, halves]).bind(
        mx.cpu(), {"x": mx.nd.array(np.ones((2, 3), np.float32))})
    out = exe.forward()
    np.testing.assert_array_equal(out[0].asnumpy(), np.full((2, 3), 8.0))
    assert out[1].shape == (3, 2, 3)
    np.testing.assert_array_equal(out[1].asnumpy()[:, 0, 0], [0.5, 1.0, 2.0])


# -- plain self-attention ------------------------------------------------------
def _masked_softmax_attention(a, w, heads, kv_heads, d, theta):
    """(N, S, D) through a (N, heads, S, S) array."""
    n, s, _ = a.shape
    pos = jnp.arange(s, dtype=jnp.float32)
    inv = theta ** (-jnp.arange(d // 2, dtype=jnp.float32) / (d // 2))
    cos, sin = jnp.cos(pos[:, None] * inv), jnp.sin(pos[:, None] * inv)

    def rot(x):
        x1, x2 = x[..., :d // 2], x[..., d // 2:]
        c, z = cos[None, :, None], sin[None, :, None]
        return jnp.concatenate([x1 * c - x2 * z, x2 * c + x1 * z], -1)

    q = rot((a @ w["q"].T).reshape(n, s, heads, d))
    k = rot((a @ w["k"].T).reshape(n, s, kv_heads, d))
    v = (a @ w["v"].T).reshape(n, s, kv_heads, d)
    k, v = (jnp.repeat(t, heads // kv_heads, 2) for t in (k, v))
    scores = jnp.einsum("nqhd,nkhd->nhqk", q, k) * d ** -0.5
    mask = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    p = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), -1)
    return jnp.einsum("nhqk,nkhd->nqhd", p, v).reshape(n, s, -1) @ w["o"].T


@pytest.mark.parametrize("kv_heads", [4, 2])
def test_self_attention_is_the_plain_masked_softmax(kv_heads):
    attn = nn.SelfAttention(64, 4, kv_heads, 16, theta=1e6, block=8, span=16,
                            weight_initializer=mx.init.Normal(0.2))
    attn.initialize()
    assert sorted(p[len(attn.prefix):] for p in attn.collect_params()) == [
        "k_weight", "o_weight", "q_weight", "v_weight"]
    a = jax.random.normal(jax.random.PRNGKey(4), (N, S, 64))
    apply, names, vals, _ = functionalize(attn)
    w = {n[len(attn.prefix)]: v for n, v in zip(names, vals)}
    run = lambda vals, a: apply(vals, (a, jnp.arange(S)), KEY)[0]  # noqa: E731
    want = _masked_softmax_attention(a, w, 4, kv_heads, 16, 1e6)
    np.testing.assert_allclose(jax.jit(run)(vals, a), want, rtol=1e-4,
                               atol=1e-5)
    c = jax.random.normal(jax.random.PRNGKey(5), want.shape)
    got = jax.jit(jax.grad(lambda vals, a: jnp.sum(run(vals, a) * c),
                           argnums=(0, 1)))(vals, a)
    ref_g = jax.grad(lambda w, a: jnp.sum(_masked_softmax_attention(
        a, w, 4, kv_heads, 16, 1e6) * c), argnums=(0, 1))(w, a)
    np.testing.assert_allclose(got[1], ref_g[1], rtol=1e-3, atol=1e-4)
    for n, g in zip(names, got[0]):
        np.testing.assert_allclose(g, ref_g[0][n[len(attn.prefix)]],
                                   rtol=1e-3, atol=1e-4, err_msg=n)
    # eagerly, one document without the leading axis is the same document
    one = attn(mx.nd.NDArray(a[1]), mx.nd.arange(S)).asnumpy()
    np.testing.assert_allclose(one, want[1], rtol=1e-4, atol=1e-5)
    # a document does not see the other, nor a query a later key
    moved = jax.jit(run)(vals, a.at[0].set(-a[0]).at[1, S // 2:].set(3.0))
    np.testing.assert_allclose(moved[1, :S // 2], want[1, :S // 2], atol=1e-5)
    assert float(jnp.abs(moved[0] - want[0]).max()) > 0.1


def test_from_config_refuses_what_is_not_built():
    with pytest.raises(ValueError, match="sliding-window"):
        OuroLM.from_config(dict(CFG, use_sliding_window=True))
    with pytest.raises(ValueError, match="sliding-window"):
        OuroLM.from_config(dict(CFG, layer_types=["sliding_attention"] * 2))
    with pytest.raises(ValueError, match="tied"):
        OuroLM.from_config(dict(CFG, tie_word_embeddings=True))
    net = OuroLM.from_config(CFG)
    assert len(net.layers) == 2 and net._passes == 4
