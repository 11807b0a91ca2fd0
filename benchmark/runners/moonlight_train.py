"""Runner of the Moonlight training cell: the Gluon train step of a text
model that has auxiliary state.

``lm_train``'s runner with what names its model replaced:
``MoonlightLM.from_config`` on the configuration's keys,
``gluon.functional.make_train_step`` with the Adam rule and
``MoonlightLMLoss``, ``jax.jit(step, donate_argnums=(0,))``.  The state's
third entry is the expert layers' selection bias, which no gradient trains
and every step moves.  A step is fed ``batch_per_chip`` documents of
``seq_len`` ids (uniform over the vocabulary slice from the seed, labels the
ids shifted by one within a document), made on the device before the window.

``correct`` compares, against ``benchmark/reference/moonlight_lm.py`` over
the same three steps: each step's loss and its two terms apart; the first
gradient by leaf (Adam's first moment after one step over ``1 - beta1``);
the parameters' change after three steps by leaf; the share of the first
expert layer's step-1 (token, expert) choices on which the two agree
(``routing_agree``); over the choices they share, each expert's sum of gates
(``gate_agree``: the widest gap over the experts, which a bias that entered
the gates moves by its size over the scores'); the share of the selection
bias's entries that stand, after the three steps, where the reference's stand
(``router_bias_agree``); and that no held pair was dropped.
"""
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import compare, seeded
from benchmark.reference import precision
from benchmark.runners import lm_train

TERMS = ("lm_loss", "balance_loss")
# device counters of a step, recorded on the program's ``step`` span
COUNTERS = ("expert_pairs", "expert_pairs_max", "moe_dropped_pairs",
            "router_pairs", "router_pairs_max")


@functools.partial(jax.jit, static_argnums=(4,))
def _gate_gap(choice, gates, ref_choice, ref_gates, experts):
    """The widest relative gap, over the ``experts``, between the two sides'
    sums of gates over the (token, expert) pairs both chose."""
    dense = lambda c, g: jnp.einsum(                              # noqa: E731
        "tke,tk->te", jax.nn.one_hot(c, experts), g)
    got, want = dense(choice, gates), dense(ref_choice, ref_gates)
    both = (got > 0) & (want > 0)
    got, want = jnp.sum(got * both, 0), jnp.sum(want * both, 0)
    return jnp.max(jnp.where(want > 0, jnp.abs(got - want)
                             / jnp.where(want > 0, want, 1.0), 0.0))


def token_batch(seed, docs, seq_len, vocab):
    """-> (ids (docs, S) int32 uniform over the slice, labels: the ids
    shifted by one within each document, its last position without one)."""
    ids = jax.random.randint(jax.random.fold_in(seeded.root_key(seed), 2),
                             (docs, seq_len), 0, vocab, jnp.int32)
    return ids, jnp.concatenate(
        [ids[:, 1:], jnp.full((docs, 1), -1, jnp.int32)], 1)


class Runner(lm_train.Runner):
    def make_step(self, cfg=None, loss_fn=None):
        """-> (step, {learnable leaf: shape}); ``self.aux_names``: the
        auxiliary state's leaves, in the state's order."""
        import mxnet_tpu as mx
        from mxnet_tpu.gluon.functional import make_train_step
        from mxnet_tpu.gluon.model_zoo.text import MoonlightLM, MoonlightLMLoss

        cfg = cfg or self.cfg
        # the seed's weights replace these: zeros, on the host
        net = MoonlightLM.from_config(
            cfg, capacity_factor=cfg["moe_capacity_factor"],
            attn_block=cfg["attn_block"], attn_span=cfg["attn_span"],
            loss_block=cfg["loss_block"],
            bias_update_rate=cfg["bias_update_rate"],
            weight_initializer=mx.init.Zero())
        net.initialize(ctx=mx.cpu())
        step, state, (names, learn_idx, aux_idx) = make_train_step(
            net, loss_fn or MoonlightLMLoss(cfg["aux_loss_alpha"]),
            learning_rate=cfg["learning_rate"], optimizer=cfg["optimizer"],
            beta1=cfg["beta1"], beta2=cfg["beta2"], epsilon=cfg["epsilon"],
            compute_dtype=cfg["compute_dtype"])
        short = [n[len(net.prefix):] for n in names]
        self.aux_names = [short[i] for i in aux_idx]
        if self.aux_names != [n for n, _, _ in self.ref.bias_spec(cfg)]:
            raise RuntimeError("the model's auxiliary state is not the "
                               "configuration's: %r" % self.aux_names)
        return step, {short[i]: tuple(v.shape)
                      for i, v in zip(learn_idx, state[0])}

    def build(self):
        super().build()
        count_step = self._count_step
        self._count_step = lambda aux, _: count_step(aux, COUNTERS)

    def seed_weights(self):
        """The seed's weights and selection bias; the embedding rows widened
        to the configuration's ``embedding_std``, the bias drawn at its
        ``router_bias_std`` (``assumed`` says why)."""
        weights = dict(seeded.make_weights(
            list(self.spec) + self.ref.bias_spec(self.cfg), self.seed))
        weights["embed_weight"] = weights["embed_weight"] * (
            self.cfg["embedding_std"] / seeded.KINDS["head"][1])
        for n in self.aux_names:
            weights[n] = weights[n] * (self.cfg["router_bias_std"]
                                       / seeded.KINDS["bias"][1])
        return weights

    def place_seed(self, seed=None):
        """State and token ids of ``seed`` (default: the run's) on the chip."""
        if seed is not None:
            self.seed = seed
        weights = self.seed_weights()
        learn = [weights[n] for n in self.names]
        self.state = (learn, {"mean": [jnp.zeros_like(v) for v in learn],
                              "var": [jnp.zeros_like(v) for v in learn],
                              "t": jnp.zeros((), jnp.int32)},
                      [weights[n] for n in self.aux_names])
        ids, self.labels = token_batch(
            self.seed, self.traffic["batch_per_chip"], self.cfg["seq_len"],
            self.cfg["vocab_size"])
        # the model is fed the labels too: the head's log-probabilities are
        # computed in row blocks inside it
        self.tokens = (ids, self.labels)
        self.key = jax.random.PRNGKey(0)          # the model draws nothing
        jax.block_until_ready((self.state, self.tokens))

    def first_steps(self):
        """The checked steps (1..3), with the readings ``correct`` is decided
        from, then the warm-up steps."""
        t0 = time.perf_counter()
        r = {"loss": [], "scalars": {}}
        dropped = 0
        for i in range(1, self.checked_steps + 1):
            loss, aux = self.call_step()
            r["loss"].append(float(loss))
            for term in TERMS:
                r["scalars"]["%s_step%d" % (term, i)] = float(aux[term])
            dropped += int(np.asarray(aux["moe_dropped_pairs"]).sum())
            if i == 1:      # Adam's first moment after one step
                g = np.asarray(jnp.stack(self._norms(self.state[1]["mean"])),
                               np.float64) / (1.0 - self.cfg["beta1"])
                r["grad"] = dict(zip(self.names, g))
                self.choice, self.gates = aux["choice"], aux["gates"]
        r["scalars"]["moe_dropped_pairs"] = float(dropped)
        w0 = self.seed_weights()
        r["delta"] = dict(zip(self.names, np.asarray(jnp.stack(
            self._delta(self.state[0], w0)), np.float64)))
        del w0
        self.bias = np.stack([np.asarray(b) for b in self.state[2]])
        self.readings = r
        for _ in range(self.traffic["warmup_steps"]):
            jax.block_until_ready(self.call_step()[0])
        self.phases["first_steps_s"] = time.perf_counter() - t0

    # -- correct -----------------------------------------------------------
    def reference_readings(self, prec="float32", steps=None):
        """The plain reference over the same first steps, from the same seed;
        its own first choices and gates and its bias after the steps ride
        along under ``_facts``."""
        cfg = self.cfg
        steps = steps or self.checked_steps
        names = [n for n, _, _ in self.spec]
        weights = self.seed_weights()
        tokens, _ = token_batch(self.seed, self.traffic["batch_per_chip"],
                                cfg["seq_len"], cfg["vocab_size"])
        model = self.ref.Reference(cfg, weights, prec, cfg["reference_block"])
        r = {"loss": [], "scalars": {"moe_dropped_pairs": 0.0,
                                     "routing_agree": 1.0, "gate_agree": 1.0,
                                     "router_bias_agree": 1.0}}
        for i in range(1, steps + 1):
            loss, parts, facts = model.step(tokens)
            r["loss"].append(float(loss))
            for term in TERMS:
                r["scalars"]["%s_step%d" % (term, i)] = float(parts[term])
            if i == 1:
                r["grad"] = dict(zip(names, np.asarray(jnp.stack(
                    precision.tree_l2_jit([model.m[n] for n in names])),
                    np.float64) / (1.0 - cfg["beta1"])))
                chosen = facts["choice"], facts["gates"]
        r["delta"] = dict(zip(names, np.asarray(jnp.stack(
            precision.tree_l2_jit([model.p[n] - weights[n]
                                   for n in names])), np.float64)))
        r["_facts"] = chosen + (np.stack([np.asarray(model.bias[n])
                                          for n in self.aux_names]),)
        return r

    def check(self, prec="float32"):
        """-> (correct, {name: [value, limit]}, extra facts)."""
        if not np.isfinite(self.last_loss):
            return False, {"last_loss_finite": [float("nan"), 0]}, {}
        want = self.reference_readings(prec)
        choice, gates, bias = want.pop("_facts")
        experts = bias.shape[1]
        # an entry stands where the reference's does if the two moved by the
        # same whole number of steps of the update's rate
        same = np.abs(self.bias - bias) < 0.5 * self.cfg["bias_update_rate"]
        got = dict(self.readings, scalars=dict(
            self.readings["scalars"],
            routing_agree=float(lm_train._agree(
                lm_train._choice_mask(self.choice, experts),
                lm_train._choice_mask(choice, experts))),
            gate_agree=1.0 - float(_gate_gap(
                self.choice, self.gates, choice, gates, experts)),
            router_bias_agree=float(np.mean(same))))
        return compare.check(got, want, self.limits)
