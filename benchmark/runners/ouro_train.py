"""Runner of the Ouro training cell: the Gluon train step of a looped
language model.

``lm_train``'s runner with what names its model replaced:
``OuroLM.from_config`` on the configuration's keys,
``gluon.functional.make_train_step`` with the Adam rule and ``OuroLMLoss``,
``jax.jit(step, donate_argnums=(0,))``.  A step is fed ``batch_per_chip``
documents of ``seq_len`` ids (uniform over the whole vocabulary from the
seed, labels the ids shifted by one within a document), made on the device
before the window; the model is fed the labels too, so that every exit's
log-probabilities are computed in row blocks: one walk of the head after the
loop, over the passes' stacked states.

``correct`` compares, against ``benchmark/reference/ouro_lm.py`` over the
same three steps: each step's loss and its two terms apart
(``expected_lm_loss``, ``exit_entropy``); at step 1 each exit's own mean loss
(``lm_loss_exit1..``) and the mean share of the positions that leaves at each
exit (``exit_mass1..``); the first gradient by leaf (Adam's first moment
after one step over ``1 - beta1``); the parameters' change after three steps
by leaf; ``grad_final_norm``, the first gradient of the final norm's scale
alone (the one leaf outside the layers that every pass applies once, so each
pass gives a like share of its gradient: among the leaves' norms it is the
one that moves when a single pass is cut off from the shared weights, where
the layers' own move by a hundredth); and ``layer_applications``, the layer
applications the device counted in a step against the reference's passes x
layers, exactly.
"""
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import compare
from benchmark.reference import precision
from benchmark.runners import lm_train
from benchmark.runners.moonlight_train import token_batch

TERMS = ("expected_lm_loss", "exit_entropy")
# per exit, read at step 1
PER_EXIT = (("lm_loss_exits", "lm_loss_exit%d"), ("exit_mass", "exit_mass%d"))
# device counters of a step, recorded on the program's ``step`` span
COUNTERS = ("exit_step_milli", "gate_tokens", "layer_applications")
FINAL_NORM = "final_norm_gamma"


class Runner(lm_train.Runner):
    def make_step(self, cfg=None, loss_fn=None):
        """-> (step, {learnable leaf: shape})."""
        import mxnet_tpu as mx
        from mxnet_tpu.gluon.functional import make_train_step
        from mxnet_tpu.gluon.model_zoo.text import OuroLM, OuroLMLoss

        cfg = cfg or self.cfg
        # the seed's weights replace these: zeros, on the host
        net = OuroLM.from_config(
            cfg, attn_block=cfg["attn_block"], attn_span=cfg["attn_span"],
            loss_block=cfg["loss_block"], weight_initializer=mx.init.Zero())
        net.initialize(ctx=mx.cpu())
        step, state, (names, learn_idx, aux_idx) = make_train_step(
            net, loss_fn or OuroLMLoss(cfg["entropy_beta"]),
            learning_rate=cfg["learning_rate"], optimizer=cfg["optimizer"],
            beta1=cfg["beta1"], beta2=cfg["beta2"], epsilon=cfg["epsilon"],
            compute_dtype=cfg["compute_dtype"])
        if aux_idx:
            raise RuntimeError("the model has auxiliary state: %r" % aux_idx)
        return step, {names[i][len(net.prefix):]: tuple(v.shape)
                      for i, v in zip(learn_idx, state[0])}

    def build(self):
        super().build()
        count_step = self._count_step
        self._count_step = lambda aux, _: count_step(aux, COUNTERS)

    def place_seed(self, seed=None):
        """State and token ids of ``seed`` (default: the run's) on the chip."""
        if seed is not None:
            self.seed = seed
        weights = self.seed_weights()
        learn = [weights[n] for n in self.names]
        self.state = (learn, {"mean": [jnp.zeros_like(v) for v in learn],
                              "var": [jnp.zeros_like(v) for v in learn],
                              "t": jnp.zeros((), jnp.int32)}, [])
        ids, self.labels = token_batch(
            self.seed, self.traffic["batch_per_chip"], self.cfg["seq_len"],
            self.cfg["vocab_size"])
        self.tokens = (ids, self.labels)
        self.key = jax.random.PRNGKey(0)          # the model draws nothing
        jax.block_until_ready((self.state, self.tokens))

    def first_steps(self):
        """The checked steps (1..3), with the readings ``correct`` is decided
        from, then the warm-up steps."""
        t0 = time.perf_counter()
        r = {"loss": [], "scalars": {}}
        for i in range(1, self.checked_steps + 1):
            loss, aux = self.call_step()
            r["loss"].append(float(loss))
            for term in TERMS:
                r["scalars"]["%s_step%d" % (term, i)] = float(aux[term])
            if i == 1:      # Adam's first moment after one step
                g = np.asarray(jnp.stack(self._norms(self.state[1]["mean"])),
                               np.float64) / (1.0 - self.cfg["beta1"])
                r["grad"] = dict(zip(self.names, g))
                r["scalars"]["grad_final_norm"] = r["grad"][FINAL_NORM]
                for key, name in PER_EXIT:
                    got = np.asarray(aux[key], np.float64)
                    for t in range(self.cfg["total_ut_steps"]):
                        # an exit the program does not have reads nan
                        r["scalars"][name % (t + 1)] = float(
                            got[t] if t < len(got) else np.nan)
                r["scalars"]["layer_applications"] = float(
                    np.asarray(aux["layer_applications"]).sum())
        w0 = self.seed_weights()
        r["delta"] = dict(zip(self.names, np.asarray(jnp.stack(
            self._delta(self.state[0], w0)), np.float64)))
        del w0
        self.readings = r
        for _ in range(self.traffic["warmup_steps"]):
            jax.block_until_ready(self.call_step()[0])
        self.phases["first_steps_s"] = time.perf_counter() - t0

    # -- correct -----------------------------------------------------------
    def reference_readings(self, prec="float32", steps=None):
        """The plain reference over the same first steps, from the same
        seed."""
        cfg = self.cfg
        steps = steps or self.checked_steps
        names = [n for n, _, _ in self.spec]
        tokens, _ = token_batch(self.seed, self.traffic["batch_per_chip"],
                                cfg["seq_len"], cfg["vocab_size"])
        # the reference keeps its own copy of the seed's weights; a third
        # set does not fit beside its step (14.9 GiB declared) and is made
        # again after the steps
        model = self.ref.Reference(cfg, self.seed_weights(), prec,
                                   cfg["reference_block"])
        r = {"loss": [], "scalars": {}}
        for i in range(1, steps + 1):
            loss, parts, facts = model.step(tokens)
            r["loss"].append(float(loss))
            for term in TERMS:
                r["scalars"]["%s_step%d" % (term, i)] = float(parts[term])
            if i == 1:
                r["grad"] = dict(zip(names, np.asarray(jnp.stack(
                    precision.tree_l2_jit([model.m[n] for n in names])),
                    np.float64) / (1.0 - cfg["beta1"])))
                r["scalars"]["grad_final_norm"] = r["grad"][FINAL_NORM]
                for key, name in PER_EXIT:
                    for t, v in enumerate(np.asarray(facts[key]), 1):
                        r["scalars"][name % t] = float(v)
                r["scalars"]["layer_applications"] = float(
                    facts["layer_applications"])
        weights = self.seed_weights()
        r["delta"] = dict(zip(names, np.asarray(jnp.stack(
            precision.tree_l2_jit([model.p[n] - weights[n]
                                   for n in names])), np.float64)))
        return r

    def check(self, prec="float32"):
        """-> (correct, {name: [value, limit]}, extra facts)."""
        if not np.isfinite(self.last_loss):
            return False, {"last_loss_finite": [float("nan"), 0]}, {}
        return compare.check(self.readings, self.reference_readings(prec),
                             self.limits)
