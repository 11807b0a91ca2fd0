"""Runner of the language-model training cells: the Gluon train step.

Builds what a user of the model zoo builds: ``KeyeLM.from_config`` on the
configuration's keys, ``gluon.functional.make_train_step`` with the Adam
rule and ``KeyeLMLoss``, ``jax.jit(step, donate_argnums=(0,))``.  Weights and
the token ids come from the seed (``benchmark/seeded.py``; ids uniform over
the vocabulary slice, one document, labels the ids shifted by one), made on
the device before the window and fed to every step.

One object, the compiled step with its chained, donated state, is driven
through its first steps in set-up (where the readings for ``correct`` are
taken) and then handed to the window.  ``correct`` compares, against
``benchmark/reference/keye_lm.py`` over the same three steps: each step's
loss and its three terms apart; the first gradient by leaf (Adam's first
moment after one step over ``1 - beta1``); the parameters' change after three
steps by leaf; the share of the first layer's step-1 (query, key) selections
and (token, expert) choices on which the two agree; and that no held pair was
dropped.
"""
import gc
import importlib
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import compare, seeded
from benchmark.reference import precision

CHECKED_STEPS = 3
TERMS = ("lm_loss", "balance_loss", "indexer_kl")
# device counters of a step, recorded on the program's ``step`` span
COUNTERS = ("selected_keys", "causal_keys", "expert_pairs",
            "expert_pairs_max", "moe_dropped_pairs")


def token_batch(seed, seq_len, vocab):
    """-> (ids (S,) int32 uniform over the slice, labels: the ids shifted by
    one, the last position without a label)."""
    ids = jax.random.randint(jax.random.fold_in(seeded.root_key(seed), 2),
                             (seq_len,), 0, vocab, jnp.int32)
    return ids, jnp.concatenate([ids[1:], jnp.full((1,), -1, jnp.int32)])


@jax.jit
def _agree(a, b):
    """Share of the bits (or entries) set in ``b`` that ``a`` has too."""
    count = lambda x: jnp.sum(jax.lax.population_count(x), dtype=jnp.float32)  # noqa: E731
    return count(a & b) / count(b)


def _choice_mask(choice, experts):
    """(T, k) chosen experts -> (T, experts) uint8, 1 where chosen."""
    return jax.nn.one_hot(choice, experts, dtype=jnp.uint8).sum(1)


class Runner:
    def __init__(self, config, traffic, seed, devices, say=print):
        if len(devices) != 1:
            raise ValueError("lm_train drives one chip, got %d" % len(devices))
        self.cfg = config
        self.traffic = traffic
        self.seed = seed
        self.say = say
        self.seq_len = config["seq_len"] * traffic["batch_per_chip"]
        self.items_per_step = self.seq_len
        self.spans = ("step",)
        self.phases = {}
        self.checked_steps = traffic.get("checked_steps", CHECKED_STEPS)
        self.limits = config["limits"]
        self.ref = importlib.import_module(
            "benchmark.reference." + config["reference"])

    # -- set-up ------------------------------------------------------------
    def make_step(self, cfg=None, loss_fn=None):
        """-> (step, {leaf: shape}): the program's train step for ``cfg``
        (default: the run's) and its leaves, in the state's order."""
        import mxnet_tpu as mx
        from mxnet_tpu.gluon.functional import make_train_step
        from mxnet_tpu.gluon.model_zoo.text import KeyeLM, KeyeLMLoss

        cfg = cfg or self.cfg
        # the seed's weights replace these: zeros, on the host
        net = KeyeLM.from_config(
            cfg, capacity_factor=cfg["moe_capacity_factor"],
            attn_block=cfg["attn_block"], attn_span=cfg["attn_span"],
            weight_initializer=mx.init.Zero())
        net.initialize(ctx=mx.cpu())
        step, state, (names, learn_idx, aux_idx) = make_train_step(
            net, loss_fn or KeyeLMLoss(cfg["balance_coef"]),
            learning_rate=cfg["learning_rate"], optimizer=cfg["optimizer"],
            beta1=cfg["beta1"], beta2=cfg["beta2"], epsilon=cfg["epsilon"],
            compute_dtype=cfg["compute_dtype"])
        if aux_idx:
            raise RuntimeError("the model has auxiliary state: %r" % aux_idx)
        return step, {names[i][len(net.prefix):]: tuple(v.shape)
                      for i, v in zip(learn_idx, state[0])}

    def compile_step(self, cfg=None, loss_fn=None):
        step, leaves = self.make_step(cfg, loss_fn)
        if list(leaves) != self.names:
            raise RuntimeError("another model's leaves")
        return jax.jit(step, donate_argnums=(0,)).lower(
            self.state, self.tokens, self.labels, self.key).compile()

    def build(self):
        t0 = time.perf_counter()
        import mxnet_tpu  # noqa: F401
        from mxnet_tpu.gluon.functional import count_step
        from mxnet_tpu.telemetry import tracing

        self._count_step, self._tracing = count_step, tracing
        self.phases["import_s"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        step, leaves = self.make_step()
        self.names = list(leaves)
        self.spec = self.ref.param_spec(self.cfg)
        shapes = {n: tuple(s) for n, s, _ in self.spec}
        if leaves != shapes:
            raise RuntimeError(
                "the program's parameters are not the configuration's: %r"
                % sorted(set(leaves.items()) ^ set(shapes.items()))[:8])
        self.phases["net_init_s"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        self.place_seed()
        self.phases["weights_s"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        lowered = jax.jit(step, donate_argnums=(0,)).lower(
            self.state, self.tokens, self.labels, self.key)
        self.phases["trace_lower_s"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        self.compiled = lowered.compile()
        self.phases["compile_or_load_s"] = time.perf_counter() - t0
        # what tracing left on the host's heap goes now, in set-up: two of
        # nine windows held one step of 2.7 and 5.5 s some seconds after the
        # harness's own collection had to free it (PERF.md section 7 row 22)
        del step, lowered
        gc.collect()
        self._norms = jax.jit(precision.tree_l2)
        self._delta = jax.jit(lambda a, b: precision.tree_l2(
            [x - b[n] for x, n in zip(a, self.names)]))

    def seed_weights(self):
        """The seed's weights, the embedding rows widened to the
        configuration's ``embedding_std`` (its ``assumed.weights`` says why:
        at the 0.02 of the other matrices every token routes to the same
        experts from the third layer on)."""
        weights = dict(seeded.make_weights(self.spec, self.seed))
        weights["embed_weight"] = weights["embed_weight"] * (
            self.cfg["embedding_std"] / seeded.KINDS["head"][1])
        return weights

    def place_seed(self, seed=None):
        """State and token ids of ``seed`` (default: the run's) on the chip."""
        if seed is not None:
            self.seed = seed
        weights = self.seed_weights()
        learn = [weights[n] for n in self.names]
        self.state = (learn, {"mean": [jnp.zeros_like(v) for v in learn],
                              "var": [jnp.zeros_like(v) for v in learn],
                              "t": jnp.zeros((), jnp.int32)}, [])
        self.tokens, self.labels = token_batch(
            self.seed, self.seq_len, self.cfg["vocab_size"])
        self.key = jax.random.PRNGKey(0)          # the model draws nothing
        jax.block_until_ready((self.state, self.tokens))

    def call_step(self):
        self.state, loss, aux = self.compiled(
            self.state, self.tokens, self.labels, self.key)
        return loss, aux

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
                self.selection = aux["selection"]
                self.choice = aux["choice"]
        r["scalars"]["moe_dropped_pairs"] = float(dropped)
        w0 = self.seed_weights()
        r["delta"] = dict(zip(self.names, np.asarray(jnp.stack(
            self._delta(self.state[0], w0)), np.float64)))
        del w0
        self.readings = r
        for _ in range(self.traffic["warmup_steps"]):
            jax.block_until_ready(self.call_step()[0])
        self.phases["first_steps_s"] = time.perf_counter() - t0

    # -- the window --------------------------------------------------------
    def window(self, seconds, span):
        """Steps until the deadline, each closed on the host by its loss.
        While a profiler session is live the program's own ``step`` span is
        open around each and takes the step's device counters.
        -> (t_start, [end time of every step])."""
        ends = []
        budget = self.traffic["max_steps"]
        t_start = time.perf_counter()
        deadline = t_start + seconds
        while time.perf_counter() < deadline and len(ends) < budget:
            with span("step"), self._tracing.start_trace("step"):
                loss, aux = self.call_step()
                loss.block_until_ready()
                self._count_step(aux, COUNTERS)
            ends.append(time.perf_counter())
        self.last_loss = float(loss)
        return t_start, ends

    def memory(self):
        """-> (bytes on the chip, which source)."""
        m = self.compiled.memory_analysis()
        declared = (m.argument_size_in_bytes + m.output_size_in_bytes
                    - m.alias_size_in_bytes + m.temp_size_in_bytes)
        return int(declared), "compiled.memory_analysis"

    def release(self):
        self.state = self.compiled = None
        gc.collect()

    # -- correct -----------------------------------------------------------
    def reference_readings(self, prec="float32", steps=None):
        """The plain reference over the same first steps, from the same seed;
        its own selection and choices ride along under ``_facts``."""
        cfg = self.cfg
        steps = steps or self.checked_steps
        names = [n for n, _, _ in self.spec]
        weights = self.seed_weights()
        tokens, _ = token_batch(self.seed, self.seq_len, cfg["vocab_size"])
        model = self.ref.Reference(cfg, weights, prec,
                                   cfg["reference_block"])
        r = {"loss": [], "scalars": {
            "moe_dropped_pairs": 0.0, "selection_agree": 1.0,
            "routing_agree": 1.0}}
        for i in range(1, steps + 1):
            loss, parts, facts = model.step(tokens)
            r["loss"].append(float(loss))
            for term in TERMS:
                r["scalars"]["%s_step%d" % (term, i)] = float(parts[term])
            if i == 1:
                r["grad"] = dict(zip(names, np.asarray(jnp.stack(
                    precision.tree_l2_jit([model.m[n] for n in names])),
                    np.float64) / (1.0 - cfg["beta1"])))
                r["_facts"] = (facts["selection"], facts["choice"])
        r["delta"] = dict(zip(names, np.asarray(jnp.stack(
            precision.tree_l2_jit([model.p[n] - weights[n]
                                   for n in names])), np.float64)))
        return r

    def check(self, prec="float32"):
        """-> (correct, {name: [value, limit]}, extra facts)."""
        if not np.isfinite(self.last_loss):
            return False, {"last_loss_finite": [float("nan"), 0]}, {}
        want = self.reference_readings(prec)
        selection, choice = want.pop("_facts")
        experts = self.cfg["num_local_experts"]
        got = dict(self.readings, scalars=dict(
            self.readings["scalars"],
            selection_agree=float(_agree(self.selection, selection)),
            routing_agree=float(_agree(_choice_mask(self.choice, experts),
                                       _choice_mask(choice, experts)))))
        return compare.check(got, want, self.limits)
