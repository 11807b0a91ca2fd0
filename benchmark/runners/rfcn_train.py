"""Runner of the Deformable R-FCN cells: the recipe's jitted train step.

Builds what ``examples/deformable_rfcn/train_fused.py`` builds (the model-zoo
``DeformableRFCN`` at the configuration's sizes, ``make_rfcn_train_step``,
``jax.jit(step, donate_argnums=(0,))`` lowered under ``jax.set_mesh``), on a
``{"dp": chips}`` mesh taken from the traffic file: parameters replicated,
the batch sharded.  Weights and the batch come from the seed
(``benchmark/seeded.py``), not from the program's initialisers.

One object, the compiled step with its chained, donated state, is driven
through its first steps in set-up (where the readings for ``correct`` are
taken) and then handed to the window.
"""
import gc
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import compare, seeded
from benchmark.reference import precision, rfcn as ref

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CHECKED_STEPS = 3


def _device_worst(x):
    """A replicated array as every chip holds it -> elementwise max |.| over
    the chips' own copies, and the widest disagreement between them."""
    copies = np.stack([np.asarray(s.data, np.float64)
                       for s in x.addressable_shards])
    return copies.max(axis=0), float(np.ptp(copies, axis=0).max())


class Runner:
    def __init__(self, config, traffic, seed, devices, say=print):
        self.cfg = config
        self.traffic = traffic
        self.seed = seed
        self.devices = list(devices)
        self.say = say
        self.chips = len(self.devices)
        self.batch = traffic["batch_per_chip"] * self.chips
        self.items_per_step = self.batch
        self.spans = ("step",)
        self.phases = {}
        # the reference walks every image of every checked step in float32:
        # a mix with a large global batch checks two steps for three
        self.checked_steps = traffic.get("checked_steps", CHECKED_STEPS)
        self.limits = config["limits"]

    # -- set-up ------------------------------------------------------------
    def build(self):
        t0 = time.perf_counter()
        import mxnet_tpu as mx
        from mxnet_tpu import parallel
        from mxnet_tpu.gluon.functional import functionalize
        from mxnet_tpu.gluon.model_zoo.detection import DeformableRFCN
        from mxnet_tpu.test_utils import load_module_by_path
        cfg = self.cfg
        recipe = load_module_by_path(
            os.path.join(REPO, "examples", "deformable_rfcn", "train_fused.py"),
            "_benchmark_rfcn_train_fused")
        self.phases["import_s"] = time.perf_counter() - t0

        t0 = time.perf_counter()
        mx.random.seed(0)
        net = DeformableRFCN(
            classes=cfg["classes"], image_shape=tuple(cfg["image_shape"]),
            units=tuple(cfg["units"]), pooled_size=cfg["pooled_size"],
            scales=tuple(cfg["anchor_scales"]), ratios=tuple(cfg["anchor_ratios"]),
            rpn_pre_nms=cfg["rpn_pre_nms"], rpn_post_nms=cfg["rpn_post_nms"],
            rpn_min_size=cfg["rpn_min_size"], batch_rois=cfg["batch_rois"],
            fg_fraction=cfg["fg_fraction"], rpn_batch=cfg["rpn_batch"],
            max_gts=cfg["max_gts"], frozen_bn=True)
        net.initialize()
        net.init_params()
        self.phases["net_init_s"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        step, state = recipe.make_rfcn_train_step(
            net, self.batch, learning_rate=cfg["learning_rate"],
            momentum=cfg["momentum"], compute_dtype=cfg["compute_dtype"])
        names = functionalize(net, train=True)[1]
        prefix = net.prefix
        names = [n[len(prefix):] for n in names]
        spec = ref.param_spec(cfg)
        if sorted(names) != sorted(n for n, _, _ in spec):
            raise RuntimeError(
                "the program's parameters are not the configuration's: %r"
                % sorted(set(names) ^ {n for n, _, _ in spec})[:8])
        shapes = {n: tuple(s) for n, s, _ in spec}
        self.learn_names = [n for n in names if not ref.is_aux(n)]
        aux_names = [n for n in names if ref.is_aux(n)]
        for got, n in zip(state[0], self.learn_names):
            if tuple(got.shape) != shapes[n]:
                raise RuntimeError("leaf %s: program %r, configuration %r"
                                   % (n, got.shape, shapes[n]))
        self.aux_names = aux_names
        self.spec = spec
        self.mesh = parallel.make_mesh({"dp": self.chips}, devices=self.devices)
        del net, state
        self.place_seed()
        self.phases["weights_s"] = time.perf_counter() - t0

        t0 = time.perf_counter()
        jstep = jax.jit(step, donate_argnums=(0,))
        with jax.set_mesh(self.mesh):
            lowered = jstep.lower(self.state, *self.batch_arrays, self.keys[0])
        self.phases["trace_lower_s"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        self.compiled = lowered.compile()
        self.phases["compile_or_load_s"] = time.perf_counter() - t0
        self._norms = jax.jit(precision.tree_l2)
        self._delta = jax.jit(lambda a, b: precision.tree_l2(
            [x - y for x, y in zip(a, b)]))

    def place_seed(self, seed=None):
        """State, batch and step keys of ``seed`` (default: the run's), on
        the mesh.  The compiled step does not depend on them, so a process
        that reads many seeds builds once and calls this for each."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        if seed is not None:
            self.seed = seed
        cfg = self.cfg
        aux_names = self.aux_names
        weights = seeded.make_weights(self.spec, self.seed)
        batch = seeded.detection_batch(
            self.seed, self.batch, cfg["image_shape"], cfg["classes"],
            cfg["max_gts"])
        repl = NamedSharding(self.mesh, P())
        rows = NamedSharding(self.mesh, P("dp"))
        # the program gets copies: its state is donated step after step
        state = ([jnp.array(weights[n], copy=True) for n in self.learn_names],
                 [jnp.zeros_like(weights[n]) for n in self.learn_names],
                 [jnp.array(weights[n], copy=True) for n in aux_names])
        self.state = jax.tree_util.tree_map(
            lambda v: jax.device_put(v, repl), state)
        self.w0 = [jax.device_put(weights[n], repl) for n in self.learn_names]
        self.batch_arrays = [jax.device_put(a, rows) for a in batch]
        n_keys = self.checked_steps + self.traffic["warmup_steps"] \
            + self.traffic["max_steps"]
        self.keys = [jax.device_put(k, repl)
                     for k in seeded.step_keys(self.seed, n_keys)]
        jax.block_until_ready((self.state, self.batch_arrays, self.keys))
        self.next_step = 0

    def call_step(self):
        """One training step through the compiled object; returns the loss
        (a device scalar) and the four parts."""
        self.state, loss, parts = self.compiled(
            self.state, *self.batch_arrays, self.keys[self.next_step])
        self.next_step += 1
        return loss, parts

    def first_steps(self):
        """The checked steps (1..3), with the readings ``correct`` is decided
        from, then the warm-up steps."""
        t0 = time.perf_counter()
        r = {"loss": [], "scalars": {}}
        disagree = 0.0
        for i in range(1, self.checked_steps + 1):
            loss, parts = self.call_step()
            r["loss"].append(float(loss))
            parts = np.asarray(parts, np.float64)
            r["scalars"]["rpn_loss_step%d" % i] = float(parts[0] + parts[1])
            if i == 1:      # momentum after one step is the first gradient
                g, d = _device_worst(jnp.stack(self._norms(self.state[1])))
                r["grad"] = dict(zip(self.learn_names, g))
                disagree = max(disagree, d / max(float(np.median(g)), 1e-30))
        dn, d = _device_worst(jnp.stack(self._delta(self.state[0], self.w0)))
        r["delta"] = dict(zip(self.learn_names, dn))
        disagree = max(disagree, d / max(float(np.median(dn)), 1e-30))
        r["scalars"]["chips_disagree"] = disagree
        self.readings = r
        self.w0 = None
        for _ in range(self.traffic["warmup_steps"]):
            jax.block_until_ready(self.call_step()[0])
        self.phases["first_steps_s"] = time.perf_counter() - t0

    # -- the window --------------------------------------------------------
    def window(self, seconds, span):
        """Steps until the deadline, each closed on the host by its loss.
        -> (t_start, [end time of every step])."""
        ends = []
        budget = self.traffic["max_steps"]
        t_start = time.perf_counter()
        deadline = t_start + seconds
        while time.perf_counter() < deadline and len(ends) < budget:
            with span("step"):
                loss, _ = self.call_step()
                loss.block_until_ready()
            ends.append(time.perf_counter())
        self.last_loss = float(loss)
        return t_start, ends

    def memory(self):
        """-> (bytes on the fullest chip, which source)."""
        m = self.compiled.memory_analysis()
        declared = (m.argument_size_in_bytes + m.output_size_in_bytes
                    - m.alias_size_in_bytes + m.temp_size_in_bytes)
        return int(declared), "compiled.memory_analysis"

    def release(self):
        self.state = self.compiled = self.keys = None
        gc.collect()

    # -- correct -----------------------------------------------------------
    def reference_readings(self, prec="float32", images=None, steps=None):
        """The plain reference over the same first steps, from the same seed
        (``images``: the rows a planted fault keeps)."""
        cfg = self.cfg
        steps = steps or self.checked_steps
        weights = seeded.make_weights(ref.param_spec(cfg), self.seed)
        data, im_info, gt = seeded.detection_batch(
            self.seed, self.batch, cfg["image_shape"], cfg["classes"],
            cfg["max_gts"])
        keys = seeded.step_keys(self.seed, steps)
        model = ref.Reference(cfg, weights, prec, cfg["reference_block"])
        w0 = {k: weights[k] for k in model.learn_names}
        r = {"loss": [], "scalars": {"chips_disagree": 0.0}}
        for i in range(1, steps + 1):
            loss, parts, g = model.step(data, im_info, gt, keys[i - 1], images)
            r["loss"].append(float(loss))
            parts = np.asarray(parts, np.float64)
            r["scalars"]["rpn_loss_step%d" % i] = float(parts[0] + parts[1])
            if i == 1:
                names = list(g)
                r["grad"] = dict(zip(names, np.asarray(
                    jnp.stack(precision.tree_l2_jit([g[k] for k in names])),
                    np.float64)))
        names = list(w0)
        r["delta"] = dict(zip(names, np.asarray(jnp.stack(precision.tree_l2_jit(
            [model.p[k] - w0[k] for k in names])), np.float64)))
        return r

    def check(self):
        """-> (correct, {name: [value, limit]}, extra facts)."""
        if not np.isfinite(self.last_loss):
            return False, {"last_loss_finite": [float("nan"), 0]}, {}
        return compare.check(self.readings, self.reference_readings(),
                             self.limits)
