"""Runner of the symbolic-classification cells: ``mx.mod.Module.fit``.

Builds what ``examples/image-classification`` builds in its benchmark mode
(``train_imagenet.py --benchmark 1``): the recipe's symbol, its
``SyntheticDataIter`` (one batch, resident on the device, handed out for
every step), ``Module.fit`` with SGD.  ``fit`` owns the loop (Symbol ->
Executor -> FusedStepper -> optimizer -> metric); the runner owns the
window: it calls ``fit`` one epoch of ``steps_per_epoch`` steps at a time
until the deadline has passed, and clocks every step in
``batch_end_callback``, where reading the running metric closes the step on
the host.  Weights and the batch come from the seed (benchmark/seeded.py).

One Module is driven through its first epoch in set-up (the first three
steps give the readings for ``correct``) and then through the window.
"""
import contextlib
import gc
import importlib
import logging
import os
import time

import jax.numpy as jnp
import numpy as np

from benchmark import compare, seeded
from benchmark.reference import precision

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CHECKED_STEPS = 3
SPANS = ("module.forward_backward", "module.update", "module.update_metric",
         "bench.callback")


class Runner:
    def __init__(self, config, traffic, seed, devices, say=print):
        if len(devices) != 1:
            raise ValueError("module_fit drives one chip, got %d" % len(devices))
        self.cfg = config
        self.traffic = traffic
        self.seed = seed
        self.say = say
        self.batch = traffic["batch_per_chip"]
        self.items_per_step = self.batch
        self.spans = SPANS
        self.phases = {}
        self.limits = config["limits"]
        self.ref = importlib.import_module(
            "benchmark.reference." + config["reference"])

    # -- set-up ------------------------------------------------------------
    def build(self):
        t0 = time.perf_counter()
        import mxnet_tpu as mx
        from mxnet_tpu.test_utils import load_module_by_path

        self.mx = mx
        cfg = self.cfg
        exdir = os.path.join(REPO, "examples", "image-classification")
        symbols = load_module_by_path(
            os.path.join(exdir, "symbols", cfg["symbol_file"]),
            "_benchmark_symbol_" + cfg["name"])
        data = load_module_by_path(os.path.join(exdir, "common", "data.py"),
                                   "_benchmark_ic_data")
        self.phases["import_s"] = time.perf_counter() - t0

        t0 = time.perf_counter()
        mx.random.seed(0)
        shape = tuple(cfg["image_shape"])
        sym = symbols.get_symbol(
            num_classes=cfg["classes"], num_layers=cfg["num_layers"],
            image_shape=",".join(str(s) for s in shape))
        self.iter = data.SyntheticDataIter(
            cfg["classes"], (self.batch,) + shape,
            CHECKED_STEPS + self.traffic["warmup_steps"], "float32")
        images, labels = seeded.classification_batch(
            self.seed, self.batch, shape, cfg["classes"])
        self.iter.data = mx.nd.NDArray(images)
        self.iter.label = mx.nd.NDArray(labels)
        spec = self.ref.param_spec(cfg)
        weights = seeded.make_weights(spec, self.seed)
        self.w0 = {n: np.asarray(weights[n]) for n, _, _ in spec}
        # the program gets copies of its own: it may donate what it is given
        args = {n: mx.nd.NDArray(jnp.array(v, copy=True))
                for n, v in weights.items() if not self.ref.is_aux(n)}
        auxs = {n: mx.nd.NDArray(jnp.array(v, copy=True))
                for n, v in weights.items() if self.ref.is_aux(n)}
        self.mod = mx.mod.Module(symbol=sym, context=mx.current_context())
        self._fit_kwargs = dict(
            optimizer=cfg["optimizer"],
            optimizer_params={"learning_rate": cfg["learning_rate"],
                              "momentum": cfg["momentum"], "wd": cfg["wd"]},
            eval_metric=self.traffic["eval_metric"],
            arg_params=args, aux_params=auxs, allow_missing=False)
        self.phases["weights_s"] = time.perf_counter() - t0
        self._epoch = 0
        self._span = None
        self.step_ends = []
        self.readings = None
        # fit warns at every call that the Module is bound already
        self._log_level = logging.getLogger().level
        logging.getLogger().setLevel(logging.ERROR)

    def _callback(self, param):
        """``batch_end_callback``: reading the metric closes the step."""
        span = self._span
        if span is None:
            running = float(param.eval_metric.get()[1])
            self.step_ends.append(time.perf_counter())
        else:
            with span("bench.callback"):
                running = float(param.eval_metric.get()[1])
                self.step_ends.append(time.perf_counter())
        self.last_metric = running
        if self._first is not None:
            self._first(param.nbatch, running)

    def _fit_epoch(self):
        self.mod.fit(self.iter, begin_epoch=self._epoch,
                     num_epoch=self._epoch + 1,
                     batch_end_callback=self._callback, **self._fit_kwargs)
        self._epoch += 1

    def _params_now(self):
        args, auxs = self.mod.get_params()
        return {n: v.asnumpy() for n, v in {**args, **auxs}.items()}

    def first_steps(self):
        """The first epoch: steps 1..3 with the readings ``correct`` is
        decided from, then the warm-up steps."""
        t0 = time.perf_counter()
        cfg = self.cfg
        lr, wd = cfg["learning_rate"], cfg["wd"]
        r = {"loss": [], "scalars": {}}
        sums = [0.0]

        def first(nbatch, running):
            if nbatch >= CHECKED_STEPS:
                return
            # the metric is the mean over the epoch so far
            sums.append(running * (nbatch + 1))
            r["loss"].append(sums[-1] - sums[-2])
            if nbatch == 0:
                # the optimizer's first step undone: with no momentum yet,
                # p1 = p0 - lr * (g + wd * p0)
                p1 = self._params_now()
                r["grad"] = {}
                for n in self.learn_names:
                    p0 = self.w0[n].astype(np.float64)
                    g = (p0 - p1[n]) / lr - (wd * p0 if self.ref.decays(n) else 0.0)
                    r["grad"][n] = float(np.linalg.norm(g))
            if nbatch == CHECKED_STEPS - 1:
                p3 = self._params_now()
                change = {n: float(np.linalg.norm(
                    p3[n].astype(np.float64) - self.w0[n])) for n in p3}
                r["delta"] = {n: change[n] for n in self.learn_names}
                r["aux"] = {n: v for n, v in change.items()
                            if self.ref.is_aux(n)}

        self.learn_names = [n for n in self.w0 if not self.ref.is_aux(n)]
        self._first = first
        self._fit_epoch()
        self._first = None
        if self.mod._fused is None and "update" not in vars(self.mod):
            # (a test that replaces this Module's ``update`` is not asked)
            raise RuntimeError("Module.fit did not engage the fused step")
        self.readings = r
        self.w0 = None
        self._fit_kwargs.update(arg_params=None, aux_params=None)
        self.iter.max_iter = self.traffic["steps_per_epoch"]
        self.phases["first_epoch_s"] = time.perf_counter() - t0

    # -- the window --------------------------------------------------------
    def _annotate(self, span):
        """Host spans around the calls ``fit`` makes into the Module, put on
        this Module object from outside (the program carries none yet)."""
        for method, name in (("forward_backward", "module.forward_backward"),
                             ("update", "module.update"),
                             ("update_metric", "module.update_metric")):
            inner = getattr(self.mod, method)

            def outer(*a, _inner=inner, _name=name, **k):
                with span(_name):
                    return _inner(*a, **k)

            setattr(self.mod, method, outer)

    def window(self, seconds, span):
        """Whole epochs until the deadline has passed.
        -> (t_start, [end time of every step])."""
        if not isinstance(span("probe"), contextlib.nullcontext):
            self._span = span
            self._annotate(span)
        self.step_ends = []
        t_start = time.perf_counter()
        deadline = t_start + seconds
        epochs = 0
        while time.perf_counter() < deadline \
                and epochs < self.traffic["max_epochs"]:
            self._fit_epoch()
            epochs += 1
        return t_start, list(self.step_ends)

    def memory(self):
        return None, "fit holds the executable, not the runner"

    def release(self):
        self.mod = self.iter = self._fit_kwargs = None
        logging.getLogger().setLevel(self._log_level)
        gc.collect()

    # -- correct -----------------------------------------------------------
    def reference_readings(self, prec="float32", images=None,
                           steps=CHECKED_STEPS):
        cfg = self.cfg
        weights = seeded.make_weights(self.ref.param_spec(cfg), self.seed)
        data, label = seeded.classification_batch(
            self.seed, self.batch, tuple(cfg["image_shape"]), cfg["classes"])
        model = self.ref.Reference(cfg, weights, prec)
        r = {"loss": [], "scalars": {}}
        for i in range(1, steps + 1):
            loss, g = model.step(data, label, images)
            r["loss"].append(float(loss))
            if i == 1:
                names = list(g)
                r["grad"] = dict(zip(names, np.asarray(jnp.stack(
                    precision.tree_l2_jit([g[k] for k in names])), np.float64)))
        names = list(weights)
        change = dict(zip(names, np.asarray(jnp.stack(precision.tree_l2_jit(
            [model.p[k] - weights[k] for k in names])), np.float64)))
        r["delta"] = {n: change[n] for n in model.learn_names}
        r["aux"] = {n: v for n, v in change.items() if self.ref.is_aux(n)}
        return r

    def check(self):
        if not np.isfinite(self.last_metric):
            return False, {"last_metric_finite": [float("nan"), 0]}, {}
        return compare.check(self.readings, self.reference_readings(),
                             self.limits)
