"""The Module.fit runner and its plain reference at toy size on the CPU
(ResNet-50's symbol at 64x64, batch 8, 10 classes).

Covered: a whole run through ``run_cell`` (the look for a chip skipped)
prints a well-formed result with ``correct`` true, which is also the
comparison of the reference with the program; with the timed path broken
underneath (a step that leaves the state unchanged; BatchNorm's running
statistics left unchanged; half of the batch left out, the mean taken over
the rest) ``correct`` comes out false; and the
control (the reference in bfloat16) fails the comparison.
"""
import json
import os
import sys

import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
sys.path.insert(0, REPO)

import jax  # noqa: E402

from benchmark import compare, run as bench_run  # noqa: E402
from benchmark.runners import module_fit  # noqa: E402

CELL = "resnet50_sym.fit_b128_synth"
# float32 against float32 at this size reads 3e-4 (losses of steps 2 and 3),
# 1e-2 (worst leaf: the step is recovered from rounded parameters); the
# control reads 2e-2 and more on the losses
TOY_LIMITS = {"loss_step1": 1e-4, "loss_step2": 2e-3, "loss_step3": 2e-3,
              "grad_median_leaf": 3e-3, "delta_median_leaf": 1e-2,
              "aux_worst_leaf": 1e-2}


def toy():
    cell, cfg, traffic = bench_run.resolve(CELL)
    cfg.update(image_shape=[3, 64, 64], classes=10, limits=TOY_LIMITS)
    traffic.update(batch_per_chip=8, warmup_steps=1, steps_per_epoch=3,
                   max_epochs=2)
    return cfg, traffic


def _run(seed, fault=None):
    cfg, traffic = toy()
    return bench_run.run_cell(CELL, seed, 0.2, 0, jax.devices()[:1],
                              config=cfg, traffic=traffic, prepare=fault)


def test_run_prints_a_well_formed_correct_result():
    res = _run(21)
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(res)[-1] == "compared"
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] % 3 == 0 and res["attempted"] >= 3   # whole epochs
    assert set(res["metrics"]) == {"items_per_s", "step_ms_p90", "setup_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    for name, limit in TOY_LIMITS.items():
        value, lim = res["compared"][name]
        assert lim == limit and value <= limit, (name, value)
    json.dumps(res)


def _state_unchanged(runner):
    runner.mod.update = lambda: None


def _aux_left_unchanged(runner):
    # the step trains, but BatchNorm's running statistics are put back to
    # what they were: no loss and no parameter of a training step reads them
    import jax.numpy as jnp

    inner = runner.mod.update
    kept = {}

    def update():
        aux = runner.mod._exec.aux_dict
        if not kept:
            kept.update({n: jnp.array(a._data, copy=True)
                         for n, a in aux.items()})
        inner()
        for n, a in aux.items():
            a._rebind(jnp.array(kept[n], copy=True))

    runner.mod.update = update


def _half_batch_left_out(runner):
    import mxnet_tpu as mx

    half = runner.batch // 2
    for name in ("data", "label"):
        a = getattr(runner.iter, name).asnumpy().copy()
        a[half:] = a[:half]
        setattr(runner.iter, name, mx.nd.array(a))


def _answer_altered(runner):
    # the metric is updated with the labels moved on by one: the loss that
    # the run reports is not the batch's
    inner = runner.mod.update_metric

    def altered(eval_metric, labels, *a, **k):
        return inner(eval_metric, [(l + 1) % 10 for l in labels], *a, **k)

    runner.mod.update_metric = altered


@pytest.mark.parametrize("fault", [_state_unchanged, _aux_left_unchanged,
                                   _half_batch_left_out, _answer_altered])
def test_a_broken_timed_path_is_not_correct(fault):
    res = _run(22, fault)
    assert res["correct"] is False
    over = [n for n, (v, lim) in res["compared"].items()
            if lim is not None and not v <= lim]
    assert over
    if fault is _aux_left_unchanged:
        # nothing else sees it: the limit on the running statistics decides
        assert over == ["aux_worst_leaf"]
        assert res["compared"]["aux_worst_leaf"][0] == pytest.approx(1.0)


def test_control_fails_the_comparison():
    cfg, traffic = toy()
    r = module_fit.Runner(cfg, traffic, 23, jax.devices()[:1], lambda m: None)
    want = r.reference_readings()
    control = compare.numbers(r.reference_readings(prec="bfloat16"), want)
    assert not compare.decide(control, TOY_LIMITS)[0]
    assert control["loss_step2"] > 3 * TOY_LIMITS["loss_step2"]
