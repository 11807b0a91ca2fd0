"""The R-FCN runner and its plain reference at toy size on the CPU.

One toy build serves every test of this file (the compiled step does not
depend on the seed).  Covered: the reference agrees with the program; a
whole run through ``run_cell`` (the look for a chip skipped) prints a
well-formed result with ``correct`` true; with the timed path broken
underneath, once for each fault this cell can have, ``correct`` comes out
false; and the control (the reference in float8) fails the comparison.
"""
import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
sys.path.insert(0, REPO)

import jax  # noqa: E402

from benchmark import compare, run as bench_run  # noqa: E402
from benchmark.runners import rfcn_train  # noqa: E402

CELL = "rfcn_r101.train_b8"
# float32 against float32 at toy size reads 1e-6 and less; the control
# (float8) reads 0.1 on the worst leaf's gradient and 1e-3 on the losses
TOY_LIMITS = {"loss_step1": 1e-4, "loss_step2": 1e-4, "loss_step3": 1e-4,
              "rpn_loss_step1": 1e-4, "grad_worst_leaf": 1e-3,
              "delta_worst_leaf": 1e-3, "chips_disagree": 1e-6}


def toy():
    _, cfg, traffic = bench_run.resolve(CELL)
    cfg.update(units=[1, 1, 1, 1], image_shape=[64, 96], classes=3,
               anchor_scales=[1, 2], rpn_pre_nms=200, rpn_post_nms=32,
               batch_rois=16, rpn_batch=32, max_gts=8, compute_dtype=None,
               reference_block=1, limits=TOY_LIMITS)
    traffic.update(batch_per_chip=2, warmup_steps=1, max_steps=6)
    return cfg, traffic


@pytest.fixture(scope="module")
def built():
    cfg, traffic = toy()
    r = rfcn_train.Runner(cfg, traffic, 11, jax.devices()[:1], lambda m: None)
    r.build()
    return r


def _run(built, seed, fault=None, cell=CELL, traffic=None, devices=None):
    """A whole run on the executable built before: ``prepare`` hands it to
    the fresh runner and plants the fault."""
    cfg, toy_traffic = toy()
    traffic = traffic or toy_traffic

    def prepare(runner):
        runner.compiled = built.compiled
        if fault is not None:
            fault(runner)

    real_build = rfcn_train.Runner.build

    def quick_build(self):
        for k in ("learn_names", "aux_names", "spec", "mesh", "_norms", "_delta"):
            setattr(self, k, getattr(built, k))
        self.phases.update(built.phases)
        self.place_seed()

    rfcn_train.Runner.build = quick_build
    try:
        return bench_run.run_cell(cell, seed, 0.5, 0,
                                  devices or jax.devices()[:1],
                                  config=cfg, traffic=traffic, prepare=prepare)
    finally:
        rfcn_train.Runner.build = real_build


def test_spec_names_every_leaf_once():
    cfg, _ = toy()
    from benchmark.reference import rfcn

    names = [n for n, _, _ in rfcn.param_spec(cfg)]
    assert len(names) == len(set(names))
    full = bench_run.resolve(CELL)[1]
    n_params = sum(int(np.prod(s)) for n, s, _ in rfcn.param_spec(full)
                   if not rfcn.is_aux(n))
    assert 45e6 < n_params < 55e6       # ResNet-101 trunk + heads: 49.9 M


def test_run_prints_a_well_formed_correct_result(built):
    res = _run(built, 12)
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(res)[-1] == "compared"
    assert res["correct"] is True and res["failed"] == 0
    assert 1 <= res["attempted"] <= 6
    assert set(res["metrics"]) == {"items_per_s", "step_ms_p90", "setup_s"}
    for m in res["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    assert res["device"]["platform"] == "cpu" and res["device"]["count"] == 1
    assert res["device"]["memory_peak_bytes"] > 0
    for name, limit in TOY_LIMITS.items():
        value, lim = res["compared"][name]
        assert lim == limit and value <= limit
    assert res["compared"]["compiled_in_window"] == [0, 0]
    json.dumps(res)


def _state_unchanged(runner):
    compiled = runner.compiled

    def stuck(state, *args):
        _, loss, parts = compiled(jax.tree_util.tree_map(lambda v: v.copy(), state),
                                  *args)
        return state, loss, parts

    stuck.memory_analysis = compiled.memory_analysis
    runner.compiled = stuck


def _half_batch_left_out(runner):
    # rows of the second half replaced by the first half's: the mean is over
    # half of the batch
    half = runner.batch // 2
    runner.batch_arrays = [np.concatenate([np.asarray(a)[:half]] * 2)
                           for a in runner.batch_arrays]
    runner.batch_arrays = [jax.numpy.asarray(a) for a in runner.batch_arrays]


def _one_leaf_never_updates(runner):
    # the offsets' convolution of the deformable PS-ROI pooling keeps its
    # first weights: the median leaf does not see it; the worst leaf reads
    # that leaf's change over the median leaf's, or 1 if it is the larger
    # (at the cell's own size 2.8 medians: 1; at this toy size 0.08)
    compiled = runner.compiled
    i = runner.learn_names.index("rfcn_trans_weight")

    def held(state, *args):
        first = state[0][i].copy()
        state, loss, parts = compiled(state, *args)
        params = list(state[0])
        params[i] = first
        return (params,) + tuple(state[1:]), loss, parts

    held.memory_analysis = compiled.memory_analysis
    runner.compiled = held


def _answer_altered(runner):
    compiled = runner.compiled

    def altered(state, *args):
        state, loss, parts = compiled(state, *args)
        return state, loss * 1.01, parts

    altered.memory_analysis = compiled.memory_analysis
    runner.compiled = altered


@pytest.mark.parametrize("fault", [_state_unchanged, _one_leaf_never_updates,
                                   _half_batch_left_out, _answer_altered])
def test_a_broken_timed_path_is_not_correct(built, fault):
    res = _run(built, 13, fault)
    assert res["correct"] is False
    over = [n for n, (v, lim) in res["compared"].items()
            if lim is not None and not v <= lim]
    assert over, res["compared"]
    if fault is _one_leaf_never_updates:
        assert over == ["delta_worst_leaf"]
        assert res["compared"]["delta_worst_leaf"][0] \
            > 10 * TOY_LIMITS["delta_worst_leaf"]


def test_control_and_shard_fault_fail_the_comparison(built):
    """The reference put in the program's place: in float8 (the control of
    a bfloat16 configuration), and over one chip's shard alone (the
    exchange between chips left out)."""
    cfg, traffic = toy()
    r = rfcn_train.Runner(cfg, traffic, 14, jax.devices()[:1], lambda m: None)
    want = r.reference_readings()
    same = compare.decide(compare.numbers(r.reference_readings(), want),
                          TOY_LIMITS)
    assert same[0]
    control = compare.numbers(r.reference_readings(prec="float8"), want)
    assert not compare.decide(control, TOY_LIMITS)[0]
    assert control["grad_worst_leaf"] > 10 * TOY_LIMITS["grad_worst_leaf"]
    shard = compare.numbers(r.reference_readings(images=[0]), want)
    assert not compare.decide(shard, TOY_LIMITS)[0]
