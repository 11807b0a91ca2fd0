"""The language-model runner, its plain reference, its counter and its readers
at toy size on the CPU.

One toy build serves the runs of this file.  Covered: a whole run through
``run_cell`` (the look for a chip skipped) prints a well-formed result with
``correct`` true; with the timed path broken underneath, once for each fault
this cell can have, ``correct`` comes out false; the control (the reference
one precision down) fails the comparison; ``work/keye_lm.py``'s closed forms
equal a brute-force count; the new readers read the step's counters under a
profiler session and nothing without one.
"""
import json
import os
import sys
import types

import numpy as np
import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
sys.path.insert(0, REPO)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmark import run as bench_run, work  # noqa: E402
from benchmark.runners import lm_train  # noqa: E402

CELL = "keye_vl2_30b_a3b.train_s16k"
# float32 against float32 at toy size reads 1e-6 and less
TOY_LIMITS = {"loss_step1": 1e-4, "loss_step3": 1e-4, "lm_loss_step1": 1e-4,
              "balance_loss_step1": 1e-4, "indexer_kl_step1": 1e-4,
              "indexer_kl_step3": 1e-3, "grad_worst_leaf": 1e-3,
              "delta_worst_leaf": 1e-2, "selection_agree": 1e-3,
              "routing_agree": 1e-3, "moe_dropped_pairs": 0}


def toy():
    """2 layers, hidden 64, 8 experts top-2 (4 held, from the third), 16
    indexer-selected keys of 64, float32."""
    _, cfg, traffic = bench_run.resolve(CELL)
    cfg.update(hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
               num_key_value_heads=2, head_dim=16, moe_intermediate_size=32,
               num_local_experts=8, num_experts=4, num_experts_per_tok=2,
               vocab_size=96, seq_len=64, compute_dtype=None,
               learning_rate=1e-3, attn_block=16,
               attn_span=32, reference_block=16,
               control_precision="bfloat16", limits=TOY_LIMITS)
    cfg["rope_scaling"] = dict(cfg["rope_scaling"], mrope_section=[2, 3, 3])
    cfg["sa_config"] = dict(cfg["sa_config"], indexer_head_dim=8,
                            indexer_num_heads=4, topk=16)
    cfg["deployment"] = dict(cfg["deployment"], first_expert=2)
    traffic.update(warmup_steps=1, max_steps=4)
    return cfg, traffic


@pytest.fixture(scope="module")
def built():
    cfg, traffic = toy()
    r = lm_train.Runner(cfg, traffic, 11, jax.devices()[:1], lambda m: None)
    r.build()
    return r


def _run(built, seed, fault=None, trace=0):
    """A whole run on the executable built before: ``prepare`` hands it to
    the fresh runner and plants the fault.  -> (result, the runner)."""
    cfg, traffic = toy()
    seen = []

    def prepare(runner):
        runner.compiled = built.compiled
        seen.append(runner)
        if fault is not None:
            fault(runner)

    real_build = lm_train.Runner.build

    def quick_build(self):
        for k in ("names", "spec", "_norms", "_delta", "_count_step",
                  "_tracing"):
            setattr(self, k, getattr(built, k))
        self.phases.update(built.phases)
        self.place_seed()

    lm_train.Runner.build = quick_build
    try:
        res = bench_run.run_cell(CELL, seed, 0.5, trace, jax.devices()[:1],
                                 config=cfg, traffic=traffic, prepare=prepare)
    finally:
        lm_train.Runner.build = real_build
    return res, seen[0]


def test_spec_is_the_share_the_issue_counts():
    from benchmark.reference import keye_lm

    full = bench_run.resolve(CELL)[1]
    spec = keye_lm.param_spec(full)
    names = [n for n, _, _ in spec]
    assert len(names) == len(set(names)) == 3 + 17 * 4
    n = {k: sum(int(np.prod(s)) for name, s, _ in spec if k in name)
         for k in ("l0_", "l0_moe_gate", "embed", "head")}
    assert n["l0_"] == 21_401_984 + 16 * 4_718_592    # 96.9 M a layer
    assert n["l0_moe_gate"] == 16 * 2048 * 768
    assert n["embed"] == n["head"] == 18992 * 2048
    total = sum(int(np.prod(s)) for _, s, _ in spec)
    assert total == 465_391_104                     # 7.45 GB at 16 B


def test_run_prints_a_well_formed_correct_result(built):
    res, runner = _run(built, 12)
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(res)[-1] == "compared"
    assert res["correct"] is True and res["failed"] == 0
    assert set(res["metrics"]) == {"items_per_s", "step_ms_p90", "setup_s"}
    for name, limit in TOY_LIMITS.items():
        value, lim = res["compared"][name]
        assert lim == limit and value <= limit, name
    assert res["compared"]["moe_dropped_pairs"] == [0.0, 0]
    assert res["compared"]["compiled_in_window"] == [0, 0]
    assert res["compared"]["lm_loss_step2"][1] is None     # only printed
    json.dumps(res)
    # the control: the same reference one precision down is not correct
    correct, compared, _ = runner.check(prec="bfloat16")
    assert not correct
    assert compared["grad_worst_leaf"][0] > 5 * TOY_LIMITS["grad_worst_leaf"]


def _wrap(runner, after):
    compiled = runner.compiled

    def step(state, *args):
        first = jax.tree_util.tree_map(lambda v: v.copy(), state)
        return after(first, *compiled(state, *args))

    step.memory_analysis = compiled.memory_analysis
    runner.compiled = step


def _one_leaf_never_updates(runner):
    i = runner.names.index("l1_attn_v_weight")

    def held(first, state, loss, aux):
        params = list(state[0])
        params[i] = first[0][i]
        return (params,) + tuple(state[1:]), loss, aux

    _wrap(runner, held)


def _one_held_expert_skipped(runner):
    # the first layer's last held expert gives nothing (its down projection
    # zeroed in the program's state, from the first step on)
    i = runner.names.index("l0_moe_down_weight")

    def prepare_state():
        learn = list(runner.state[0])
        learn[i] = learn[i].at[-1].set(0.0)
        runner.state = (learn,) + tuple(runner.state[1:])

    prepare_state()


def _gates_not_renormalised(runner):
    runner.compiled = runner.compile_step(
        dict(runner.cfg, norm_topk_prob=False))


def _kl_left_out(runner):
    from mxnet_tpu.gluon.model_zoo.text import KeyeLMLoss

    whole = KeyeLMLoss(runner.cfg["balance_coef"])

    def no_kl(out, labels):
        loss, aux = whole(out, labels)
        return loss - aux["indexer_kl"], aux

    runner.compiled = runner.compile_step(loss_fn=no_kl)


def _indexer_ignored(runner):
    # every query keeps its topk nearest keys: the score is the key's position
    from mxnet_tpu.ops import transformer

    real = transformer._index_scores

    def position(iq, ik, iw, with_vjp=False):
        pos = jnp.arange(ik.shape[0], dtype=jnp.float32)[None, :]
        if with_vjp:
            scores, vjp = real(iq, ik, iw, True)
            return scores * 0 + pos, vjp
        return real(iq, ik, iw) * 0 + pos

    transformer._index_scores = position
    try:
        runner.compiled = runner.compile_step()
    finally:
        transformer._index_scores = real


@pytest.mark.parametrize("fault,by", [
    (_one_leaf_never_updates, "delta_worst_leaf"),
    # the held experts add little to a residual stream this wide: the loss
    # moves by 3e-5, their own gradients by a sixth and more
    (_one_held_expert_skipped, "grad_worst_leaf"),
    (_gates_not_renormalised, "grad_worst_leaf"),
    (_kl_left_out, "loss_step1"),
    (_indexer_ignored, "selection_agree")])
def test_a_broken_timed_path_is_not_correct(built, fault, by):
    res, _ = _run(built, 13, fault)
    assert res["correct"] is False
    over = [n for n, (v, lim) in res["compared"].items()
            if lim is not None and not v <= lim]
    assert by in over, res["compared"]


def test_dropped_pairs_fail_the_run(built):
    """A buffer of held pairs that is too small is counted, and not correct."""
    def small_buffer(runner):
        runner.compiled = runner.compile_step(
            dict(runner.cfg, moe_capacity_factor=0.5))

    res, _ = _run(built, 14, small_buffer)
    assert res["compared"]["moe_dropped_pairs"][0] > 0
    assert res["correct"] is False


def test_closed_forms_against_a_brute_force_count():
    full = bench_run.resolve(CELL)[1]
    counter = work.counter(full)
    assert counter.__name__ == "benchmark.work.keye_lm"
    S, k = 16384, 2048
    t = np.arange(S)
    selected, causal = np.minimum(t + 1, k).sum(), (t + 1).sum()
    assert counter.selected_keys_mean(full) == pytest.approx(selected / S)
    assert counter.causal_keys_mean(full) == pytest.approx(causal / S)
    assert counter.selected_share(full) * 100 == pytest.approx(23.4, abs=0.05)
    macs = {l["name"]: l["macs"] for l in counter.layers(full)}
    assert len(macs) == 9 * 4 + 1
    assert macs["l0_attn_qkv"] + macs["l0_attn_o"] == 18_874_368
    assert macs["l0_index_proj"] == 2048 * (16 * 64 + 64 + 16)
    assert macs["l3_index_scores_causal"] == pytest.approx(causal / S * 1024)
    assert macs["l0_attn_scores_selected"] == pytest.approx(
        selected / S * 32 * 128)
    assert macs["l0_moe_experts_held"] == 8 * 16 / 128 * 3 * 2048 * 768
    assert macs["lm_head"] == 2048 * 18992
    # the issue's arithmetic: 100.5 M model FLOPs a token and layer forward,
    # the indexer's scores and the selected-key attention 48 % of them
    layer = sum(2 * m for n, m in macs.items()
                if n.startswith("l0_") and not n.endswith("_bwd"))
    assert layer == pytest.approx(100.5e6, rel=0.005)
    sparse = 2 * (macs["l0_index_scores_causal"]
                  + macs["l0_attn_scores_selected"]
                  + macs["l0_attn_values_selected"])
    assert sparse / layer == pytest.approx(0.48, abs=0.005)
    # a step: 21.9 TFLOP of model work (the issue's 23.6 takes the index
    # scores' backward over all causal keys; the KL term needs the selected)
    step = work.train_flops_per_item(full) * S
    assert step == pytest.approx(21.9e12, rel=0.005)
    scores_bwd_over_causal = 4 * S * (
        4 * macs["l0_index_scores_causal"]
        - 2 * macs["l0_index_scores_selected_bwd"])
    assert step + scores_bwd_over_causal == pytest.approx(23.6e12, rel=0.005)
    toy_cfg = toy()[0]
    assert counter.selected_keys_mean(toy_cfg) == (16 * 17 / 2 + 48 * 16) / 64


NEW_READERS = ["attn.selected_share", "moe.expert_load_max_over_mean"]


@pytest.mark.parametrize("name", NEW_READERS)
def test_new_readers_give_none_without_their_counters(name, monkeypatch):
    from mxnet_tpu.telemetry import tracing

    tracing._reset_for_tests()
    run = types.SimpleNamespace(config=toy()[0])
    read = bench_run.load_reader("layer_metrics", name)
    assert read(run) is None                    # no span was ever recorded
    monkeypatch.delattr(tracing, "snapshot")    # a program without the ring
    assert read(run) is None


def test_new_readers_read_the_steps_counters_under_a_session(built, tmp_path):
    from mxnet_tpu.telemetry import tracing

    tracing._reset_for_tests()
    cfg, _ = toy()
    runner = lm_train.Runner(cfg, toy()[1], 15, jax.devices()[:1],
                             lambda m: None)
    for k in ("names", "spec", "compiled", "_count_step", "_tracing"):
        setattr(runner, k, getattr(built, k))
    runner.place_seed()
    jax.profiler.start_trace(str(tmp_path))
    try:
        runner.window(0.2, jax.profiler.TraceAnnotation)
    finally:
        jax.profiler.stop_trace()
    run = types.SimpleNamespace(config=cfg)
    share = bench_run.load_reader("layer_metrics", NEW_READERS[0])(run)
    # ties at zero (four index heads) select a little over the closed form
    closed = work.counter(cfg).selected_share(cfg) * 100
    assert closed <= share < closed * 1.15
    load = bench_run.load_reader("layer_metrics", NEW_READERS[1])(run)
    assert 1.0 <= load <= cfg["num_experts"]
    tracing._reset_for_tests()


def test_the_cell_is_declared_as_the_issue_gives_it():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = bench["workloads"][-1]
    assert cell == {
        "name": CELL, "config": "keye_vl2_30b_a3b_lm_ep8",
        "traffic": "train_s16k_b1", "chips": 1,
        "why": "1 sequence of 16384 tokens on the device, closed loop of the "
               "jitted Adam step: indexer, top-2048 selection and selected-key "
               "attention are most of the step; 16 of 128 experts at 1/8 of "
               "deployment load"}
    entry = bench["configs"][-1]
    assert entry["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert len(entry["source"]) == 191
    reports = [m["name"] for m in bench["per_layer"] if CELL in m["workloads"]]
    assert set(NEW_READERS + ["device.mfu", "ops.conv_ms", "device.hbm_peak_gb",
                              "compile.backend_s"]) <= set(reports)
    cfg = bench_run.resolve(CELL)[1]
    assert cfg["deployment"]["published"] == {
        "num_hidden_layers": 48, "num_experts": 128, "vocab_size": 151936}
    assert cfg["deployment"]["chips_sharing_each_layer"] == 8
