"""The Moonlight runner, its plain reference, its counter and its reader at
toy size on the CPU.

One toy build serves the runs of this file.  Covered: a whole run through
``run_cell`` (the look for a chip skipped) prints a well-formed result with
``correct`` true; with the timed path broken underneath, once for each fault
the issue names, ``correct`` comes out false; the control (the reference one
precision down) fails the comparison; ``work/moonlight_lm.py``'s closed forms
equal a brute-force count; the new reader reads the step's counters under a
profiler session and nothing without one; the cell, its configuration and the
mirror key are declared as the issue gives them.
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
from benchmark.runners import moonlight_train  # noqa: E402

CELL = "moonlight_16b_a3b.train_s8k"
# float32 against float32 at toy size reads 1e-7; the bfloat16 control reads
# 8e-4 by the worst leaf's gradient and 8e-5 by the median leaf's
TOY_LIMITS = {"loss_step1": 1e-4, "loss_step3": 1e-4, "lm_loss_step1": 1e-4,
              "balance_loss_step1": 1e-4, "balance_loss_step3": 1e-3,
              "grad_worst_leaf": 1e-4, "grad_median_leaf": 1e-5,
              "delta_worst_leaf": 1e-2,
              "routing_agree": 1e-3, "gate_agree": 1e-4,
              "router_bias_agree": 1e-3,
              "moe_dropped_pairs": 0}


def toy():
    """1 dense + 1 expert layer, hidden 64, 4 heads of 16 + 8 / 12 over a
    latent of 20, 8 experts top-2 (4 held, from the third), 2 documents of
    32, float32."""
    _, cfg, traffic = bench_run.resolve(CELL)
    cfg.update(hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
               num_key_value_heads=4, kv_lora_rank=20, qk_nope_head_dim=16,
               qk_rope_head_dim=8, v_head_dim=12, intermediate_size=96,
               moe_intermediate_size=48, n_routed_experts=4, num_experts=4,
               num_experts_per_tok=2, vocab_size=96, seq_len=32,
               compute_dtype=None, learning_rate=1e-3, attn_block=8,
               attn_span=16, loss_block=16, reference_block=8,
               router_bias_std=0.1, control_precision="bfloat16",
               limits=TOY_LIMITS)
    cfg["deployment"] = dict(cfg["deployment"], first_expert=2,
                             published={"n_routed_experts": 8})
    traffic.update(warmup_steps=1, max_steps=4)
    return cfg, traffic


@pytest.fixture(scope="module")
def built():
    cfg, traffic = toy()
    r = moonlight_train.Runner(cfg, traffic, 11, jax.devices()[:1],
                               lambda m: None)
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

    real_build = moonlight_train.Runner.build

    def quick_build(self):
        for k in ("names", "aux_names", "spec", "_norms", "_delta",
                  "_count_step", "_tracing"):
            setattr(self, k, getattr(built, k))
        self.phases.update(built.phases)
        self.place_seed()

    moonlight_train.Runner.build = quick_build
    try:
        res = bench_run.run_cell(CELL, seed, 0.5, trace, jax.devices()[:1],
                                 config=cfg, traffic=traffic, prepare=prepare)
    finally:
        moonlight_train.Runner.build = real_build
    return res, seen[0]


def test_spec_is_the_share_the_issue_counts():
    from benchmark.reference import moonlight_lm

    full = bench_run.resolve(CELL)[1]
    spec = moonlight_lm.param_spec(full)
    names = [n for n, _, _ in spec]
    assert len(names) == len(set(names)) == 3 + 10 + 14 * 4
    n = {k: sum(int(np.prod(s)) for name, s, _ in spec if k in name)
         for k in ("l0_", "l1_", "l1_attn", "l1_moe_shared", "l1_moe_gate",
                   "embed", "head")}
    assert n["l1_attn"] == 13_762_560 + 2048 + 512      # + the two norms
    assert n["l1_moe_shared"] == 3 * 2048 * 2816
    assert n["l1_moe_gate"] == 8 * 2048 * 1408
    assert round(n["l1_"] / 1e6, 2) == 100.41           # an expert layer
    assert round(n["l0_"] / 1e6, 2) == 82.97            # the dense layer
    assert n["embed"] == n["head"] == 20480 * 2048
    total = sum(int(np.prod(s)) for _, s, _ in spec)
    assert total == 568_484_352                       # 9.10 GB at 16 B
    assert [(n, s) for n, s, _ in moonlight_lm.bias_spec(full)] == [
        ("l%d_moe_router_bias" % l, (64,)) for l in (1, 2, 3, 4)]


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
    # the bias moved, by whole steps of its rate, in every expert layer
    moved = np.abs(runner.bias - np.stack(
        [np.asarray(runner.seed_weights()[n]) for n in runner.aux_names]))
    assert moved.shape == (1, 8) and (moved.max(1) > 5e-4).all()
    # the control: the same reference one precision down is not correct
    correct, compared, _ = runner.check(prec="bfloat16")
    assert not correct
    assert compared["grad_worst_leaf"][0] > 5 * TOY_LIMITS["grad_worst_leaf"]


def _bias_never_updated(runner):
    compiled = runner.compiled

    def step(state, *args):
        bias = [b.copy() for b in state[2]]
        (learn, moments, _), loss, aux = compiled(state, *args)
        return (learn, moments, bias), loss, aux

    step.memory_analysis = compiled.memory_analysis
    runner.compiled = step


def _shared_expert_left_out(runner):
    # the first expert layer's shared expert gives nothing (its down
    # projection zeroed in the program's state, from the first step on)
    i = runner.names.index("l1_moe_shared_down_weight")
    learn = list(runner.state[0])
    learn[i] = jnp.zeros_like(learn[i])
    runner.state = (learn,) + tuple(runner.state[1:])


def _scaling_factor_dropped(runner):
    runner.compiled = runner.compile_step(
        dict(runner.cfg, routed_scaling_factor=1.0))


def _patched(module, name, replacement):
    """Compile the run's step with ``module.name`` replaced."""
    def fault(runner):
        real = getattr(module, name)
        setattr(module, name, replacement(real))
        try:
            runner.compiled = runner.compile_step()
        finally:
            setattr(module, name, real)

    return fault


def _gate_with_the_bias(real):
    def route(x, w, top_k, normalize=True, scoring="softmax", bias=None,
              scale=1.0):
        probs, choice, _ = real(x, w, top_k, normalize, scoring, bias, scale)
        gates = jnp.take_along_axis(probs + bias, choice, axis=1)
        gates = gates / (jnp.sum(gates, -1, keepdims=True) + 1e-20)
        return probs, choice, gates * scale

    return route


def _nope_dims_only(real):
    def project(sizes, *args):
        q, k, v = real(sizes, *args)
        nope = sizes[1]
        return q.at[..., nope:].set(0), k, v

    return project


def _documents_as_one(real):
    def forward(q, k, v, block, span):
        N, S = q.shape[:2]
        one = lambda a: a.reshape((1, N * S) + a.shape[2:])    # noqa: E731
        return real(one(q), one(k), one(v), block, span).reshape(
            (N, S, q.shape[2], v.shape[3]))

    return forward


def _faults():
    from mxnet_tpu.ops import transformer
    from mxnet_tpu.parallel import moe

    return [
        (_bias_never_updated, "router_bias_agree"),
        (_patched(moe, "route", _gate_with_the_bias), "gate_agree"),
        (_shared_expert_left_out, "grad_worst_leaf"),
        (_scaling_factor_dropped, "grad_worst_leaf"),
        (_patched(transformer, "_latent_project", _nope_dims_only),
         "grad_worst_leaf"),
        (_patched(transformer, "_causal_forward", _documents_as_one),
         "grad_worst_leaf")]


@pytest.mark.parametrize("which", range(6), ids=[
    "bias_never_updated", "bias_used_as_a_gate", "shared_expert_left_out",
    "routed_scaling_factor_dropped", "attention_over_qk_nope_only",
    "a_document_attends_into_the_other"])
def test_a_broken_timed_path_is_not_correct(built, which):
    fault, by = _faults()[which]
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
    assert counter.__name__ == "benchmark.work.moonlight_lm"
    S = 8192
    causal = (np.arange(S) + 1).sum() / S
    assert counter.causal_keys_mean(full) == pytest.approx(causal) == 4096.5
    macs = {l["name"]: l["macs"] for l in counter.layers(full)}
    assert len(macs) == 7 + 4 * 9 + 1
    proj = sum(macs["l2_attn_%s" % n] for n in ("q", "kv_a", "kv_b", "o"))
    assert proj == 2048 * 3072 + 2048 * 576 + 512 * 4096 + 2048 * 2048
    # every (query, key <= query) pair of a document, 16 heads of 192 / 128
    assert macs["l0_attn_scores_causal"] == pytest.approx(causal * 16 * 192)
    assert macs["l4_attn_values_causal"] == pytest.approx(causal * 16 * 128)
    assert macs["l0_dense_ffn"] == 3 * 2048 * 11264
    assert "l0_moe_router" not in macs and "l1_dense_ffn" not in macs
    assert macs["l1_moe_router"] == 2048 * 64
    assert macs["l1_moe_shared"] == 3 * 2048 * 2816
    assert macs["l1_moe_experts_held"] == 6 * 8 / 64 * 3 * 2048 * 1408
    assert macs["lm_head"] == 2048 * 20480
    # the issue's arithmetic: 380.5 M multiply-adds a token, 37.4 TFLOP a
    # step of 16 384 tokens, latent attention 46 % of it
    assert work.forward_macs(full) == pytest.approx(380.5e6, rel=5e-4)
    step = work.train_flops_per_item(full) * 16384
    assert step == pytest.approx(37.4e12, rel=1e-3)
    attention = sum(m for n, m in macs.items() if "_attn_" in n)
    assert attention / work.forward_macs(full) == pytest.approx(0.46, abs=0.005)
    assert counter.causal_keys_mean(toy()[0]) == 16.5


READER = "moe.router_load_max_over_mean"


def test_new_reader_gives_none_without_its_counters(monkeypatch):
    from mxnet_tpu.telemetry import tracing

    tracing._reset_for_tests()
    run = types.SimpleNamespace(config=toy()[0])
    read = bench_run.load_reader("layer_metrics", READER)
    assert read(run) is None                    # no span was ever recorded
    monkeypatch.delattr(tracing, "snapshot")    # a program without the ring
    assert read(run) is None


def test_readers_read_the_steps_counters_under_a_session(built, tmp_path):
    from mxnet_tpu.telemetry import tracing

    tracing._reset_for_tests()
    cfg, traffic = toy()
    runner = moonlight_train.Runner(cfg, traffic, 15, jax.devices()[:1],
                                    lambda m: None)
    for k in ("names", "aux_names", "spec", "compiled", "_count_step",
              "_tracing"):
        setattr(runner, k, getattr(built, k))
    runner.place_seed()
    jax.profiler.start_trace(str(tmp_path))
    try:
        runner.window(0.2, jax.profiler.TraceAnnotation)
    finally:
        jax.profiler.stop_trace()
    roots = [s for s in tracing.snapshot() if s["name"] == "step"]
    assert roots and all(s["attrs"]["router_pairs"] == 2 * 64
                         and s["attrs"]["moe_dropped_pairs"] == 0
                         for s in roots)
    run = types.SimpleNamespace(config=cfg)
    load = bench_run.load_reader("layer_metrics", READER)(run)
    assert 1.0 <= load <= 8
    held = bench_run.load_reader("layer_metrics",
                                 "moe.expert_load_max_over_mean")(run)
    assert 1.0 <= held <= cfg["num_experts"]
    tracing._reset_for_tests()


def test_the_cell_is_declared_as_the_issue_gives_it():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(c for c in bench["workloads"] if c["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "moonlight_16b_a3b_ep8", "train_s8k", 1)
    assert "attention sees every token" in cell["why"]
    assert "1/8 of their deployment load" in cell["why"]
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert entry["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                "vocab_size"]
    assert entry["source"] == ("https://huggingface.co/moonshotai/"
                               "Moonlight-16B-A3B/blob/main/config.json")
    reports = [m["name"] for m in bench["per_layer"] if CELL in m["workloads"]]
    assert set(reports) == {
        "frontend.host_gap_ms", "ops.conv_ms", "ops.formatting_ms",
        "cache.xla_hit_share", "device.idle_share", "device.mfu",
        "device.hbm_peak_gb", "compile.trace_lower_s", "compile.backend_s",
        "moe.expert_load_max_over_mean", READER}
    cfg, traffic = bench_run.resolve(CELL)[1:]
    # the accepted reader of moe.expert_load_max_over_mean takes the held
    # count from ``num_experts``: the mirror key stays equal
    assert cfg["num_experts"] == cfg["n_routed_experts"] == 8
    assert cfg["deployment"]["published"] == {
        "num_hidden_layers": 27, "n_routed_experts": 64, "vocab_size": 163840}
    assert cfg["deployment"]["chips_sharing_each_layer"] == 8
    # every published width stands
    assert [cfg[k] for k in (
        "hidden_size", "num_attention_heads", "qk_nope_head_dim",
        "qk_rope_head_dim", "v_head_dim", "kv_lora_rank", "intermediate_size",
        "moe_intermediate_size", "num_experts_per_tok", "n_shared_experts",
        "routed_scaling_factor")] == [2048, 16, 128, 64, 128, 512, 11264,
                                      1408, 6, 2, 2.446]
    assert (cfg["num_hidden_layers"], cfg["vocab_size"], cfg["seq_len"],
            traffic["batch_per_chip"]) == (5, 20480, 8192, 2)
    assert set(cfg["limits"]) == {
        "loss_step1", "loss_step3", "lm_loss_step3", "routing_agree",
        "gate_agree", "router_bias_agree", "grad_worst_leaf",
        "grad_median_leaf", "delta_median_leaf", "delta_worst_leaf",
        "moe_dropped_pairs"}
    assert cfg["limits"]["moe_dropped_pairs"] == 0
