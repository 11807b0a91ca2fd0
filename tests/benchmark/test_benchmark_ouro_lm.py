"""The Ouro runner, its plain reference, its counter and its reader at toy
size on the CPU.

One toy build serves the runs of this file.  Covered: a whole run through
``run_cell`` (the look for a chip skipped) prints a well-formed result with
``correct`` true; with the timed path broken underneath, once for each fault
the issue names, ``correct`` comes out false; the control (the reference one
precision down) fails the comparison; ``work/ouro_lm.py``'s closed forms equal
a brute-force count; the new reader reads the step's counters under a
profiler session and nothing without one; the cell and its configuration are
declared as the issue gives them, looked up by name.
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
from jax import lax  # noqa: E402

from benchmark import run as bench_run, work  # noqa: E402
from benchmark.runners import ouro_train  # noqa: E402

CELL = "ouro_2_6b.train_s4k"
READER = "loop.expected_exit_step"
# float32 against float32 at toy size reads 1e-6 and under; the bfloat16
# control reads 2e-3 by the worst leaf's gradient, 3e-4 by the median leaf's
TOY_LIMITS = {"loss_step1": 1e-5, "loss_step2": 1e-5, "loss_step3": 2e-5,
              "expected_lm_loss_step1": 1e-5, "expected_lm_loss_step2": 1e-5,
              "expected_lm_loss_step3": 2e-5, "exit_entropy_step1": 1e-5,
              "exit_entropy_step3": 1e-4,
              "lm_loss_exit1": 1e-5, "lm_loss_exit2": 1e-5,
              "lm_loss_exit3": 1e-5, "lm_loss_exit4": 1e-5,
              "exit_mass1": 1e-5, "exit_mass2": 1e-5, "exit_mass3": 1e-5,
              "exit_mass4": 1e-5,
              "grad_worst_leaf": 1e-4, "grad_median_leaf": 1e-5,
              "grad_final_norm": 1e-5,
              "delta_worst_leaf": 1e-2, "delta_median_leaf": 1e-3,
              "layer_applications": 0}


def toy():
    """2 layers applied 4 times, hidden 64, 4 heads of 16, feed-forward 96,
    vocabulary 96, 1 document of 32, float32."""
    _, cfg, traffic = bench_run.resolve(CELL)
    cfg.update(hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
               num_key_value_heads=4, head_dim=16, intermediate_size=96,
               vocab_size=96, seq_len=32, compute_dtype=None,
               learning_rate=1e-3, attn_block=8, attn_span=16, loss_block=16,
               reference_block=8, control_precision="bfloat16",
               limits=TOY_LIMITS)
    traffic.update(warmup_steps=1, max_steps=4)
    return cfg, traffic


@pytest.fixture(scope="module")
def built():
    cfg, traffic = toy()
    r = ouro_train.Runner(cfg, traffic, 11, jax.devices()[:1], lambda m: None)
    r.build()
    return r


_REFERENCE = {}


def _run(built, seed, fault=None, trace=0):
    """A whole run on the executable built before: ``prepare`` hands it to
    the fresh runner and plants the fault.  The reference's readings of a
    seed are computed once for the file.  -> (result, the runner)."""
    cfg, traffic = toy()
    seen = []

    def prepare(runner):
        runner.compiled = built.compiled
        seen.append(runner)
        if fault is not None:
            fault(runner)

    real_build = ouro_train.Runner.build
    real_reference = ouro_train.Runner.reference_readings

    def quick_build(self):
        for k in ("names", "spec", "_norms", "_delta", "_count_step",
                  "_tracing"):
            setattr(self, k, getattr(built, k))
        self.phases.update(built.phases)
        self.place_seed()

    def kept_reference(self, prec="float32", steps=None):
        key = (self.seed, prec, steps)
        if key not in _REFERENCE:
            _REFERENCE[key] = real_reference(self, prec, steps)
        return _REFERENCE[key]

    ouro_train.Runner.build = quick_build
    ouro_train.Runner.reference_readings = kept_reference
    try:
        res = bench_run.run_cell(CELL, seed, 0.5, trace, jax.devices()[:1],
                                 config=cfg, traffic=traffic, prepare=prepare)
    finally:
        ouro_train.Runner.build = real_build
        ouro_train.Runner.reference_readings = real_reference
    return res, seen[0]


def test_spec_is_the_stage_the_issue_counts():
    from benchmark.reference import ouro_lm

    full = bench_run.resolve(CELL)[1]
    spec = ouro_lm.param_spec(full)
    names = [n for n, _, _ in spec]
    assert len(names) == len(set(names)) == 5 + 11 * 8
    n = {k: sum(int(np.prod(s)) for name, s, _ in spec if k in name)
         for k in ("l3_", "l3_attn", "l3_ffn", "embed", "head")}
    assert n["l3_attn"] == 4 * 2048 * 2048 + 2 * 2048      # + its two norms
    assert n["l3_ffn"] == 3 * 2048 * 5632 + 2 * 2048
    assert n["l3_"] == 51_388_416
    assert n["embed"] == n["head"] == 49152 * 2048
    assert [s for name, s, _ in spec if name.startswith("gate_")] == [
        (1, 2048), (1,)]
    total = sum(int(np.prod(s)) for _, s, _ in spec)
    assert total == 612_438_017                       # 9.80 GB at 16 B


def test_run_prints_a_well_formed_correct_result(built):
    res, runner = _run(built, 12)
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(res)[-1] == "compared"
    assert res["correct"] is True and res["failed"] == 0
    assert set(res["metrics"]) == {"items_per_s", "step_ms_p90", "setup_s"}
    for name, limit in TOY_LIMITS.items():
        value, lim = res["compared"][name]
        assert lim == limit and value <= limit, name
    assert res["compared"]["layer_applications"] == [0.0, 0]
    assert res["compared"]["compiled_in_window"] == [0, 0]
    assert res["compared"]["exit_entropy_step2"][1] is None   # only printed
    got = runner.readings["scalars"]
    assert got["layer_applications"] == 8.0
    assert sum(got["exit_mass%d" % t] for t in (1, 2, 3, 4)) \
        == pytest.approx(1.0, rel=1e-5)
    json.dumps(res)
    # the control: the same reference one precision down is not correct
    correct, compared, _ = runner.check(prec="bfloat16")
    assert not correct
    assert compared["grad_worst_leaf"][0] > 5 * TOY_LIMITS["grad_worst_leaf"]
    assert compared["grad_median_leaf"][0] > 5 * TOY_LIMITS["grad_median_leaf"]


def _patched(owner, name, replacement, **compile_args):
    """Compile the run's step with ``owner.name`` replaced."""
    def fault(runner):
        kept = vars(owner)[name]          # a staticmethod stays one
        setattr(owner, name, replacement(getattr(owner, name)))
        try:
            runner.compiled = runner.compile_step(**compile_args)
        finally:
            setattr(owner, name, kept)

    return fault


def _three_passes(runner):
    runner.compiled = runner.compile_step(dict(runner.cfg, total_ut_steps=3))


def _entropy_sign_turned(runner):
    from mxnet_tpu.gluon.model_zoo.text import OuroLMLoss

    runner.compiled = runner.compile_step(
        loss_fn=OuroLMLoss(-runner.cfg["entropy_beta"]))


def _carry_detached(real):
    import mxnet_tpu as mx

    def loop(fn, times):
        def body(*carry):
            (h, applied), outs = fn(*carry)
            return [mx.nd.BlockGrad(h), applied], outs
        return real(body, times)

    return loop


def _a_pass_on_its_own_copy(first):
    """The first or the last pass runs a copy of the weights: its gradient
    goes to the copy, which nothing of this step reads again, and not to the
    shared weights."""
    import mxnet_tpu as mx
    from mxnet_tpu.gluon import block

    def alone(fn, *carry):
        params = [p for _, p in block._TRACING.params]
        shared = [p._data for p in params]
        for p in params:
            p._data = mx.nd.NDArray(lax.stop_gradient(p._data._data))
        try:
            carry, outs = fn(*carry)
        finally:
            for p, d in zip(params, shared):
                p._data = d
        return carry, [mx.nd.expand_dims(o, axis=0) for o in outs]

    def replacement(real):
        def loop(fn, times):
            def run(*carry):
                if first:
                    carry, one = alone(fn, *carry)
                    carry, rest = real(fn, times - 1)(*carry)
                    pairs = zip(one, rest)
                else:
                    carry, rest = real(fn, times - 1)(*carry)
                    carry, one = alone(fn, *carry)
                    pairs = zip(rest, one)
                return carry, [mx.nd.concat(a, b, dim=0) for a, b in pairs]
            return run
        return loop

    return replacement


def _final_norm_outside_the_loop(real):
    """The next pass starts from the state before the final norm; the gate
    and the head still read the normed one."""
    from mxnet_tpu.gluon.model_zoo.text import ouro_lm

    def hybrid_forward(self, *args, **params):
        kept, norm = ouro_lm.loop, self.final_norm
        before = []

        def watched(x):                 # the final norm, its input kept
            before.append(x)
            return norm(x)

        def loop(fn, times):
            def body(*carry):
                (_, applied), outs = fn(*carry)
                return [before.pop(), applied], outs
            return kept(body, times)

        ouro_lm.loop = loop
        object.__setattr__(self, "final_norm", watched)
        try:
            return real(self, *args, **params)
        finally:
            ouro_lm.loop = kept
            object.__setattr__(self, "final_norm", norm)

    return hybrid_forward


def _last_exit_takes_its_gates_share(real):
    def exit_shares(lam):
        shares = real(lam)
        return shares[:-1] + [shares[-1] * lam[lam.shape[0] - 1]]

    return staticmethod(exit_shares)


def _exits_two_and_three_swapped(real):
    def exit_shares(lam):
        shares = real(lam)
        return [shares[0], shares[2], shares[1]] + shares[3:]

    return staticmethod(exit_shares)


def _no_norm_after_the_feed_forward(real):
    def hybrid_forward(self, F, x, positions):
        y = x + self.attn_post_norm(self.attn(self.attn_norm(x), positions))
        return y + self.ffn(self.ffn_norm(y))

    return hybrid_forward


def _faults():
    from mxnet_tpu.gluon.model_zoo.text import ouro_lm

    return [
        (_three_passes, "layer_applications"),
        (_patched(ouro_lm, "loop", _carry_detached), "grad_median_leaf"),
        (_patched(ouro_lm.OuroLM, "hybrid_forward",
                  _final_norm_outside_the_loop), "loss_step1"),
        (_patched(ouro_lm.OuroLMLoss, "exit_shares",
                  _last_exit_takes_its_gates_share), "exit_mass4"),
        (_entropy_sign_turned, "loss_step1"),
        (_patched(ouro_lm._LoopLayer, "hybrid_forward",
                  _no_norm_after_the_feed_forward), "lm_loss_exit1"),
        (_patched(ouro_lm, "loop", _a_pass_on_its_own_copy(first=False)),
         "grad_final_norm"),
        (_patched(ouro_lm.OuroLMLoss, "exit_shares",
                  _exits_two_and_three_swapped), "exit_mass2"),
        (_patched(ouro_lm, "loop", _a_pass_on_its_own_copy(first=True)),
         "grad_median_leaf")]


@pytest.mark.parametrize("which", range(9), ids=[
    "three_passes_instead_of_four", "no_gradient_through_the_carried_state",
    "final_norm_left_out_of_the_loop", "last_exit_given_its_gates_share",
    "entropy_terms_sign_turned", "norm_after_a_sub_layer_left_out",
    "the_last_pass_with_its_own_copy_of_the_weights",
    "exits_two_and_three_swapped",
    "the_first_pass_with_its_own_copy_of_the_weights"])
def test_a_broken_timed_path_is_not_correct(built, which):
    fault, by = _faults()[which]
    res, _ = _run(built, 13, fault)
    assert res["correct"] is False
    over = [n for n, (v, lim) in res["compared"].items()
            if lim is not None and not v <= lim]
    assert by in over, res["compared"]


def test_closed_forms_against_a_brute_force_count():
    full = bench_run.resolve(CELL)[1]
    counter = work.counter(full)
    assert counter.__name__ == "benchmark.work.ouro_lm"
    S = 4096
    causal = (np.arange(S) + 1).sum() / S
    assert counter.causal_keys_mean(full) == pytest.approx(causal) == 2048.5
    rows = counter.layers(full)
    macs = {l["name"]: l["macs"] for l in rows}
    assert len(macs) == len(rows) == 4 * (8 * 7 + 2) and all(
        l["trained"] for l in rows)
    # one layer application, by hand: 16 heads of 128, every (query, key <=
    # query) pair of the document, the gated feed-forward
    brute = (4 * 2048 * 2048 + 2 * causal * 16 * 128 + 3 * 2048 * 5632)
    application = sum(m for n, m in macs.items() if n.startswith("p3_l5_"))
    assert application == brute == 59_770_880
    assert macs["p1_l0_attn_scores_causal"] == macs["p4_l7_attn_values_causal"] \
        == causal * 16 * 128
    assert macs["p2_exit_lm_head"] == 2048 * 49152 and macs["p4_exit_gate"] == 2048
    # the issue's arithmetic: 2 315 329 536 multiply-adds a token, 56.9 TFLOP
    # a step of 4096 tokens, the four exits' head 17 % of it
    assert work.forward_macs(full) == 2_315_329_536
    assert 32 * brute == 1_912_668_160
    step = work.train_flops_per_item(full) * 4096
    assert step == pytest.approx(56.9e12, rel=1e-3)
    head = sum(m for n, m in macs.items() if n.endswith("lm_head"))
    assert head / work.forward_macs(full) == pytest.approx(0.174, abs=0.001)
    deep = dict(full, num_hidden_layers=48)
    assert head / work.forward_macs(deep) == pytest.approx(0.034, abs=0.001)
    assert counter.causal_keys_mean(toy()[0]) == 16.5


def test_new_reader_gives_none_without_its_counters(monkeypatch):
    from mxnet_tpu.telemetry import tracing

    tracing._reset_for_tests()
    run = types.SimpleNamespace(config=toy()[0])
    read = bench_run.load_reader("layer_metrics", READER)
    assert read(run) is None                    # no span was ever recorded
    monkeypatch.delattr(tracing, "snapshot")    # a program without the ring
    assert read(run) is None


def test_reader_reads_the_steps_counters_under_a_session(built, tmp_path):
    from mxnet_tpu.telemetry import tracing

    tracing._reset_for_tests()
    cfg, traffic = toy()
    runner = ouro_train.Runner(cfg, traffic, 15, jax.devices()[:1],
                               lambda m: None)
    for k in ("names", "spec", "compiled", "_count_step", "_tracing"):
        setattr(runner, k, getattr(built, k))
    runner.place_seed()
    jax.profiler.start_trace(str(tmp_path))
    try:
        runner.window(0.2, jax.profiler.TraceAnnotation)
    finally:
        jax.profiler.stop_trace()
    roots = [s for s in tracing.snapshot() if s["name"] == "step"]
    assert roots and all(s["attrs"]["layer_applications"] == 8
                         and s["attrs"]["gate_tokens"] == 32 for s in roots)
    run = types.SimpleNamespace(config=cfg)
    step = bench_run.load_reader("layer_metrics", READER)(run)
    assert 1.0 < step < 4.0
    assert step == pytest.approx(
        sum(s["attrs"]["exit_step_milli"] for s in roots)
        / (32 * len(roots)) / 1000)
    tracing._reset_for_tests()


def test_the_cell_is_declared_as_the_issue_gives_it():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(c for c in bench["workloads"] if c["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "ouro_2_6b_loop4", "train_s4k", 1)
    assert "all 32 layer applications and 4 exits" in cell["why"]
    assert "17 %" in cell["why"] and "3 %" in cell["why"]
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == ("https://huggingface.co/ByteDance/Ouro-2.6B/"
                               "blob/main/config.json")
    reports = [m["name"] for m in bench["per_layer"] if CELL in m["workloads"]]
    assert set(reports) == {
        "frontend.host_gap_ms", "ops.conv_ms", "ops.formatting_ms",
        "cache.xla_hit_share", "device.idle_share", "device.mfu",
        "device.hbm_peak_gb", "compile.trace_lower_s", "compile.backend_s",
        READER}
    new = next(m for m in bench["per_layer"] if m["name"] == READER)
    assert (new["unit"], new["better"], new["source"], new["layer"],
            new["moves"], new["workloads"]) == (
        "count", "lower", "program_counter", "operators", "items_per_s",
        [CELL])
    cfg, traffic = bench_run.resolve(CELL)[1:]
    assert cfg["deployment"]["published"] == {"num_hidden_layers": 48}
    assert cfg["deployment"]["chips_sharing_each_layer"] == 1
    assert "looped on themselves" in cfg["deployment"]["departure"]
    # every published width and the loop's length stand
    assert [cfg[k] for k in (
        "hidden_size", "num_attention_heads", "num_key_value_heads",
        "head_dim", "intermediate_size", "vocab_size", "total_ut_steps",
        "rope_theta", "rms_norm_eps", "max_position_embeddings")] == [
        2048, 16, 16, 128, 5632, 49152, 4, 1000000, 1e-6, 65536]
    assert cfg["layer_types"] == ["full_attention"] * 48
    assert (cfg["num_hidden_layers"], cfg["seq_len"], cfg["entropy_beta"],
            traffic["batch_per_chip"], traffic["trace_seconds"]) == (
        8, 4096, 0.1, 1, 12)
    assert cfg["limits"]["layer_applications"] == 0
    assert set(cfg["limits"]) == {
        "loss_step1", "loss_step2", "loss_step3", "expected_lm_loss_step1",
        "expected_lm_loss_step2", "expected_lm_loss_step3",
        "exit_entropy_step1", "lm_loss_exit1", "lm_loss_exit2",
        "lm_loss_exit3", "lm_loss_exit4", "exit_mass1", "exit_mass2",
        "exit_mass3", "exit_mass4", "grad_worst_leaf", "grad_median_leaf",
        "grad_final_norm",
        "delta_median_leaf", "delta_worst_leaf", "layer_applications"}
