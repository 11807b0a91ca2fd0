"""The per-layer metrics of set-up that read ``compile_cache.stats()``'s
compile-stage unions and counters (ISSUE 37), on the CPU.

Covered: after the set-up of a toy functional cell (Ouro's looped step) and
of a toy ``Module.fit`` cell, each reader gives a finite number, and the
set-up traced registered operators and built programs; against a program
without the keys (a parent commit) each reader gives ``None`` and does not
raise; the four are declared for the six accepted cells and move
``setup_s``.
"""
import json
import math
import os
import sys
import types

import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
sys.path.insert(0, REPO)

import jax  # noqa: E402

from benchmark import run as bench_run  # noqa: E402

READERS = ("compile.trace_s", "compile.lower_s", "compile.programs",
           "compile.ops_traced")
CELLS = ("rfcn_r101.train_b8", "resnet50_sym.fit_b128_synth",
         "rfcn_r101.train_dp4", "keye_vl2_30b_a3b.train_s16k",
         "moonlight_16b_a3b.train_s8k", "ouro_2_6b.train_s4k")


def _ouro_toy():
    _, cfg, traffic = bench_run.resolve("ouro_2_6b.train_s4k")
    cfg.update(hidden_size=32, num_hidden_layers=1, num_attention_heads=2,
               num_key_value_heads=2, head_dim=16, intermediate_size=48,
               vocab_size=64, seq_len=16, compute_dtype=None, attn_block=8,
               attn_span=16, loss_block=16)
    traffic.update(warmup_steps=1)
    return cfg, traffic


def _fit_toy():
    _, cfg, traffic = bench_run.resolve("resnet50_sym.fit_b128_synth")
    cfg.update(image_shape=[3, 64, 64], classes=10)
    traffic.update(batch_per_chip=4, warmup_steps=1, steps_per_epoch=2,
                   max_epochs=1)
    return cfg, traffic


@pytest.fixture(scope="module", params=["ouro_train", "module_fit"])
def set_up(request):
    """A toy cell's set-up as ``run.run_cell`` takes it (build, the checked
    and warm-up steps), then ``compile_cache.stats()``: -> (the stats at
    the end of set-up, what set-up added to them)."""
    import importlib

    from mxnet_tpu import compile_cache

    cfg, traffic = (_ouro_toy if request.param == "ouro_train"
                    else _fit_toy)()
    runner = importlib.import_module(
        "benchmark.runners." + request.param).Runner(
            cfg, traffic, 2147483901, jax.devices()[:1], lambda m: None)
    before = compile_cache.stats()
    runner.build()
    runner.first_steps()
    after = compile_cache.stats()
    runner.release()
    return after, {k: after[k] - before[k] for k in after}


@pytest.mark.parametrize("name", READERS)
def test_reader_gives_a_finite_number_after_set_up(name, set_up):
    stats, added = set_up
    value = bench_run.load_reader("layer_metrics", name)(
        types.SimpleNamespace(cache_stats=stats))
    assert value is not None and math.isfinite(float(value)) and value > 0
    assert added["programs"] >= 1 and added["ops_traced"] >= 10
    assert 0 < added["trace_union_s"] <= added["trace_s"]
    assert 0 < added["lower_union_s"] <= added["lower_s"] + 1e-9


@pytest.mark.parametrize("name", READERS)
def test_reader_gives_none_on_a_program_without_its_key(name):
    """The driver runs these readers over the parent commit too, whose
    ``compile_cache.stats()`` has the sums but neither unions nor counts."""
    run = types.SimpleNamespace(cache_stats={
        "xla_hits": 3, "xla_misses": 0, "trace_s": 2.0, "lower_s": 1.0,
        "backend_s": 1.0, "cache_load_s": 0.5})
    assert bench_run.load_reader("layer_metrics", name)(run) is None


def test_metrics_are_declared_for_the_accepted_cells():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert [m["name"] for m in bench["per_layer"][-4:]] == list(READERS)
    for m in bench["per_layer"][-4:]:
        assert m["workloads"] == list(CELLS)
        assert m["moves"] == "setup_s" and m["better"] == "lower"
        assert m["layer"] == "compile cache"
        assert m["source"] == "program_counter"
        assert m["unit"] == ("s" if m["name"].endswith("_s") else "count")
