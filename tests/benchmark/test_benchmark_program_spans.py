"""The per-layer metrics that read the program's own spans and counters
(ISSUE 26), on a toy ``Module.fit`` on the CPU.

Covered: under a ``jax.profiler`` session, which is how a ``--trace 1`` run
takes its window, every new reader returns a finite number; with no session
(a ``--trace 0`` run, a cell that bypasses ``Module.fit``) the span readers
return ``None``; and against a program without the spans or the durations (a
parent commit) every reader returns ``None`` and does not raise.
"""
import json
import math
import os
import sys
import types

import numpy as np
import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
sys.path.insert(0, REPO)

import jax  # noqa: E402

from benchmark import run as bench_run  # noqa: E402

SPAN_READERS = ("frontend.update_prepare_ms", "frontend.update_dispatch_ms",
                "frontend.update_commit_ms", "frontend.dispatches_per_step",
                "input.data_wait_share")
STATS_READERS = ("compile.trace_lower_s", "compile.backend_s")
STEPS = 4


def _fit():
    import mxnet_tpu as mx

    data = mx.sym.var("data")
    net = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(data, name="fc", num_hidden=4), name="softmax")
    x = np.random.RandomState(0).randn(8 * STEPS, 8).astype(np.float32)
    mod = mx.mod.Module(net)
    mod.fit(mx.io.NDArrayIter(x, np.zeros((8 * STEPS,), np.float32),
                              batch_size=8),
            num_epoch=1, optimizer="sgd", eval_metric="ce")


def _run():
    from mxnet_tpu import compile_cache

    return types.SimpleNamespace(cache_stats=dict(compile_cache.stats()))


@pytest.fixture
def fresh_ring(monkeypatch):
    from mxnet_tpu.telemetry import tracing

    monkeypatch.delenv("MXNET_TRACE", raising=False)
    tracing._reset_for_tests()
    yield tracing
    tracing._reset_for_tests()


@pytest.fixture
def traced_window(fresh_ring, tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    try:
        _fit()
    finally:
        jax.profiler.stop_trace()
    return _run()


@pytest.mark.parametrize("name", SPAN_READERS + STATS_READERS)
def test_reader_gives_a_finite_number_on_a_traced_window(name, traced_window):
    value = bench_run.load_reader("layer_metrics", name)(traced_window)
    assert value is not None and math.isfinite(float(value)) and value >= 0
    if name == "frontend.dispatches_per_step":
        assert value >= 1           # the one fused program, at the least
    if name == "input.data_wait_share":
        assert value < 100


@pytest.mark.parametrize("name", SPAN_READERS)
def test_span_reader_gives_none_with_no_session(name, fresh_ring):
    _fit()
    assert bench_run.load_reader("layer_metrics", name)(_run()) is None


@pytest.mark.parametrize("name", SPAN_READERS + STATS_READERS)
def test_reader_gives_none_on_a_program_without_what_it_reads(
        name, fresh_ring, monkeypatch):
    """The driver runs these readers over the parent commit too: no
    ``tracing.snapshot``, no durations in ``compile_cache.stats()``."""
    monkeypatch.delattr(fresh_ring, "snapshot")
    run = types.SimpleNamespace(cache_stats={"xla_hits": 3, "xla_misses": 0})
    assert bench_run.load_reader("layer_metrics", name)(run) is None


def test_new_metrics_are_declared_with_their_cells():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    declared = {m["name"]: m for m in bench["per_layer"]}
    every = [w["name"] for w in bench["workloads"]]
    for name in SPAN_READERS:
        assert declared[name]["workloads"] == ["resnet50_sym.fit_b128_synth"]
        assert declared[name]["moves"] == "items_per_s"
    for name in STATS_READERS:
        assert declared[name]["workloads"] == every
        assert declared[name]["moves"] == "setup_s"
    assert declared["frontend.dispatches_per_step"]["source"] == "program_counter"
