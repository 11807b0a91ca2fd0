"""benchmark/trace_reduce.py on a recorded TPU xplane and on hand-made
intervals.  The fixture (benchmark/fixtures/one_chip_4_steps.xplane.pb) is
four steps of a small jitted program on one v5e chip: a convolution fusion,
a layout copy, a Pallas kernel named ``probe_double_kernel`` and a
reduction, with ``step`` / ``bench.callback`` host spans around them."""
import os
import sys

import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
sys.path.insert(0, REPO)

from benchmark import trace_reduce as tr  # noqa: E402

FIXTURE = os.path.join(REPO, "benchmark", "fixtures",
                       "one_chip_4_steps.xplane.pb")


@pytest.fixture(scope="module")
def trace():
    return tr.load(FIXTURE, ("step", "bench.callback"))


def test_parse_op():
    text = ("%fusion.3 = (f32[]{:T(128)}, f32[8,64]{1,0:T(8,128)S(1)}) "
            "fusion(bf16[8,64]{1,0:T(8,128)(2,1)} %copy), kind=kOutput, "
            "calls=%fused_computation")
    assert tr.parse_op(text) == ("fusion.3", "fusion")
    assert tr.is_convolution("fusion.3", "fusion", text)
    assert tr.parse_op("%all-reduce-start.1 = f32[4]{0} all-reduce-start("
                       "f32[4]{0} %x), replica_groups={}") \
        == ("all-reduce-start.1", "all-reduce-start")
    assert tr.is_collective("all-reduce-start")
    assert tr.is_formatting("copy.1", "copy") \
        and tr.is_formatting("transpose_fusion", "fusion") \
        and not tr.is_formatting("fusion.3", "fusion")
    assert tr.parse_op("jit_step") == ("jit_step", "")


def test_interval_arithmetic():
    assert tr.union_length([(0, 2), (1, 3), (5, 6)]) == 4
    assert tr.subtract_length([(0, 10)], [(1, 2), (4, 6), (9, 12)]) == 6
    assert tr.subtract_length([(0, 1), (2, 3)], []) == 2
    assert tr.gaps([(0, 1), (3, 4), (3.5, 5)]) == [(1, 3)]


def test_fixture_busy_steps_and_op_time(trace):
    assert len(trace.devices) == 1
    dev = trace.devices[0]
    assert len(dev.steps()) == 4
    assert dev.main_module() == "jit_step"
    assert dev.busy_s() == pytest.approx(557.634e-6, rel=1e-4)
    conv = dev.time_where(lambda o: tr.is_convolution(o[2], o[3], o[4]))
    fmt = dev.time_where(lambda o: tr.is_formatting(o[2], o[3]))
    kern = dev.time_where(lambda o: o[3] == "custom-call"
                          and "probe_double_kernel" in o[2])
    assert conv == pytest.approx(450.986e-6, rel=1e-4)
    assert fmt == pytest.approx(101.295e-6, rel=1e-4)
    assert kern == pytest.approx(0.351e-6, rel=1e-2)
    assert dev.exposed_collective_s() == 0.0
    top = trace.top_ops(3)
    assert top[0][0] == "fusion:fusion" and top[1][0] == "copy:copy"


def test_fixture_idle_goes_to_the_host_span_that_covers_it(trace):
    gaps = dict(trace.idle_by_span(("step", "bench.callback")))
    # between steps the host slept inside bench.callback
    assert gaps["bench.callback"] == pytest.approx(10.04e-3, rel=1e-2)
    assert sum(gaps.values()) < 10.2e-3


def test_mfu_is_over_the_devices_busy_time_not_the_hosts_window(trace):
    import types

    from benchmark import run as bench_run, work

    read = bench_run.load_reader("layer_metrics", "device.mfu")
    counts = types.SimpleNamespace(train_flops_per_item=lambda cfg: 1e9)
    run = types.SimpleNamespace(
        trace=trace, steps=4, items_per_step=2, chips=1, config={},
        work=counts, peaks=work.peaks("TPU v5 lite"), window_s=0.05)
    want = 1e9 * 2 * 4 / (557.634e-6 * 197e12) * 100
    assert read(run) == pytest.approx(want, rel=1e-4)
    run.window_s = 5.0           # the host stalls: idle_share's, not mfu's
    assert read(run) == pytest.approx(want, rel=1e-4)
    run.steps = 0
    assert read(run) is None


def test_exposed_collective_on_hand_made_device():
    dev = tr.Device(0)
    def op(s, e, name, opcode):
        dev.ops.append((s, e, name, opcode, "%%%s = f32[] %s()" % (name, opcode)))
    op(0.0, 1.0, "fusion.1", "fusion")
    op(1.0, 1.5, "all-reduce.1", "all-reduce")          # nothing else: exposed
    op(1.5, 3.0, "while.1", "while")                    # a container, skipped
    op(1.5, 2.0, "all-reduce-start.2", "all-reduce-start")
    op(1.6, 2.0, "fusion.2", "fusion")                  # hides 0.4 of it
    assert dev.exposed_collective_s() == pytest.approx(0.5 + 0.1)
    assert dev.busy_s() == pytest.approx(3.0)


def test_a_cpu_trace_is_no_device_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    jnp.ones((8, 8)).sum().block_until_ready()
    jax.profiler.stop_trace()
    with pytest.raises(ValueError, match="no TPU plane"):
        tr.load(tr.find_xplane(str(tmp_path)))
