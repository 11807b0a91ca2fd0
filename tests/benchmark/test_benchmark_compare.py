"""benchmark/compare.py: the numbers and the decision."""
import os
import sys

import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
sys.path.insert(0, REPO)

from benchmark import compare  # noqa: E402


def _readings(scale=1.0, tiny=1e-9):
    return {"loss": [2.0 * scale, 1.9, 1.8], "scalars": {"rpn": 0.5},
            "grad": {"a": 1.0 * scale, "b": 0.01, "frozen": tiny},
            "delta": {"a": 0.1, "b": 0.001 * scale, "frozen": tiny * 50}}


def test_worst_leaf_is_against_the_median_leaf_at_least():
    nums = compare.numbers(_readings(1.1), _readings())
    assert nums["loss_step1"] == pytest.approx(0.1)
    assert nums["loss_step2"] == 0 and nums["rpn"] == 0
    assert nums["grad_worst_leaf"] == pytest.approx(0.1)
    # leaf b is small: its gap is measured against the median leaf (0.0505)
    assert nums["delta_worst_leaf"] == pytest.approx(0.0001 / 0.0505, rel=1e-3)
    assert nums["_grad_worst_leaf_name"] == "a"


def test_leaves_without_a_gradient_are_left_out():
    got = _readings()
    got["delta"]["frozen"] = 1.0      # moved by rounding alone
    nums = compare.numbers(got, _readings())
    assert nums["delta_worst_leaf"] == 0


def test_decide():
    nums = {"loss_step1": 0.02, "grad_worst_leaf": 0.5, "_name": "x"}
    ok, compared = compare.decide(nums, {"loss_step1": 0.05})
    assert ok and compared == {"loss_step1": [0.02, 0.05],
                               "grad_worst_leaf": [0.5, None]}
    assert not compare.decide(nums, {"loss_step1": 0.01})[0]
    assert not compare.decide({"loss_step1": float("nan")},
                              {"loss_step1": 1.0})[0]
    assert not compare.decide(nums, {})[0], "nothing compared is no proof"


def test_rounding_of_the_controls_is_the_low_types_own():
    """reference/precision.py rounds by arithmetic (the TPU compiler drops an
    astype there and back): the values are those astype gives, and the
    gradient passes straight through."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.reference import precision as P

    rng = np.random.RandomState(0)
    x = np.concatenate([rng.randn(20000).astype(np.float32) * s
                        for s in (1e-3, 1e-2, 1.0, 30.0, 200.0)])
    x = np.concatenate([np.clip(x, -448, 448), np.float32(
        [0, 2 ** -6, 2 ** -7, 2 ** -9, 2 ** -10, 1.5 * 2 ** -9, 448, 1.0625])])
    want8 = np.asarray(jnp.asarray(x).astype(jnp.float8_e4m3fn).astype(jnp.float32))
    want16 = np.asarray(jnp.asarray(x).astype(jnp.bfloat16).astype(jnp.float32))
    assert (np.asarray(jax.jit(P._e4m3)(jnp.asarray(x))) == want8).all()
    assert (np.asarray(jax.jit(P._round_bf16)(jnp.asarray(x))) == want16).all()
    for prec in ("bfloat16", "float8"):
        g = jax.grad(lambda v: P.round_in(v, prec).sum())(jnp.asarray(x[:64]))
        assert (np.asarray(g) == 1.0).all()
    assert P.round_in(jnp.asarray(x), "float32") is not None
    with pytest.raises(ValueError, match="unknown precision"):
        P.round_in(jnp.asarray(x), "int4")


def test_runtime_peak_counts_what_executables_reserve():
    from benchmark import run as bench_run

    class Dev:
        def __init__(self, stats):
            self.stats = stats

        def memory_stats(self):
            return self.stats

    devs = [Dev({"peak_bytes_in_use": 10, "peak_bytes_reserved": 70}),
            Dev({"peak_bytes_in_use": 30, "peak_bytes_reserved": 40}),
            Dev(None)]
    assert bench_run.runtime_peak_bytes(devs) == 80
    assert bench_run.runtime_peak_bytes([Dev(None)]) == 0


@pytest.mark.parametrize("runner_name, cell, held", [
    ("rfcn_train", "rfcn_r101.train_b8", ["delta_worst_leaf"]),
    ("module_fit", "resnet50_sym.fit_b128_synth",
     ["delta_worst_leaf", "aux_worst_leaf"])])
def test_limits_are_the_configurations_and_hold_the_worst_leaf(
        runner_name, cell, held):
    """A leaf that never updates, or running statistics left unchanged,
    read 1: every configuration holds the worst leaf under that, over what
    sound runs read (PERF.md section 6)."""
    import importlib

    from benchmark import run as bench_run

    _, cfg, traffic = bench_run.resolve(cell)
    mod = importlib.import_module("benchmark.runners." + runner_name)
    r = mod.Runner(cfg, traffic, 1, [object()], lambda m: None)
    assert r.limits == cfg["limits"] and "limits" not in traffic
    for name in held:
        assert 0 < r.limits[name] <= 1 / 3
    unchanged = {name: 1.0 for name in held}
    assert not compare.decide(unchanged, r.limits)[0]
