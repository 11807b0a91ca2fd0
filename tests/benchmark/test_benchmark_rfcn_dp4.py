"""The R-FCN runner data-parallel over four (virtual CPU) devices at toy
size: the mesh comes from the traffic file, parameters are replicated, the
batch is sharded by rows, and the global batch's step agrees with the plain
reference (which knows no mesh) on every chip's copy.  With the exchange
between the chips left out, ``correct`` comes out false."""
import os
import sys

import numpy as np
import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax  # noqa: E402

from benchmark import compare, run as bench_run  # noqa: E402
from benchmark.runners import rfcn_train  # noqa: E402
from test_benchmark_rfcn import TOY_LIMITS, _run, toy  # noqa: E402

CELL = "rfcn_r101.train_dp4"


def toy_traffic():
    traffic = bench_run.resolve(CELL)[2]
    assert traffic["chips"] == 4 and traffic["mesh"] == {"dp": 4}
    assert traffic["checked_steps"] == 2
    traffic.update(batch_per_chip=1, warmup_steps=1, max_steps=3)
    return traffic


@pytest.fixture(scope="module")
def built():
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices (tests/conftest.py gives 8 virtual ones)")
    r = rfcn_train.Runner(toy()[0], toy_traffic(), 31, jax.devices()[:4],
                          lambda m: None)
    r.build()
    return r


def test_dp4_run_agrees_with_the_reference_on_every_chip(built):
    assert built.items_per_step == 4
    assert sorted(s.device.id for s in built.batch_arrays[0].addressable_shards) \
        == [d.id for d in jax.devices()[:4]]
    assert len(built.state[0][0].addressable_shards) == 4      # replicated
    res = _run(built, 32, cell=CELL, traffic=toy_traffic(),
               devices=jax.devices()[:4])
    assert res["correct"] is True and 1 <= res["attempted"] <= 3
    assert res["device"]["count"] == 4
    assert res["compared"]["chips_disagree"] == [0.0, TOY_LIMITS["chips_disagree"]]
    assert "loss_step3" not in res["compared"]      # two checked steps


def _exchange_left_out(runner):
    # every chip is fed the first chip's rows: the step is that of one shard
    # alone, which is what a chip computes when the gradients are not summed
    per_chip = runner.batch // runner.chips
    runner.batch_arrays = [
        jax.device_put(np.concatenate([np.asarray(a)[:per_chip]] * runner.chips),
                       a.sharding) for a in runner.batch_arrays]


def test_the_exchange_between_chips_left_out_is_not_correct(built):
    res = _run(built, 33, _exchange_left_out, cell=CELL,
               traffic=toy_traffic(), devices=jax.devices()[:4])
    assert res["correct"] is False
    assert [n for n, (v, lim) in res["compared"].items()
            if lim is not None and not v <= lim]
    # and the reference over one shard alone, put in the program's place
    r = rfcn_train.Runner(toy()[0], toy_traffic(), 33, jax.devices()[:4],
                          lambda m: None)
    want = r.reference_readings()
    shard = compare.numbers(r.reference_readings(images=[0]), want)
    assert not compare.decide(shard, TOY_LIMITS)[0]
