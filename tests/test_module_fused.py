"""Fused Module train step (ISSUE 3, module/fused_step.py).

Coverage demanded by the issue:
- fused-vs-legacy numerical parity after N steps for sgd, momentum sgd and
  adam — including BatchNorm aux updates and a Dropout graph (same
  per-node folded key on both paths);
- the fallback cases (monitor installed, grad_req mix, kvstore update)
  still route through the legacy path;
- acceptance: one training step on the fused path issues exactly ONE
  compiled device dispatch (jit cache entries + telemetry counters).
"""
import re

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import module as mod_mod
from mxnet_tpu.io import DataBatch
from mxnet_tpu.module import fused_step
from mxnet_tpu.telemetry import instrument as tin

STEPS = 5
BATCH = 8


def _sym(bn=True, dropout=True):
    data = mx.sym.var("data")
    # no_bias under BN: a bias there has an exactly-zero true gradient, and
    # adam turns float noise on a zero gradient into arbitrary-signed
    # +-lr*step drift on ANY two differently-compiled runs — a degenerate
    # parametrization, not a path difference (docs/PERF_NOTES.md)
    x = mx.sym.FullyConnected(data, name="fc1", num_hidden=16, no_bias=bn)
    if bn:
        x = mx.sym.BatchNorm(x, name="bn1")
    x = mx.sym.Activation(x, name="relu1", act_type="relu")
    if dropout:
        x = mx.sym.Dropout(x, name="drop1", p=0.5)
    x = mx.sym.FullyConnected(x, name="fc2", num_hidden=4)
    return mx.sym.SoftmaxOutput(x, name="softmax")


def _batches(steps=STEPS, batch=BATCH, dim=8):
    rng = np.random.RandomState(7)
    return [
        DataBatch(data=[mx.nd.array(rng.randn(batch, dim).astype(np.float32))],
                  label=[mx.nd.array(rng.randint(0, 4, (batch,)).astype(np.float32))])
        for _ in range(steps)
    ]


def _make_module(sym=None, **kwargs):
    mod = mod_mod.Module(sym if sym is not None else _sym(), **kwargs)
    mod.bind(data_shapes=[("data", (BATCH, 8))],
             label_shapes=[("softmax_label", (BATCH,))])
    rng = np.random.RandomState(3)
    shapes = {n: a.shape for n, a in mod._exec.arg_dict.items()}
    arg = {n: mx.nd.array(rng.randn(*shapes[n]).astype(np.float32) * 0.1)
           for n in sorted(mod._param_names)}
    mod.init_params(arg_params=arg)
    return mod


def _train(monkeypatch, fused, optimizer, opt_params, sym=None, steps=STEPS):
    monkeypatch.setenv("MXNET_MODULE_FUSED_STEP", "1" if fused else "0")
    mx.random.seed(11)  # same per-step key sequence on both paths
    mod = _make_module(sym)
    mod.init_optimizer(optimizer=optimizer, optimizer_params=dict(opt_params))
    for b in _batches(steps):
        mod.forward_backward(b)
        mod.update()
    arg_params, aux_params = mod.get_params()
    return ({n: v.asnumpy() for n, v in arg_params.items()},
            {n: v.asnumpy() for n, v in aux_params.items()},
            mod.get_outputs()[0].asnumpy(), mod)


@pytest.mark.parametrize("optimizer,opt_params", [
    ("sgd", {"learning_rate": 0.1}),
    ("sgd", {"learning_rate": 0.1, "momentum": 0.9}),
    ("adam", {"learning_rate": 0.01}),
], ids=["sgd", "sgd_mom", "adam"])
def test_fused_legacy_parity(monkeypatch, optimizer, opt_params):
    """Identical params after N steps — BatchNorm aux and Dropout included
    (both paths consume one RNG key per step and fold the same per-node
    crc32 streams, so the masks match)."""
    arg_f, aux_f, out_f, mod_f = _train(monkeypatch, True, optimizer, opt_params)
    arg_l, aux_l, out_l, mod_l = _train(monkeypatch, False, optimizer, opt_params)
    assert mod_f._fused is not None, "fused path never engaged"
    assert mod_l._fused is None, "legacy run built a fused stepper"
    for n in arg_f:
        np.testing.assert_allclose(arg_f[n], arg_l[n], rtol=2e-5, atol=1e-6,
                                   err_msg="param %s" % n)
    for n in aux_f:
        np.testing.assert_allclose(aux_f[n], aux_l[n], rtol=2e-5, atol=1e-6,
                                   err_msg="aux %s" % n)
    np.testing.assert_allclose(out_f, out_l, rtol=2e-5, atol=1e-6)
    # aux actually moved (BatchNorm stats trained, not just preserved)
    assert any(np.abs(v).max() > 1e-4 for v in aux_f.values())


def test_momentum_state_matches_legacy_updater(monkeypatch):
    """Fused steps maintain the very Updater states save_optimizer_states
    pickles — switching paths mid-run stays consistent."""
    _, _, _, mod_f = _train(monkeypatch, True, "sgd",
                            {"learning_rate": 0.1, "momentum": 0.9})
    _, _, _, mod_l = _train(monkeypatch, False, "sgd",
                            {"learning_rate": 0.1, "momentum": 0.9})
    for i in mod_l._updater.states:
        np.testing.assert_allclose(mod_f._updater.states[i].asnumpy(),
                                   mod_l._updater.states[i].asnumpy(),
                                   rtol=2e-5, atol=1e-6)
    assert mod_f._optimizer.num_update == mod_l._optimizer.num_update


# -- fallback routing ---------------------------------------------------------
def _assert_legacy_step(mod, batch):
    """forward_backward must execute immediately (legacy), not stage."""
    mod.forward_backward(batch)
    assert not mod._fused_pending
    assert mod._fused is None
    mod.update()
    assert mod._fused is None


def test_fallback_env_disabled(monkeypatch):
    monkeypatch.setenv("MXNET_MODULE_FUSED_STEP", "0")
    assert not fused_step.fused_enabled()
    mod = _make_module()
    mod.init_optimizer(optimizer="sgd", optimizer_params={"learning_rate": 0.1})
    _assert_legacy_step(mod, _batches(1)[0])


def test_fallback_monitor_all(monkeypatch):
    """monitor_all=True is the un-jitted escape hatch (ISSUE 12): the
    executor callback observes every node, forcing the legacy path.  A
    default pattern-filtered Monitor now rides the fused step instead
    (tests/test_trainhealth.py::test_monitor_rides_fused_step)."""
    monkeypatch.setenv("MXNET_MODULE_FUSED_STEP", "1")
    mod = _make_module()
    mod.init_optimizer(optimizer="sgd", optimizer_params={"learning_rate": 0.1})
    mod.install_monitor(mx.monitor.Monitor(1, stat_func=lambda x: x,
                                           pattern=".*", monitor_all=True))
    assert fused_step.fused_ineligible_reason(mod) == "monitor"
    _assert_legacy_step(mod, _batches(1)[0])


def test_fallback_grad_req_mix(monkeypatch):
    """fixed_param_names makes grad_req a write/null mix — legacy path, and
    the fixed param must stay fixed."""
    monkeypatch.setenv("MXNET_MODULE_FUSED_STEP", "1")
    mod = _make_module(_sym(bn=False), fixed_param_names=["fc1_weight"])
    mod.init_optimizer(optimizer="sgd", optimizer_params={"learning_rate": 1.0})
    assert fused_step.fused_ineligible_reason(mod) == "grad_req"
    before = mod.get_params()[0]["fc1_weight"].asnumpy()
    _assert_legacy_step(mod, _batches(1)[0])
    np.testing.assert_allclose(mod.get_params()[0]["fc1_weight"].asnumpy(),
                               before)


def test_fallback_kvstore(monkeypatch):
    monkeypatch.setenv("MXNET_MODULE_FUSED_STEP", "1")
    mod = _make_module()
    mod.init_optimizer(kvstore=mx.kv.create("local"), optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1})
    assert fused_step.fused_ineligible_reason(mod) == "kvstore"
    w0 = mod.get_params()[0]["fc2_weight"].asnumpy()
    _assert_legacy_step(mod, _batches(1)[0])
    assert not np.allclose(mod.get_params()[0]["fc2_weight"].asnumpy(), w0)


def test_fallback_unsupported_optimizer(monkeypatch):
    monkeypatch.setenv("MXNET_MODULE_FUSED_STEP", "1")
    mod = _make_module()
    mod.init_optimizer(optimizer="rmsprop",
                       optimizer_params={"learning_rate": 0.01})
    assert fused_step.fused_ineligible_reason(mod) == "optimizer"
    _assert_legacy_step(mod, _batches(1)[0])


def test_interleaved_access_flushes_through_legacy(monkeypatch):
    """get_outputs between forward_backward and update materializes the
    staged step on the legacy path; the whole step still matches a pure
    legacy run."""
    monkeypatch.setenv("MXNET_MODULE_FUSED_STEP", "1")
    mx.random.seed(11)
    mod = _make_module()
    mod.init_optimizer(optimizer="sgd", optimizer_params={"learning_rate": 0.1})
    b = _batches(1)[0]
    mod.forward_backward(b)
    assert mod._fused_pending
    out = mod.get_outputs()[0]          # interleaved read: flush
    assert not mod._fused_pending
    assert out.shape == (BATCH, 4)
    mod.update()                        # legacy loop on the flushed grads
    arg_i = {n: v.asnumpy() for n, v in mod.get_params()[0].items()}

    arg_l, _, out_l, _ = _train(monkeypatch, False, "sgd",
                                {"learning_rate": 0.1}, steps=1)
    for n in arg_i:
        np.testing.assert_allclose(arg_i[n], arg_l[n], rtol=2e-5, atol=1e-6,
                                   err_msg=n)
    np.testing.assert_allclose(out.asnumpy(), out_l, rtol=2e-5, atol=1e-6)


def test_fit_uses_fused_path(monkeypatch):
    """The stock fit loop (forward_backward -> update -> update_metric)
    engages the fused path and still trains to threshold."""
    monkeypatch.setenv("MXNET_MODULE_FUSED_STEP", "1")
    from mxnet_tpu.io import NDArrayIter

    rng = np.random.RandomState(0)
    X = rng.randn(200, 8).astype(np.float32)
    W = rng.randn(8, 4).astype(np.float32)
    y = np.argmax(X @ W, axis=1).astype(np.float32)
    train = NDArrayIter(X, y, batch_size=50, shuffle=True,
                        label_name="softmax_label")
    mod = mod_mod.Module(_sym(bn=False, dropout=False))
    mod.fit(train, optimizer="adam", optimizer_params={"learning_rate": 0.02},
            num_epoch=10)
    assert mod._fused is not None, "fit never took the fused path"
    score = mod.score(NDArrayIter(X, y, batch_size=50,
                                  label_name="softmax_label"), "acc")[0][1]
    assert score > 0.8, score


# -- acceptance: one dispatch per step, counted ------------------------------
def test_fused_single_dispatch_per_step(monkeypatch, tmp_path):
    monkeypatch.setenv("MXNET_TELEMETRY", "1")
    monkeypatch.setenv("MXNET_TELEMETRY_FILE", str(tmp_path / "t.jsonl"))
    monkeypatch.setenv("MXNET_MODULE_FUSED_STEP", "1")
    tin._reset_for_tests()
    try:
        mx.random.seed(11)
        mod = _make_module()
        mod.init_optimizer(optimizer="sgd",
                           optimizer_params={"learning_rate": 0.1,
                                             "momentum": 0.9})
        steps = 6
        for b in _batches(steps):
            mod.forward_backward(b)
            mod.update()
        r = tin.registry()
        assert r.get("train_steps_total").value(path="fused") == steps
        # THE acceptance criterion: one compiled dispatch per fused step
        assert r.get("step_dispatches_total").value(path="fused") == steps
        assert r.get("step_dispatches_total").value(path="legacy") == 0
        # one executable for the one shape signature
        assert mod._fused.cache_size() == 1
        assert r.get("jit_compiles_total").value(fn="module_fused_step") == 1
        assert r.get("jit_cache_hits_total").value(fn="module_fused_step") \
            == steps - 1
        assert r.get("module_fused_fallback_total") is None
        # and the bench summary exposes the ratio
        assert tin.summary()["dispatches_per_step"] == 1.0
    finally:
        tin._reset_for_tests()


def test_legacy_dispatch_count_counted(monkeypatch, tmp_path):
    """Legacy step = 2 (fwd+bwd) + P optimizer dispatches — the storm the
    fused path removes, kept measurable for bench regression tracking."""
    monkeypatch.setenv("MXNET_TELEMETRY", "1")
    monkeypatch.setenv("MXNET_TELEMETRY_FILE", str(tmp_path / "t.jsonl"))
    monkeypatch.setenv("MXNET_MODULE_FUSED_STEP", "0")
    tin._reset_for_tests()
    try:
        mx.random.seed(11)
        mod = _make_module()
        mod.init_optimizer(optimizer="sgd",
                           optimizer_params={"learning_rate": 0.1})
        for b in _batches(2):
            mod.forward_backward(b)
            mod.update()
        r = tin.registry()
        nparams = len(mod._param_names)
        assert r.get("train_steps_total").value(path="legacy") == 2
        assert r.get("step_dispatches_total").value(path="legacy") \
            == 2 * (2 + nparams)
        assert r.get("module_fused_fallback_total").value(reason="disabled") == 2
        assert tin.summary()["dispatches_per_step"] == 2 + nparams
    finally:
        tin._reset_for_tests()


# -- non-finite sentinel (ISSUE 4 satellite, MXNET_NANCHECK) ------------------
def _nan_batch():
    x = np.random.RandomState(5).randn(BATCH, 8).astype(np.float32)
    x[0, 0] = np.nan
    from mxnet_tpu.io import DataBatch as DB

    return DB(data=[mx.nd.array(x)],
              label=[mx.nd.array(np.zeros(BATCH, np.float32))])


def _nancheck_module(monkeypatch, fused):
    monkeypatch.setenv("MXNET_NANCHECK", "1")
    monkeypatch.setenv("MXNET_MODULE_FUSED_STEP", "1" if fused else "0")
    mod = _make_module(_sym(bn=False, dropout=False))
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1})
    return mod


def test_nancheck_fused_raises_one_step_late(monkeypatch):
    """The flag is folded into the fused dispatch outputs and read before
    the NEXT dispatch (no per-step sync) — the raise names the bad step."""
    from mxnet_tpu.base import MXNetError

    mod = _nancheck_module(monkeypatch, fused=True)
    mod.forward_backward(_nan_batch())
    mod.update()  # step 1 dispatches; flag not yet read
    mod.forward_backward(_batches(1)[0])
    with pytest.raises(MXNetError, match="step 1"):
        mod.update()
    assert mod._fused is not None and mod._fused._nancheck


def test_nancheck_legacy_raises_before_update(monkeypatch):
    from mxnet_tpu.base import MXNetError

    mod = _nancheck_module(monkeypatch, fused=False)
    before = {n: v.asnumpy() for n, v in mod._exec.arg_dict.items()
              if n in mod._param_names}
    mod.forward_backward(_nan_batch())
    with pytest.raises(MXNetError, match="step 1"):
        mod.update()
    # the check fires BEFORE the optimizer writes nan into the weights
    for n, v in before.items():
        assert np.isfinite(mod._exec.arg_dict[n].asnumpy()).all(), n


def test_nancheck_off_is_inert(monkeypatch):
    monkeypatch.delenv("MXNET_NANCHECK", raising=False)
    monkeypatch.setenv("MXNET_MODULE_FUSED_STEP", "1")
    mod = _make_module(_sym(bn=False, dropout=False))
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1})
    for _ in range(2):  # nan flows through silently, as before
        mod.forward_backward(_nan_batch())
        mod.update()
    assert mod._fused is not None and not mod._fused._nancheck


def test_nancheck_counter_and_stale_rebuild(monkeypatch, tmp_path):
    """A trip bumps nonfinite_total{where}; flipping MXNET_NANCHECK mid-run
    rebuilds the stepper (the flag changes the step's output structure)."""
    monkeypatch.setenv("MXNET_TELEMETRY", "1")
    monkeypatch.setenv("MXNET_TELEMETRY_FILE", str(tmp_path / "t.jsonl"))
    tin._reset_for_tests()
    try:
        from mxnet_tpu.base import MXNetError

        mod = _nancheck_module(monkeypatch, fused=False)
        mod.forward_backward(_nan_batch())
        with pytest.raises(MXNetError):
            mod.update()
        assert tin.registry().get("nonfinite_total").value(where="legacy") == 1

        monkeypatch.setenv("MXNET_MODULE_FUSED_STEP", "1")
        monkeypatch.delenv("MXNET_NANCHECK", raising=False)
        mod2 = _make_module(_sym(bn=False, dropout=False))
        mod2.init_optimizer(optimizer="sgd",
                            optimizer_params={"learning_rate": 0.1})
        mod2.forward_backward(_batches(1)[0])
        mod2.update()
        first = mod2._fused
        assert not first._nancheck
        monkeypatch.setenv("MXNET_NANCHECK", "1")
        mod2.forward_backward(_batches(1)[0])
        mod2.update()
        assert mod2._fused is not first and mod2._fused._nancheck
    finally:
        tin._reset_for_tests()


def test_nancheck_last_step_drains_at_get_params(monkeypatch):
    """The deferred fused flag is checked at Module.get_params() (fit's
    epoch-end sync) so a run whose FINAL step went non-finite still raises."""
    from mxnet_tpu.base import MXNetError

    mod = _nancheck_module(monkeypatch, fused=True)
    mod.forward_backward(_nan_batch())
    mod.update()  # last step of the "run": flag pending, nothing read yet
    with pytest.raises(MXNetError, match="step 1"):
        mod.get_params()


def test_nancheck_stale_rebuild_does_not_swallow_flag(monkeypatch):
    """Swapping the optimizer (stale stepper -> rebuild) must drain the
    pending flag, not discard it with the old stepper."""
    from mxnet_tpu.base import MXNetError

    mod = _nancheck_module(monkeypatch, fused=True)
    mod.forward_backward(_nan_batch())
    mod.update()
    with pytest.raises(MXNetError, match="step 1"):
        mod.init_optimizer(optimizer="adam",
                           optimizer_params={"learning_rate": 0.01},
                           force_init=True)


# -- packed boundary: the carried state crosses the jit as flat buffers -------
CONV_DATA = (BATCH, 3, 8, 8)


def _conv_sym():
    data = mx.sym.var("data")
    x = mx.sym.Convolution(data, name="conv1", num_filter=8, kernel=(3, 3),
                           pad=(1, 1), no_bias=True)
    x = mx.sym.BatchNorm(x, name="bn1")
    x = mx.sym.Activation(x, name="relu1", act_type="relu")
    x = mx.sym.Pooling(x, name="pool1", kernel=(2, 2), stride=(2, 2),
                       pool_type="max")
    x = mx.sym.FullyConnected(x, name="fc1", num_hidden=4)
    return mx.sym.SoftmaxOutput(x, name="softmax")


def _conv_batches(steps=STEPS):
    rng = np.random.RandomState(7)
    return [DataBatch(
        data=[mx.nd.array(rng.randn(*CONV_DATA).astype(np.float32))],
        label=[mx.nd.array(rng.randint(0, 4, (BATCH,)).astype(np.float32))])
        for _ in range(steps)]


def _bind_shapes(mod, **kw):
    mod.bind(data_shapes=[("data", CONV_DATA)],
             label_shapes=[("softmax_label", (BATCH,))], **kw)


def _conv_module(per_leaf, optimizer, opt_params):
    """The small convnet, trained packed, or per leaf: a second Module bound
    to share its arrays (as bucketing binds them) keeps one array a leaf.
    ``opt_params["mults"]``, if given, is the (lr_mult, wd_mult) pair of
    per-parameter dicts set on the optimizer."""
    opt_params = dict(opt_params)
    mults = opt_params.pop("mults", None)
    mod = mod_mod.Module(_conv_sym())
    _bind_shapes(mod)
    if per_leaf:
        _bind_shapes(mod_mod.Module(_conv_sym()), shared_module=mod)
    rng = np.random.RandomState(3)
    mod.init_params(arg_params={
        n: mx.nd.array(rng.randn(*mod._exec.arg_dict[n].shape)
                       .astype(np.float32) * 0.1)
        for n in sorted(mod._param_names)})
    mod.init_optimizer(optimizer=optimizer, optimizer_params=opt_params)
    if mults is not None:
        mod._optimizer.set_lr_mult(mults[0])
        mod._optimizer.set_wd_mult(mults[1])
    return mod


def _snapshot(mod):
    """Every array the step carries or returns, read through the Module's
    own surfaces: the executor's dicts, the Updater's slots, the heads."""
    ex = mod._exec
    out = {"arg:" + n: ex.arg_dict[n].asnumpy() for n in mod._param_names}
    out.update({"grad:" + n: ex.grad_dict[n].asnumpy()
                for n in mod._param_names})
    out.update({"aux:" + n: a.asnumpy() for n, a in ex.aux_dict.items()})
    for i, st in mod._updater.states.items():
        for j, leaf in enumerate(fused_step._state_arrays(st)):
            out["state:%d.%d" % (i, j)] = leaf.asnumpy()
    out["head"] = mod.get_outputs()[0].asnumpy()
    return out


def _read_params(mod):
    args, auxs = mod.get_params()
    return {n: v.asnumpy() for n, v in {**args, **auxs}.items()}


def _read_states(mod, tmp_path):
    import pickle

    fname = str(tmp_path / ("s%d" % id(mod)))
    mod.save_optimizer_states(fname)
    with open(fname, "rb") as f:
        states = pickle.load(f)
    return {"%d.%d" % (i, j): leaf for i, st in states.items()
            for j, leaf in enumerate([] if st is None else
                                     [st] if isinstance(st, np.ndarray)
                                     else st)}


def _train_both(monkeypatch, optimizer, opt_params, between=None):
    """The same steps packed and per leaf; ``between(mod, step)`` after each
    update on both sides -> (packed module, per-leaf module, the readings
    ``between`` returned, a pair a step)."""
    monkeypatch.setenv("MXNET_MODULE_FUSED_STEP", "1")
    mods, readings = [], []
    for per_leaf in (False, True):
        mx.random.seed(11)
        mod = _conv_module(per_leaf, optimizer, opt_params)
        seen = []
        for i, b in enumerate(_conv_batches()):
            mod.forward_backward(b)
            mod.update()
            assert mod._fused._packed is not per_leaf
            if between is not None:
                seen.append(between(mod, i))
        mods.append(mod)
        readings.append(seen)
    return mods[0], mods[1], list(zip(*readings))


OPTIMIZERS = pytest.mark.parametrize("optimizer,opt_params", [
    ("sgd", {"learning_rate": 0.1, "momentum": 0.9, "wd": 1e-3}),
    ("adam", {"learning_rate": 0.01}),
    # lr and wd differ leaf by leaf: the packed step spreads them over the
    # flat buffer's elements
    ("sgd", {"learning_rate": 0.1, "momentum": 0.9, "wd": 1e-3,
             "mults": ({"conv1_weight": 0.5, "fc1_bias": 2.0},
                       {"fc1_weight": 3.0, "bn1_gamma": 0.0})}),
    # no momentum: no optimizer state, so no state buffer
    ("sgd", {"learning_rate": 0.1, "wd": 1e-3}),
], ids=["sgd_mom", "adam", "sgd_mom_mults", "sgd"])


@OPTIMIZERS
def test_packed_and_per_leaf_steps_are_bit_equal(monkeypatch, optimizer,
                                                 opt_params):
    """Only the boundary changes: params, gradients, optimizer slots,
    BatchNorm statistics and heads after 5 steps are the per-leaf step's,
    bit for bit, on the CPU."""
    packed, leaf, _ = _train_both(monkeypatch, optimizer, opt_params)
    got, want = _snapshot(packed), _snapshot(leaf)
    assert got.keys() == want.keys()
    has_state = optimizer == "adam" or "momentum" in opt_params
    assert any(k.startswith("state:") for k in got) is has_state
    for k in want:
        np.testing.assert_array_equal(got[k], want[k], err_msg=k)


@pytest.mark.parametrize("reader", ["get_params", "executor_dicts",
                                    "save_optimizer_states"])
def test_reads_between_steps_see_the_current_step(monkeypatch, tmp_path,
                                                  reader):
    """Whatever surface reads the state between two packed steps sees the
    step just taken, and reading does not disturb the steps after it."""
    read = {"get_params": _read_params,
            "executor_dicts": _snapshot,
            "save_optimizer_states": lambda m: _read_states(m, tmp_path),
            }[reader]
    packed, leaf, pairs = _train_both(
        monkeypatch, "sgd", {"learning_rate": 0.1, "momentum": 0.9},
        between=lambda mod, i: read(mod))
    assert len(pairs) == STEPS
    for step, (got, want) in enumerate(pairs):
        assert got.keys() == want.keys() and got
        for k in want:
            np.testing.assert_array_equal(got[k], want[k],
                                          err_msg="step %d %s" % (step, k))
    assert not packed._fused._newer  # the last read brought them back


def _write_set_params(mod, i):
    args, auxs = mod.get_params()
    args = dict(args, conv1_weight=args["conv1_weight"] * 0.5)
    mod.set_params(args, auxs)


def _write_init_params(mod, i):
    mod.init_params(arg_params={"fc1_bias": mx.nd.array(
        np.full((4,), 0.1 * (i + 1), np.float32))},
        allow_missing=True, force_init=True)


def _write_in_place(mod, i):
    w = mod._exec.arg_dict["fc1_weight"]
    w += 0.01
    mod._exec.aux_dict["bn1_moving_var"][:] = 2.0


def _write_held(mod, i):
    # arrays handed out before the steps, written after one without a
    # read in between: the write is newer than the packed step's value
    if i == 0:
        mod._held = (mod._exec.arg_dict["conv1_weight"],
                     mod._updater.states[0])
    w, mom = mod._held
    w[:] = 0.05
    mom[:] = 0.0


@pytest.mark.parametrize("write", [_write_set_params, _write_init_params,
                                   _write_in_place, _write_held],
                         ids=["set_params", "init_params_force",
                              "in_place", "held_array"])
def test_a_write_between_steps_is_what_the_next_step_trains_from(
        monkeypatch, write):
    packed, leaf, _ = _train_both(
        monkeypatch, "sgd", {"learning_rate": 0.1, "momentum": 0.9}, write)
    got, want = _snapshot(packed), _snapshot(leaf)
    for k in want:
        np.testing.assert_array_equal(got[k], want[k], err_msg=k)


def test_packed_step_still_reports_nonfinite_and_health(monkeypatch):
    """MXNET_NANCHECK and MXNET_TRAINHEALTH ride the packed step: the
    health stats of each step come out, and a non-finite step raises
    before the next one."""
    from mxnet_tpu.base import MXNetError
    from mxnet_tpu.telemetry import trainhealth

    monkeypatch.setenv("MXNET_TRAINHEALTH", "1")
    trainhealth._reset_for_tests()
    try:
        mod = _nancheck_module(monkeypatch, fused=True)
        mod.forward_backward(_batches(1)[0])
        mod.update()
        assert mod._fused._packed and mod._fused._nancheck
        stepno, stats = mod._fused.pop_health()
        assert stepno == 1
        assert np.isfinite(float(stats["global_grad_norm"]))
        assert float(stats["global_grad_norm"]) > 0
        mod.forward_backward(_nan_batch())
        mod.update()
        mod.forward_backward(_batches(1)[0])
        with pytest.raises(MXNetError, match="step 2"):
            mod.update()
    finally:
        trainhealth._reset_for_tests()


@pytest.mark.parametrize("layout,buffers", [
    # 1 param + 1 grad buffer, data, label, key, lr, wd in; params, head,
    # grads out
    ("packed", 7 + 3),
    # 4 params + 4 grads in and out, the same five others, the head
    ("mesh", 13 + 9),
    ("shared", 13 + 9),
])
def test_buffers_counter_reads_what_a_launch_moves(monkeypatch, layout,
                                                   buffers):
    from mxnet_tpu import parallel
    from mxnet_tpu.telemetry import tracing

    monkeypatch.setenv("MXNET_MODULE_FUSED_STEP", "1")
    monkeypatch.setenv("MXNET_TRACE", "1")
    tracing._reset_for_tests()
    try:
        mesh = parallel.make_mesh({"dp": 8}) if layout == "mesh" else None
        mod = _make_module(_sym(bn=False, dropout=False), mesh=mesh)
        if layout == "shared":
            mod_mod.Module(_sym(bn=False, dropout=False)).bind(
                data_shapes=[("data", (BATCH, 8))],
                label_shapes=[("softmax_label", (BATCH,))],
                shared_module=mod)
        mod.init_optimizer(optimizer="sgd",
                           optimizer_params={"learning_rate": 0.1})
        for b in _batches(2):
            with tracing.start_trace("step"):
                mod.forward_backward(b)
                mod.update()
        assert mod._fused._packed is (layout == "packed")
        counts = [s["attrs"]["buffers"] for s in tracing.snapshot()
                  if s["name"] == "fused.dispatch"]
        assert counts == [buffers, buffers]
    finally:
        tracing._reset_for_tests()


def _drop_a_momentum(mod, i):
    # from the second step on, one parameter updates without momentum
    # beside others that keep theirs: a layout that mixes update kinds
    if i == 0:
        mod._updater.states[mod._param_names.index("fc1_bias")] = None


def test_a_layout_of_mixed_kinds_updates_leaf_by_leaf(monkeypatch):
    """Where a buffer's leaves do not share one update kind, each leaf's
    update goes into its segment of the donated buffers on its own: bit
    for bit the per-leaf step's."""
    packed, leaf, _ = _train_both(
        monkeypatch, "sgd", {"learning_rate": 0.1, "momentum": 0.9,
                             "wd": 1e-3}, _drop_a_momentum)
    assert packed._fused._nflat == 0
    got, want = _snapshot(packed), _snapshot(leaf)
    assert got.keys() == want.keys()
    assert len([k for k in got if k.startswith("state:")]) == 4
    for k in want:
        np.testing.assert_array_equal(got[k], want[k], err_msg=k)


@pytest.mark.parametrize("layout,flat_leaves", [("uniform", 5), ("mixed", 0)])
def test_packed_step_updates_its_buffers_in_place(monkeypatch, layout,
                                                  flat_leaves):
    """The lowered packed step concatenates no parameter or optimizer-state
    buffer anew: of the concatenations of a parameter or state buffer's
    size, only the gradients' pack is left.  ``flat_update_leaves`` counts
    the leaves updated on whole buffers: every learnable leaf where they
    share one kind, none where one parameter has no momentum slot (each
    leaf then goes into its segment by a ``dynamic_update_slice``)."""
    from mxnet_tpu.telemetry import tracing

    monkeypatch.setenv("MXNET_MODULE_FUSED_STEP", "1")
    monkeypatch.setenv("MXNET_TRACE", "1")
    tracing._reset_for_tests()
    try:
        mod = _conv_module(False, "sgd", {"learning_rate": 0.1,
                                          "momentum": 0.9})
        if layout == "mixed":
            mod._updater.states[mod._param_names.index("fc1_bias")] = None
        with tracing.start_trace("step"):
            mod.forward_backward(_conv_batches(1)[0])
            mod.update()
        counts = [s["attrs"]["flat_update_leaves"] for s in tracing.snapshot()
                  if s["name"] == "fused.dispatch"]
    finally:
        tracing._reset_for_tests()
    assert counts == [flat_leaves]
    f = mod._fused
    params, grads, states, aux = f._bufs
    nparam = len(mod._param_names)
    text = f._jit.lower(
        params, grads, states, aux,
        [mod._exec.arg_dict[n]._data for n in f._const_names],
        mx.random.next_key(), np.zeros(nparam, np.float32),
        np.zeros(nparam, np.float32)).as_text()
    sizes = {b.shape[0] for b in params + states}
    built = [int(n) for n in re.findall(
        r"stablehlo\.concatenate .*-> tensor<(\d+)xf32>", text)]
    assert sorted(n for n in built if n in sizes) == [
        b.shape[0] for b in params]
    assert ("stablehlo.dynamic_update_slice" in text) is (layout == "mixed")
