"""Compile plane — per-executable XLA cost/memory ledger (ISSUE 13).

Covers: the zero-overhead off path (no rows, plain jits, untouched
AOT-cache keys), node scopes in the HLO with no gate, row recording at every compile
site (executor forward, fused train step, CachedFunction), degradation
when ``cost_analysis()``/``memory_analysis()`` return None / raise / drop
keys, the declared-vs-measured drift cross-check, the persistent ledger +
``bench_compare --gate-cost``, warmup report columns, the Engine stats
block, bench-summary keys, and autotune trial cost features.
"""
import json
import os
import re

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu.telemetry import costplane


@pytest.fixture(autouse=True)
def _clean_costplane(monkeypatch):
    monkeypatch.delenv("MXNET_COSTPLANE", raising=False)
    monkeypatch.delenv("MXNET_COST_LEDGER", raising=False)
    costplane._reset_for_tests()
    yield
    costplane._reset_for_tests()


def _mlp():
    data = mx.sym.var("data")
    fc1 = mx.sym.FullyConnected(data, name="fc1", num_hidden=8)
    return mx.sym.FullyConnected(mx.sym.Activation(fc1, act_type="relu"),
                                 name="fc2", num_hidden=4)


# -- off path -----------------------------------------------------------------
def test_off_path_no_rows_plain_jit(tmp_path, monkeypatch):
    monkeypatch.setenv("MXNET_COST_LEDGER", str(tmp_path / "ledger.jsonl"))
    exe = _mlp().simple_bind(data=(2, 8), grad_req="null")
    exe.forward(is_train=False)
    assert costplane.row_count() == 0
    assert costplane.rows() == []
    import jax

    assert isinstance(exe._fwd_cache[False], type(jax.jit(lambda x: x)))
    assert not (tmp_path / "ledger.jsonl").exists()


def test_node_scopes_reach_op_name_without_the_gate():
    """Every plan node traces inside ``jax.named_scope(node.name)`` whether
    or not the plane is on (scopes are trace-time metadata), so the compiled
    HLO's ``op_name`` names the symbol's nodes and a device trace reads by
    node."""
    import jax

    exe = _mlp().simple_bind(data=(2, 8), grad_req="null")
    hlo = jax.jit(exe._graph_fn(False)).lower(
        *exe._aot_example_args()).compile().as_text()
    op_names = set(re.findall(r'op_name="([^"]+)"', hlo))
    for node in ("fc1", "fc2"):
        assert any("/%s/" % node in o for o in op_names), sorted(op_names)


def test_aot_cache_key_unchanged_by_gate(tmp_path, monkeypatch):
    """The gate must not move AOT-cache identity: the same logical key and
    entry path come out whether or not the plane is on."""
    from mxnet_tpu import compile_cache

    monkeypatch.setenv("MXNET_AOT_CACHE", str(tmp_path / "aot"))
    import jax

    fn = jax.jit(lambda x: x + 1)
    keys, paths = [], []
    for gate in ("0", "1"):
        monkeypatch.setenv("MXNET_COSTPLANE", gate)
        cf = compile_cache.CachedFunction(fn, ("k", 1), name="t")
        sig = cf._sig((np.zeros((2, 2), np.float32),))
        keys.append(cf._key)
        paths.append(cf._path(sig))
    assert keys[0] == keys[1]
    assert paths[0] == paths[1]


# -- recording ----------------------------------------------------------------
def test_executor_records_one_row_per_signature(monkeypatch):
    monkeypatch.setenv("MXNET_COSTPLANE", "1")
    exe = _mlp().simple_bind(data=(2, 8), grad_req="null")
    exe.forward(is_train=False)
    exe.forward(is_train=False)  # steady state: no new row
    assert costplane.row_count() == 1
    row = costplane.rows()[0]
    assert row["site"] == "executor_fwd"
    assert row["kind"] == "compile"
    # CPU XLA reports both surfaces (probed in-container); a row carries
    # real numbers and no partial markers here
    assert isinstance(row["flops"], int) and row["flops"] > 0
    assert isinstance(row["bytes_accessed"], int) and row["bytes_accessed"] > 0
    assert isinstance(row["peak_bytes"], int) and row["peak_bytes"] > 0
    assert row["partial"] == []
    assert row["backend"] == "cpu"
    assert row["compile_s"] >= 0
    assert set(row["fingerprints"]) == {"passes", "numerics", "autotune"}
    # second mode = second program = second row
    exe2 = exe.reshape(data=(4, 8))
    exe2.forward(is_train=False)
    assert costplane.row_count() == 2


def test_fused_step_records_row(monkeypatch, tmp_path):
    from mxnet_tpu import module as mod_mod
    from mxnet_tpu.io import DataBatch

    monkeypatch.setenv("MXNET_COSTPLANE", "1")
    monkeypatch.setenv("MXNET_MODULE_FUSED_STEP", "1")
    sym = mx.sym.SoftmaxOutput(_mlp(), name="softmax")
    mod = mod_mod.Module(sym)
    mod.bind(data_shapes=[("data", (6, 8))],
             label_shapes=[("softmax_label", (6,))])
    mod.init_params()
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1})
    rng = np.random.RandomState(0)
    for _ in range(3):
        b = DataBatch(data=[nd.array(rng.randn(6, 8).astype(np.float32))],
                      label=[nd.array(rng.randint(0, 4, (6,))
                                      .astype(np.float32))])
        mod.forward_backward(b)
        mod.update()
    rows = [r for r in costplane.rows() if r["site"] == "fused_step"]
    assert len(rows) == 1  # one signature, one row across 3 steps
    assert rows[0]["flops"] > 0


def test_cached_function_records_compile_then_restore_row(tmp_path,
                                                          monkeypatch):
    """CachedFunction: a fresh XLA compile records a ``compile`` row; a
    disk restore built nothing but still publishes a ``restore`` row
    (compile_s 0.0, the entry's STORED cost fingerprint — ISSUE 20: a
    warm pod restart must give the cross-rank ledger diff something to
    diff).  ``load_ledger`` keeps skipping restore rows — the persisted
    ledger remains a record of what was *built*."""
    from mxnet_tpu import compile_cache

    monkeypatch.setenv("MXNET_AOT_CACHE", str(tmp_path / "aot"))
    monkeypatch.setenv("MXNET_COSTPLANE", "1")
    import jax

    fn = jax.jit(lambda x: x * 2.0)
    x = np.ones((3, 3), np.float32)
    cf = compile_cache.CachedFunction(fn, ("cp", 1), name="cp_t")
    cf(x)
    assert costplane.row_count() == 1
    compiled = costplane.rows()[0]
    assert compiled["site"] == "cp_t" and compiled["kind"] == "compile"
    # second instance, same key: restores from disk — a restore row, not
    # a second compile row
    cf2 = compile_cache.CachedFunction(fn, ("cp", 1), name="cp_t")
    info = cf2.prepare(x)
    assert info["source"] == "disk"
    assert costplane.row_count() == 2
    restored = costplane.rows()[1]
    assert restored["kind"] == "restore"
    assert restored["key"] == compiled["key"]
    assert restored["compile_s"] == 0.0
    assert restored["flops"] == compiled["flops"]
    assert restored["bytes_accessed"] == compiled["bytes_accessed"]
    assert [r["kind"] for r in costplane.rows()].count("compile") == 1


def test_ledger_roundtrip_last_wins(tmp_path, monkeypatch):
    path = tmp_path / "ledger.jsonl"
    monkeypatch.setenv("MXNET_COSTPLANE", "1")
    monkeypatch.setenv("MXNET_COST_LEDGER", str(path))
    sym = _mlp()
    for _ in range(2):  # two binds, same program: same ledger key twice
        exe = sym.simple_bind(data=(2, 8), grad_req="null")
        exe.forward(is_train=False)
    assert costplane.row_count() == 2
    assert len(path.read_text().strip().splitlines()) == 2
    led = costplane.load_ledger(str(path))
    assert len(led) == 1  # keyed by fingerprint, last row wins
    (row,) = led.values()
    assert row["flops"] > 0


def test_ledger_reader_skips_garbage(tmp_path):
    path = tmp_path / "ledger.jsonl"
    good = {"kind": "compile", "key": "a-1", "flops": 10}
    path.write_text("not json\n" + json.dumps(good) + "\n"
                    + json.dumps({"kind": "other"}) + "\n")
    assert list(costplane.load_ledger(str(path))) == ["a-1"]


# -- degradation --------------------------------------------------------------
class _Stub:
    def __init__(self, cost, memory):
        self._cost, self._memory = cost, memory

    def cost_analysis(self):
        if isinstance(self._cost, Exception):
            raise self._cost
        return self._cost

    def memory_analysis(self):
        if isinstance(self._memory, Exception):
            raise self._memory
        return self._memory


class _Mem:
    def __init__(self, **kw):
        for k, v in kw.items():
            setattr(self, k, v)


@pytest.mark.parametrize("cost,memory,partial", [
    (None, RuntimeError("no mem"), ["cost", "memory"]),
    (RuntimeError("boom"), RuntimeError("boom"), ["cost", "memory"]),
    ([], None, ["cost", "memory"]),          # empty list + None-attrs object
    ({"unrelated": 1.0}, _Mem(), ["cost", "memory"]),   # missing keys/attrs
    ({"flops": 8.0}, _Mem(temp_size_in_bytes=4), []),   # partial-but-usable
    ({"flops": float("nan"), "bytes accessed": -3}, _Mem(), ["cost",
                                                             "memory"]),
])
def test_extract_degrades_never_raises(cost, memory, partial):
    feat, got_partial = costplane.extract(_Stub(cost, memory))
    assert got_partial == partial
    for v in feat.values():
        assert v is None or isinstance(v, int)


def test_partial_row_still_recorded(monkeypatch):
    """A backend reporting nothing yields a PARTIAL row, never a crash and
    never a dropped row — the degradation acceptance."""
    monkeypatch.setenv("MXNET_COSTPLANE", "1")
    row = costplane.record_compile("site_x", ("k",), "sig",
                                   _Stub(RuntimeError("unimplemented"),
                                         RuntimeError("unimplemented")),
                                   0.1)
    assert row is not None
    assert row["flops"] is None and row["peak_bytes"] is None
    assert sorted(row["partial"]) == ["cost", "memory"]
    assert costplane.row_count() == 1
    assert costplane.status()["partial"] == {"cost": 1, "memory": 1}
    assert costplane.totals() == {"flops": None, "peak_bytes": None,
                                  "rows": 1}


def test_record_compile_off_gate_noop():
    assert costplane.record_compile("s", ("k",), "sig",
                                    _Stub(None, None), 0.0) is None
    assert costplane.row_count() == 0


# -- declared-vs-measured cross-check ----------------------------------------
def test_crosscheck_flags_inflated_declarations():
    feat = {"flops": 1000, "bytes_accessed": 5000}
    honest = {"k1": {"calls": 2, "flops": 100, "bytes": 400}}
    assert costplane.crosscheck(feat, honest) == []
    inflated = {"k1": {"calls": 2, "flops": 100, "bytes": 400},
                "k2": {"calls": 1, "flops": 5000, "bytes": 10}}
    assert costplane.crosscheck(feat, inflated) == ["k2"]
    # backend measured nothing on an axis -> that axis never flags
    assert costplane.crosscheck({"flops": None, "bytes_accessed": None},
                                inflated) == []


def test_drift_counted_in_row_and_status(monkeypatch):
    monkeypatch.setenv("MXNET_COSTPLANE", "1")
    monkeypatch.setattr(
        costplane, "kernel_delta",
        lambda snap: {"fake_kernel": {"calls": 1, "flops": 10**15,
                                      "bytes": 1}})
    row = costplane.record_compile(
        "s", ("k",), "sig",
        _Stub({"flops": 100.0, "bytes accessed": 100.0}, _Mem()), 0.0,
        tc0={})
    assert row["drift"] == ["fake_kernel"]
    assert costplane.status()["drift"] == {"fake_kernel": 1}


def test_overlapping_trace_brackets_degrade_to_no_attribution(monkeypatch):
    """Concurrent lowers (the warmup thread pool) share one process-global
    Pallas registry: overlapping brackets cannot attribute kernel calls to
    their own executable, so both degrade to an empty delta — no declared
    row, no false drift — instead of cross-attributing."""
    monkeypatch.setenv("MXNET_COSTPLANE", "1")
    fake = {"k": {"flops_sum": 100, "bytes_sum": 10, "calls": 1,
                  "per_shape": {1: None}, "shape": None}}

    def fake_snapshot():
        return {k: v["calls"] for k, v in fake.items()}

    monkeypatch.setattr(costplane, "kernel_snapshot", fake_snapshot)
    # _delta_since reads the REAL process-global registry — fake it too,
    # or kernels traced by earlier test files (autotune's dconv trials)
    # leak into this bracket's delta and the test becomes order-dependent
    from mxnet_tpu.ops import pallas_kernels

    monkeypatch.setattr(
        pallas_kernels, "traced_costs",
        lambda: {k: {"flops": v["flops_sum"], "bytes_accessed":
                     v["bytes_sum"], "calls": v["calls"]}
                 for k, v in fake.items()})
    a = costplane.open_trace_bracket()
    assert not a.dirty
    b = costplane.open_trace_bracket()  # overlaps a -> both dirty
    assert a.dirty and b.dirty
    costplane.close_trace_bracket(a)
    costplane.close_trace_bracket(b)
    assert costplane.kernel_delta(a) == {}
    assert costplane.kernel_delta(b) == {}
    # a clean, non-overlapping bracket still attributes
    c = costplane.open_trace_bracket()
    assert not c.dirty and c.snap == {"k": 1}
    costplane.close_trace_bracket(c)
    assert costplane.kernel_delta(c) == {}  # nothing new traced


def test_instrument_jit_concurrent_first_call_single_row(monkeypatch):
    """Two threads racing the same new signature through an instrumented
    jit must produce ONE compile and ONE ledger row."""
    import threading

    import jax
    import jax.numpy as jnp

    monkeypatch.setenv("MXNET_COSTPLANE", "1")
    fn = costplane.instrument_jit(jax.jit(lambda x: jnp.tanh(x).sum()),
                                  "race_site", ("race",))
    x = np.ones((4, 4), np.float32)
    barrier = threading.Barrier(2)
    outs = []

    def call():
        barrier.wait()
        outs.append(float(fn(x)))

    ts = [threading.Thread(target=call) for _ in range(2)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert len(outs) == 2 and outs[0] == outs[1]
    assert costplane.row_count() == 1
    assert fn._cache_size() == 1


# -- surfaces -----------------------------------------------------------------
def test_engine_stats_and_warmup_columns(monkeypatch):
    from mxnet_tpu import serving
    from mxnet_tpu.test_utils import tiny_mlp_checkpoint

    sym, params = tiny_mlp_checkpoint()
    eng = serving.Engine(sym, params, {"data": (8,)}, start=False,
                         name="cp_eng")
    try:
        assert eng.stats()["costplane"] is None  # gate off
        monkeypatch.setenv("MXNET_COSTPLANE", "1")
        report = eng.warmup()
        fresh = [r for r in report if r["fresh"]]
        assert fresh and all(r["xla_flops"] is not None
                             and r["xla_peak_bytes"] is not None
                             for r in fresh)
        st = eng.stats()
        assert st["costplane"]["rows"] >= len(fresh)
        assert st["costplane"]["by_site"]["executor_fwd"] >= len(fresh)
        assert st["warmup"]["xla_flops"] == sum(r["xla_flops"]
                                                for r in fresh)
        assert st["warmup"]["xla_peak_bytes"] == max(r["xla_peak_bytes"]
                                                     for r in fresh)
        # re-warm: already live, no new rows, columns None
        report2 = eng.warmup()
        assert all(not r["fresh"] and r["xla_flops"] is None
                   for r in report2)
    finally:
        eng.close()


def test_warmup_columns_none_when_off():
    from mxnet_tpu import serving
    from mxnet_tpu.test_utils import tiny_mlp_checkpoint

    sym, params = tiny_mlp_checkpoint()
    eng = serving.Engine(sym, params, {"data": (8,)}, start=False)
    try:
        report = eng.warmup()
        assert all(r["xla_flops"] is None and r["xla_peak_bytes"] is None
                   for r in report)
        assert eng.stats()["warmup"]["xla_flops"] is None
    finally:
        eng.close()


def test_summary_keys(monkeypatch, tmp_path):
    from mxnet_tpu import telemetry
    from mxnet_tpu.telemetry import instrument as tin

    monkeypatch.setenv("MXNET_TELEMETRY", "1")
    monkeypatch.setenv("MXNET_TELEMETRY_FILE", str(tmp_path / "t.jsonl"))
    tin._reset_for_tests()
    try:
        s = telemetry.summary()
        assert s["xla_flops"] is None and s["xla_peak_bytes"] is None
        monkeypatch.setenv("MXNET_COSTPLANE", "1")
        exe = _mlp().simple_bind(data=(2, 8), grad_req="null")
        exe.forward(is_train=False)
        s = telemetry.summary()
        assert isinstance(s["xla_flops"], int) and s["xla_flops"] > 0
        assert isinstance(s["xla_peak_bytes"], int) and s["xla_peak_bytes"] > 0
        # the row also hit the registry mirror
        assert tin.registry().get("compile_rows_total").value(
            site="executor_fwd") == 1
    finally:
        tin._reset_for_tests()


# -- autotune trial features --------------------------------------------------
def test_measure_candidate_features(monkeypatch):
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.autotune import measure

    measure._reset_stats_for_tests()
    x = jnp.ones((8, 8), jnp.float32)

    def build():
        return jax.jit(lambda a: jnp.tanh(a @ a).sum())

    cfg = {"nblk": 64}
    measure.measure_candidate("cp_test_kernel", cfg, build, (x,),
                              warmup=1, repeat=1)
    assert measure.features_for("cp_test_kernel", cfg) is None  # gate off
    monkeypatch.setenv("MXNET_COSTPLANE", "1")
    measure.measure_candidate("cp_test_kernel", cfg, build, (x,),
                              warmup=1, repeat=1)
    feats = measure.features_for("cp_test_kernel", cfg)
    assert feats is not None and feats["flops"] > 0
    # ISSUE 18 widened the trial feature vector: compile_s and the
    # declared-vs-measured drift count feed the learned cost model
    assert set(feats) == {"flops", "bytes_accessed", "temp_bytes",
                          "peak_bytes", "compile_s", "drift"}
    assert feats["compile_s"] >= 0 and feats["drift"] >= 0
    assert measure.measurements() == 2
    measure._reset_stats_for_tests()


# -- ledger diff gate ---------------------------------------------------------
def _write_ledger(path, rows):
    with open(path, "w", encoding="utf-8") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")


def _ledger_row(key, flops, peak, compile_s=0.5, site="executor_fwd"):
    return {"kind": "compile", "key": key, "site": site, "flops": flops,
            "bytes_accessed": flops * 4 if flops else None,
            "peak_bytes": peak, "compile_s": compile_s}


def test_bench_compare_gate_cost(tmp_path, capsys):
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools"))
    import bench_compare

    base = str(tmp_path / "base.jsonl")
    same = str(tmp_path / "same.jsonl")
    worse = str(tmp_path / "worse.jsonl")
    rows = [_ledger_row("a-1", 1000, 4096), _ledger_row("b-2", 500, 2048)]
    _write_ledger(base, rows)
    _write_ledger(same, rows)
    _write_ledger(worse, [_ledger_row("a-1", 2000, 4096),   # flops doubled
                          _ledger_row("b-2", 500, 8192)])   # peak x4
    # identical -> silent pass, even gated
    assert bench_compare.main([base, same, "--gate-cost"]) == 0
    out = capsys.readouterr().out
    assert "REGRESSION" not in out
    # seeded regression -> nonzero ONLY under --gate-cost
    assert bench_compare.main([base, worse]) == 0
    assert bench_compare.main([base, worse, "--gate-cost"]) == 1
    out = capsys.readouterr().out
    assert "flops" in out and "peak_bytes" in out
    # gate demands ledgers; mixing kinds is a usage error (a real bench
    # capture, written here — a missing file would exit 2 for the wrong
    # reason and mask a broken kind check)
    bench = str(tmp_path / "bench.json")
    with open(bench, "w") as f:
        json.dump({"metric": "m", "value": 1.0, "unit": "img/s"}, f)
    assert bench_compare.main([bench, base, "--gate-cost"]) == 2


def test_bench_compare_ledger_added_removed(tmp_path, capsys):
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools"))
    import bench_compare

    base = str(tmp_path / "base.jsonl")
    new = str(tmp_path / "new.jsonl")
    _write_ledger(base, [_ledger_row("a-1", 1000, 4096)])
    _write_ledger(new, [_ledger_row("c-3", 900, 1024)])
    assert bench_compare.main([base, new, "--gate-cost"]) == 0
    out = capsys.readouterr().out
    assert "added" in out and "removed" in out


def test_trace_summary_ledger_totals(tmp_path):
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools"))
    import trace_summary

    path = str(tmp_path / "l.jsonl")
    _write_ledger(path, [
        _ledger_row("a-1", 1000, 4096),
        _ledger_row("a-1", 1200, 5000),   # same key: last wins
        _ledger_row("b-2", None, None),   # partial row, null-safe
    ])
    # make the partial row detectable
    with open(path) as f:
        lines = f.read().splitlines()
    row = json.loads(lines[-1])
    row["partial"] = ["cost", "memory"]
    lines[-1] = json.dumps(row)
    with open(path, "w") as f:
        f.write("\n".join(lines) + "\n")
    t = trace_summary.ledger_totals(path)
    assert t == {"flops": 1200, "bytes_accessed": 4800, "peak_bytes": 5000,
                 "rows": 2, "partial_rows": 1}
