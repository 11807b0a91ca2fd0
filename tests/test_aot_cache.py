"""AOT compilation + persistent executable cache (ISSUE 6, compile_cache.py).

Coverage demanded by the issue:
- compile-once acceptance: a second Engine warming the same ladder against
  the same cache dir restores every bucket from disk — zero fresh compiles
  (misses), all hits — and still serves correctly;
- cache invalidation is CORRUPTION-SAFE: a stale jax/jaxlib version key, a
  mesh-descriptor mismatch, and a truncated cache file each produce a clean
  miss + recompile (counted in ``aot_cache_errors_total{reason}``), never a
  crash, and the bad entry is overwritten;
- the warmup lowering split: report rows carry ``lower_s``/``compile_s``
  and ``Engine.stats()`` gains the ``warmup`` block, with and without the
  cache;
- the cache-off path is untouched: no CachedFunction in the executor, no
  cache rows in the warmup report;
- the CPU donation guard: ``donated=True`` callables never read or write
  disk entries on the CPU backend (restored donated executables compute
  wrong trajectories there — compile_cache.py docstring).
"""
import glob
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import compile_cache as cc
from mxnet_tpu import serving
from mxnet_tpu.serving import BucketLadder, Engine
from mxnet_tpu.telemetry import instrument as tin


@pytest.fixture
def aot_dir(tmp_path, monkeypatch):
    d = tmp_path / "aot"
    monkeypatch.setenv("MXNET_AOT_CACHE", str(d))
    cc._reset_stats_for_tests()
    yield str(d)
    cc._reset_stats_for_tests()


@pytest.fixture
def aot_off(monkeypatch):
    monkeypatch.delenv("MXNET_AOT_CACHE", raising=False)
    cc._reset_stats_for_tests()
    yield
    cc._reset_stats_for_tests()


@pytest.fixture
def tel_enabled(monkeypatch, tmp_path):
    monkeypatch.setenv("MXNET_TELEMETRY", "1")
    monkeypatch.setenv("MXNET_TELEMETRY_FILE", str(tmp_path / "t.jsonl"))
    tin._reset_for_tests()
    yield
    tin._reset_for_tests()


def _mlp_engine(**kw):
    from mxnet_tpu.test_utils import tiny_mlp_checkpoint

    sym, params = tiny_mlp_checkpoint()
    kw.setdefault("ladder", BucketLadder((1, 2, 4)))
    kw.setdefault("start", False)
    return Engine(sym, params, {"data": (8,)}, **kw)


def _exec_entries(aot_dir):
    return sorted(glob.glob(os.path.join(aot_dir, "exec", "*.jx")))


# -- engine warm restart ------------------------------------------------------
class TestEngineWarmRestart:
    def test_cold_warmup_populates_cache(self, aot_dir):
        eng = _mlp_engine()
        report = eng.warmup()
        assert [r["cache"] for r in report] == ["miss", "miss", "miss"]
        assert all(r["fresh"] for r in report)
        s = cc.stats()
        assert (s["hits"], s["misses"], s["errors"]) == (0, 3, 0)
        assert len(_exec_entries(aot_dir)) == 3
        w = eng.stats()["warmup"]
        assert w["buckets"] == 3 and w["cache_misses"] == 3
        assert w["cache_hits"] == 0 and w["total_s"] > 0
        eng.close()

    def test_second_engine_compiles_zero_fresh_modules(self, aot_dir):
        eng1 = _mlp_engine()
        eng1.warmup()
        eng1.close()
        before = cc.stats()
        eng2 = _mlp_engine()
        report = eng2.warmup()
        after = cc.stats()
        # the acceptance: every bucket restored, ZERO fresh compiles
        assert [r["cache"] for r in report] == ["hit", "hit", "hit"]
        assert after["misses"] == before["misses"]  # no new compile
        assert after["hits"] == before["hits"] + 3
        assert after["errors"] == 0
        w = eng2.stats()["warmup"]
        assert w["cache_hits"] == 3 and w["cache_misses"] == 0
        # disk restores are not XLA compiles: the warm restart reports 0
        assert eng2.stats()["compiles"] == 0
        # ...and the restored executables actually serve, with parity
        eng2.start()
        x = np.random.RandomState(0).rand(2, 8).astype(np.float32)
        out = eng2.predict({"data": x})
        eng2.close()
        eng3 = _mlp_engine(start=True)  # no cache entries consumed: fresh jit
        os.environ.pop("MXNET_AOT_CACHE")
        np.testing.assert_allclose(out[0],
                                   eng3.predict({"data": x})[0], atol=1e-6)
        eng3.close()

    def test_rewarmup_reports_no_phantom_hits(self, aot_dir):
        eng = _mlp_engine()
        eng.warmup()
        hits_before = cc.stats()["hits"]
        report = eng.warmup()  # same process: everything already live
        # in-process "cached" is neither a disk restore nor a compile
        assert [r["cache"] for r in report] == [None, None, None]
        assert not any(r["fresh"] for r in report)
        w = eng.stats()["warmup"]
        assert w["cache_hits"] == 0 and w["cache_misses"] == 0
        assert cc.stats()["hits"] == hits_before
        eng.close()

    def test_report_splits_lower_and_compile(self, aot_dir):
        eng = _mlp_engine()
        report = eng.warmup()
        # phase 1 (concurrent trace+lower) is reported per bucket,
        # separately from the device-exclusive compile+forward
        assert all(r["lower_s"] > 0 for r in report)
        assert all(r["compile_s"] > 0 for r in report)
        eng.close()

    def test_warmup_stats_block_without_cache(self, aot_off):
        eng = _mlp_engine()
        report = eng.warmup()
        assert [r["cache"] for r in report] == [None, None, None]
        w = eng.stats()["warmup"]
        assert w["buckets"] == 3 and w["fresh"] == 3
        assert w["cache_hits"] == 0 and w["cache_misses"] == 0
        assert w["total_s"] > 0
        assert cc.stats()["misses"] == 0  # cache never touched
        eng.close()

    def test_off_path_uses_plain_jit(self, aot_off):
        eng = _mlp_engine()
        fwd = eng._proto._exec._compiled(False)
        assert not isinstance(fwd, cc.CachedFunction)
        assert eng._proto.aot_lower() is None
        eng.close()


# -- invalidation: every bad entry is a clean miss + recompile ----------------
def _cached_fn(key=("t",), name="t", mesh_desc=None, donated=False):
    import jax

    return cc.CachedFunction(jax.jit(lambda x: x * 2 + 1), key, name=name,
                             mesh_desc=mesh_desc, donated=donated)


class TestInvalidation:
    def test_stale_jax_version_key(self, aot_dir, tel_enabled, monkeypatch):
        import jax.numpy as jnp

        x = jnp.ones((4,))
        f1 = _cached_fn()
        np.testing.assert_allclose(f1(x), 3.0)
        assert cc.stats()["misses"] == 1
        # "restart" onto a different jax/jaxlib build
        monkeypatch.setattr(cc, "_versions", lambda: ("0.0.0", "0.0.0"))
        f2 = _cached_fn()
        np.testing.assert_allclose(f2(x), 3.0)  # recompiled, not crashed
        s = cc.stats()
        assert s["errors"] == 1 and s["misses"] == 2
        err = tin.registry().get("aot_cache_errors_total")
        assert sum(v["value"] for v in err.samples()
                   if v["labels"]["reason"] == "key_mismatch") == 1
        # the stale entry was overwritten: a third consumer (same stubbed
        # version) now hits
        f3 = _cached_fn()
        np.testing.assert_allclose(f3(x), 3.0)
        assert cc.stats()["hits"] == 1

    def test_mesh_shape_mismatch(self, aot_dir, tel_enabled):
        import jax.numpy as jnp

        x = jnp.ones((4,))
        f1 = _cached_fn(mesh_desc={"axes": ["dp"], "shape": [8]})
        f1(x)
        assert cc.stats()["misses"] == 1
        # restart onto a different topology: same logical key, mesh differs
        f2 = _cached_fn(mesh_desc={"axes": ["dp"], "shape": [4]})
        np.testing.assert_allclose(f2(x), 3.0)
        s = cc.stats()
        assert s["errors"] == 1 and s["misses"] == 2 and s["hits"] == 0
        err = tin.registry().get("aot_cache_errors_total")
        assert sum(v["value"] for v in err.samples()
                   if v["labels"]["reason"] == "key_mismatch") == 1

    def test_truncated_cache_file(self, aot_dir, tel_enabled):
        import jax.numpy as jnp

        x = jnp.ones((4,))
        _cached_fn()(x)
        (entry,) = _exec_entries(aot_dir)
        with open(entry, "rb") as f:
            blob = f.read()
        with open(entry, "wb") as f:
            f.write(blob[:64])  # torn write / disk corruption
        f2 = _cached_fn()
        np.testing.assert_allclose(f2(x), 3.0)
        s = cc.stats()
        assert s["errors"] == 1 and s["misses"] == 2
        err = tin.registry().get("aot_cache_errors_total")
        assert sum(v["value"] for v in err.samples()
                   if v["labels"]["reason"] == "deserialize") == 1
        # recompile re-stored a good entry: next consumer hits
        f3 = _cached_fn()
        f3(x)
        assert cc.stats()["hits"] == 1

    def test_garbage_file_never_crashes(self, aot_dir):
        import jax.numpy as jnp

        x = jnp.ones((4,))
        _cached_fn()(x)
        (entry,) = _exec_entries(aot_dir)
        with open(entry, "wb") as f:
            f.write(b"\x00not a pickle")
        np.testing.assert_allclose(_cached_fn()(x), 3.0)
        assert cc.stats()["errors"] == 1

    def test_hit_and_miss_counters_reach_registry(self, aot_dir, tel_enabled):
        import jax.numpy as jnp

        x = jnp.ones((4,))
        _cached_fn()(x)
        _cached_fn()(x)
        r = tin.registry()
        miss = r.get("aot_cache_misses_total")
        hit = r.get("aot_cache_hits_total")
        assert sum(v["value"] for v in miss.samples()
                   if v["labels"]["tier"] == "exec") == 1
        assert sum(v["value"] for v in hit.samples()
                   if v["labels"]["tier"] == "exec") == 1


# -- fused stepper ------------------------------------------------------------
def _tiny_module():
    from mxnet_tpu import module as mod_mod

    data = mx.sym.var("data")
    x = mx.sym.FullyConnected(data, name="fc1", num_hidden=16)
    x = mx.sym.Activation(x, name="relu1", act_type="relu")
    x = mx.sym.FullyConnected(x, name="fc2", num_hidden=4)
    sym = mx.sym.SoftmaxOutput(x, name="softmax")
    mod = mod_mod.Module(sym)
    mod.bind(data_shapes=[("data", (8, 8))],
             label_shapes=[("softmax_label", (8,))])
    rng = np.random.RandomState(3)
    mod.init_params(arg_params={
        n: mx.nd.array(rng.randn(*a.shape).astype(np.float32) * 0.1)
        for n, a in mod._exec.arg_dict.items()
        if n not in ("data", "softmax_label")})
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1})
    return mod


def _steps(mod, n=2):
    from mxnet_tpu.io import DataBatch

    rng = np.random.RandomState(7)
    for _ in range(n):
        b = DataBatch(
            data=[mx.nd.array(rng.randn(8, 8).astype(np.float32))],
            label=[mx.nd.array(rng.randint(0, 4, (8,)).astype(np.float32))])
        mod.forward_backward(b)
        mod.update()
    return mod.get_outputs()[0].asnumpy()


class TestFusedStepper:
    def test_fused_step_wrapped_and_parity(self, aot_dir):
        mx.random.seed(11)
        mod = _tiny_module()
        out_aot = _steps(mod)
        assert isinstance(mod._fused._jit, cc.CachedFunction)
        os.environ.pop("MXNET_AOT_CACHE")
        mx.random.seed(11)
        out_plain = _steps(_tiny_module())
        np.testing.assert_allclose(out_aot, out_plain, atol=1e-6)

    def test_donated_cpu_guard_skips_disk(self, aot_dir):
        """Restored donated executables are unsound on XLA:CPU (wrong
        trajectories under load — compile_cache.py docstring), so the
        fused step must neither write nor read disk entries here, while
        the in-memory AOT split still dispatches correctly."""
        mx.random.seed(11)
        mod = _tiny_module()
        _steps(mod)
        fused_entries = [p for p in _exec_entries(aot_dir)
                         if "fused_step" in os.path.basename(p)]
        assert fused_entries == []
        s = cc.stats()
        assert s["hits"] == 0 and s["misses"] == 0 and s["errors"] == 0

    def test_cache_size_tracks_signatures(self, aot_dir):
        mx.random.seed(11)
        mod = _tiny_module()
        _steps(mod)
        assert mod._fused.cache_size() == 1  # one shape signature, once

    def test_packed_and_per_leaf_entries_are_distinct(self, aot_dir):
        """The same symbol's packed step and per-leaf step (a Module whose
        arrays another Module shares) are two programs: two keys, apart
        by the packed marker and the packed layout alone."""
        from mxnet_tpu import module as mod_mod

        packed = _tiny_module()
        leaf = _tiny_module()
        mod_mod.Module(leaf._symbol).bind(
            data_shapes=[("data", (8, 8))],
            label_shapes=[("softmax_label", (8,))], shared_module=leaf)
        mx.random.seed(11)
        out_packed = _steps(packed)
        mx.random.seed(11)
        out_leaf = _steps(leaf)
        assert packed._fused._packed and not leaf._fused._packed
        assert packed._fused._aot_key == leaf._fused._aot_key + ("packed",)
        assert packed._fused._jit._key != leaf._fused._jit._key
        np.testing.assert_array_equal(out_packed, out_leaf)


# -- predictor surface --------------------------------------------------------
class TestPredictorAOT:
    def test_aot_warm_roundtrip(self, aot_dir):
        from mxnet_tpu.predictor import Predictor
        from mxnet_tpu.test_utils import tiny_mlp_checkpoint

        sym, params = tiny_mlp_checkpoint()
        p1 = Predictor(sym, params, {"data": (2, 8)})
        row = p1.aot_warm()
        assert row["source"] == "compile" and row["compile_s"] > 0
        x = np.random.RandomState(1).rand(2, 8).astype(np.float32)
        ref = p1.forward(data=x)[0].asnumpy()
        # "restart": a sibling predictor restores the executable
        p2 = Predictor(sym, params, {"data": (2, 8)})
        row2 = p2.aot_warm()
        assert row2["source"] == "disk"
        np.testing.assert_allclose(p2.forward(data=x)[0].asnumpy(), ref,
                                   atol=1e-6)
        assert cc.stats()["hits"] == 1
