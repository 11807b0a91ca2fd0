"""Telemetry-driven autotuning (ISSUE 9, mxnet_tpu/autotune/).

Coverage demanded by the issue:
- winner-store invalidation is CORRUPTION-SAFE (mirrors test_aot_cache):
  a stale jax/jaxlib version fingerprint and a changed device kind each
  produce a silent miss + re-search (never a stale winner), and a
  truncated or garbage store file never crashes;
- persistence acceptance: a second search against a warm store performs
  ZERO new measurements;
- the searcher measures the hand-tuned default first and keeps it on a
  tie — adopting a winner can never regress shipped behavior;
- ``MXNET_AUTOTUNE`` unset => byte-identical behavior: the dconv grid
  ignores persisted winners, the Engine ladder selection never imports
  the package, no store file is read;
- the ladder tuner's replay objective and never-worse proposal;
- dconv numeric parity across tuned block sizes;
- the ``--gate-warmup`` / ``--prune-baseline`` tool satellites.
"""
import json
import math
import os

import numpy as np
import pytest

import mxnet_tpu as mx  # noqa: F401  (conftest seeding imports it anyway)
from mxnet_tpu import autotune
from mxnet_tpu.autotune import costmodel as cm
from mxnet_tpu.autotune import ladder as lt
from mxnet_tpu.autotune import measure as ms
from mxnet_tpu.autotune import space as sps
from mxnet_tpu.autotune import store as st
from mxnet_tpu.telemetry import instrument as tin

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _load_tool(relpath):
    from mxnet_tpu.test_utils import load_module_by_path

    return load_module_by_path(os.path.join(REPO, relpath))


@pytest.fixture
def at_on(tmp_path, monkeypatch):
    """Autotuning ON against a private store file; counters reset."""
    monkeypatch.setenv("MXNET_AUTOTUNE", "1")
    monkeypatch.setenv("MXNET_AUTOTUNE_CACHE", str(tmp_path / "at.json"))
    st._reset_stats_for_tests()
    ms._reset_stats_for_tests()
    yield str(tmp_path / "at.json")
    st._reset_stats_for_tests()
    ms._reset_stats_for_tests()


@pytest.fixture
def at_off(tmp_path, monkeypatch):
    """Gate unset but a store file PRESENT — the off path must never read
    it."""
    monkeypatch.delenv("MXNET_AUTOTUNE", raising=False)
    monkeypatch.setenv("MXNET_AUTOTUNE_CACHE", str(tmp_path / "at.json"))
    st._reset_stats_for_tests()
    yield str(tmp_path / "at.json")
    st._reset_stats_for_tests()


@pytest.fixture
def tel_enabled(monkeypatch, tmp_path):
    monkeypatch.setenv("MXNET_TELEMETRY", "1")
    monkeypatch.setenv("MXNET_TELEMETRY_FILE", str(tmp_path / "t.jsonl"))
    tin._reset_for_tests()
    yield
    tin._reset_for_tests()


def _counter_total(name, **labels):
    m = tin.registry().get(name)
    if m is None:
        return 0
    return sum(v["value"] for v in m.samples()
               if all(v["labels"].get(k) == lv for k, lv in labels.items()))


# -- winner store -------------------------------------------------------------
class TestStore:
    def test_record_lookup_roundtrip(self, at_on):
        assert autotune.lookup("k", "sig-a") is None
        autotune.record("k", "sig-a", {"nblk": 64}, score=0.5)
        assert autotune.lookup("k", "sig-a") == {"nblk": 64}
        assert autotune.lookup("k", "sig-b") is None  # other sig untouched
        s = autotune.stats()
        assert s["hits"] == 1 and s["misses"] == 2 and s["errors"] == 0

    def test_counters_reach_registry(self, at_on, tel_enabled):
        autotune.record("k", "s", {"x": 1})
        autotune.lookup("k", "s")
        autotune.lookup("k", "other")
        assert _counter_total("autotune_cache_hits_total", kernel="k") == 1
        assert _counter_total("autotune_cache_misses_total", kernel="k") == 1

    def test_stale_jax_version_is_silent_miss(self, at_on, monkeypatch):
        autotune.record("k", "s", {"nblk": 32})
        assert autotune.lookup("k", "s") == {"nblk": 32}
        # "restart" onto a different jax/jaxlib build
        monkeypatch.setattr(st, "_versions", lambda: ("0.0.0", "0.0.0"))
        assert autotune.lookup("k", "s") is None  # rejected, not crashed
        s = autotune.stats()
        assert s["errors"] == 1
        # the re-search overwrites under the new fingerprint: hits again
        autotune.record("k", "s", {"nblk": 64})
        assert autotune.lookup("k", "s") == {"nblk": 64}

    def test_device_kind_change_is_clean_miss(self, at_on, monkeypatch):
        real_kind = st._device_kind
        autotune.record("k", "s", {"nblk": 32})
        monkeypatch.setattr(st, "_device_kind", lambda: "TPU v5e")
        # different device kind = different key: a miss, then its own entry
        assert autotune.lookup("k", "s") is None
        autotune.record("k", "s", {"nblk": 256})
        assert autotune.lookup("k", "s") == {"nblk": 256}
        monkeypatch.setattr(st, "_device_kind", real_kind)
        # the original device kind's winner survived alongside
        assert autotune.lookup("k", "s") == {"nblk": 32}

    def test_truncated_store_never_crashes(self, at_on):
        autotune.record("k", "s", {"nblk": 64})
        with open(at_on, "rb") as f:
            blob = f.read()
        with open(at_on, "wb") as f:
            f.write(blob[:16])  # torn write
        assert autotune.lookup("k", "s") is None
        assert autotune.stats()["errors"] >= 1
        # re-record repairs the file
        autotune.record("k", "s", {"nblk": 64})
        assert autotune.lookup("k", "s") == {"nblk": 64}

    def test_garbage_store_never_crashes(self, at_on):
        with open(at_on, "w") as f:
            f.write("\x00 not json at all")
        assert autotune.lookup("k", "s") is None
        autotune.record("k2", "s2", {"a": 1})
        assert autotune.lookup("k2", "s2") == {"a": 1}

    def test_malformed_entry_config_rejected(self, at_on):
        autotune.record("k", "s", {"nblk": 64})
        with open(at_on) as f:
            payload = json.load(f)
        key = next(iter(payload["entries"]))
        payload["entries"][key]["config"] = "not-a-dict"
        with open(at_on, "w") as f:
            json.dump(payload, f)
        assert autotune.lookup("k", "s") is None
        assert autotune.stats()["errors"] == 1

    def test_clear_by_kernel(self, at_on):
        autotune.record("a", "s", {"x": 1})
        autotune.record("b", "s", {"x": 2})
        assert autotune.clear(kernel="a") == 1
        assert autotune.lookup("a", "s") is None
        assert autotune.lookup("b", "s") == {"x": 2}
        assert autotune.clear() == 1
        assert autotune.entries() == {}

    def test_override_wins_without_store_read(self, at_on):
        autotune.record("k", "s", {"nblk": 128})
        with autotune.override("k", {"nblk": 32}):
            assert autotune.config_for("k", "s") == {"nblk": 32}
        assert autotune.config_for("k", "s") == {"nblk": 128}


# -- the MXNET_AUTOTUNE off path ----------------------------------------------
class TestOffPath:
    def test_lookup_never_touches_store(self, at_off):
        with open(at_off, "w") as f:
            f.write("garbage that would count an error if read")
        assert autotune.lookup("k", "s") is None
        assert autotune.stats() == {"hits": 0, "misses": 0, "errors": 0}

    def test_dconv_grid_ignores_winner(self, at_off, monkeypatch):
        from mxnet_tpu.ops import pallas_kernels as pk

        monkeypatch.setenv("MXNET_AUTOTUNE", "1")
        autotune.record("dconv_col_pallas",
                        autotune.dconv_shape_sig(512, 2432, 512, 4),
                        {"nblk": 64})
        assert pk._dconv_grid(512, 2432, 512, 4) == (64, 512)
        monkeypatch.delenv("MXNET_AUTOTUNE")
        # gate off: the persisted winner is invisible — no store read at all
        monkeypatch.setattr(st, "lookup",
                            lambda *a, **k: pytest.fail("store read on the "
                                                        "off path"))
        assert pk._dconv_grid(512, 2432, 512, 4) == (128, 512)

    def test_engine_keeps_default_ladder(self, at_off, monkeypatch):
        from mxnet_tpu.serving import Engine
        from mxnet_tpu.test_utils import tiny_mlp_checkpoint

        monkeypatch.setenv("MXNET_AUTOTUNE", "1")
        autotune.record(autotune.LADDER_KERNEL,
                        autotune.ladder_sig({"data": (8,)}),
                        {"batch_sizes": [1, 3, 6]})
        monkeypatch.delenv("MXNET_AUTOTUNE")
        sym, params = tiny_mlp_checkpoint()
        eng = Engine(sym, params, {"data": (8,)}, start=False)
        assert eng.ladder.batch_sizes == (1, 2, 4, 8)
        eng.close()


# -- dconv wiring -------------------------------------------------------------
class TestDconvWiring:
    def test_tuned_grid_and_numeric_parity(self, at_on):
        """A tuned block size changes the grid, not the numbers: outputs
        and gradients across nblk in {32, 128} are identical (interpret
        mode; padded rows carry lf=0 so block layout is value-neutral)."""
        import jax
        import jax.numpy as jnp

        from mxnet_tpu.ops import pallas_kernels as pk

        BG, N, H, W, C = 2, 70, 5, 8, 16
        HW = H * W
        rng = np.random.RandomState(0)
        y0 = jnp.asarray(rng.randint(0, H - 1, (BG, N)).astype(np.int32))
        y1 = jnp.minimum(y0 + 1, H - 1)
        x0 = jnp.asarray(rng.randint(0, W - 1, (BG, N)).astype(np.int32))
        x1 = jnp.minimum(x0 + 1, W - 1)
        ly = jnp.asarray(rng.rand(BG, N).astype(np.float32))
        lx = jnp.asarray(rng.rand(BG, N).astype(np.float32))
        lf = jnp.asarray((rng.rand(BG, N) > 0.2).astype(np.float32))
        ft = jnp.asarray(rng.randn(BG, C, HW).astype(np.float32))
        g = jnp.asarray(rng.randn(BG, C, N).astype(np.float32))

        def run(nblk):
            with autotune.override("dconv_col_pallas", {"nblk": nblk}):
                assert pk._dconv_grid(N, HW, C, 4)[0] == min(nblk, N)

                def loss(ly, lx, lf, ft):
                    out = pk.dconv_col_pallas(y0, y1, x0, x1, ly, lx, lf,
                                              ft, (H, W), True)
                    return jnp.sum(out * g)

                out = pk.dconv_col_pallas(y0, y1, x0, x1, ly, lx, lf, ft,
                                          (H, W), True)
                grads = jax.grad(loss, argnums=(0, 1, 2, 3))(ly, lx, lf, ft)
                return out, grads

        out_a, g_a = run(32)
        out_b, g_b = run(128)
        np.testing.assert_allclose(np.asarray(out_a), np.asarray(out_b),
                                   rtol=1e-6, atol=1e-6)
        for ga, gb in zip(g_a, g_b):
            np.testing.assert_allclose(np.asarray(ga), np.asarray(gb),
                                       rtol=1e-5, atol=1e-6)

    def test_winner_revalidated_against_current_vmem_budget(
            self, at_on, monkeypatch):
        """A winner persisted under a larger MXNET_DCONV_VMEM_MB must not
        be adopted once the budget shrinks below its working set — the
        guard re-decides at adoption time, so a stale winner can never
        hard-fail Mosaic (it falls back to the hand-tuned default)."""
        from mxnet_tpu.ops import pallas_kernels as pk

        N, HW, C, itemsize = 4096, 2432, 512, 2
        with autotune.override("dconv_col_pallas", {"nblk": 512}):
            # generous budget: the pinned winner is adopted
            monkeypatch.setenv("MXNET_DCONV_VMEM_MB", "256")
            assert pk._dconv_grid(N, HW, C, itemsize)[0] == 512
            # shrunk budget: same winner now exceeds the backward working
            # set -> default, not a crash
            monkeypatch.setenv("MXNET_DCONV_VMEM_MB", "24")
            assert not pk.dconv_fits_vmem(HW, C, itemsize, nblk=512)
            assert pk._dconv_grid(N, HW, C, itemsize)[0] == pk._DCONV_NBLK

    def test_space_constraint_is_the_vmem_guard(self):
        sp = autotune.get_space("dconv_col_pallas")
        # north-star res5: 256/512-row blocks blow the backward VMEM budget
        cfgs = sp.configs(N=2432, HW=2432, C=512, itemsize=2)
        nblks = {c["nblk"] for c in cfgs}
        assert 128 in nblks and 512 not in nblks
        # tiny problems admit everything
        assert len(sp.configs(N=128, HW=32, C=16, itemsize=4)) == 5


# -- searcher -----------------------------------------------------------------
class TestSearch:
    def _space(self, choices=(32, 64, 128), default=128):
        return autotune.TuningSpace("k", {"nblk": choices},
                                    {"nblk": default})

    def test_default_wins_ties(self):
        best, results = autotune.run_search(self._space(),
                                            lambda cfg: 1.0)  # all tie
        assert best == {"nblk": 128}
        assert results[0]["config"] == {"nblk": 128}  # measured first

    def test_strictly_better_candidate_wins(self):
        best, results = autotune.run_search(
            self._space(), lambda cfg: 0.5 if cfg["nblk"] == 64 else 1.0)
        assert best == {"nblk": 64}
        assert len(results) == 3

    def test_greedy_descent_beyond_max_trials(self):
        space = autotune.TuningSpace(
            "k", {"a": tuple(range(8)), "b": tuple(range(8))},
            {"a": 0, "b": 0})

        def measure(cfg):  # separable bowl, optimum (5, 3)
            return (cfg["a"] - 5) ** 2 + (cfg["b"] - 3) ** 2 + 1.0

        best, results = autotune.run_search(space, measure, max_trials=40)
        assert best == {"a": 5, "b": 3}
        assert len(results) <= 40

    def test_measure_candidate_counts_trials(self, at_on, tel_enabled):
        import jax.numpy as jnp

        before = autotune.measurements()
        t = autotune.measure_candidate(
            "k", {"nblk": 1}, lambda: (lambda x: x + 1),
            (jnp.ones((4,)),), warmup=1, repeat=2)
        assert t > 0
        assert autotune.measurements() == before + 1
        assert _counter_total("autotune_trials_total", kernel="k") == 1
        assert tin.summary()["autotune_trials"] == 1


# -- ladder tuner -------------------------------------------------------------
def _mk_trace(path, recs):
    with open(path, "w") as f:
        for r in recs:
            f.write(json.dumps(r) + "\n")
    return str(path)


def _rec(t, n, shape=(8,), klass="open"):
    return {"t": t, "n": n, "shapes": {"data": list(shape)}, "class": klass}


class TestLadder:
    def test_objective_hand_computed(self):
        # two n=1 requests 1 ms apart coalesce (within max_wait); one n=3
        # a second later is its own batch.  Ladder (2, 4): batch of 2 is
        # exact, batch of 3 pads to 4.  vol(sample) = 8.
        recs = [_rec(0.0, 1), _rec(0.001, 1), _rec(1.0, 3)]
        # padded = 2*8 + 4*8 = 48; real = 5*8 = 40; compiles = 2
        assert lt.objective((2, 4), recs) == pytest.approx(48 / 40 * 2)
        # single rung 4: (4+4)*8 / 40 * 1
        assert lt.objective((4,), recs) == pytest.approx(64 / 40)

    def test_oversize_goes_direct(self):
        recs = [_rec(0.0, 9), _rec(1.0, 1)]
        # n=9 > top rung 4: exact one-off (no padding, inflation stays 1)
        # but its own compile — 2 rungs + 1 direct signature
        assert lt.objective((1, 4), recs) == pytest.approx(3.0)

    def test_propose_beats_default_on_skewed_traffic(self, tmp_path):
        recs = [_rec(i * 0.05, n) for i, n in enumerate([3, 5, 6] * 20)]
        tuned, rep = lt.propose(recs)
        assert rep["objective_tuned"] < rep["objective_default"]
        assert lt.objective(tuned, recs) == pytest.approx(
            rep["objective_tuned"])

    def test_propose_never_worse_keeps_default(self):
        # traffic the default ladder serves exactly: all n=8, far apart
        recs = [_rec(i * 1.0, 8) for i in range(10)]
        tuned, rep = lt.propose(recs, default=(8,))
        assert tuned == (8,)
        assert rep["objective_tuned"] == rep["objective_default"]

    def test_load_trace_validates(self, tmp_path):
        p = _mk_trace(tmp_path / "t.jsonl", [_rec(0.0, 1)])
        assert len(lt.load_trace(p)) == 1
        bad = tmp_path / "bad.jsonl"
        bad.write_text('{"t": 0.0, "n": 0, "shapes": {}, "class": "x"}\n')
        with pytest.raises(ValueError):
            lt.load_trace(str(bad))
        empty = tmp_path / "empty.jsonl"
        empty.write_text("")
        with pytest.raises(ValueError):
            lt.load_trace(str(empty))

    def test_ladder_sig_matches_engine_side(self):
        recs = [_rec(0.0, 1, shape=(3, 4)), _rec(0.1, 2, shape=(3, 4))]
        shapes = lt.trace_sample_shapes(recs)
        assert lt.ladder_sig(shapes) == lt.ladder_sig({"data": (3, 4)})


# -- engine adoption ----------------------------------------------------------
class TestEngineAdoption:
    def test_tuned_ladder_adopted(self, at_on):
        from mxnet_tpu.serving import BucketLadder, Engine
        from mxnet_tpu.test_utils import tiny_mlp_checkpoint

        autotune.record(autotune.LADDER_KERNEL,
                        autotune.ladder_sig({"data": (8,)}),
                        {"batch_sizes": [1, 3, 6]})
        sym, params = tiny_mlp_checkpoint()
        eng = Engine(sym, params, {"data": (8,)}, start=False)
        assert eng.ladder.batch_sizes == (1, 3, 6)
        eng.close()
        # an explicit ladder argument always wins over the store
        eng2 = Engine(sym, params, {"data": (8,)},
                      ladder=BucketLadder((1, 2)), start=False)
        assert eng2.ladder.batch_sizes == (1, 2)
        eng2.close()

    def test_malformed_ladder_winner_falls_back(self, at_on):
        from mxnet_tpu.serving import Engine
        from mxnet_tpu.test_utils import tiny_mlp_checkpoint

        autotune.record(autotune.LADDER_KERNEL,
                        autotune.ladder_sig({"data": (8,)}),
                        {"batch_sizes": "garbage"})
        sym, params = tiny_mlp_checkpoint()
        eng = Engine(sym, params, {"data": (8,)}, start=False)
        assert eng.ladder.batch_sizes == (1, 2, 4, 8)
        eng.close()

    def test_aot_fingerprint_keys_store_state(self, at_on, monkeypatch):
        """Adopted winners shape traced programs, so the AOT-cache env
        fingerprint must fold the store state in while the gate is on —
        and stay byte-identical to a pre-autotune build when it is off
        (an executable traced under one winner set can never restore
        under another, nor cross the gate boundary)."""
        from mxnet_tpu import compile_cache

        fp_on = compile_cache._env_fingerprint()
        assert fp_on["autotune"] == autotune.store.state_digest()
        autotune.record("dconv_col_pallas", "sigX", {"nblk": 256})
        fp_after = compile_cache._env_fingerprint()
        assert fp_after["autotune"] != fp_on["autotune"]
        monkeypatch.delenv("MXNET_AUTOTUNE")
        fp_off = compile_cache._env_fingerprint()
        assert "autotune" not in fp_off

    def test_numeric_string_winner_rejected(self, at_on):
        # "248" would iterate into rungs (2, 4, 8) if types weren't
        # checked — a malformed winner must keep the default, not adopt a
        # ladder nobody proposed
        autotune.record(autotune.LADDER_KERNEL,
                        autotune.ladder_sig({"data": (9,)}),
                        {"batch_sizes": "248"})
        assert autotune.tuned_ladder({"data": (9,)}) is None


# -- CLI ----------------------------------------------------------------------
class TestCLI:
    def test_dconv_search_then_warm_store_zero_measurements(self, at_on):
        at = _load_tool("tools/autotune.py")
        argv = ["search", "--kernel", "dconv_col_pallas",
                "--n", "64", "--h", "4", "--w", "8", "--c", "16",
                "--warmup", "1", "--repeat", "1"]
        assert at.main(list(argv)) == 0
        first = autotune.measurements()
        assert first > 0
        sig = autotune.dconv_shape_sig(64, 32, 16, 4)
        winner = autotune.lookup("dconv_col_pallas", sig)
        assert winner is not None and "nblk" in winner
        # persistence acceptance: the second run measures NOTHING
        assert at.main(list(argv)) == 0
        assert autotune.measurements() == first
        # --force re-searches
        assert at.main(list(argv) + ["--force"]) == 0
        assert autotune.measurements() > first

    def test_dconv_search_on_a_deformable_layers_samples(self, at_on):
        """``--offset``: the search measures on a dilated 3x3 grid plus
        small offsets (narrow bands) where the default draw is uniform
        over the map (every band the whole map); more rows than the grid
        has are refused."""
        at = _load_tool("tools/autotune.py")
        argv = ["search", "--kernel", "dconv_col_pallas", "--h", "6",
                "--w", "8", "--c", "16", "--warmup", "1", "--repeat", "1",
                "--offset", "1"]
        assert at.main(argv + ["--n", "432"]) == 0
        winner = autotune.lookup("dconv_col_pallas",
                                 autotune.dconv_shape_sig(432, 48, 16, 4))
        assert winner is not None and "nblk" in winner
        with pytest.raises(SystemExit, match="9\\*h\\*w = 432"):
            at.main(argv + ["--n", "433"])

    def test_ladder_search_roundtrip(self, at_on, tmp_path, capsys):
        at = _load_tool("tools/autotune.py")
        trace = _mk_trace(tmp_path / "t.jsonl",
                          [_rec(i * 0.05, n)
                           for i, n in enumerate([3, 5, 6] * 10)])
        assert at.main(["search", "--trace", trace]) == 0
        line = [l for l in capsys.readouterr().out.splitlines()
                if l.startswith("AUTOTUNE ")][-1]
        payload = json.loads(line[len("AUTOTUNE "):])
        assert payload["objective_tuned"] < payload["objective_default"]
        tuned = autotune.tuned_ladder({"data": (8,)})
        assert tuned == tuple(payload["config"]["batch_sizes"])
        # warm second run, then show + clear
        assert at.main(["search", "--trace", trace]) == 0
        line2 = [l for l in capsys.readouterr().out.splitlines()
                 if l.startswith("AUTOTUNE ")][-1]
        assert json.loads(line2[len("AUTOTUNE "):])["cached"] is True
        assert at.main(["show"]) == 0
        assert "bucket_ladder" in capsys.readouterr().out
        assert at.main(["clear"]) == 0
        assert autotune.entries() == {}

    def test_show_features_surface(self, at_on, capsys):
        at = _load_tool("tools/autotune.py")
        autotune.record(
            "dconv_col_pallas", "N64-HW32-C16-i4", {"nblk": 64}, score=1e-4,
            meta={"strategy": "grid", "grid": 5,
                  "cost": {"flops": 3.0},
                  "trial_costs": [{"config": {"nblk": 64}, "seconds": 1e-4,
                                   "cost": {"flops": 3.0}}]})
        assert at.main(["show"]) == 0
        plain = capsys.readouterr().out
        assert "cost:" not in plain and "trial rows:" not in plain
        assert at.main(["show", "--features"]) == 0
        out = capsys.readouterr().out
        assert 'cost: {"flops": 3.0}' in out
        assert "trial rows: 1 (strategy=grid, grid=5)" in out

    def test_predict_strategy_in_process(self, at_on, monkeypatch, capsys):
        """Grid-seed one shape under MXNET_COSTPLANE (the trial rows the
        model trains on), then a predict search at a FRESH shape measures
        only default + top-1 and surfaces trials_saved."""
        monkeypatch.setenv("MXNET_COSTPLANE", "1")
        at = _load_tool("tools/autotune.py")

        def lines():
            return [json.loads(l[len("AUTOTUNE "):])
                    for l in capsys.readouterr().out.splitlines()
                    if l.startswith("AUTOTUNE ")]

        # two seeded shapes: the runner dedups by EFFECTIVE (N-capped)
        # nblk, so N64 measures 2 configs and N96 measures 3 — 5 rows
        for n in ("64", "96"):
            assert at.main(["search", "--kernel", "dconv_col_pallas",
                            "--n", n, "--h", "4", "--w", "8", "--c", "16",
                            "--strategy", "grid",
                            "--warmup", "0", "--repeat", "1"]) == 0
            seeded = lines()[-1]
            assert seeded["strategy"] == "grid" and not seeded["cached"]
        from mxnet_tpu.autotune import costmodel as cmod

        assert len(cmod.training_rows("dconv_col_pallas")) >= cmod.MIN_ROWS
        assert at.main(["search", "--kernel", "dconv_col_pallas",
                        "--n", "128", "--h", "4", "--w", "8", "--c", "16",
                        "--strategy", "predict",
                        "--top-k", "1", "--warmup", "0",
                        "--repeat", "1"]) == 0
        pred = lines()[-1]
        assert pred["strategy"] == "predict"
        assert pred["measurements"] == 2 and pred["grid"] == 3
        assert pred["trials_saved"] == 1
        # never-worse: a non-default winner strictly beat the default
        default_cfg = autotune.get_space("dconv_col_pallas").default
        assert pred["config"] == default_cfg \
            or pred["best_s"] < pred["default_s"]


# -- tool satellites ----------------------------------------------------------
class TestToolSatellites:
    def test_bench_compare_gate_warmup_opt_in(self, tmp_path):
        bc = _load_tool("tools/bench_compare.py")

        def capture(path, warmup_s):
            json.dump({"metric": "m", "value": 100.0, "unit": "img/s",
                       "telemetry": {"compile_s": 1.0,
                                     "peak_hbm_bytes": None,
                                     "data_wait_frac": 0.0,
                                     "warmup_s": warmup_s}},
                      open(path, "w"))
            return path

        base = capture(str(tmp_path / "b.json"), 1.0)
        slow = capture(str(tmp_path / "s.json"), 2.0)
        # default: Δwarmup% shown, never gated
        assert bc.main([base, slow, "--threshold", "5"]) == 0
        # opt-in gate trips on the doubled warmup
        assert bc.main([base, slow, "--threshold", "5",
                        "--gate-warmup"]) == 1
        # regression-free pair passes with the gate on
        ok = capture(str(tmp_path / "ok.json"), 1.02)
        assert bc.main([base, ok, "--threshold", "5", "--gate-warmup"]) == 0

    def test_mxlint_prune_baseline(self, tmp_path, capsys):
        from mxnet_tpu.analysis import source_lint

        lint = _load_tool("tools/mxlint.py")
        src = tmp_path / "m.py"
        src.write_text("import jax\n\n@jax.jit\ndef f(x):\n"
                       "    return float(x)\n")
        # same root the CLI lints with, so fingerprints line up
        (f,) = source_lint.lint_paths([str(src)], root=REPO)
        bl = tmp_path / "baseline.txt"
        bl.write_text("# header comment\n"
                      "%s  # justified, must survive\n"
                      "m.py::gone@dead line::some-rule\n" % f.fingerprint)
        # pruning the SHARED default baseline from a partial lint is
        # refused (out-of-scope entries would all look stale), and the
        # baseline file is left untouched
        rc = lint.main([str(src), "--prune-baseline"])
        assert rc == 2
        rc = lint.main([str(src), "--baseline", str(bl),
                        "--prune-baseline"])
        assert rc == 0
        out = capsys.readouterr().out
        assert "pruned 1 stale" in out
        text = bl.read_text()
        assert f.fingerprint in text and "justified, must survive" in text
        assert "gone@dead line" not in text
        assert text.startswith("# header comment")
        # second prune: nothing stale left
        assert lint.main([str(src), "--baseline", str(bl),
                          "--prune-baseline"]) == 0
        assert "no stale entries" in capsys.readouterr().out


# -- learned cost model (ISSUE 18) --------------------------------------------
def _synthetic_rows(sigs=(64, 128, 256), nblks=(32, 64, 128, 256)):
    """Training rows whose latency grows with the block size at every
    shape — any sane fit must rank small blocks first."""
    rows = []
    for n in sigs:
        for nblk in nblks:
            rows.append({"kernel": "k", "device_kind": "cpu",
                         "sig": "N%d-HW32-C16-i4" % n,
                         "config": {"nblk": nblk},
                         "seconds": 1e-6 * nblk * (1.0 + n / 512.0),
                         "cost": None})
    return rows


class TestCostModel:
    def test_fit_ranks_monotone_cost(self):
        m = cm.CostModel().fit(_synthetic_rows())
        assert m.ready
        ranked = m.rank("N128-HW32-C16-i4",
                        [{"nblk": b} for b in (256, 32, 128, 64)])
        assert [c["nblk"] for c in ranked] == [32, 64, 128, 256]

    def test_transfer_to_unseen_shape(self):
        """Shape-signature features carry the fit to a sig never searched:
        the model still orders blocks by cost at N512."""
        m = cm.CostModel().fit(_synthetic_rows(sigs=(64, 128, 256)))
        unseen = "N512-HW32-C16-i4"
        assert m.predict_one(unseen, {"nblk": 32}) \
            < m.predict_one(unseen, {"nblk": 256})

    def test_training_rows_filters_junk(self, at_on):
        autotune.record("k", "N64-HW32-C16-i4", {"nblk": 64}, score=1e-4,
                        meta={"trial_costs": [
                            {"config": {"nblk": 64}, "seconds": 1e-4,
                             "cost": {"flops": 2.0}},
                            {"config": {"nblk": 32},
                             "seconds": float("inf")},   # failed sentinel
                            {"config": {"nblk": 16}, "seconds": -1.0},
                            {"config": "junk", "seconds": 1e-4},
                            "not-a-dict"]})
        autotune.record("other", "sigY", {"x": 1}, meta={"trial_costs": [
            {"config": {"x": 1}, "seconds": 2e-4}]})
        rows = cm.training_rows("k")
        assert len(rows) == 1
        assert rows[0]["config"] == {"nblk": 64}
        assert rows[0]["cost"] == {"flops": 2.0}
        # no kernel filter: both kernels' usable rows
        assert len(cm.training_rows()) == 2

    def test_model_for_needs_min_rows(self, at_on):
        autotune.record("k", "N64-HW32-C16-i4", {"nblk": 64}, meta={
            "trial_costs": [{"config": {"nblk": b}, "seconds": 1e-6 * b}
                            for b in (32, 64)]})
        assert cm.model_for("k") is None  # 2 < MIN_ROWS
        autotune.record("k", "N128-HW32-C16-i4", {"nblk": 64}, meta={
            "trial_costs": [{"config": {"nblk": b}, "seconds": 2e-6 * b}
                            for b in (32, 64, 128)]})
        m = cm.model_for("k")
        assert m is not None and m.ready

    def test_default_top_k(self, monkeypatch):
        monkeypatch.delenv("MXNET_AUTOTUNE_TOPK", raising=False)
        assert cm.default_top_k(8) == 2
        assert cm.default_top_k(3) == 1   # never zero
        monkeypatch.setenv("MXNET_AUTOTUNE_TOPK", "3")
        assert cm.default_top_k(100) == 3
        monkeypatch.setenv("MXNET_AUTOTUNE_TOPK", "garbage")
        assert cm.default_top_k(8) == 2   # unparsable = unset

    def test_model_enabled_gate(self, monkeypatch):
        monkeypatch.delenv("MXNET_AUTOTUNE_MODEL", raising=False)
        assert cm.model_enabled()          # default ON (advisory)
        monkeypatch.setenv("MXNET_AUTOTUNE_MODEL", "0")
        assert not cm.model_enabled()


class TestPredictThenMeasure:
    def _space(self):
        return autotune.TuningSpace("k", {"nblk": (32, 64, 128, 256)},
                                    {"nblk": 128})

    def test_default_first_and_measurement_budget(self):
        measured = []

        def measure(cfg):
            measured.append(cfg["nblk"])
            return 1e-6 * cfg["nblk"]

        best, results, rep = autotune.predict_then_measure(
            self._space(), measure, lambda c: 1e-6 * c["nblk"], top_k=1)
        assert measured[0] == 128            # default, before any ranking
        assert measured == [128, 32]         # + only the top-1 prediction
        assert rep == {"candidates": 4, "measured": 2, "saved": 2}
        assert best == {"nblk": 32}

    def test_tie_keeps_default(self):
        best, results, rep = autotune.predict_then_measure(
            self._space(), lambda cfg: 1.0, lambda c: c["nblk"], top_k=3)
        assert best == {"nblk": 128}
        assert results[0]["config"] == {"nblk": 128}

    def test_strictly_better_candidate_wins(self):
        best, _, _ = autotune.predict_then_measure(
            self._space(),
            lambda cfg: 0.5 if cfg["nblk"] == 32 else 1.0,
            lambda c: c["nblk"], top_k=1)
        assert best == {"nblk": 32}

    def test_failed_candidate_never_wins(self):
        """A ranked candidate whose measurement comes back as the failed
        sentinel (+inf) can never displace the measured default."""
        best, results, _ = autotune.predict_then_measure(
            self._space(),
            lambda cfg: ms.FAILED_TRIAL if cfg["nblk"] != 128 else 1.0,
            lambda c: c["nblk"], top_k=2)
        assert best == {"nblk": 128}
        assert sum(1 for r in results if math.isinf(r["seconds"])) == 2

    def test_prediction_raise_ranks_last(self):
        """predict() raising for one candidate must not kill the search —
        that candidate ranks last and is simply not measured under a small
        top_k."""
        measured = []

        def predict(cfg):
            if cfg["nblk"] == 32:
                raise RuntimeError("no features for this one")
            return 1e-6 * cfg["nblk"]

        def measure(cfg):
            measured.append(cfg["nblk"])
            return 1.0

        best, _, rep = autotune.predict_then_measure(
            self._space(), measure, predict, top_k=1)
        assert 32 not in measured and rep["measured"] == 2
        assert best == {"nblk": 128}

    def test_counters_and_summary_surface(self, at_on, tel_enabled):
        autotune.predict_then_measure(
            self._space(), lambda cfg: 1e-6 * cfg["nblk"],
            lambda c: c["nblk"], top_k=1)
        assert _counter_total("autotune_predicted_trials_total",
                              kernel="k") == 4
        assert _counter_total("autotune_measured_trials_total",
                              kernel="k") == 2
        assert tin.summary()["trials_saved"] == 2


class TestStoreFormatBump:
    def test_format_is_v2(self):
        # the ISSUE 18 bump: v2 entries guarantee the trial_costs schema
        assert st._FORMAT == 2

    def test_v1_entry_is_silent_miss_and_no_training_row(self, at_on):
        autotune.record("k", "s", {"nblk": 64}, meta={"trial_costs": [
            {"config": {"nblk": 64}, "seconds": 1e-4}]})
        assert autotune.lookup("k", "s") == {"nblk": 64}
        assert len(cm.training_rows("k")) == 1
        with open(at_on) as f:
            payload = json.load(f)
        for ent in payload["entries"].values():
            ent["env"]["format"] = 1   # "restart" onto a pre-v2 store
        with open(at_on, "w") as f:
            json.dump(payload, f)
        st._reset_stats_for_tests()
        assert autotune.lookup("k", "s") is None   # rejected, not crashed
        assert autotune.stats()["errors"] == 1
        assert cm.training_rows("k") == []         # model never sees v1 rows
        # the re-search overwrites under the current format: whole again
        autotune.record("k", "s", {"nblk": 32}, meta={"trial_costs": [
            {"config": {"nblk": 32}, "seconds": 1e-4}]})
        assert autotune.lookup("k", "s") == {"nblk": 32}
        assert len(cm.training_rows("k")) == 1


# -- the widened space registry (ISSUE 18) ------------------------------------
class TestNewSpaces:
    def test_nms_lane_alignment(self):
        sp = autotune.get_space("nms_alive_pallas")
        assert not sp.admits({"tile": 100}, N=512)   # not lane-aligned
        assert sp.admits({"tile": 512}, N=512)
        assert sp.default == {"tile": 256}

    def test_nms_vmem_prunes_under_shrunk_budget(self, monkeypatch):
        sp = autotune.get_space("nms_alive_pallas")
        assert {c["tile"] for c in sp.configs(N=1024)} == {128, 256, 512,
                                                           1024}
        # a 4 MB budget rejects the 1024-tile's ~12.5 MB working set
        monkeypatch.setenv("MXNET_DCONV_VMEM_MB", "4")
        tiles = {c["tile"] for c in sp.configs(N=1024)}
        assert 1024 not in tiles and {128, 256, 512} <= tiles

    def test_quant_constraint(self):
        assert not sps._quant_constraint({"block": 0})
        # uncapped huge block blows the budget...
        assert not sps._quant_constraint({"block": 1 << 20})
        # ...but the dispatch site caps at rows, so admission judges the
        # EFFECTIVE block
        assert sps._quant_constraint({"block": 1 << 20}, rows=256)

    def test_fused_zero_pruned_off_mesh(self):
        sp = autotune.get_space("fused_step_layout")
        off = sp.configs(mesh=False)
        assert all(c["zero"] == 0 for c in off) and len(off) == 4
        on = sp.configs(mesh=True)
        assert len(on) == 8
        assert off[0] == on[0] == {"zero": 0, "prefetch": 2}  # default first


# -- new kernel dispatch wiring (ISSUE 18) ------------------------------------
class TestNewKernelWiring:
    def test_off_path_never_reads_store(self, at_off, monkeypatch):
        from mxnet_tpu.ops import pallas_kernels as pk

        monkeypatch.setattr(st, "lookup",
                            lambda *a, **k: pytest.fail("store read on the "
                                                        "off path"))
        assert pk._nms_tile(1, 512) == pk._NMS_TILE
        assert pk._quant_block("quantize_int8_pallas", 1024, 4, 1) == 512
        assert pk._quant_block(None, 100, 4, 1) == 100  # un-keyed: rows cap

    def test_nms_tile_adoption_and_revalidation(self, at_on, monkeypatch):
        from mxnet_tpu.ops import pallas_kernels as pk

        sig = autotune.nms_shape_sig(1, 1024)
        autotune.record("nms_alive_pallas", sig, {"tile": 1024})
        assert pk._nms_tile(1, 1024) == 1024
        # a shrunk budget rejects the same persisted winner at trace time
        monkeypatch.setenv("MXNET_DCONV_VMEM_MB", "4")
        assert pk._nms_tile(1, 1024) == pk._NMS_TILE
        monkeypatch.delenv("MXNET_DCONV_VMEM_MB")
        # misaligned and malformed winners keep the default
        autotune.record("nms_alive_pallas", sig, {"tile": 100})
        assert pk._nms_tile(1, 1024) == pk._NMS_TILE
        autotune.record("nms_alive_pallas", sig, {"tile": "garbage"})
        assert pk._nms_tile(1, 1024) == pk._NMS_TILE

    def test_quant_block_adoption(self, at_on):
        from mxnet_tpu.ops import pallas_kernels as pk

        sig = autotune.quant_shape_sig(1024, 4)
        autotune.record("quantize_int8_pallas", sig, {"block": 256})
        assert pk._quant_block("quantize_int8_pallas", 1024, 4, 1) == 256
        autotune.record("quantize_int8_pallas", sig, {"block": -8})
        assert pk._quant_block("quantize_int8_pallas", 1024, 4, 1) == 512

    def test_quantize_parity_across_blocks(self, at_on):
        """A tuned row block changes the grid, never the values — and the
        module-level jit wrapper's cache is cleared so each pin actually
        retraces (the CLI runner depends on the same idiom)."""
        import jax.numpy as jnp

        from mxnet_tpu.ops import pallas_kernels as pk

        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(16, 128).astype(np.float32))
        got = {}
        for blk in (2, 16):
            with autotune.override("quantize_int8_pallas", {"block": blk}):
                pk.quantize_int8_pallas.clear_cache()
                q = np.asarray(pk.quantize_int8_pallas(x, 4.0,
                                                       interpret=True))
            with autotune.override("dequantize_int8_pallas",
                                   {"block": blk}):
                pk.dequantize_int8_pallas.clear_cache()
                d = np.asarray(pk.dequantize_int8_pallas(
                    jnp.asarray(q), 4.0, interpret=True))
            got[blk] = (q, d)
        pk.quantize_int8_pallas.clear_cache()
        pk.dequantize_int8_pallas.clear_cache()
        np.testing.assert_array_equal(got[2][0], got[16][0])
        np.testing.assert_allclose(got[2][1], got[16][1], rtol=0, atol=0)

    def test_failed_trial_sentinel(self, at_on, tel_enabled):
        """A candidate whose build raises is a FAILED trial, not a search
        abort: +inf sentinel, its own counter, no timing counted, and its
        cost features scrubbed so the model never trains on it."""
        def bad_build():
            raise RuntimeError("mosaic said no")

        before = autotune.measurements()
        t = autotune.measure_candidate("k", {"nblk": 1}, bad_build, (),
                                       warmup=0, repeat=1)
        assert t == ms.FAILED_TRIAL and math.isinf(t)
        assert autotune.measurements() == before     # not a counted timing
        assert ms.failed_measurements() == 1
        assert _counter_total("autotune_failed_trials_total",
                              kernel="k") == 1
        assert ms.features_for("k", {"nblk": 1}) is None


# -- serving bucket stats (ISSUE 9 satellite) ---------------------------------
class TestBucketStats:
    def test_stats_expose_per_bucket_waste_and_hits(self):
        from mxnet_tpu.serving import BucketLadder, Engine
        from mxnet_tpu.test_utils import tiny_mlp_checkpoint

        sym, params = tiny_mlp_checkpoint()
        eng = Engine(sym, params, {"data": (8,)},
                     ladder=BucketLadder((1, 4)), start=True)
        try:
            eng.predict({"data": np.zeros((3, 8), np.float32)})
            eng.predict({"data": np.zeros((4, 8), np.float32)})
            eng.predict({"data": np.zeros((1, 8), np.float32)})
            s = eng.stats()
            bs = s["bucket_stats"]
            b4 = bs["b4[data=8]"]
            b1 = bs["b1[data=8]"]
            assert b1["batches"] == b1["requests"] == 1
            assert b1["padding_waste"] == 0.0
            assert b4["batches"] == 2 and b4["requests"] == 2
            # the n=3 batch wasted 1/4 of its rows, the n=4 none → mean 1/8
            assert b4["padding_waste"] == pytest.approx(0.125, abs=1e-4)
            # back-compat: "buckets" still maps label -> batch count
            assert s["buckets"] == {"b4[data=8]": 2, "b1[data=8]": 1}
        finally:
            eng.close()
