"""Pod observability plane (ISSUE 19, telemetry/podplane.py).

Coverage demanded by the issue's merge-semantics satellite plus the
tentpole contracts:
- the off path: ``MXNET_POD_METRICS`` unset ⇒ no plane, no thread, no
  socket, registry and ops_server untouched, ``/podz`` still routable;
- histogram sub-bucket merge is exact: associative, order-independent,
  and equal to observing the union (the slo.py encoding's point);
- rank-labeled counter collisions are SUMMED in the fleet rollup, never
  clobbered; pushed series mirror under ``pod_``-prefixed rank-labeled
  gauges without colliding with rank 0's local series;
- a stale snapshot (rank restart with an older incarnation epoch, or an
  out-of-order seq) is dropped with a counter;
- ledger divergence fires per key on flops/bytes mismatch (compile_s is
  skew, not divergence), with a flight-recorder dump naming key+ranks;
- straggler verdicts are edge-triggered with hysteresis;
- incidents mint once per (rank, reason) window and broadcast over the
  push channel, tagging a dump on the pushing rank;
- the fit loop feeds ``note_step`` when the gate is on.
"""
import glob
import json
import os
import socket
import threading
import time

import numpy as np
import pytest

from mxnet_tpu.telemetry import flightrec, ops_server, podplane
from mxnet_tpu.telemetry import instrument as tin
from mxnet_tpu.telemetry.registry import MetricError
from mxnet_tpu.telemetry.slo import NBUCKETS, WindowedQuantile, \
    quantile_of_counts


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _snap(rank, epoch=100.0, seq=1, steps=0, hist=None, metrics=(),
          ledger=None, **kw):
    base = {"v": 1, "rank": rank, "size": 2, "epoch": epoch, "seq": seq,
            "unix_ts": time.time(), "steps": steps,
            "step_hist": list(hist) if hist is not None
            else [0] * (NBUCKETS + 2),
            "metrics": list(metrics), "healthz": None,
            "heartbeat_age_s": None, "flightrec": False,
            "ledger": dict(ledger or {}), "slo_breaches": 0, "nonfinite": 0}
    base.update(kw)
    return base


@pytest.fixture
def pod_off(monkeypatch):
    for var in ("MXNET_POD_METRICS", "MXNET_POD_METRICS_ADDR",
                "MXNET_POD_PUSH_S", "MXNET_COORDINATOR"):
        monkeypatch.delenv(var, raising=False)
    podplane._reset_for_tests()
    yield
    podplane._reset_for_tests()


@pytest.fixture
def pod_on(monkeypatch, tmp_path):
    """Gate on, instant pushes, a real loopback channel, frec armed."""
    port = _free_port()
    monkeypatch.setenv("MXNET_POD_METRICS", "1")
    monkeypatch.setenv("MXNET_POD_METRICS_ADDR", "127.0.0.1:%d" % port)
    monkeypatch.setenv("MXNET_POD_PUSH_S", "0")
    monkeypatch.setenv("MXNET_FLIGHTREC_DIR", str(tmp_path / "frec"))
    podplane._reset_for_tests()
    flightrec._reset_for_tests()
    yield ("127.0.0.1", port), tmp_path
    podplane._reset_for_tests()
    flightrec._reset_for_tests()


# -- off path -----------------------------------------------------------------
class TestOffPath:
    def test_no_plane_no_thread_no_socket(self, pod_off):
        before = {t.name for t in threading.enumerate()}
        assert podplane.plane() is None
        assert podplane.plane() is None  # stable, never lazily flips on
        assert podplane.status() is None
        assert podplane.podz() == {"enabled": False}
        after = {t.name for t in threading.enumerate()}
        assert before == after  # zero new threads (ergo zero listeners)

    def test_registry_untouched(self, pod_off, monkeypatch, tmp_path):
        """Telemetry ON but the pod gate OFF: exercising the module-level
        surfaces adds nothing to the registry — the pod plane is invisible
        to /metrics until explicitly enabled."""
        monkeypatch.setenv("MXNET_TELEMETRY", "1")
        monkeypatch.setenv("MXNET_TELEMETRY_FILE", str(tmp_path / "t.jsonl"))
        tin._reset_for_tests()
        try:
            before = json.dumps(tin.registry().collect(), default=str)
            assert podplane.plane() is None
            podplane.podz()
            podplane.status()
            after = json.dumps(tin.registry().collect(), default=str)
            assert before == after
        finally:
            tin._reset_for_tests()

    def test_fit_loop_off_path(self, pod_off, monkeypatch):
        """The base_module wiring resolves to None and the loop never
        calls note_step — same `is None` contract as trainhealth."""
        import mxnet_tpu as mx
        from mxnet_tpu import module as mod_mod
        from mxnet_tpu.io import NDArrayIter

        calls = []
        monkeypatch.setattr(podplane.PodPlane, "note_step",
                            lambda self, s: calls.append(s))
        data = mx.sym.var("data")
        sym = mx.sym.SoftmaxOutput(
            mx.sym.FullyConnected(data, num_hidden=4), name="softmax")
        mod = mod_mod.Module(sym)
        rng = np.random.RandomState(0)
        it = NDArrayIter(rng.randn(8, 8).astype(np.float32),
                         rng.randint(0, 4, (8,)).astype(np.float32),
                         batch_size=8)
        mod.fit(it, num_epoch=1,
                optimizer_params={"learning_rate": 0.1})
        assert calls == []

    def test_podz_endpoint_reports_disabled(self, pod_off, monkeypatch):
        monkeypatch.setenv("MXNET_OPS_PORT", "0")
        ops_server.stop()
        try:
            port = ops_server.maybe_start()
            import urllib.request

            with urllib.request.urlopen(
                    "http://127.0.0.1:%d/podz" % port, timeout=5) as r:
                assert json.loads(r.read()) == {"enabled": False}
        finally:
            ops_server.stop()


# -- mergeable histogram semantics --------------------------------------------
class TestHistogramMerge:
    def _counts(self, samples):
        wq = WindowedQuantile(window_s=3600.0)
        for v in samples:
            wq.observe(v, now=0.0)
        return wq._merged(0.0)

    def _vadd(self, a, b):
        return [x + y for x, y in zip(a, b)]

    def test_merge_exact_associative_order_independent(self):
        rng = np.random.RandomState(7)
        parts = [rng.lognormal(-3, 1, 500), rng.lognormal(-2, 0.5, 300),
                 rng.lognormal(-4, 2, 700)]
        vecs = [self._counts(p) for p in parts]
        union = self._counts(np.concatenate(parts))
        ab_c = self._vadd(self._vadd(vecs[0], vecs[1]), vecs[2])
        a_bc = self._vadd(vecs[0], self._vadd(vecs[1], vecs[2]))
        cba = self._vadd(self._vadd(vecs[2], vecs[1]), vecs[0])
        # associativity and commutativity are EXACT (integer vector adds)
        assert ab_c == a_bc == cba
        # and merging vectors == observing the union: same counts, so the
        # merged quantile is identical, not merely approximate
        assert ab_c == union
        for q in (0.5, 0.95, 0.99):
            assert quantile_of_counts(ab_c, q) \
                == quantile_of_counts(union, q)

    def test_aggregator_merged_counts_sum_ranks(self):
        agg = podplane.Aggregator(size=3)
        vecs = []
        rng = np.random.RandomState(3)
        for rank in range(3):
            v = self._counts(rng.lognormal(-3, 1, 200))
            vecs.append(v)
            agg.ingest(_snap(rank, hist=v, steps=10), now=0.0)
        want = self._vadd(self._vadd(vecs[0], vecs[1]), vecs[2])
        assert agg.merged_step_counts() == want


# -- rollup + mirror semantics ------------------------------------------------
class TestRollupAndMirror:
    def test_counter_collisions_summed_not_clobbered(self, pod_off):
        agg = podplane.Aggregator(size=2)
        m = [["serve_requests_total", "counter", {"engine": "e"}, 5.0],
             ["hbm_bytes", "gauge", {"dev": "0"}, 100.0]]
        agg.ingest(_snap(0, metrics=m, steps=1), now=0.0)
        m2 = [["serve_requests_total", "counter", {"engine": "e"}, 7.0],
              ["hbm_bytes", "gauge", {"dev": "0"}, 300.0]]
        agg.ingest(_snap(1, metrics=m2, steps=1), now=0.0)
        roll = agg.fleet_rollup()
        assert roll["counters"]["serve_requests_total{engine=e}"] == 12.0
        g = roll["gauges"]["hbm_bytes{dev=0}"]
        assert (g["min"], g["max"], g["mean"]) == (100.0, 300.0, 200.0)

    def test_mirror_rank_labeled_no_collision(self, pod_off, monkeypatch,
                                              tmp_path):
        """Rank 0 already owns a rank-LESS `steps_total`; the pushed copy
        lands under `pod_steps_total{rank=N}` — same registry, no
        MetricError, both readable."""
        monkeypatch.setenv("MXNET_TELEMETRY", "1")
        monkeypatch.setenv("MXNET_TELEMETRY_FILE", str(tmp_path / "t.jsonl"))
        tin._reset_for_tests()
        try:
            local = tin.registry().counter("steps_total", "local", ())
            local.inc(3)
            agg = podplane.Aggregator(size=2)
            agg.ingest(_snap(1, metrics=[
                ["steps_total", "counter", {}, 9.0]], steps=1), now=0.0)
            agg.ingest(_snap(0, metrics=[
                ["steps_total", "counter", {}, 3.0]], seq=1, steps=1),
                now=0.0)
            assert tin.registry().counter("steps_total", "", ()).value() \
                == 3.0
            mirrored = tin.registry().get("pod_steps_total")
            vals = {s["labels"]["rank"]: s["value"]
                    for s in mirrored.samples()}
            assert vals == {"1": 9.0, "0": 3.0}
        finally:
            tin._reset_for_tests()


# -- stale-snapshot semantics -------------------------------------------------
class TestStaleDrop:
    def test_out_of_order_seq_dropped(self, pod_off):
        agg = podplane.Aggregator(size=2)
        assert agg.ingest(_snap(1, seq=2, steps=20), now=0.0)["ok"]
        v = agg.ingest(_snap(1, seq=1, steps=10), now=0.0)
        assert v == {"ok": False, "reason": "stale"}
        assert agg.stale_dropped == 1
        assert agg.podz(now=0.0)["ranks"]["1"]["steps"] == 20

    def test_restart_supersedes_old_incarnation(self, pod_off):
        agg = podplane.Aggregator(size=2)
        agg.ingest(_snap(1, epoch=100.0, seq=50, steps=500), now=0.0)
        # the restarted rank begins a NEW incarnation at seq 1: accepted
        assert agg.ingest(_snap(1, epoch=200.0, seq=1, steps=3),
                          now=0.0)["ok"]
        assert agg.podz(now=0.0)["ranks"]["1"]["steps"] == 3
        # ...and a straggler push from the DEAD incarnation arriving late
        # is dropped, not merged back
        v = agg.ingest(_snap(1, epoch=100.0, seq=51, steps=501), now=0.0)
        assert v["reason"] == "stale"
        assert agg.stale_dropped == 1
        assert agg.podz(now=0.0)["ranks"]["1"]["steps"] == 3

    def test_stale_counter_on_registry(self, pod_off, monkeypatch,
                                       tmp_path):
        monkeypatch.setenv("MXNET_TELEMETRY", "1")
        monkeypatch.setenv("MXNET_TELEMETRY_FILE", str(tmp_path / "t.jsonl"))
        tin._reset_for_tests()
        try:
            agg = podplane.Aggregator(size=2)
            agg.ingest(_snap(1, seq=2), now=0.0)
            agg.ingest(_snap(1, seq=2), now=0.0)
            assert tin.registry().total("pod_snapshots_stale_total") == 1.0
        finally:
            tin._reset_for_tests()


# -- ledger divergence --------------------------------------------------------
class TestLedgerDivergence:
    def test_divergence_fires_once_per_key_with_dump(self, pod_off,
                                                     monkeypatch,
                                                     tmp_path):
        monkeypatch.setenv("MXNET_FLIGHTREC_DIR", str(tmp_path / "frec"))
        flightrec._reset_for_tests()
        try:
            flightrec.record("warm", x=1)  # a non-empty ring can dump
            agg = podplane.Aggregator(size=2)
            agg.ingest(_snap(0, ledger={"k1": [100, 4096, 0.2],
                                        "same": [1, 1, 0.1]}), now=0.0)
            assert agg.divergences == 0
            agg.ingest(_snap(1, ledger={"k1": [999, 4096, 0.3],
                                        "same": [1, 1, 0.9]}), now=0.0)
            assert agg.divergences == 1  # k1 only; "same" differs solely
            # in compile_s, which is skew, not divergence
            pz = agg.podz(now=0.0)
            assert set(pz["ledger_divergences"]) == {"k1"}
            d = pz["ledger_divergences"]["k1"]
            assert d["ranks"] == [0, 1]
            assert d["fingerprints"]["0"][:2] == [100, 4096]
            assert d["fingerprints"]["1"][:2] == [999, 4096]
            # compile_s spread for the non-diverged key shows up as skew
            assert pz["skew"]["compile_s"]["same"] == pytest.approx(0.8)
            # the dump names the key and both ranks
            (dump,) = glob.glob(str(tmp_path / "frec" /
                                    "*pod_ledger_divergence*.json"))
            meta = json.load(open(dump))["flightrec"]
            assert meta["key"] == "k1" and meta["ranks"] == [0, 1]
            # repeated ingests never re-fire the same key
            agg.ingest(_snap(1, seq=2, ledger={"k1": [999, 4096, 0.3]}),
                       now=0.0)
            assert agg.divergences == 1
            # ...and a divergence is ALSO an incident (the broadcast is
            # how the non-aggregating rank learns to dump)
            assert [i["reason"] for i in agg.incidents()] \
                == ["ledger_divergence"]
        finally:
            flightrec._reset_for_tests()


# -- straggler verdicts -------------------------------------------------------
class TestStragglerVerdicts:
    def test_edge_triggered_with_hysteresis(self, pod_off, monkeypatch):
        monkeypatch.setenv("MXNET_POD_STRAGGLER_LAG", "10")
        monkeypatch.setenv("MXNET_POD_STRAGGLER_AGE_S", "1000")
        agg = podplane.Aggregator(size=2)
        agg.ingest(_snap(0, seq=1, steps=100), now=0.0)
        agg.ingest(_snap(1, seq=1, steps=95), now=0.0)   # lag 5: fine
        assert agg.straggler_verdicts == 0
        agg.ingest(_snap(1, seq=2, steps=96), now=0.0)
        agg.ingest(_snap(0, seq=2, steps=120), now=0.0)  # lag 24: verdict
        assert agg.straggler_verdicts == 1
        assert agg.podz(now=0.0)["ranks"]["1"]["straggler"] is True
        # STILL behind: edge-triggered, no second verdict
        agg.ingest(_snap(0, seq=3, steps=130), now=0.0)
        assert agg.straggler_verdicts == 1
        # recovers to lag 8 — above lag/2=5, hysteresis holds the verdict
        agg.ingest(_snap(1, seq=3, steps=122), now=0.0)
        assert agg.straggler_verdicts == 1
        assert agg.podz(now=0.0)["ranks"]["1"]["straggler"] is True
        # recovers below half the threshold: one recovery edge
        agg.ingest(_snap(1, seq=4, steps=127), now=0.0)
        assert agg.straggler_verdicts == 2
        assert agg.podz(now=0.0)["ranks"]["1"]["straggler"] is False

    def test_push_age_straggler_and_death_incident(self, pod_off,
                                                   monkeypatch):
        monkeypatch.setenv("MXNET_POD_STRAGGLER_AGE_S", "10")
        agg = podplane.Aggregator(size=2)
        agg.ingest(_snap(0, steps=5), now=0.0)
        agg.ingest(_snap(1, steps=5), now=0.0)
        assert agg.podz(now=5.0)["ranks"]["1"]["straggler"] is False
        # rank 1 stops pushing; rank 0 keeps going
        agg.ingest(_snap(0, seq=2, steps=6), now=11.0)
        pz = agg.podz(now=12.0)
        assert pz["ranks"]["1"]["straggler"] is True
        assert pz["ranks"]["1"]["dead"] is False
        assert not any(i["reason"] == "rank_death" for i in pz["incidents"])
        # past 3x the age threshold: presumed dead, incident minted
        pz = agg.podz(now=31.0)
        assert pz["ranks"]["1"]["dead"] is True
        deaths = [i for i in pz["incidents"] if i["reason"] == "rank_death"]
        assert len(deaths) == 1 and deaths[0]["rank"] == 1


# -- incidents ----------------------------------------------------------------
class TestIncidents:
    def test_mint_throttled_per_rank_reason(self, pod_off):
        agg = podplane.Aggregator(size=2)
        assert agg.mint_incident("slo_breach", 1, now=0.0) is not None
        assert agg.mint_incident("slo_breach", 1, now=1.0) is None
        assert agg.mint_incident("slo_breach", 0, now=1.0) is not None
        assert agg.mint_incident("nonfinite", 1, now=1.0) is not None
        assert agg.mint_incident("slo_breach", 1, now=40.0) is not None
        assert len(agg.incidents()) == 4

    def test_slo_and_nonfinite_edges_mint(self, pod_off):
        agg = podplane.Aggregator(size=2)
        agg.ingest(_snap(1, seq=1, slo_breaches=2, nonfinite=0), now=0.0)
        assert agg.incidents() == []  # no baseline = no edge
        agg.ingest(_snap(1, seq=2, slo_breaches=2, nonfinite=0), now=1.0)
        assert agg.incidents() == []  # unchanged = no edge
        agg.ingest(_snap(1, seq=3, slo_breaches=3, nonfinite=1), now=2.0)
        assert sorted(i["reason"] for i in agg.incidents()) \
            == ["nonfinite", "slo_breach"]

    def test_broadcast_tags_dump_on_pushing_rank(self, pod_on):
        """The correlation contract end-to-end over a real socket: rank 0
        mints, the id rides the push response, rank 1 writes a dump
        carrying the shared id."""
        addr, tmp_path = pod_on
        r1_dir = tmp_path / "frec_r1"
        p0 = podplane.PodPlane(rank=0, size=2, addr=addr)
        p1 = podplane.PodPlane(rank=1, size=2, addr=addr)
        try:
            inc = p0.aggregator.mint_incident("slo_breach", 0, breaches=3)
            os.environ["MXNET_FLIGHTREC_DIR"] = str(r1_dir)
            flightrec._reset_for_tests()
            flightrec.record("warm", x=1)
            p1.note_step(0.01)  # push -> response carries the incident
            deadline = time.monotonic() + 10.0
            dumps = []

            def rank1_dumps():
                # both planes live in this process and share its recorder:
                # rank 0's detector thread dumps its own observation of the
                # incident into the same directory (observer_rank 0)
                metas = [json.load(open(f))["flightrec"] for f in
                         glob.glob(str(r1_dir / "*pod_incident*.json"))]
                return [m for m in metas if m["observer_rank"] == 1]

            while time.monotonic() < deadline and not dumps:
                dumps = rank1_dumps()
                time.sleep(0.05)
            assert dumps, "rank 1 never dumped the broadcast incident"
            meta = dumps[0]
            assert meta["incident"] == inc["id"]
            assert meta["why"] == "slo_breach"
            assert p1.push_stats()["incidents_seen"] == 1
            # the same id never re-dumps
            p1.note_step(0.01)
            time.sleep(0.2)
            assert len(rank1_dumps()) == 1
        finally:
            p0.close()
            p1.close()


# -- live plane over the socket -----------------------------------------------
class TestLivePlane:
    def test_two_rank_aggregation_and_podz(self, pod_on):
        addr, _ = pod_on
        p0 = podplane.PodPlane(rank=0, size=2, addr=addr)
        p1 = podplane.PodPlane(rank=1, size=2, addr=addr)
        try:
            p0.seed_ledger("site#fwd", flops=100, bytes_accessed=64)
            p1.seed_ledger("site#fwd", flops=999, bytes_accessed=64)
            for _ in range(3):
                p0.note_step(0.002)
                p1.note_step(0.004)
            deadline = time.monotonic() + 10.0
            pz = p0.podz()
            while time.monotonic() < deadline \
                    and pz["ranks_reporting"] < 2:
                time.sleep(0.05)
                pz = p0.podz()
            assert pz["ranks_reporting"] == 2
            assert pz["ranks"]["0"]["steps"] == 3
            assert pz["ranks"]["1"]["steps"] == 3
            assert pz["ranks"]["1"]["step_p50_ms"] is not None
            assert pz["ledger_divergence_count"] == 1
            assert pz["fleet"]["max_step_lag"] == 0
            assert p1.push_stats()["push_failures"] == 0
        finally:
            p0.close()
            p1.close()

    def test_push_failure_degrades_never_raises(self, pod_on):
        """No listener at the address: every push counts a failure and
        note_step still returns — the step path never blocks or throws."""
        p1 = podplane.PodPlane(rank=1, size=2,
                               addr=("127.0.0.1", _free_port()))
        try:
            for _ in range(3):
                p1.note_step(0.001)
            st = p1.push_stats()
            assert st["push_failures"] == 3 and st["steps"] == 3
            assert st["connected"] is False
        finally:
            p1.close()

    def test_fit_loop_feeds_note_step(self, pod_on, monkeypatch):
        """base_module wiring: gate on ⇒ one note_step per batch."""
        import mxnet_tpu as mx
        from mxnet_tpu import module as mod_mod
        from mxnet_tpu.io import NDArrayIter

        calls = []
        monkeypatch.setattr(podplane.PodPlane, "note_step",
                            lambda self, s: calls.append(s))
        data = mx.sym.var("data")
        sym = mx.sym.SoftmaxOutput(
            mx.sym.FullyConnected(data, num_hidden=4), name="softmax")
        mod = mod_mod.Module(sym)
        rng = np.random.RandomState(0)
        it = NDArrayIter(rng.randn(16, 8).astype(np.float32),
                         rng.randint(0, 4, (16,)).astype(np.float32),
                         batch_size=8)
        mod.fit(it, num_epoch=1,
                optimizer_params={"learning_rate": 0.1})
        assert len(calls) == 2 and all(s > 0 for s in calls)


# -- CLI rendering ------------------------------------------------------------
class TestPodStatusCli:
    def _tool(self):
        import importlib.util
        import sys as _sys

        tools = os.path.join(os.path.dirname(__file__), "..", "tools")
        _sys.path.insert(0, os.path.abspath(tools))
        try:
            import pod_status
        finally:
            _sys.path.pop(0)
        return pod_status

    def test_render_tables(self, pod_off):
        pod_status = self._tool()
        agg = podplane.Aggregator(size=2)
        agg.ingest(_snap(0, steps=10), now=0.0)
        agg.ingest(_snap(1, steps=8, ledger={"k": [1, 2, 0.1]}), now=0.0)
        text = pod_status.render_podz(agg.podz(now=0.0))
        assert "pod aggregator: 2/2 ranks reporting" in text
        assert "max_lag=2" in text
        assert pod_status.render_podz({"enabled": False}) \
            == "pod plane disabled (MXNET_POD_METRICS unset)"

    def test_collect_groups_by_incident(self, pod_on, tmp_path, capsys):
        pod_status = self._tool()
        addr, base = pod_on
        p0 = podplane.PodPlane(rank=0, size=2, addr=addr)
        p1 = podplane.PodPlane(rank=1, size=2, addr=addr)
        try:
            flightrec.record("warm", x=1)
            inc = p0.aggregator.mint_incident("nonfinite", 1, trips=1)
            p0.tick()   # rank 0 observes + dumps its own incident
            r1_dir = base / "frec_r1"
            os.environ["MXNET_FLIGHTREC_DIR"] = str(r1_dir)
            flightrec._reset_for_tests()
            flightrec.record("warm", x=1)
            p1.note_step(0.01)
            deadline = time.monotonic() + 10.0
            while time.monotonic() < deadline and not glob.glob(
                    str(r1_dir / "*pod_incident*")):
                time.sleep(0.05)
            out = tmp_path / "merged"
            rc = pod_status.collect([str(base / "frec"), str(r1_dir)],
                                    str(out))
            assert rc == 0
            (merged,) = glob.glob(str(out / "*.json"))
            assert inc["id"] in os.path.basename(merged)
            evs = json.load(open(merged))["traceEvents"]
            # both ranks' dumps landed on ONE timeline, every event
            # rank-labeled (the observer_rank metadata became explicit
            # --rank flags, force-stamped into event args)
            ranks = {e.get("args", {}).get("rank") for e in evs
                     if e.get("ph") != "M"}
            assert {0, 1} <= ranks
        finally:
            p0.close()
            p1.close()
