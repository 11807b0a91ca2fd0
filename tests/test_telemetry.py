"""Telemetry subsystem (ISSUE 1): registry types/labels, sink round-trips,
the MXNET_TELEMETRY=0 no-op guarantee, memory-gauge CPU fallback, profiler
satellites (metadata drop, Counter thread safety), custom-call cost
registry, the trace_summary CLI golden output, and the bench schema lint."""
import json
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.telemetry import (Histogram, JsonlSink, MetricError,
                                 PrometheusSink, ProfilerSink, Registry,
                                 render_prometheus)
from mxnet_tpu.telemetry import instrument as tin

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


@pytest.fixture
def tel_enabled(monkeypatch, tmp_path):
    """Fresh global registry with telemetry ON, JSONL in tmp."""
    monkeypatch.setenv("MXNET_TELEMETRY", "1")
    monkeypatch.setenv("MXNET_TELEMETRY_FILE", str(tmp_path / "t.jsonl"))
    tin._reset_for_tests()
    yield tmp_path / "t.jsonl"
    tin._reset_for_tests()


@pytest.fixture
def tel_disabled(monkeypatch):
    monkeypatch.delenv("MXNET_TELEMETRY", raising=False)
    tin._reset_for_tests()
    yield
    tin._reset_for_tests()


# -- registry types / labels -------------------------------------------------
class TestRegistry:
    def test_counter_labels_and_totals(self):
        r = Registry()
        c = r.counter("steps_total", "steps", ("fn",))
        c.inc(fn="a")
        c.inc(2.5, fn="a")
        c.inc(fn="b")
        assert c.value(fn="a") == 3.5
        assert r.total("steps_total") == 4.5
        assert r.counter("steps_total", labelnames=("fn",)) is c  # idempotent

    def test_counter_misuse(self):
        r = Registry()
        c = r.counter("c", labelnames=("fn",))
        with pytest.raises(MetricError):
            c.inc(-1, fn="a")          # counters are monotonic
        with pytest.raises(MetricError):
            c.inc(1)                   # missing label
        with pytest.raises(MetricError):
            c.inc(1, fn="a", extra="x")  # unknown label
        with pytest.raises(MetricError):
            r.gauge("c")               # type conflict on the same name
        with pytest.raises(MetricError):
            r.counter("c", labelnames=("other",))  # label-set conflict

    def test_gauge(self):
        r = Registry()
        g = r.gauge("hbm", labelnames=("device",))
        g.set(100, device="tpu:0")
        g.inc(5, device="tpu:0")
        g.dec(1, device="tpu:0")
        g.set(7, device="tpu:1")
        assert g.value(device="tpu:0") == 104
        assert r.max_value("hbm") == 104

    def test_histogram_buckets(self):
        r = Registry()
        h = r.histogram("lat", buckets=(0.1, 1.0, 10.0))
        for v in (0.05, 0.5, 0.5, 5.0, 50.0):
            h.observe(v)
        (s,) = h.samples()
        assert s["count"] == 5 and s["sum"] == pytest.approx(56.05)
        assert s["buckets"] == [[0.1, 1], [1.0, 3], [10.0, 4], ["+Inf", 5]]
        assert r.hist_sum("lat") == pytest.approx(56.05)

    def test_histogram_bucket_conflict(self):
        r = Registry()
        r.histogram("h", buckets=(1.0, 2.0))
        r.histogram("h")                       # no buckets requested: ok
        r.histogram("h", buckets=(2.0, 1.0))   # same set, order-insensitive
        with pytest.raises(MetricError):
            r.histogram("h", buckets=(0.5,))

    def test_counter_thread_safety(self):
        r = Registry()
        c = r.counter("n")
        threads = [threading.Thread(
            target=lambda: [c.inc() for _ in range(1000)]) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert c.value() == 8000


# -- profiler satellites -----------------------------------------------------
class TestProfilerSatellites:
    def test_counter_increment_thread_safe(self):
        from mxnet_tpu import profiler

        ctr = profiler.Counter(None, "hammer")
        threads = [threading.Thread(
            target=lambda: [ctr.increment() for _ in range(1000)])
            for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert ctr._value.get() == 8000

    def test_domain_metadata_survives_late_start(self, tmp_path):
        """A Domain created BEFORE set_state('run') must still name its pid
        in the dump (satellite: unconditional metadata recording)."""
        from mxnet_tpu import profiler

        dom = profiler.Domain("early_domain")  # profiler NOT running yet
        fname = str(tmp_path / "p.json")
        profiler.set_config(filename=fname)
        profiler.set_state("run")
        with dom.new_task("work"):
            pass
        profiler.set_state("stop")
        profiler.dump()
        evs = json.load(open(fname))["traceEvents"]
        metas = [e for e in evs if e.get("ph") == "M"
                 and e.get("name") == "process_name"
                 and e.get("args", {}).get("name") == "early_domain"]
        assert metas and metas[0]["pid"] == dom.pid
        assert any(e.get("name") == "work" for e in evs)


# -- sinks -------------------------------------------------------------------
class TestSinks:
    def test_jsonl_round_trip(self, tmp_path):
        path = str(tmp_path / "ev.jsonl")
        r = Registry()
        r.add_sink(JsonlSink(path))
        r.counter("c", labelnames=("k",)).inc(3, k="x")
        r.event("compile", fn="step", seconds=1.5)
        r.flush()
        lines = [json.loads(l) for l in open(path)]
        assert lines[0]["kind"] == "compile" and lines[0]["seconds"] == 1.5
        snap = lines[1]
        assert snap["kind"] == "metrics"
        (c,) = [m for m in snap["metrics"] if m["name"] == "c"]
        assert c["samples"] == [{"labels": {"k": "x"}, "value": 3.0}]

    def test_prometheus_render_and_file(self, tmp_path):
        r = Registry()
        r.counter("req_total", "requests", ("code",)).inc(4, code="200")
        r.gauge("temp").set(1.5)
        h = Histogram("lat", buckets=(1.0,))
        h.observe(0.5)
        text = render_prometheus(r.collect() + [h.snapshot()])
        assert '# TYPE req_total counter' in text
        assert 'req_total{code="200"} 4.0' in text
        assert "temp 1.5" in text
        assert 'lat_bucket{le="1.0"} 1' in text
        assert 'lat_bucket{le="+Inf"} 1' in text
        assert "lat_count 1" in text
        path = str(tmp_path / "metrics.prom")
        r.add_sink(PrometheusSink(path))
        r.flush()
        assert 'req_total{code="200"} 4.0' in open(path).read()

    def test_prometheus_label_escaping(self):
        """Exposition-format label values escape backslash, double-quote and
        line feed — in that order, so nothing double-escapes (ISSUE 4
        satellite: quotes/backslashes/newlines in label values)."""
        r = Registry()
        c = r.counter("esc_total", "", ("path",))
        c.inc(1, path='C:\\dir "quoted"\nnext')
        text = render_prometheus(r.collect())
        assert ('esc_total{path="C:\\\\dir \\"quoted\\"\\nnext"} 1.0'
                in text)
        # and the escaped line stays one physical line
        (line,) = [l for l in text.splitlines() if l.startswith("esc_total{")]
        assert "\n" not in line

    def test_prometheus_help_escaping(self):
        """HELP text escapes only backslash and line feed; quotes pass
        through verbatim (the old shared escaper emitted an undefined \\"
        sequence there — the escaping fix this test demanded)."""
        r = Registry()
        r.counter('q_total', 'says "hi" with \\ and\nnewline')
        text = render_prometheus(r.collect())
        assert ('# HELP q_total says "hi" with \\\\ and\\nnewline'
                in text)

    def test_jsonl_unwritable_path_never_raises(self, tmp_path):
        """A bad MXNET_TELEMETRY_FILE must not kill the training step: the
        sink warns once and disables itself."""
        blocker = tmp_path / "f"
        blocker.write_text("")  # a FILE where a directory is needed
        r = Registry()
        r.add_sink(JsonlSink(str(blocker / "sub" / "t.jsonl")))
        r.event("compile", fn="s", seconds=1.0)  # swallowed, no raise
        r.counter("c").inc()
        r.flush()

    def test_profiler_bridge_sink(self, tmp_path):
        from mxnet_tpu import profiler

        fname = str(tmp_path / "p.json")
        profiler.set_config(filename=fname)
        profiler.set_state("run")
        r = Registry()
        r.add_sink(ProfilerSink())
        r.counter("tel_c", labelnames=("fn",)).inc(5, fn="a")
        r.flush()
        profiler.set_state("stop")
        evs = json.loads(profiler.dumps(reset=True))["traceEvents"]
        samples = [e for e in evs if e.get("ph") == "C"
                   and e.get("name") == "tel_c{fn=a}"]
        assert samples and samples[-1]["args"]["tel_c{fn=a}"] == 5.0


# -- gating / no-op guarantee ------------------------------------------------
class TestGating:
    def test_noop_guard_helpers(self, tel_disabled):
        import jax

        assert not tin.enabled()
        f = jax.jit(lambda x: x + 1)
        assert tin.instrument_step(f) is f        # step object unchanged
        assert tin.step_probe("fit") is None
        assert tin.summary() is None
        assert tin.event("x") is None
        assert tin.sample_memory() == {}

    def test_noop_guard_make_train_step(self, tel_disabled):
        """With MXNET_TELEMETRY unset the mesh-jitted train step is returned
        unwrapped (acceptance criterion: step object and timings unchanged)."""
        import jax

        from mxnet_tpu import gluon, parallel
        from mxnet_tpu.gluon.functional import make_train_step

        net = gluon.nn.Sequential()
        net.add(gluon.nn.Dense(4))
        net.initialize()
        net(mx.nd.zeros((2, 8)))
        mesh = parallel.make_mesh({"dp": len(jax.devices())})
        step, _state, _meta = make_train_step(
            net, gluon.loss.SoftmaxCrossEntropyLoss(), mesh=mesh)
        assert not hasattr(step, "_telemetry_instrumented")  # no wrapper
        assert hasattr(step, "lower")             # still the raw jitted fn

    def test_no_jsonl_written_when_disabled(self, tel_disabled, tmp_path,
                                            monkeypatch):
        monkeypatch.setenv("MXNET_TELEMETRY_FILE", str(tmp_path / "no.jsonl"))
        tin.registry().counter("c").inc()
        assert not (tmp_path / "no.jsonl").exists()

    def test_late_enable_attaches_jsonl_sink(self, monkeypatch, tmp_path):
        """A registry first touched while disabled must still gain the JSONL
        sink when MXNET_TELEMETRY is enabled later in-process."""
        monkeypatch.delenv("MXNET_TELEMETRY", raising=False)
        monkeypatch.setenv("MXNET_TELEMETRY_FILE", str(tmp_path / "l.jsonl"))
        tin._reset_for_tests()
        try:
            tin.registry().counter("early").inc()   # disabled: no sink
            assert not tin.registry().sinks()
            monkeypatch.setenv("MXNET_TELEMETRY", "1")
            tin.event("compile", fn="late", seconds=1.0)
            tin.flush()
            kinds = [json.loads(l)["kind"] for l in open(tmp_path / "l.jsonl")]
            assert kinds == ["compile", "metrics"]
        finally:
            tin._reset_for_tests()

    def test_instrument_step_counts_compiles(self, tel_enabled):
        import jax

        f = tin.instrument_step(jax.jit(lambda x: x * 2), name="t",
                                batch_size=4)
        assert f._telemetry_instrumented is not None
        f(np.float32(1.0))   # compile
        f(np.float32(2.0))   # cache hit
        f(np.ones((2,), np.float32))  # new shape -> recompile
        r = tin.registry()
        assert r.total("jit_compiles_total") == 2
        assert r.total("jit_cache_hits_total") == 1
        assert r.total("jit_compile_seconds_total") > 0
        assert r.total("samples_total") == 12

    def test_memory_gauge_cpu_fallback(self, tel_enabled):
        """CPU devices report no memory_stats: empty reading, no gauges,
        summary carries peak_hbm_bytes=None — never an error."""
        assert tin.sample_memory() == {}
        s = tin.summary()
        assert s["peak_hbm_bytes"] is None
        assert s["data_wait_frac"] == 0.0

    def test_summary_and_jsonl_event_log(self, tel_enabled):
        tin.note_compile(2.0, fn="step")
        probe = tin.step_probe("fit", batch_size=8)
        probe.record_data_wait(1.0)
        probe.record_step(3.0, loss=0.5)
        s = tin.summary()
        assert s["compile_s"] == 2.0
        assert s["data_wait_frac"] == pytest.approx(1.0 / 6.0, abs=1e-4)
        tin.flush()
        lines = [json.loads(l) for l in open(tel_enabled)]
        assert [l["kind"] for l in lines[:-1]] == ["compile"]
        names = {m["name"] for m in lines[-1]["metrics"]}
        assert {"jit_compile_seconds_total", "data_wait_seconds_total",
                "step_seconds", "samples_per_sec", "last_loss"} <= names

    def test_summary_dispatches_per_step(self, tel_enabled):
        """ISSUE 3 regression surface: dispatches/step ratio from the
        train-step counters — null with no producer, 1.0 fused, 2+P
        legacy."""
        assert tin.summary()["dispatches_per_step"] is None
        tin.note_train_step("legacy")
        tin.note_dispatch(2, path="legacy")  # fwd+bwd
        tin.note_dispatch(4, path="legacy")  # per-param optimizer storm
        assert tin.summary()["dispatches_per_step"] == 6.0
        tin.note_train_step("fused")
        tin.note_dispatch(1, path="fused")
        assert tin.summary()["dispatches_per_step"] == 3.5
        tin.note_fused_fallback("monitor")
        assert tin.registry().get("module_fused_fallback_total") \
            .value(reason="monitor") == 1

    def test_note_helpers_noop_when_disabled(self, tel_disabled):
        tin.note_dispatch(3, path="legacy")
        tin.note_train_step("fused")
        tin.note_fused_fallback("monitor")
        tin._reset_for_tests()
        assert tin.registry().get("step_dispatches_total") is None


# -- wiring ------------------------------------------------------------------
class TestWiring:
    def test_speedometer_reports_data_wait(self, tel_enabled, caplog):
        import logging

        from mxnet_tpu.callback import Speedometer
        from mxnet_tpu.model import BatchEndParam

        r = tin.registry()
        wait = r.counter("data_wait_seconds_total", labelnames=("loop",))
        sp = Speedometer(batch_size=4, frequent=2, auto_reset=False)
        with caplog.at_level(logging.INFO):
            sp(BatchEndParam(epoch=0, nbatch=0, eval_metric=None, locals={}))
            wait.inc(0.25, loop="module_fit")
            sp(BatchEndParam(epoch=0, nbatch=1, eval_metric=None, locals={}))
            sp(BatchEndParam(epoch=0, nbatch=2, eval_metric=None, locals={}))
        lines = [rec.message for rec in caplog.records
                 if "samples/sec" in rec.message]
        assert lines and "data-wait=" in lines[-1]
        assert r.max_value("speedometer_samples_per_sec") > 0

    def test_speedometer_format_unchanged_when_disabled(self, tel_disabled,
                                                        caplog):
        import logging

        from mxnet_tpu.callback import Speedometer
        from mxnet_tpu.model import BatchEndParam

        sp = Speedometer(batch_size=4, frequent=1)
        with caplog.at_level(logging.INFO):
            sp(BatchEndParam(epoch=0, nbatch=0, eval_metric=None, locals={}))
            sp(BatchEndParam(epoch=0, nbatch=1, eval_metric=None, locals={}))
        (line,) = [rec.message for rec in caplog.records
                   if "samples/sec" in rec.message]
        assert "data-wait" not in line
        assert line.startswith("Iter[0] Batch [1]\tSpeed:")

    def test_kvstore_bytes_counters(self, tel_enabled):
        kv = mx.kv.create("local")
        kv.init("w", mx.nd.zeros((4, 8)))
        kv.push("w", mx.nd.ones((4, 8)))
        out = mx.nd.zeros((4, 8))
        kv.pull("w", out=out)
        r = tin.registry()
        assert r.total("kvstore_bytes_pushed_total") == 4 * 8 * 4
        assert r.total("kvstore_bytes_pulled_total") == 4 * 8 * 4


# -- custom-call cost registry ----------------------------------------------
class TestCostRegistry:
    def test_cost_fns_positive(self):
        from mxnet_tpu.ops import pallas_kernels as pk

        fns = pk.cost_fns()
        assert {"quantize_int8_pallas", "nms_alive_pallas",
                "dconv_col_pallas_fwd", "dconv_col_pallas_bwd"} <= set(fns)
        for cost in (fns["quantize_int8_pallas"]((8, 128)),
                     fns["nms_alive_pallas"](2, 6000),
                     fns["dconv_col_pallas_fwd"](8, 1024, 2432, 256, 2)):
            assert cost["flops"] > 0 and cost["bytes_accessed"] > 0

    def test_trace_time_recording_and_multishape_mean(self):
        # recording fires at TRACE time only: reset, then use shapes no
        # other test traces, so a jit cache hit cannot hide the record
        import jax.numpy as jnp

        from mxnet_tpu.ops import pallas_kernels as pk

        pk.reset_traced_costs()
        x = jnp.asarray(np.random.randn(56, 128).astype(np.float32))
        pk.quantize_int8_pallas(x, jnp.float32(3.0), interpret=True)
        ent = pk.traced_costs()["quantize_int8_pallas"]
        assert ent["flops"] == 5 * 56 * 128
        assert ent["shape"] == [56, 128]
        assert ent["calls"] == 1 and ent["shapes"] == 1
        # a second traced shape: per-invocation cost becomes the mean, not
        # last-shape-wins (one price must cover shapeless trace events)
        x2 = jnp.asarray(np.random.randn(168, 128).astype(np.float32))
        pk.quantize_int8_pallas(x2, jnp.float32(3.0), interpret=True)
        ent = pk.traced_costs()["quantize_int8_pallas"]
        assert ent["calls"] == 2 and ent["shapes"] == 2
        assert ent["flops"] == (5 * 56 * 128 + 5 * 168 * 128) // 2

    def test_profiler_dump_embeds_costs(self, tmp_path):
        import jax.numpy as jnp

        from mxnet_tpu import profiler
        from mxnet_tpu.ops import pallas_kernels as pk

        x = jnp.asarray(np.ones((40, 128), np.float32))
        pk.quantize_int8_pallas(x, jnp.float32(1.0), interpret=True)
        evs = json.loads(profiler.dumps())["traceEvents"]
        (meta,) = [e for e in evs if e.get("name") == "custom_call_costs"]
        assert meta["args"]["quantize_int8_pallas"]["bytes_accessed"] > 0


# -- trace_summary CLI -------------------------------------------------------
GOLDEN_TRACE = {
    "traceEvents": [
        {"name": "custom_call_costs", "ph": "M", "pid": 0, "args": {
            "dconv_col_pallas_fwd": {"flops": 2_000_000,
                                     "bytes_accessed": 1_000_000, "calls": 1},
            "nms_alive_pallas": {"flops": 500_000,
                                 "bytes_accessed": 4_000_000, "calls": 1},
        }},
        # 3 invocations of the dconv kernel at 1 ms each
        {"name": "dconv_col_pallas_fwd", "ph": "X", "ts": 0, "dur": 1000,
         "pid": 0, "tid": 1},
        {"name": "dconv_col_pallas_fwd", "ph": "X", "ts": 2000, "dur": 1000,
         "pid": 0, "tid": 1},
        {"name": "dconv_col_pallas_fwd", "ph": "X", "ts": 4000, "dur": 1000,
         "pid": 0, "tid": 1},
        {"name": "unregistered_op", "ph": "X", "ts": 0, "dur": 500,
         "pid": 0, "tid": 1},
    ],
    "displayTimeUnit": "ms",
}


class TestTraceSummary:
    def _run(self, *argv):
        return subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "trace_summary.py"),
             "--device-kind", "TPU v5 lite"]
            + list(argv), capture_output=True, text=True, timeout=300)

    def test_peaks_have_no_default(self, tmp_path):
        """A trace does not name its chip: no peaks given, or an unknown
        device kind, is an error — never v5e's numbers by default."""
        trace = tmp_path / "trace.json"
        trace.write_text(json.dumps(GOLDEN_TRACE))
        tool = os.path.join(REPO, "tools", "trace_summary.py")
        for extra in ([], ["--device-kind", "TPU v9"]):
            res = subprocess.run([sys.executable, tool, str(trace)] + extra,
                                 capture_output=True, text=True, timeout=300)
            assert res.returncode == 2 and "peak" in res.stderr, res.stderr

    def test_golden_table(self, tmp_path):
        trace = tmp_path / "trace.json"
        trace.write_text(json.dumps(GOLDEN_TRACE))
        res = self._run(str(trace))
        assert res.returncode == 0, res.stderr[-500:]
        out = res.stdout
        # registered custom calls appear with non-zero FLOPs/bytes
        # (acceptance criterion: no longer invisible to cost accounting)
        dconv = [l for l in out.splitlines()
                 if l.startswith("dconv_col_pallas_fwd")]
        assert dconv, out
        # 3 calls x 2 MFLOP in 3 ms -> 2.0 GFLOP/s; 3 MB moved -> 1.0 GB/s
        assert "2.0" in dconv[0] and "1.00" in dconv[0]
        # cost-only row for the kernel with no trace events
        assert any(l.startswith("nms_alive_pallas") for l in out.splitlines())
        assert "2 registered custom call(s)" in out

    def test_golden_json(self, tmp_path):
        trace = tmp_path / "trace.json"
        trace.write_text(json.dumps(GOLDEN_TRACE))
        res = self._run(str(trace), "--json")
        assert res.returncode == 0, res.stderr[-500:]
        rows = {r["op"]: r for r in json.loads(res.stdout)["rows"]}
        d = rows["dconv_col_pallas_fwd"]
        assert d["calls"] == 3 and d["total_ms"] == pytest.approx(3.0)
        assert d["flops"] == 6_000_000 and d["bytes"] == 3_000_000
        assert d["gflops_s"] == pytest.approx(2.0)
        assert d["gb_s"] == pytest.approx(1.0)
        assert d["intensity"] == pytest.approx(2.0)
        assert d["bound"] == "memory"
        n = rows["nms_alive_pallas"]         # cost-only row
        assert n["total_ms"] is None and n["flops"] == 500_000
        u = rows["unregistered_op"]          # timed but costless
        assert u["flops"] is None and u["total_ms"] == pytest.approx(0.5)

    def test_match_prefers_exact_then_longest(self, tmp_path):
        """dequantize ops must not be billed at the quantize cost (substring
        trap), nor backward kernels at the forward alias's cost."""
        trace = {"traceEvents": [
            {"name": "custom_call_costs", "ph": "M", "pid": 0, "args": {
                "quantize_int8_pallas": {"flops": 50, "bytes_accessed": 10},
                "dequantize_int8_pallas": {"flops": 20, "bytes_accessed": 10},
                "dconv_col_pallas_fwd": {"flops": 100, "bytes_accessed": 10},
                "dconv_col_pallas_bwd": {"flops": 200, "bytes_accessed": 10},
            }},
            {"name": "custom-call.dequantize_int8", "ph": "X", "ts": 0,
             "dur": 10, "pid": 0, "tid": 1},
            {"name": "dconv_col_pallas_bwd", "ph": "X", "ts": 20,
             "dur": 10, "pid": 0, "tid": 1},
        ]}
        f = tmp_path / "t.json"
        f.write_text(json.dumps(trace))
        res = self._run(str(f), "--json")
        assert res.returncode == 0, res.stderr[-500:]
        rows = {r["op"]: r for r in json.loads(res.stdout)["rows"]}
        assert rows["custom-call.dequantize_int8"]["flops"] == 20
        assert rows["dconv_col_pallas_bwd"]["flops"] == 200

    def test_costs_from_telemetry_jsonl(self, tmp_path):
        trace = tmp_path / "trace.json"
        trace.write_text(json.dumps({"traceEvents": [
            {"name": "dconv_col_pallas_fwd", "ph": "X", "ts": 0,
             "dur": 100, "pid": 0, "tid": 1}]}))
        jl = tmp_path / "tel.jsonl"
        jl.write_text(json.dumps(
            {"ts": 1, "kind": "custom_call_cost",
             "name": "dconv_col_pallas_fwd", "flops": 1000,
             "bytes_accessed": 2000}) + "\n")
        res = self._run(str(trace), "--costs", str(jl), "--json")
        assert res.returncode == 0, res.stderr[-500:]
        (row,) = json.loads(res.stdout)["rows"]
        assert row["flops"] == 1000 and row["bytes"] == 2000


# -- bench schema lint -------------------------------------------------------
class TestBenchSchema:
    def test_self_test_and_captures(self, tmp_path):
        # no capture is checked in; one written here keeps the driver-capture
        # reader (the ``parsed`` field) under the lint
        cap = tmp_path / "BENCH_capture.json"
        cap.write_text(json.dumps(
            {"rc": 0, "parsed": {"metric": "m", "value": 1.0,
                                 "unit": "img/s", "vs_baseline": None}}))
        res = subprocess.run(
            [sys.executable, os.path.join(REPO, "ci", "check_bench_schema.py"),
             "--self-test", str(cap)],
            capture_output=True, text=True, timeout=300)
        assert res.returncode == 0, res.stdout + res.stderr

    def test_validate_line_rejects_bad_telemetry(self):
        from mxnet_tpu.test_utils import load_module_by_path

        cbs = load_module_by_path(
            os.path.join(REPO, "ci", "check_bench_schema.py"),
            "check_bench_schema")
        good = {"metric": "m", "value": 1.0, "unit": "img/s",
                "vs_baseline": None,
                "telemetry": {"compile_s": 22.7, "peak_hbm_bytes": None,
                              "data_wait_frac": 0.0}}
        cbs.validate_line(good)
        bad = dict(good, telemetry={"compile_s": "fast",
                                    "peak_hbm_bytes": None,
                                    "data_wait_frac": 0.0})
        with pytest.raises(cbs.SchemaError):
            cbs.validate_line(bad)
