"""Structured span tracing (ISSUE 4, telemetry/tracing.py): the
MXNET_TRACE=0 no-op guarantee, sampling, the bounded ring, cross-thread
context propagation with flow events, the serving request lifecycle
(queue/assemble/execute across submit and device-loop threads, drop
reasons), fit-loop step/data_wait spans, kvstore/Predictor spans, the
exporter's chrome-trace invariants (ci/check_trace.py), and the
trace_merge clock rebase.  ISSUE 26: the switch is the profiler session
(spans land on the xplane's host plane as TraceAnnotations), the spans
inside FusedStepper.run, ``count("dispatch")``, ``snapshot()``, operator
scopes in the lowered program, and compile_cache's stage durations."""
import json
import os
import threading

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.telemetry import tracing

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _load_tool(relpath):
    from mxnet_tpu.test_utils import load_module_by_path

    return load_module_by_path(os.path.join(REPO, relpath))


@pytest.fixture
def tr_enabled(monkeypatch, tmp_path):
    """Fresh global tracer with tracing ON, export path in tmp."""
    monkeypatch.setenv("MXNET_TRACE", "1")
    monkeypatch.setenv("MXNET_TRACE_FILE", str(tmp_path / "trace.json"))
    monkeypatch.delenv("MXNET_TRACE_SAMPLE", raising=False)
    monkeypatch.delenv("MXNET_TRACE_BUFFER", raising=False)
    tracing._reset_for_tests()
    yield tmp_path / "trace.json"
    tracing._reset_for_tests()


@pytest.fixture
def tr_disabled(monkeypatch):
    monkeypatch.delenv("MXNET_TRACE", raising=False)
    tracing._reset_for_tests()
    yield
    tracing._reset_for_tests()


def _export_events(path):
    tracing.export(str(path))
    return json.load(open(path))["traceEvents"]


def _spans(events):
    return [e for e in events if e.get("ph") == "X"]


# -- gating / no-op guarantee -------------------------------------------------
class TestGating:
    def test_noop_guard_tracing(self, tr_disabled, tmp_path, monkeypatch):
        """MXNET_TRACE unset: the shared NULL_SPAN singleton comes back from
        every entry point, no Tracer object is ever created, and no file is
        written — the traced code paths carry only the env check."""
        monkeypatch.setenv("MXNET_TRACE_FILE", str(tmp_path / "no.json"))
        root = tracing.start_trace("step", step=1)
        assert root is tracing.NULL_SPAN
        assert not root  # falsy ⇒ `if root:` guards cost nothing
        with root:
            assert tracing.span("child") is tracing.NULL_SPAN
        assert root.context() is None
        assert root.set(x=1) is root and root.finish() is root
        assert tracing._tracer is None  # nothing allocated
        assert tracing.export() is None
        assert not (tmp_path / "no.json").exists()

    def test_unsampled_root_propagates_nothing(self, tr_enabled, monkeypatch):
        monkeypatch.setenv("MXNET_TRACE_SAMPLE", "0")
        root = tracing.start_trace("step")
        assert root is tracing.NULL_SPAN
        with root:
            assert tracing.span("child") is tracing.NULL_SPAN

    def test_sampling_is_systematic(self, tr_enabled, monkeypatch):
        monkeypatch.setenv("MXNET_TRACE_SAMPLE", "0.5")
        kept = sum(bool(tracing.start_trace("t")) for _ in range(10))
        assert kept == 5  # floor(n*0.5) increments on every 2nd root

    def test_serving_and_module_paths_untouched_when_disabled(
            self, tr_disabled):
        from mxnet_tpu.serving import BucketLadder, Engine
        from mxnet_tpu.test_utils import tiny_mlp_checkpoint

        sym, params = tiny_mlp_checkpoint()
        with Engine(sym, params, {"data": (8,)},
                    ladder=BucketLadder((1, 2))) as eng:
            req = eng.submit({"data": np.zeros((1, 8), np.float32)})
            req.result(5.0)
            assert not hasattr(req, "_trace_root")
        assert tracing._tracer is None


# -- core tracer --------------------------------------------------------------
class TestTracer:
    def test_span_nesting_and_export(self, tr_enabled, tmp_path):
        with tracing.start_trace("root", kind="test") as root:
            with tracing.span("child", n=3) as child:
                pass
        events = _export_events(tmp_path / "e.json")
        (sync,) = [e for e in events if e.get("name") == "clock_sync"]
        assert sync["args"]["unix_ts"] > 0
        xs = {e["name"]: e for e in _spans(events)}
        assert xs["root"]["args"]["trace"] == xs["child"]["args"]["trace"]
        assert xs["child"]["args"]["parent"] == xs["root"]["args"]["span"]
        assert xs["child"]["args"]["n"] == 3
        assert xs["root"]["args"]["kind"] == "test"
        assert xs["root"]["dur"] >= xs["child"]["dur"] >= 0
        assert any(e.get("name") == "thread_name" for e in events
                   if e["ph"] == "M")
        # export(reset=True) drained the ring
        assert not _spans(_export_events(tmp_path / "e2.json"))

    def test_ring_buffer_bounded(self, monkeypatch, tmp_path):
        monkeypatch.setenv("MXNET_TRACE", "1")
        monkeypatch.setenv("MXNET_TRACE_BUFFER", "8")
        tracing._reset_for_tests()
        try:
            for i in range(20):
                tracing.start_trace("t", i=i).finish()
            events = _export_events(tmp_path / "ring.json")
            spans = _spans(events)
            assert len(spans) == 8
            assert [s["args"]["i"] for s in spans] == list(range(12, 20))
        finally:
            tracing._reset_for_tests()

    def test_cross_thread_flow(self, tr_enabled, tmp_path):
        root = tracing.start_trace("producer")
        ctx = root.context()
        done = threading.Event()

        def consumer():
            with tracing.span("consumer", parent=ctx):
                pass
            done.set()

        threading.Thread(target=consumer).start()
        assert done.wait(5.0)
        root.finish()
        events = _export_events(tmp_path / "x.json")
        xs = {e["name"]: e for e in _spans(events)}
        assert xs["producer"]["args"]["trace"] == \
            xs["consumer"]["args"]["trace"]
        assert xs["producer"]["tid"] != xs["consumer"]["tid"]
        (s,) = [e for e in events if e.get("ph") == "s"]
        (f,) = [e for e in events if e.get("ph") == "f"]
        assert s["id"] == f["id"] == xs["producer"]["args"]["span"]
        assert s["ts"] <= f["ts"]

    def test_finish_idempotent_and_drop_attr(self, tr_enabled, tmp_path):
        root = tracing.start_trace("r")
        sp = tracing.span("queue", parent=root)
        sp.finish(drop="timeout")
        sp.finish(drop="error")  # loses the race: first reason sticks
        root.finish()
        xs = {e["name"]: e for e in _spans(_export_events(tmp_path / "d.json"))}
        assert xs["queue"]["args"]["drop"] == "timeout"

    def test_span_without_active_trace_is_null(self, tr_enabled):
        assert tracing.span("orphan") is tracing.NULL_SPAN

    def test_unconsumed_context_leaves_no_orphan_flow(self, tr_enabled,
                                                      tmp_path):
        """A captured-but-never-bound context (a traced request batched
        behind another trace's owner) must not export an unmatched 's' —
        the anchor rides with the first 'f' bind."""
        root = tracing.start_trace("r")
        root.context()  # captured, never consumed
        root.finish()
        events = _export_events(tmp_path / "u.json")
        assert not [e for e in events if e.get("ph") in ("s", "f")]

    def test_context_bound_twice_keeps_one_s(self, tr_enabled, tmp_path):
        root = tracing.start_trace("r")
        ctx = root.context()
        tracing.span("c1", parent=ctx).finish()
        tracing.span("c2", parent=ctx).finish()
        root.finish()
        events = _export_events(tmp_path / "two.json")
        assert len([e for e in events if e.get("ph") == "s"]) == 1
        assert len([e for e in events if e.get("ph") == "f"]) == 2

    def test_flow_ring_eviction_exports_whole_pairs(self, monkeypatch,
                                                    tmp_path):
        """Oldest-first eviction can cut through an s/f pair; the export
        drops the widowed half so ci/check_trace.py always passes."""
        monkeypatch.setenv("MXNET_TRACE", "1")
        monkeypatch.setenv("MXNET_TRACE_BUFFER", "4")  # flow ring = 8
        tracing._reset_for_tests()
        try:
            for _ in range(10):
                root = tracing.start_trace("r")
                tracing.span("c", parent=root.context()).finish()
                root.finish()
            events = _export_events(tmp_path / "ev.json")
            ct = _load_tool("ci/check_trace.py")
            assert ct.validate(events) == []
        finally:
            tracing._reset_for_tests()


# -- wired hot paths ----------------------------------------------------------
class TestServingTrace:
    def test_request_lifecycle_across_threads(self, tr_enabled, tmp_path):
        """The ISSUE 4 acceptance: one request's queue/assemble/execute
        spans share a trace id across the submit and the device-loop
        threads, flow-linked."""
        from mxnet_tpu.serving import BucketLadder, Engine
        from mxnet_tpu.test_utils import tiny_mlp_checkpoint

        sym, params = tiny_mlp_checkpoint()
        with Engine(sym, params, {"data": (8,)}, ladder=BucketLadder((1, 2)),
                    max_wait_ms=1.0, name="tr") as eng:
            for _ in range(3):
                eng.predict({"data": np.zeros((1, 8), np.float32)})
        events = _export_events(tmp_path / "serve.json")
        by_trace = {}
        for e in _spans(events):
            by_trace.setdefault(e["args"]["trace"], []).append(e)
        full = [evs for evs in by_trace.values()
                if {"request", "queue", "classify", "assemble",
                    "execute", "reply"} <= {e["name"] for e in evs}]
        assert full, "no complete request trace"
        evs = full[0]
        tids = {e["tid"] for e in evs}
        assert len(tids) >= 2, "request trace never crossed threads"
        execute = [e for e in evs if e["name"] == "execute"]
        classify = [e for e in evs if e["name"] == "classify"]
        assert execute[0]["tid"] != classify[0]["tid"]
        # predictor dispatch nests under the device-loop execute span
        pf = [e for e in evs if e["name"] == "predictor_forward"]
        assert pf and pf[0]["args"]["parent"] == execute[0]["args"]["span"]
        # flow events pair up and link the handoff
        ids_s = {e["id"] for e in events if e.get("ph") == "s"}
        ids_f = {e["id"] for e in events if e.get("ph") == "f"}
        assert ids_s and ids_f <= ids_s

    def test_drop_reason_lands_on_span(self, tr_enabled, tmp_path):
        from mxnet_tpu.serving import (BucketLadder, Engine, RequestTimeout)
        from mxnet_tpu.test_utils import tiny_mlp_checkpoint

        sym, params = tiny_mlp_checkpoint()
        eng = Engine(sym, params, {"data": (8,)}, ladder=BucketLadder((1,)),
                     max_wait_ms=5.0, start=False, name="drops")
        req = eng.submit({"data": np.zeros((1, 8), np.float32)},
                         timeout=0.001)
        import time

        time.sleep(0.05)  # deadline long expired before the loop starts
        eng.start()
        with pytest.raises(RequestTimeout):
            req.result(5.0)
        eng.close()
        events = _export_events(tmp_path / "drop.json")
        dropped = [e for e in _spans(events)
                   if e["args"].get("drop") == "timeout"]
        assert dropped, "timeout reap never stamped a drop reason"
        names = {e["name"] for e in dropped}
        assert "queue" in names and "request" in names

    def test_sampled_out_requests_record_nothing(self, tr_enabled,
                                                 monkeypatch, tmp_path):
        monkeypatch.setenv("MXNET_TRACE_SAMPLE", "0")
        from mxnet_tpu.serving import BucketLadder, Engine
        from mxnet_tpu.test_utils import tiny_mlp_checkpoint

        sym, params = tiny_mlp_checkpoint()
        with Engine(sym, params, {"data": (8,)},
                    ladder=BucketLadder((1, 2))) as eng:
            eng.predict({"data": np.zeros((1, 8), np.float32)})
        assert not _spans(_export_events(tmp_path / "none.json"))


class TestTrainingTrace:
    def _fit(self, batches=2):
        data = mx.sym.var("data")
        fc = mx.sym.FullyConnected(data, name="fc", num_hidden=4)
        net = mx.sym.SoftmaxOutput(fc, name="softmax")
        X = np.random.RandomState(0).randn(8 * batches, 8).astype(np.float32)
        y = np.zeros((8 * batches,), np.float32)
        mod = mx.mod.Module(net)
        mod.fit(mx.io.NDArrayIter(X, y, batch_size=8), num_epoch=1,
                optimizer="sgd")

    def test_fit_step_spans(self, tr_enabled, tmp_path):
        self._fit()
        events = _export_events(tmp_path / "fit.json")
        xs = _spans(events)
        steps = [e for e in xs if e["name"] == "step"]
        assert len(steps) == 2
        assert sorted(s["args"]["step"] for s in steps) == [0, 1]
        by_trace = {}
        for e in xs:
            by_trace.setdefault(e["args"]["trace"], set()).add(e["name"])
        step_traces = [n for n in by_trace.values() if "step" in n]
        assert all({"data_wait", "forward_backward", "update",
                    "update_metric"} <= n for n in step_traces)

    def test_kvstore_spans_nest_in_trace(self, tr_enabled, tmp_path):
        from mxnet_tpu import kvstore

        kv = kvstore.create("local")
        kv.init("w", mx.nd.zeros((4,)))
        out = mx.nd.zeros((4,))
        with tracing.start_trace("step", step=0):
            kv.push("w", mx.nd.ones((4,)))
            kv.pull("w", out=out)
        xs = {e["name"] for e in _spans(_export_events(tmp_path / "kv.json"))}
        assert {"kv_push", "kv_pull", "step"} <= xs


# -- the profiler session as the switch (ISSUE 26) ----------------------------
FUSED = ("fused.prepare", "fused.dispatch", "fused.commit")


def _host_events(trace_dir, names):
    """[(name, start_ns, duration_ns)] of the xplane's host-plane events
    whose name is in ``names``."""
    import glob

    import jax

    (path,) = glob.glob(os.path.join(str(trace_dir), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    (host,) = [pl for pl in data.planes if pl.name == "/host:CPU"]
    return [(e.name, e.start_ns, e.duration_ns) for line in host.lines
            for e in line.events if e.name in names]


def _inside(inner, outers):
    return any(o[1] <= inner[1] and inner[1] + inner[2] <= o[1] + o[2]
               for o in outers)


class TestProfilerSession:
    def test_fit_spans_land_on_the_xplanes_host_plane(self, tr_disabled,
                                                       tmp_path):
        """No MXNET_TRACE: a live jax.profiler session alone records the
        spans, and writes each as a TraceAnnotation into the trace the
        device's lines are in — nested by containment, not by an offset."""
        import jax

        jax.profiler.start_trace(str(tmp_path))
        try:
            TestTrainingTrace()._fit(batches=3)
        finally:
            jax.profiler.stop_trace()
        snap = tracing.snapshot()
        evs = _host_events(tmp_path, ("step", "update") + FUSED)
        by = {n: [e for e in evs if e[0] == n] for n in ("step", "update")
              + FUSED}
        assert [len(by[n]) for n in by] == [3] * 5
        assert all(_inside(u, by["step"]) for u in by["update"])
        for n in FUSED:
            assert all(_inside(e, by["update"]) for e in by[n])
            ring = sorted(s["dur_us"] for s in snap if s["name"] == n)
            plane = sorted(e[2] / 1e3 for e in by[n])
            # the annotation opens after the span's clock starts and closes
            # before it stops
            assert all(0 <= r - p < 5e3 for r, p in zip(ring, plane)), \
                (n, ring, plane)
        updates = {s["span"]: s for s in snap if s["name"] == "update"}
        assert all(s["parent"] in updates for s in snap if s["name"] in FUSED)
        # prepare, dispatch, commit tile update: nothing else of size in it
        for u in updates.values():
            parts = sum(s["dur_us"] for s in snap if s["parent"] == u["span"])
            assert parts <= u["dur_us"]

    def test_roots_under_a_session_are_never_sampled_out(self, tr_disabled,
                                                         monkeypatch,
                                                         tmp_path):
        import jax

        monkeypatch.setenv("MXNET_TRACE_SAMPLE", "0")
        jax.profiler.start_trace(str(tmp_path))
        try:
            roots = [tracing.start_trace("step", step=i) for i in range(4)]
            for r in roots:
                with r:
                    pass
        finally:
            jax.profiler.stop_trace()
        assert all(roots)
        assert len(tracing.snapshot()) == 4
        assert tracing.start_trace("step") is tracing.NULL_SPAN  # session over

    def test_fit_with_no_session_and_no_env_creates_no_ring(self,
                                                            tr_disabled):
        TestTrainingTrace()._fit()
        mx.nd.ones((2,)) + 1        # an eager operator: count() finds no span
        tracing.count("dispatch")
        assert tracing._tracer is None
        assert tracing.snapshot() == []
        assert tracing.current() is None

    def test_dispatch_counts_fall_under_the_span_that_launched(self,
                                                               tr_enabled):
        TestTrainingTrace()._fit()
        snap = tracing.snapshot()
        steps = [s for s in snap if s["name"] == "step"]
        assert len(steps) == 2
        for st in steps:
            mine = [s for s in snap if s["trace"] == st["trace"]]
            (upd,) = [s for s in mine if s["name"] == "update"]
            assert upd["attrs"]["dispatch"] == 1  # the one fused program
            assert sum(s["attrs"].get("dispatch", 0) for s in mine) >= 1
        with tracing.start_trace("eager") as root:
            x = mx.nd.ones((2, 2))
            (x + x).wait_to_read()
            assert root.attrs["dispatch"] == 1
            import jax

            jax.jit(lambda a: (mx.nd.NDArray(a) * 2)._data)(x._data)
            assert root.attrs["dispatch"] == 1  # traced, not launched


def _lowered_gluon():
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.gluon import nn
    from mxnet_tpu.gluon.functional import functionalize

    net = nn.HybridSequential()
    net.add(nn.Dense(4, activation="relu"))
    net.initialize()
    net(mx.nd.ones((2, 8)))
    apply, _, vals, _ = functionalize(net)
    return jax.jit(lambda v, x: apply(v, x, jax.random.PRNGKey(0))[0]).lower(
        vals, jnp.ones((2, 8))), ("FullyConnected", "Activation")


def _lowered_symbol():
    import jax

    fc = mx.sym.FullyConnected(mx.sym.var("data"), name="fc1", num_hidden=8)
    exe = mx.sym.Activation(fc, name="act1", act_type="relu").simple_bind(
        data=(2, 8), grad_req="null")
    return jax.jit(exe._graph_fn(False)).lower(
        *exe._aot_example_args()), ("fc1", "act1")


@pytest.mark.parametrize("lower", [_lowered_gluon, _lowered_symbol])
def test_operator_scopes_reach_the_lowered_op_name(lower, monkeypatch):
    """Both front ends put their operator's name on every op they trace,
    with no switch: the lowered module's locations and the compiled HLO's
    ``op_name`` carry it, and so does the device trace's ``tf_op``."""
    import re

    monkeypatch.delenv("MXNET_COSTPLANE", raising=False)
    lowered, names = lower()
    locs = set(re.findall(r'loc\("(jit\([^"]+)"', lowered.as_text(
        debug_info=True)))
    op_names = set(re.findall(r'op_name="([^"]+)"',
                              lowered.compile().as_text()))
    for n in names:
        assert any("/%s/" % n in loc for loc in locs), (n, sorted(locs))
    assert any("/%s/" % names[0] in o for o in op_names), sorted(op_names)


@pytest.mark.parametrize("lower", [_lowered_gluon, _lowered_symbol])
def test_ops_traced_counts_operators_traced_and_no_eager_call(lower):
    """Both front ends add one to ``ops_traced`` for each operator they
    trace (the helpers' eager forward and binding count nothing); an eager
    operator counts nothing."""
    from mxnet_tpu import compile_cache

    n0 = compile_cache.stats()["ops_traced"]
    _, names = lower()
    n1 = compile_cache.stats()["ops_traced"]
    assert n1 - n0 == len(names)
    x = mx.nd.ones((2, 2))
    (x + x * 2).wait_to_read()
    assert compile_cache.stats()["ops_traced"] == n1


def _nested_step():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def inner(x):
        return jnp.tanh(x @ x).sum()

    def step(x):
        return sum(inner(x * i) for i in range(4))

    x = jnp.ones((8, 8)) + 37.0          # its eager programs compile here
    jax.block_until_ready(x)
    return jax.jit(step), x


def test_union_counts_a_nested_trace_once():
    """JAX reports the inner jit's traces on their own and inside the outer
    trace: ``trace_s`` sums them, ``trace_union_s`` is the outer span's
    length; one program is lowered and compiled."""
    from jax._src import monitoring

    from mxnet_tpu import compile_cache

    step, x = _nested_step()
    spans = []

    def listen(name, start, end, fun_name="", **kw):
        spans.append((name.rsplit("/", 1)[-1], fun_name, end - start))

    monitoring.register_event_time_span_listener(listen)
    try:
        s0 = compile_cache.stats()
        step(x).block_until_ready()
        s1 = compile_cache.stats()
    finally:
        monitoring.unregister_event_time_span_listener(listen)
    d = {k: s1[k] - s0[k] for k in s1}
    (outer,) = [t for n, f, t in spans
                if n == "jaxpr_trace_duration" and f == "step"]
    assert d["trace_union_s"] == pytest.approx(outer, rel=1e-6, abs=1e-9)
    assert d["trace_s"] > d["trace_union_s"]
    assert d["lower_union_s"] == pytest.approx(d["lower_s"])
    assert d["backend_union_s"] == pytest.approx(d["backend_s"])
    assert d["programs"] == 1


def test_union_of_intervals():
    from mxnet_tpu.compile_cache import _Union

    u = _Union()
    for a, b in [(5, 6), (1, 2), (1.5, 1.75), (3, 4), (1.9, 3.1), (0, 0.5),
                 (10, 11), (0.5, 1)]:
        u.add(a, b)
    assert u.total == pytest.approx(1 + 3 + 1 + 1)   # [0, 4], 5-6, 10-11
    assert u.starts == [0, 5, 10] and u.ends == [4, 6, 11]


def test_compile_spans_join_the_ring_on_its_clock(tr_enabled):
    """Under ``MXNET_TRACE`` each stage of a compile is a finished span in
    the ring, named by its program, between the ``_now_us`` readings taken
    around the call, the inner jit's traces inside the outer trace."""
    from mxnet_tpu.profiler import _now_us

    step, x = _nested_step()
    tracing._reset_for_tests()
    t0 = _now_us()
    step(x).block_until_ready()
    t1 = _now_us()
    spans = tracing.snapshot()
    assert {s["name"] for s in spans} == {"compile.trace", "compile.lower",
                                          "compile.backend"}
    for s in spans:
        assert t0 <= s["start_us"] and s["start_us"] + s["dur_us"] <= t1
    (outer,) = [s for s in spans if s["name"] == "compile.trace"
                and s["attrs"]["fun_name"] == "step"]
    inner = [s for s in spans if s["attrs"]["fun_name"] == "inner"]
    assert len(inner) == 4 and all(
        outer["start_us"] <= s["start_us"]
        and s["start_us"] + s["dur_us"] <= outer["start_us"] + outer["dur_us"]
        for s in inner)
    (low,) = [s for s in spans if s["name"] == "compile.lower"]
    assert low["attrs"]["fun_name"] == "jit(step)"
    assert low["start_us"] >= outer["start_us"] + outer["dur_us"]
    with tracing.start_trace("step") as root:   # a compile inside a span
        import jax

        jax.jit(lambda a: a * 3)(x).block_until_ready()
    mine = [s for s in tracing.snapshot() if s["trace"] == root.trace_id
            and s["name"].startswith("compile.")]
    assert {s["name"] for s in mine} == {"compile.trace", "compile.lower",
                                         "compile.backend"}
    assert all(s["parent"] == root.span_id for s in mine)


def test_compile_with_no_session_and_no_env_creates_no_ring(tr_disabled):
    import jax

    from mxnet_tpu import compile_cache

    n = compile_cache.stats()["programs"]
    jax.jit(lambda a: a * 5 + 1)(np.ones(3, np.float32)).block_until_ready()
    assert compile_cache.stats()["programs"] == n + 1
    assert tracing._tracer is None and tracing.snapshot() == []


def test_compile_cache_stats_sum_jaxs_stage_durations(tmp_path):
    """trace_s / lower_s / backend_s grow on a fresh compile; on a load from
    the persistent cache backend_s and cache_load_s grow and nothing is
    compiled.  Either is one program."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache as cc

    from mxnet_tpu import compile_cache

    def f(x):
        return jnp.tanh(x) @ x + 26.0

    def grown(after, before):
        return {k for k in ("trace_s", "lower_s", "backend_s", "cache_load_s")
                if after[k] > before[k]}

    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    old = [getattr(jax.config, k) for k in keys]
    try:
        for k, v in zip(keys, (str(tmp_path), 0.0, -1)):
            jax.config.update(k, v)
        cc.reset_cache()
        x = jnp.ones((8, 8))
        s0 = compile_cache.stats()
        jax.jit(f)(x).block_until_ready()
        s1 = compile_cache.stats()
        assert grown(s1, s0) == {"trace_s", "lower_s", "backend_s"}
        assert s1["xla_misses"] > s0["xla_misses"]
        assert s1["programs"] == s0["programs"] + 1
        jax.clear_caches()
        jax.jit(f)(x).block_until_ready()
        s2 = compile_cache.stats()
        assert {"backend_s", "cache_load_s"} <= grown(s2, s1)
        assert s2["xla_hits"] > s1["xla_hits"]
        assert s2["xla_misses"] == s1["xla_misses"]
        assert s2["programs"] == s1["programs"] + 1
        assert s2["cache_load_s"] - s1["cache_load_s"] <= \
            s2["backend_s"] - s1["backend_s"]
    finally:
        for k, v in zip(keys, old):
            jax.config.update(k, v)
        cc.reset_cache()


# -- exporter invariants / tools ----------------------------------------------
class TestExportTools:
    def test_export_passes_check_trace(self, tr_enabled, tmp_path):
        from mxnet_tpu.serving import BucketLadder, Engine
        from mxnet_tpu.test_utils import tiny_mlp_checkpoint

        sym, params = tiny_mlp_checkpoint()
        with Engine(sym, params, {"data": (8,)},
                    ladder=BucketLadder((1, 2)), max_wait_ms=1.0) as eng:
            for _ in range(4):
                eng.predict({"data": np.zeros((2, 8), np.float32)})
        events = _export_events(tmp_path / "v.json")
        ct = _load_tool("ci/check_trace.py")
        assert ct.validate(events) == []

    def test_check_trace_flags_malformed(self):
        ct = _load_tool("ci/check_trace.py")
        bad_ts = [{"name": "a", "ph": "X", "ts": -1, "dur": 2,
                   "pid": 0, "tid": 0}]
        assert any("bad ts" in p for p in ct.validate(bad_ts))
        overlap = [{"name": "a", "ph": "X", "ts": 0, "dur": 10, "pid": 0,
                    "tid": 0},
                   {"name": "b", "ph": "X", "ts": 5, "dur": 10, "pid": 0,
                    "tid": 0}]
        assert any("must nest" in p for p in ct.validate(overlap))
        orphan_f = [{"ph": "f", "bt": "e", "id": 7, "ts": 1.0, "pid": 0,
                     "tid": 0, "name": "h"}]
        assert any("without an 's'" in p for p in ct.validate(orphan_f))
        unmatched_s = [{"ph": "s", "id": 7, "ts": 1.0, "pid": 0, "tid": 0,
                        "name": "h"}]
        assert any("matching 'f'" in p for p in ct.validate(unmatched_s))
        ok = [{"name": "a", "ph": "X", "ts": 0, "dur": 10, "pid": 0,
               "tid": 0},
              {"name": "b", "ph": "X", "ts": 2, "dur": 3, "pid": 0,
               "tid": 0},
              {"ph": "s", "id": 1, "ts": 1.0, "pid": 0, "tid": 0,
               "name": "h"},
              {"ph": "f", "bt": "e", "id": 1, "ts": 2.0, "pid": 0, "tid": 0,
               "name": "h"}]
        assert ct.validate(ok) == []

    def test_trace_merge_clock_rebase(self, tmp_path):
        tm = _load_tool("tools/trace_merge.py")
        a = {"traceEvents": [
            {"name": "clock_sync", "ph": "M", "pid": 0,
             "args": {"unix_ts": 1000.0, "trace_ts_us": 500.0}},
            {"name": "a", "ph": "X", "ts": 500.0, "dur": 10.0, "pid": 0,
             "tid": 1, "args": {"trace": 1}}]}
        # same wall-clock moment, different trace epoch: b's event is 2s
        # after a's on the shared clock
        b = {"traceEvents": [
            {"name": "clock_sync", "ph": "M", "pid": 0,
             "args": {"unix_ts": 1002.0, "trace_ts_us": 9000.0}},
            {"name": "b", "ph": "X", "ts": 9000.0, "dur": 5.0, "pid": 0,
             "tid": 1},
            {"ph": "s", "id": 3, "ts": 9001.0, "pid": 0, "tid": 1,
             "name": "h"}]}
        pa, pb = str(tmp_path / "a.json"), str(tmp_path / "b.json")
        json.dump(a, open(pa, "w"))
        json.dump(b, open(pb, "w"))
        out = str(tmp_path / "m.json")
        assert tm.main([pa, pb, "-o", out]) == 0
        evs = json.load(open(out))["traceEvents"]
        ea = [e for e in evs if e.get("name") == "a"][0]
        eb = [e for e in evs if e.get("name") == "b"][0]
        assert eb["ts"] - ea["ts"] == pytest.approx(2e6)  # 2 s in us
        assert eb["pid"] == tm.PID_STRIDE  # namespaced
        (s,) = [e for e in evs if e.get("ph") == "s"]
        assert s["id"] == "m1.3"

    def test_bench_compare_gate(self, tmp_path):
        bc = _load_tool("tools/bench_compare.py")

        def capture(path, value, dps=None, metric="m_imgs_per_sec"):
            line = {"metric": metric, "value": value, "unit": "img/s"}
            if dps is not None:
                line["telemetry"] = {"compile_s": 1.0,
                                     "peak_hbm_bytes": None,
                                     "data_wait_frac": 0.0,
                                     "dispatches_per_step": dps}
            json.dump({"n": 1, "cmd": "x", "rc": 0, "parsed": line},
                      open(path, "w"))
            return path

        base = capture(str(tmp_path / "b.json"), 100.0, dps=1.0)
        ok = capture(str(tmp_path / "ok.json"), 98.0, dps=1.0)
        slow = capture(str(tmp_path / "slow.json"), 80.0, dps=1.0)
        stormy = capture(str(tmp_path / "storm.json"), 100.0, dps=12.0)
        other = capture(str(tmp_path / "other.json"), 1.0,
                        metric="different_metric")
        assert bc.main([base, ok, "--threshold", "5"]) == 0
        assert bc.main([base, slow, "--threshold", "5"]) == 1
        assert bc.main([base, stormy, "--threshold", "5"]) == 1
        # a different metric is reported, never gated
        assert bc.main([base, other, "--threshold", "5"]) == 0
        # bare bench-line files (no driver wrapper) load too
        bare = str(tmp_path / "bare.json")
        json.dump({"metric": "m_imgs_per_sec", "value": 99.0,
                   "unit": "img/s"}, open(bare, "w"))
        assert bc.main([base, bare, "--threshold", "5"]) == 0

    def test_bench_compare_multichip_gate(self, tmp_path):
        """MULTICHIP_r*.json captures diff on ok + dryrun phases (ISSUE 5):
        a capture that lost `ok` or dropped a phase exits non-zero; mixing
        capture kinds is an error."""
        bc = _load_tool("tools/bench_compare.py")
        tail_full = ("dryrun_multichip(8): mesh dp=4 tp=2, loss 2.9 -> 2.0\n"
                     "dryrun_multichip(8): pp gpipe loss 0.006, sp out, "
                     "ep moe loss 0.2 — all phases OK\n"
                     "dryrun_multichip(8): detection dp=8 step loss 5.3 — OK\n"
                     "dryrun_multichip(8): detection ZeRO-sharded state "
                     "(params+momentum over dp): 50.0 MB/device vs 399.4 MB "
                     "replicated, step loss 5.1 — OK\n")

        def capture(path, ok=True, tail=tail_full, skipped=False):
            json.dump({"n_devices": 8, "rc": 0 if ok else 1, "ok": ok,
                       "skipped": skipped, "tail": tail}, open(path, "w"))
            return path

        base = capture(str(tmp_path / "m1.json"))
        same = capture(str(tmp_path / "m2.json"))
        broke = capture(str(tmp_path / "m3.json"), ok=False)
        lost_zero = capture(str(tmp_path / "m4.json"),
                            tail=tail_full.rsplit("dryrun_multichip(8): "
                                                  "detection ZeRO", 1)[0])
        skipped = capture(str(tmp_path / "m5.json"), ok=False, tail="",
                          skipped=True)
        assert bc.main([base, same]) == 0
        assert bc.main([base, broke]) == 1
        assert bc.main([base, lost_zero]) == 1
        # driver had no devices that round: reported, never gated
        assert bc.main([base, skipped]) == 0
        # growing a phase relative to an older baseline is fine
        assert bc.main([lost_zero, base]) == 0
        # mixed kinds refuse loudly
        bench = str(tmp_path / "bench.json")
        json.dump({"metric": "m", "value": 1.0}, open(bench, "w"))
        assert bc.main([base, bench]) == 2
