"""One cell of the benchmark, once.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything about a cell is data: ``BENCHMARK.json`` names the cell's
configuration and traffic, ``benchmark/configs/<config>.json`` names the
runner (``benchmark/runners/<runner>.py``), and each per-layer metric is
``benchmark/layer_metrics/<name>.py`` (an end-to-end metric
``benchmark/end_to_end/<name>.py``).  This file holds no model, cell or
metric name.  See benchmark/README.md.
"""
import time

T_PROCESS = time.perf_counter()

import argparse                # noqa: E402
import contextlib              # noqa: E402
import gc                      # noqa: E402
import importlib               # noqa: E402
import importlib.util          # noqa: E402
import json                    # noqa: E402
import os                      # noqa: E402
import shutil                  # noqa: E402
import sys                     # noqa: E402
import tempfile                # noqa: E402
import types                   # noqa: E402

import numpy as np             # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

NO_CHIP = 3


def say(msg):
    print("[benchmark] " + msg, file=sys.stderr, flush=True)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def resolve(workload, bench=None):
    """-> (cell, configuration file's content, traffic file's content)."""
    bench = bench or load_json(ROOT, "BENCHMARK.json")
    cells = {c["name"]: c for c in bench["workloads"]}
    if workload not in cells:
        raise SystemExit("no workload %r in BENCHMARK.json (has: %s)"
                         % (workload, ", ".join(sorted(cells))))
    cell = cells[workload]
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = load_json(ROOT, entry["file"])
    traffic = load_json(HERE, "traffic", cell["traffic"] + ".json")
    return cell, config, traffic


def cell_metrics(bench, cell, group):
    """The metrics of ``group`` that this cell reports."""
    e2e = [m["name"] for m in bench["end_to_end"]
           if cell["name"] in m.get("workloads", [cell["name"]])]
    if group == "end_to_end":
        return [m for m in bench["end_to_end"] if m["name"] in e2e]
    return [m for m in bench["per_layer"]
            if (cell["name"] in m["workloads"] if "workloads" in m
                else m["moves"] in e2e)]


def load_reader(group, name):
    path = os.path.join(HERE, group, name + ".py")
    spec = importlib.util.spec_from_file_location(
        "benchmark_layer_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def find_devices(chips):
    """The chips the cell asks for, or exit: a run without the accelerator
    prints no result."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        print("benchmark/run.py: needs %d TPU chip(s), found platform=%r "
              "device_kind=%r count=%d (JAX_PLATFORMS=%r)"
              % (chips, devs[0].platform, devs[0].device_kind, len(devs),
                 os.environ.get("JAX_PLATFORMS")), file=sys.stderr)
        raise SystemExit(NO_CHIP)
    return devs[:chips]


def runtime_peak_bytes(devices):
    """Peak device memory on the fullest chip as the runtime counts it: the
    arrays in use at their peak plus the most it ever reserved for running
    executables, which is where their temporaries live (PERF.md section 6,
    PR 25: ``peak_bytes_in_use`` alone does not see them)."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, stats.get("peak_bytes_in_use", 0)
                   + stats.get("peak_bytes_reserved", 0))
    return peak


def run_cell(workload, seed, seconds, trace, devices, bench=None,
             config=None, traffic=None, prepare=None):
    """Drive one run on ``devices`` and return the result line's object.
    ``config`` / ``traffic`` replace the cell's files (the CPU tests run toy
    sizes); ``prepare(runner)`` is called between building and the first
    steps (the tests break the timed path there, and hand over an
    executable they have built before)."""
    import jax

    bench = bench or load_json(ROOT, "BENCHMARK.json")
    cell, file_config, file_traffic = resolve(workload, bench)
    config = config or file_config
    traffic = traffic or file_traffic
    kind = devices[0].device_kind
    say("cell %s seed %d seconds %s trace %d on %d x %s"
        % (workload, seed, seconds, trace, len(devices), kind))

    runner_mod = importlib.import_module("benchmark.runners." + config["runner"])
    runner = runner_mod.Runner(config, traffic, seed, devices, say)
    runner.build()
    if prepare is not None:
        prepare(runner)
    runner.first_steps()
    from mxnet_tpu import compile_cache

    cache_at_setup = dict(compile_cache.stats())
    say("set-up phases (s): %s" % json.dumps(
        {k: round(v, 2) for k, v in runner.phases.items()}))

    # what set-up left on the host's heap (the traced programs) is not
    # walked again by a collection that falls into the window
    gc.collect()
    gc.freeze()
    trace_dir = None
    span = lambda name: contextlib.nullcontext()     # noqa: E731
    if trace:
        seconds = min(seconds, traffic.get("trace_seconds", seconds))
        trace_dir = tempfile.mkdtemp(prefix="benchmark_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        span = jax.profiler.TraceAnnotation
    try:
        t_start, ends = runner.window(seconds, span)
    finally:
        gc.unfreeze()
        if trace:
            jax.profiler.stop_trace()
    compiled_in_window = (compile_cache.stats()["xla_misses"]
                          - cache_at_setup["xla_misses"])
    steps = len(ends)
    window_s = ends[-1] - t_start
    durations = np.diff(np.asarray([t_start] + ends))
    window = types.SimpleNamespace(
        t_process=T_PROCESS, t_start=t_start, ends=ends, steps=steps,
        durations=durations, items_per_step=runner.items_per_step)
    say("window: %d steps in %.3f s, step ms min %.2f median %.2f max %.2f "
        "(step %d)" % (steps, window_s, durations.min() * 1e3,
                       float(np.median(durations)) * 1e3,
                       durations.max() * 1e3, int(durations.argmax()) + 1))

    hbm, source = runtime_peak_bytes(devices), "memory_stats"
    declared, declared_by = runner.memory()
    say("memory: runtime peak in use + peak reserved %d, %s %s"
        % (hbm, declared_by, declared))
    if (declared or 0) > hbm:
        hbm, source = declared, declared_by
    say("memory_source %s" % source)
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": int(hbm)}

    runner.release()
    t0 = time.perf_counter()
    correct, compared, facts = runner.check()
    say("reference and comparison: %.1f s; %s"
        % (time.perf_counter() - t0, json.dumps(facts, default=float)))
    failed = 0
    if compiled_in_window:
        say("FAILED: %d program(s) compiled inside the window"
            % compiled_in_window)
        correct = False
        failed = steps
    compared["compiled_in_window"] = [compiled_in_window, 0]

    result = {"correct": bool(correct), "attempted": steps, "failed": failed}
    if not trace:
        result["metrics"] = {
            m["name"]: {"value": float(load_reader("end_to_end", m["name"])(window)),
                        "unit": m["unit"]}
            for m in cell_metrics(bench, cell, "end_to_end")}
        result["device"] = device
    else:
        from benchmark import trace_reduce, work

        try:
            tr = trace_reduce.load(trace_reduce.find_xplane(trace_dir),
                                   runner.spans)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        run = types.SimpleNamespace(
            trace=tr, steps=steps, window_s=window_s, chips=len(devices),
            items_per_step=runner.items_per_step, config=config,
            traffic=traffic, peaks=work.peaks(kind), work=work,
            cache_stats=cache_at_setup, hbm_bytes=hbm)
        metrics = {}
        for m in cell_metrics(bench, cell, "per_layer"):
            value = load_reader("layer_metrics", m["name"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        result["metrics"] = metrics
        device["busy_s"] = float(np.mean([d.busy_s() for d in tr.devices]))
        device["window_s"] = window_s
        result["device"] = device
        result["breakdown"] = {"device_ops": tr.top_ops(10),
                               "idle_gaps": tr.idle_by_span(runner.spans, 10)}
    result["compared"] = compared
    for name, (value, limit) in compared.items():
        say("compared %s = %r limit %r" % (name, value, limit))
    say("correct = %s" % result["correct"])
    return result


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell, _, _ = resolve(args.workload)
    # the one compile cache of the run, at a fixed place inside the checkout
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    import jax

    # every program persists, however quick its compile: the second run of a
    # cell finds all of them and set-up stays the same from run to run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    devices = find_devices(cell["chips"])
    result = run_cell(args.workload, args.seed, args.seconds, args.trace,
                      devices)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
