#!/usr/bin/env python
"""Schema lint for bench.py's JSON line (ISSUE 1 CI satellite).

A driver capture of a bench run and the live ``python
bench.py`` output must stay machine-parseable: one JSON object with exactly
the known keys, including the optional ``telemetry`` block added by
MXNET_TELEMETRY.  Run from ci/run_tests.sh unit tier::

    python ci/check_bench_schema.py --self-test [capture.json ...]
    python bench.py | python ci/check_bench_schema.py -   # lint a live line

Driver captures are validated through their ``parsed`` field; raw files
containing a bare bench line are validated directly.
"""
from __future__ import annotations

import json
import sys

# "tier" (ISSUE 15 precision tiers): the compilation tier the benched
# plan ran under — optional (captures predating the tier read as fp32);
# bench_compare diffs same-tier rows only, cross-tier rows display-only
TOP_KEYS = {"metric", "value", "unit", "vs_baseline", "telemetry", "tier"}
TIER_VALUES = {"fp32", "bf16", "int8"}
TEL_REQ_KEYS = {"compile_s", "peak_hbm_bytes", "data_wait_frac"}
# dispatches_per_step (ISSUE 3 fused Module step), warmup_s (ISSUE 6 AOT
# cache restart surface), the graph-pass keys (ISSUE 7: plan nodes in/out
# of the pass pipeline + its wall time), autotune_trials (ISSUE 9:
# candidate configs measured — 0/null in steady state, when the winner
# store answers) and the serve latency quantiles (ISSUE 10: submit->reply
# p50/p99 from the serve_latency_seconds histogram — null when no serving
# ran) are optional: captures predating that work carry only the three
# original keys
# analysis_findings (ISSUE 11): graph-IR analyzer diagnostics the manager
# recorded this process — null when nothing was recorded (no
# check()/warmup analysis ran, or everything analyzed was clean)
# trainhealth_drain_s (ISSUE 12): host seconds the training-health plane's
# per-step drain cost — THE health-overhead number (the in-graph stat
# reductions ride the fused dispatch for free); null when no drain ran
# xla_flops / xla_peak_bytes (ISSUE 13 compile plane): XLA-measured module
# flops summed (and peak executable bytes maxed) over every executable the
# process built — null when MXNET_COSTPLANE is off or the backend cannot
# report (the partial-row contract)
# trials_saved (ISSUE 18 learned autotuning): measurements the cost model
# skipped under predict-then-measure (ranked minus measured candidates) —
# null when no ranked search ran this process
# pod (ISSUE 19 pod observability plane): rank-0 aggregator rollup for a
# multichip run — {ranks, max_step_lag, ledger_divergences, incidents},
# all non-negative ints; null/absent when MXNET_POD_METRICS is off or the
# benched process was not the aggregating rank
TEL_OPT_KEYS = {"dispatches_per_step", "warmup_s",
                "graph_nodes_pre", "graph_nodes_post", "pass_time_s",
                "autotune_trials", "trials_saved",
                "serve_p50_ms", "serve_p99_ms",
                "analysis_findings", "trainhealth_drain_s",
                "xla_flops", "xla_peak_bytes", "pod"}
TEL_KEYS = TEL_REQ_KEYS | TEL_OPT_KEYS
POD_KEYS = {"ranks", "max_step_lag", "ledger_divergences", "incidents"}

# SERVE_BENCH line (tools/loadgen.py, ISSUE 2) — docs/SERVING.md schema
SERVE_PREFIX = "SERVE_BENCH "
SERVE_REQ_KEYS = {"mode", "requests", "completed", "shed", "timeouts",
                  "errors", "shed_rate", "duration_s", "throughput_rps",
                  "latency_ms_p50", "latency_ms_p99", "compiles"}
SERVE_OPT_KEYS = {"concurrency", "rate_rps", "batch_fill_mean",
                  "padding_waste_mean", "first_request_ms", "warmup_s",
                  # ISSUE 10 live-ops surface: per-size-class percentiles
                  # + goodput under a --slo-ms target
                  "latency_by_class", "goodput_rps", "slo_ms",
                  # ISSUE 15: the engine's compiled precision tier
                  "tier",
                  # ISSUE 16 quality plane: {tier: {p50, p99, n,
                  # violations}} over shadow-sampled contract fractions —
                  # absent when MXNET_QUALITYPLANE is off or nothing was
                  # sampled during the run
                  "divergence",
                  # ISSUE 17 router: per-priority-class breakdown
                  # ({class: {requests, completed, sheds, downgrades,
                  # p50_ms, p99_ms, goodput_rps[, slo_ms]}}) and the policy
                  # mode the fronting Router ran — both absent on bare
                  # Engine runs (--router off)
                  "priority", "router_policy"}
SERVE_MODES = {"closed", "open"}
ROUTER_POLICIES = {"degrade", "shed"}
PRIORITY_REQ_KEYS = {"requests", "completed", "sheds", "downgrades",
                     "p50_ms", "p99_ms", "goodput_rps"}
PRIORITY_OPT_KEYS = {"slo_ms"}


class SchemaError(ValueError):
    pass


# loadgen request-trace record (tools/loadgen.py --save-trace, ISSUE 9) —
# the offline input the bucket-ladder tuner replays (autotune/ladder.py)
TRACE_KEYS = {"t", "n", "shapes", "class"}


def validate_trace_line(obj, where="<line>"):
    """Validate one --save-trace JSONL record; raises SchemaError."""
    if not isinstance(obj, dict):
        raise SchemaError("%s: trace record must be a JSON object, got %s"
                          % (where, type(obj).__name__))
    if set(obj) != TRACE_KEYS:
        raise SchemaError("%s: trace record keys %s != %s"
                          % (where, sorted(obj), sorted(TRACE_KEYS)))
    if not _num(obj["t"]) or obj["t"] < 0:
        raise SchemaError("%s: 't' must be a non-negative number (seconds "
                          "since run start)" % where)
    if not isinstance(obj["n"], int) or isinstance(obj["n"], bool) \
            or obj["n"] < 1:
        raise SchemaError("%s: 'n' must be a positive int sample count"
                          % where)
    shp = obj["shapes"]
    if not isinstance(shp, dict) or not shp:
        raise SchemaError("%s: 'shapes' must be a non-empty object of "
                          "input -> per-sample dims" % where)
    for name, dims in shp.items():
        if not isinstance(name, str) or not isinstance(dims, list) or any(
                not isinstance(d, int) or isinstance(d, bool) or d < 0
                for d in dims):
            raise SchemaError(
                "%s: shapes[%r] must be a list of non-negative int dims"
                % (where, name))
    if not isinstance(obj["class"], str) or not obj["class"]:
        raise SchemaError("%s: 'class' must be a non-empty string" % where)


def validate_trace_file(path):
    """Validate every line of a --save-trace JSONL file; empty = error
    (an empty trace replays to nothing — the tuner would crash later)."""
    n = 0
    with open(path, encoding="utf-8") as f:
        for i, line in enumerate(f, 1):
            line = line.strip()
            if not line:
                continue
            validate_trace_line(json.loads(line), "%s:%d" % (path, i))
            n += 1
    if not n:
        raise SchemaError("%s: empty trace file" % path)
    return n


def _num(x):
    return isinstance(x, (int, float)) and not isinstance(x, bool)


def validate_line(obj, where="<line>"):
    """Validate one bench JSON line dict; raises SchemaError."""
    if not isinstance(obj, dict):
        raise SchemaError("%s: bench line must be a JSON object, got %s"
                          % (where, type(obj).__name__))
    unknown = set(obj) - TOP_KEYS
    if unknown:
        raise SchemaError("%s: unknown top-level keys %s (schema: %s)"
                          % (where, sorted(unknown), sorted(TOP_KEYS)))
    for req in ("metric", "value", "unit"):
        if req not in obj:
            raise SchemaError("%s: missing required key %r" % (where, req))
    if not isinstance(obj["metric"], str) or not obj["metric"]:
        raise SchemaError("%s: 'metric' must be a non-empty string" % where)
    if not _num(obj["value"]):
        raise SchemaError("%s: 'value' must be a number" % where)
    if not isinstance(obj["unit"], str):
        raise SchemaError("%s: 'unit' must be a string" % where)
    if "vs_baseline" in obj and obj["vs_baseline"] is not None \
            and not _num(obj["vs_baseline"]):
        raise SchemaError("%s: 'vs_baseline' must be a number or null" % where)
    if "tier" in obj and obj["tier"] not in TIER_VALUES:
        raise SchemaError("%s: 'tier' must be one of %s (omit for legacy "
                          "fp32 captures), got %r"
                          % (where, sorted(TIER_VALUES), obj["tier"]))
    if "telemetry" in obj:
        tel = obj["telemetry"]
        if tel is None:
            return
        if not isinstance(tel, dict):
            raise SchemaError("%s: 'telemetry' must be an object or null"
                              % where)
        unknown = set(tel) - TEL_KEYS
        if unknown:
            raise SchemaError("%s: unknown telemetry keys %s (schema: %s)"
                              % (where, sorted(unknown), sorted(TEL_KEYS)))
        for k in TEL_REQ_KEYS:
            if k not in tel:
                raise SchemaError("%s: telemetry block missing %r" % (where, k))
        if not _num(tel["compile_s"]):
            raise SchemaError("%s: telemetry.compile_s must be a number"
                              % where)
        if tel["peak_hbm_bytes"] is not None \
                and not isinstance(tel["peak_hbm_bytes"], int):
            raise SchemaError(
                "%s: telemetry.peak_hbm_bytes must be an int or null" % where)
        if not _num(tel["data_wait_frac"]) or not 0 <= tel["data_wait_frac"] <= 1:
            raise SchemaError(
                "%s: telemetry.data_wait_frac must be a number in [0, 1]"
                % where)
        dps = tel.get("dispatches_per_step")
        if dps is not None and (not _num(dps) or dps < 0):
            raise SchemaError(
                "%s: telemetry.dispatches_per_step must be a non-negative "
                "number or null" % where)
        ws = tel.get("warmup_s")
        if ws is not None and (not _num(ws) or ws < 0):
            raise SchemaError(
                "%s: telemetry.warmup_s must be a non-negative number or "
                "null" % where)
        for k in ("graph_nodes_pre", "graph_nodes_post"):
            gn = tel.get(k)
            if gn is not None and (not isinstance(gn, int)
                                   or isinstance(gn, bool) or gn < 0):
                raise SchemaError(
                    "%s: telemetry.%s must be a non-negative int or null"
                    % (where, k))
        pt = tel.get("pass_time_s")
        if pt is not None and (not _num(pt) or pt < 0):
            raise SchemaError(
                "%s: telemetry.pass_time_s must be a non-negative number "
                "or null" % where)
        for k in ("autotune_trials", "trials_saved"):
            at = tel.get(k)
            if at is not None and (not isinstance(at, int)
                                   or isinstance(at, bool) or at < 0):
                raise SchemaError(
                    "%s: telemetry.%s must be a non-negative int "
                    "or null" % (where, k))
        for k in ("serve_p50_ms", "serve_p99_ms", "trainhealth_drain_s"):
            sv = tel.get(k)
            if sv is not None and (not _num(sv) or sv < 0):
                raise SchemaError(
                    "%s: telemetry.%s must be a non-negative number or "
                    "null" % (where, k))
        for k in ("xla_flops", "xla_peak_bytes"):
            xv = tel.get(k)
            if xv is not None and (not isinstance(xv, int)
                                   or isinstance(xv, bool) or xv < 0):
                raise SchemaError(
                    "%s: telemetry.%s must be a non-negative int or null"
                    % (where, k))
        if tel.get("serve_p50_ms") is not None \
                and tel.get("serve_p99_ms") is not None \
                and tel["serve_p99_ms"] < tel["serve_p50_ms"]:
            raise SchemaError(
                "%s: telemetry serve p99 below p50 — percentiles swapped?"
                % where)
        pod = tel.get("pod")
        if pod is not None:
            if not isinstance(pod, dict):
                raise SchemaError(
                    "%s: telemetry.pod must be an object or null" % where)
            unknown_pod = set(pod) - POD_KEYS
            if unknown_pod:
                raise SchemaError(
                    "%s: unknown telemetry.pod keys %s (schema: %s)"
                    % (where, sorted(unknown_pod), sorted(POD_KEYS)))
            for k, pv in pod.items():
                if not isinstance(pv, int) or isinstance(pv, bool) \
                        or pv < 0:
                    raise SchemaError(
                        "%s: telemetry.pod.%s must be a non-negative int"
                        % (where, k))
            if "ranks" in pod and pod["ranks"] < 1:
                raise SchemaError(
                    "%s: telemetry.pod.ranks must be >= 1 (an aggregator "
                    "always counts itself)" % where)


def validate_serve_line(obj, where="<line>"):
    """Validate one SERVE_BENCH JSON dict; raises SchemaError."""
    if not isinstance(obj, dict):
        raise SchemaError("%s: SERVE_BENCH must be a JSON object, got %s"
                          % (where, type(obj).__name__))
    unknown = set(obj) - SERVE_REQ_KEYS - SERVE_OPT_KEYS
    if unknown:
        raise SchemaError("%s: unknown SERVE_BENCH keys %s (schema: %s + "
                          "optional %s)" % (where, sorted(unknown),
                                            sorted(SERVE_REQ_KEYS),
                                            sorted(SERVE_OPT_KEYS)))
    missing = SERVE_REQ_KEYS - set(obj)
    if missing:
        raise SchemaError("%s: SERVE_BENCH missing required keys %s"
                          % (where, sorted(missing)))
    if obj["mode"] not in SERVE_MODES:
        raise SchemaError("%s: mode must be one of %s, got %r"
                          % (where, sorted(SERVE_MODES), obj["mode"]))
    for k in ("requests", "completed", "shed", "timeouts", "errors",
              "compiles"):
        if not isinstance(obj[k], int) or isinstance(obj[k], bool) \
                or obj[k] < 0:
            raise SchemaError("%s: %r must be a non-negative int, got %r"
                              % (where, k, obj[k]))
    for k in ("shed_rate", "duration_s", "throughput_rps",
              "latency_ms_p50", "latency_ms_p99"):
        if not _num(obj[k]) or obj[k] < 0:
            raise SchemaError("%s: %r must be a non-negative number, got %r"
                              % (where, k, obj[k]))
    if obj["shed_rate"] > 1:
        raise SchemaError("%s: shed_rate must be in [0, 1]" % where)
    if obj["latency_ms_p99"] < obj["latency_ms_p50"]:
        raise SchemaError("%s: p99 latency below p50 — percentiles swapped?"
                          % where)
    if obj["completed"] > obj["requests"]:
        raise SchemaError("%s: completed > requests" % where)
    for k in ("batch_fill_mean", "padding_waste_mean"):
        if k in obj and (not _num(obj[k]) or not 0 <= obj[k] <= 1):
            raise SchemaError("%s: %r must be a number in [0, 1]" % (where, k))
    if "warmup_s" in obj and (not _num(obj["warmup_s"]) or obj["warmup_s"] < 0):
        raise SchemaError("%s: 'warmup_s' must be a non-negative number"
                          % where)
    if "first_request_ms" in obj:
        fr = obj["first_request_ms"]
        if not isinstance(fr, dict) or not fr:
            raise SchemaError(
                "%s: 'first_request_ms' must be a non-empty object of "
                "size-class -> ms" % where)
        for k, v in fr.items():
            if not isinstance(k, str) or not _num(v) or v < 0:
                raise SchemaError(
                    "%s: first_request_ms[%r] must map a string size class "
                    "to a non-negative number" % (where, k))
    if "goodput_rps" in obj and (not _num(obj["goodput_rps"])
                                 or obj["goodput_rps"] < 0):
        raise SchemaError("%s: 'goodput_rps' must be a non-negative number"
                          % where)
    if "slo_ms" in obj and (not _num(obj["slo_ms"]) or obj["slo_ms"] <= 0):
        raise SchemaError("%s: 'slo_ms' must be a positive number (omit "
                          "the key when no target was set)" % where)
    if "tier" in obj and obj["tier"] not in TIER_VALUES:
        raise SchemaError("%s: 'tier' must be one of %s (omit for legacy "
                          "fp32 captures), got %r"
                          % (where, sorted(TIER_VALUES), obj["tier"]))
    if "latency_by_class" in obj:
        bc = obj["latency_by_class"]
        if not isinstance(bc, dict) or not bc:
            raise SchemaError(
                "%s: 'latency_by_class' must be a non-empty object of "
                "size-class -> {p50_ms, p99_ms, n}" % where)
        for k, v in bc.items():
            if not isinstance(k, str) or not isinstance(v, dict) \
                    or set(v) != {"p50_ms", "p99_ms", "n"}:
                raise SchemaError(
                    "%s: latency_by_class[%r] must be an object with "
                    "exactly {p50_ms, p99_ms, n}" % (where, k))
            if not isinstance(v["n"], int) or isinstance(v["n"], bool) \
                    or v["n"] < 1:
                raise SchemaError(
                    "%s: latency_by_class[%r].n must be a positive int"
                    % (where, k))
            for pk in ("p50_ms", "p99_ms"):
                if not _num(v[pk]) or v[pk] < 0:
                    raise SchemaError(
                        "%s: latency_by_class[%r].%s must be a "
                        "non-negative number" % (where, k, pk))
            if v["p99_ms"] < v["p50_ms"]:
                raise SchemaError(
                    "%s: latency_by_class[%r] p99 below p50 — percentiles "
                    "swapped?" % (where, k))
    if "divergence" in obj:
        div = obj["divergence"]
        if not isinstance(div, dict) or not div:
            raise SchemaError(
                "%s: 'divergence' must be a non-empty object of "
                "tier -> {p50, p99, n, violations} (omit the key when the "
                "quality plane is off)" % where)
        for k, v in div.items():
            if k not in TIER_VALUES:
                raise SchemaError(
                    "%s: divergence tier must be one of %s, got %r"
                    % (where, sorted(TIER_VALUES), k))
            if not isinstance(v, dict) \
                    or set(v) != {"p50", "p99", "n", "violations"}:
                raise SchemaError(
                    "%s: divergence[%r] must be an object with exactly "
                    "{p50, p99, n, violations}" % (where, k))
            for ck in ("n", "violations"):
                if not isinstance(v[ck], int) or isinstance(v[ck], bool) \
                        or v[ck] < 0:
                    raise SchemaError(
                        "%s: divergence[%r].%s must be a non-negative int"
                        % (where, k, ck))
            for pk in ("p50", "p99"):
                if not _num(v[pk]) or v[pk] < 0:
                    raise SchemaError(
                        "%s: divergence[%r].%s must be a non-negative "
                        "number" % (where, k, pk))
            if v["p99"] < v["p50"]:
                raise SchemaError(
                    "%s: divergence[%r] p99 below p50 — percentiles "
                    "swapped?" % (where, k))
    if "router_policy" in obj and obj["router_policy"] not in ROUTER_POLICIES:
        raise SchemaError(
            "%s: 'router_policy' must be one of %s (omit the key when no "
            "router fronted the run), got %r"
            % (where, sorted(ROUTER_POLICIES), obj["router_policy"]))
    if "priority" in obj:
        pb = obj["priority"]
        if not isinstance(pb, dict) or not pb:
            raise SchemaError(
                "%s: 'priority' must be a non-empty object of priority "
                "class -> per-class stats (omit the key when no --class-mix "
                "ran)" % where)
        for k, v in pb.items():
            if not isinstance(k, str) or not k:
                raise SchemaError(
                    "%s: priority class names must be non-empty strings"
                    % where)
            if not isinstance(v, dict):
                raise SchemaError("%s: priority[%r] must be an object"
                                  % (where, k))
            unknown = set(v) - PRIORITY_REQ_KEYS - PRIORITY_OPT_KEYS
            if unknown:
                raise SchemaError(
                    "%s: priority[%r] unknown keys %s (schema: %s + "
                    "optional %s)" % (where, k, sorted(unknown),
                                      sorted(PRIORITY_REQ_KEYS),
                                      sorted(PRIORITY_OPT_KEYS)))
            missing = PRIORITY_REQ_KEYS - set(v)
            if missing:
                raise SchemaError("%s: priority[%r] missing keys %s"
                                  % (where, k, sorted(missing)))
            for ck in ("requests", "completed", "sheds", "downgrades"):
                if not isinstance(v[ck], int) or isinstance(v[ck], bool) \
                        or v[ck] < 0:
                    raise SchemaError(
                        "%s: priority[%r].%s must be a non-negative int"
                        % (where, k, ck))
            if v["completed"] > v["requests"]:
                raise SchemaError("%s: priority[%r] completed > requests"
                                  % (where, k))
            for nk in ("p50_ms", "p99_ms", "goodput_rps"):
                if not _num(v[nk]) or v[nk] < 0:
                    raise SchemaError(
                        "%s: priority[%r].%s must be a non-negative number"
                        % (where, k, nk))
            if v["p99_ms"] < v["p50_ms"]:
                raise SchemaError(
                    "%s: priority[%r] p99 below p50 — percentiles swapped?"
                    % (where, k))
            if "slo_ms" in v and (not _num(v["slo_ms"]) or v["slo_ms"] <= 0):
                raise SchemaError(
                    "%s: priority[%r].slo_ms must be a positive number "
                    "(omit when no per-class target was set)" % (where, k))


def validate_capture(path):
    """Validate a driver capture (or a raw bench line file)."""
    with open(path, encoding="utf-8") as f:
        obj = json.load(f)
    if isinstance(obj, dict) and "parsed" in obj:
        if obj.get("rc", 0) != 0:
            print("%s: rc=%s capture — skipping parse check" % (path, obj["rc"]))
            return
        if obj["parsed"] is None:
            raise SchemaError("%s: rc=0 capture with no parsed bench line"
                              % path)
        validate_line(obj["parsed"], path)
    else:
        validate_line(obj, path)


def self_test():
    good = [
        {"metric": "m", "value": 1.5, "unit": "img/s", "vs_baseline": None},
        {"metric": "m", "value": 1, "unit": "img/s", "vs_baseline": 2.0,
         "telemetry": {"compile_s": 3.2, "peak_hbm_bytes": 123,
                       "data_wait_frac": 0.01}},
        {"metric": "m", "value": 1, "unit": "img/s",
         "telemetry": {"compile_s": 0.0, "peak_hbm_bytes": None,
                       "data_wait_frac": 0.0}},
        {"metric": "m", "value": 1, "unit": "samples/s",
         "telemetry": {"compile_s": 0.0, "peak_hbm_bytes": None,
                       "data_wait_frac": 0.0, "dispatches_per_step": 1.0}},
        {"metric": "m", "value": 1, "unit": "samples/s",
         "telemetry": {"compile_s": 0.0, "peak_hbm_bytes": None,
                       "data_wait_frac": 0.0, "dispatches_per_step": None}},
        {"metric": "m", "value": 1, "unit": "samples/s",
         "telemetry": {"compile_s": 0.0, "peak_hbm_bytes": None,
                       "data_wait_frac": 0.0, "warmup_s": 1.25}},
        {"metric": "m", "value": 1, "unit": "samples/s",
         "telemetry": {"compile_s": 0.0, "peak_hbm_bytes": None,
                       "data_wait_frac": 0.0, "warmup_s": None}},
        {"metric": "m", "value": 1, "unit": "samples/s",
         "telemetry": {"compile_s": 0.0, "peak_hbm_bytes": None,
                       "data_wait_frac": 0.0, "graph_nodes_pre": 34,
                       "graph_nodes_post": 27, "pass_time_s": 0.002}},
        {"metric": "m", "value": 1, "unit": "samples/s",
         "telemetry": {"compile_s": 0.0, "peak_hbm_bytes": None,
                       "data_wait_frac": 0.0, "graph_nodes_pre": None,
                       "graph_nodes_post": None, "pass_time_s": None}},
        {"metric": "m", "value": 1, "unit": "samples/s",
         "telemetry": {"compile_s": 0.0, "peak_hbm_bytes": None,
                       "data_wait_frac": 0.0, "autotune_trials": 15}},
        {"metric": "m", "value": 1, "unit": "samples/s",
         "telemetry": {"compile_s": 0.0, "peak_hbm_bytes": None,
                       "data_wait_frac": 0.0, "autotune_trials": None}},
        # ISSUE 18 learned autotuning: measurements the cost model skipped
        {"metric": "m", "value": 1, "unit": "samples/s",
         "telemetry": {"compile_s": 0.0, "peak_hbm_bytes": None,
                       "data_wait_frac": 0.0, "autotune_trials": 2,
                       "trials_saved": 3}},
        {"metric": "m", "value": 1, "unit": "samples/s",
         "telemetry": {"compile_s": 0.0, "peak_hbm_bytes": None,
                       "data_wait_frac": 0.0, "trials_saved": None}},
        {"metric": "m", "value": 1, "unit": "samples/s",
         "telemetry": {"compile_s": 0.0, "peak_hbm_bytes": None,
                       "data_wait_frac": 0.0, "serve_p50_ms": 2.5,
                       "serve_p99_ms": 11.0}},
        {"metric": "m", "value": 1, "unit": "samples/s",
         "telemetry": {"compile_s": 0.0, "peak_hbm_bytes": None,
                       "data_wait_frac": 0.0, "serve_p50_ms": None,
                       "serve_p99_ms": None}},
        {"metric": "m", "value": 1, "unit": "samples/s",
         "telemetry": {"compile_s": 0.0, "peak_hbm_bytes": None,
                       "data_wait_frac": 0.0,
                       "trainhealth_drain_s": 0.0213}},
        {"metric": "m", "value": 1, "unit": "samples/s",
         "telemetry": {"compile_s": 0.0, "peak_hbm_bytes": None,
                       "data_wait_frac": 0.0,
                       "trainhealth_drain_s": None}},
        {"metric": "m", "value": 1, "unit": "samples/s",
         "telemetry": {"compile_s": 0.0, "peak_hbm_bytes": None,
                       "data_wait_frac": 0.0, "xla_flops": 528383,
                       "xla_peak_bytes": 32788}},
        {"metric": "m", "value": 1, "unit": "samples/s",
         "telemetry": {"compile_s": 0.0, "peak_hbm_bytes": None,
                       "data_wait_frac": 0.0, "xla_flops": None,
                       "xla_peak_bytes": None}},
        # ISSUE 15: per-tier deploy-twin rows
        {"metric": "m", "value": 1, "unit": "samples/s", "tier": "fp32"},
        {"metric": "m", "value": 1, "unit": "samples/s", "tier": "bf16"},
        {"metric": "m", "value": 1, "unit": "samples/s", "tier": "int8"},
        # ISSUE 19 pod observability: aggregator rollup on multichip rows
        {"metric": "m", "value": 1, "unit": "samples/s",
         "telemetry": {"compile_s": 0.0, "peak_hbm_bytes": None,
                       "data_wait_frac": 0.0,
                       "pod": {"ranks": 2, "max_step_lag": 3,
                               "ledger_divergences": 0, "incidents": 1}}},
        {"metric": "m", "value": 1, "unit": "samples/s",
         "telemetry": {"compile_s": 0.0, "peak_hbm_bytes": None,
                       "data_wait_frac": 0.0, "pod": None}},
    ]
    bad = [
        {},                                                  # empty
        {"metric": "m", "value": "fast", "unit": "img/s"},   # value type
        {"metric": "m", "value": 1, "unit": "img/s", "extra": 1},
        {"metric": "m", "value": 1, "unit": "img/s",
         "telemetry": {"compile_s": 1.0}},                   # missing keys
        {"metric": "m", "value": 1, "unit": "img/s",
         "telemetry": {"compile_s": 1.0, "peak_hbm_bytes": 1.5,
                       "data_wait_frac": 0.0}},              # float bytes
        {"metric": "m", "value": 1, "unit": "img/s",
         "telemetry": {"compile_s": 1.0, "peak_hbm_bytes": None,
                       "data_wait_frac": 1.7}},              # frac range
        {"metric": "m", "value": 1, "unit": "img/s",
         "telemetry": {"compile_s": 1.0, "peak_hbm_bytes": None,
                       "data_wait_frac": 0.0,
                       "dispatches_per_step": -2}},          # negative dps
        {"metric": "m", "value": 1, "unit": "img/s",
         "telemetry": {"compile_s": 1.0, "peak_hbm_bytes": None,
                       "data_wait_frac": 0.0, "warmup_s": -1}},  # neg warmup
        {"metric": "m", "value": 1, "unit": "img/s",
         "telemetry": {"compile_s": 1.0, "peak_hbm_bytes": None,
                       "data_wait_frac": 0.0,
                       "graph_nodes_post": 1.5}},        # float node count
        {"metric": "m", "value": 1, "unit": "img/s",
         "telemetry": {"compile_s": 1.0, "peak_hbm_bytes": None,
                       "data_wait_frac": 0.0,
                       "graph_nodes_pre": -3}},          # negative nodes
        {"metric": "m", "value": 1, "unit": "img/s",
         "telemetry": {"compile_s": 1.0, "peak_hbm_bytes": None,
                       "data_wait_frac": 0.0,
                       "pass_time_s": -0.1}},            # negative pass time
        {"metric": "m", "value": 1, "unit": "img/s",
         "telemetry": {"compile_s": 1.0, "peak_hbm_bytes": None,
                       "data_wait_frac": 0.0,
                       "autotune_trials": 1.5}},         # float trial count
        {"metric": "m", "value": 1, "unit": "img/s",
         "telemetry": {"compile_s": 1.0, "peak_hbm_bytes": None,
                       "data_wait_frac": 0.0,
                       "trials_saved": -1}},             # negative saved
        {"metric": "m", "value": 1, "unit": "img/s",
         "telemetry": {"compile_s": 1.0, "peak_hbm_bytes": None,
                       "data_wait_frac": 0.0,
                       "trials_saved": 2.5}},            # float saved
        {"metric": "m", "value": 1, "unit": "img/s",
         "telemetry": {"compile_s": 1.0, "peak_hbm_bytes": None,
                       "data_wait_frac": 0.0,
                       "serve_p50_ms": -1.0}},           # negative latency
        {"metric": "m", "value": 1, "unit": "img/s",
         "telemetry": {"compile_s": 1.0, "peak_hbm_bytes": None,
                       "data_wait_frac": 0.0, "serve_p50_ms": 9.0,
                       "serve_p99_ms": 3.0}},            # p99 < p50
        {"metric": "m", "value": 1, "unit": "img/s",
         "telemetry": {"compile_s": 1.0, "peak_hbm_bytes": None,
                       "data_wait_frac": 0.0,
                       "trainhealth_drain_s": -0.5}},    # negative drain
        {"metric": "m", "value": 1, "unit": "img/s",
         "telemetry": {"compile_s": 1.0, "peak_hbm_bytes": None,
                       "data_wait_frac": 0.0,
                       "trainhealth_drain_s": True}},    # bool drain
        {"metric": "m", "value": 1, "unit": "img/s",
         "telemetry": {"compile_s": 1.0, "peak_hbm_bytes": None,
                       "data_wait_frac": 0.0,
                       "xla_flops": 1.5}},               # float flops
        {"metric": "m", "value": 1, "unit": "img/s",
         "telemetry": {"compile_s": 1.0, "peak_hbm_bytes": None,
                       "data_wait_frac": 0.0,
                       "xla_peak_bytes": -8}},           # negative peak
        {"metric": "m", "value": 1, "unit": "img/s",
         "tier": "fp16"},                                # unknown tier
        {"metric": "m", "value": 1, "unit": "img/s",
         "tier": None},                                  # null tier (omit it)
        # ISSUE 19 pod block
        {"metric": "m", "value": 1, "unit": "img/s",
         "telemetry": {"compile_s": 1.0, "peak_hbm_bytes": None,
                       "data_wait_frac": 0.0,
                       "pod": {"ranks": 2.5}}},          # float ranks
        {"metric": "m", "value": 1, "unit": "img/s",
         "telemetry": {"compile_s": 1.0, "peak_hbm_bytes": None,
                       "data_wait_frac": 0.0,
                       "pod": {"ranks": 2,
                               "ledger_divergences": -1}}},  # negative
        {"metric": "m", "value": 1, "unit": "img/s",
         "telemetry": {"compile_s": 1.0, "peak_hbm_bytes": None,
                       "data_wait_frac": 0.0,
                       "pod": {"ranks": 2, "bogus": 1}}},  # unknown key
        {"metric": "m", "value": 1, "unit": "img/s",
         "telemetry": {"compile_s": 1.0, "peak_hbm_bytes": None,
                       "data_wait_frac": 0.0,
                       "pod": {"ranks": 0}}},            # rankless pod
        {"metric": "m", "value": 1, "unit": "img/s",
         "telemetry": {"compile_s": 1.0, "peak_hbm_bytes": None,
                       "data_wait_frac": 0.0,
                       "pod": {"ranks": 2,
                               "incidents": True}}},     # bool counter
        {"metric": "m", "value": 1, "unit": "img/s",
         "telemetry": {"compile_s": 1.0, "peak_hbm_bytes": None,
                       "data_wait_frac": 0.0,
                       "pod": [2]}},                     # wrong type
    ]
    serve_good = {"mode": "closed", "requests": 10, "completed": 9,
                  "shed": 1, "timeouts": 0, "errors": 0, "shed_rate": 0.1,
                  "duration_s": 1.5, "throughput_rps": 6.0,
                  "latency_ms_p50": 2.0, "latency_ms_p99": 9.5,
                  "compiles": 3, "concurrency": 4}
    serve_bad = [
        {},
        dict(serve_good, mode="sideways"),           # unknown mode
        dict(serve_good, shed_rate=1.2),             # rate out of range
        dict(serve_good, compiles=1.5),              # non-int counter
        dict(serve_good, latency_ms_p99=1.0),        # p99 < p50
        dict(serve_good, completed=11),              # completed > requests
        dict(serve_good, extra=1),                   # unknown key
        {k: v for k, v in serve_good.items() if k != "throughput_rps"},
        dict(serve_good, warmup_s=-0.5),             # negative warmup
        dict(serve_good, first_request_ms={}),       # empty map
        dict(serve_good, first_request_ms={"1": -2}),  # negative latency
        dict(serve_good, first_request_ms=[1.0]),    # wrong type
        dict(serve_good, goodput_rps=-1.0),          # negative goodput
        dict(serve_good, slo_ms=0),                  # zero target
        dict(serve_good, latency_by_class={}),       # empty class map
        dict(serve_good, latency_by_class={          # missing n
            "1": {"p50_ms": 1.0, "p99_ms": 2.0}}),
        dict(serve_good, latency_by_class={          # p99 < p50
            "1": {"p50_ms": 5.0, "p99_ms": 2.0, "n": 3}}),
        dict(serve_good, latency_by_class={          # zero count
            "1": {"p50_ms": 1.0, "p99_ms": 2.0, "n": 0}}),
        dict(serve_good, tier="fp16"),               # unknown tier
        dict(serve_good, tier=None),                 # null tier (omit it)
        # ISSUE 16 quality-plane divergence block
        dict(serve_good, divergence={}),             # empty map (omit it)
        dict(serve_good, divergence=None),           # null (omit it)
        dict(serve_good, divergence={                # unknown tier key
            "fp16": {"p50": 0.1, "p99": 0.2, "n": 4, "violations": 0}}),
        dict(serve_good, divergence={                # missing violations
            "bf16": {"p50": 0.1, "p99": 0.2, "n": 4}}),
        dict(serve_good, divergence={                # p99 < p50
            "bf16": {"p50": 0.5, "p99": 0.2, "n": 4, "violations": 0}}),
        dict(serve_good, divergence={                # float count
            "bf16": {"p50": 0.1, "p99": 0.2, "n": 4.5, "violations": 0}}),
        dict(serve_good, divergence={                # negative violations
            "bf16": {"p50": 0.1, "p99": 0.2, "n": 4, "violations": -1}}),
        # ISSUE 17 router priority block
        dict(serve_good, router_policy="static"),    # unknown policy mode
        dict(serve_good, router_policy=None),        # null (omit it)
        dict(serve_good, priority={}),               # empty map (omit it)
        dict(serve_good, priority={"paid": {         # missing downgrades
            "requests": 5, "completed": 5, "sheds": 0,
            "p50_ms": 1.0, "p99_ms": 2.0, "goodput_rps": 4.0}}),
        dict(serve_good, priority={"paid": {         # completed > requests
            "requests": 5, "completed": 6, "sheds": 0, "downgrades": 0,
            "p50_ms": 1.0, "p99_ms": 2.0, "goodput_rps": 4.0}}),
        dict(serve_good, priority={"paid": {         # p99 < p50
            "requests": 5, "completed": 5, "sheds": 0, "downgrades": 0,
            "p50_ms": 3.0, "p99_ms": 2.0, "goodput_rps": 4.0}}),
        dict(serve_good, priority={"paid": {         # float counter
            "requests": 5, "completed": 4.5, "sheds": 0, "downgrades": 0,
            "p50_ms": 1.0, "p99_ms": 2.0, "goodput_rps": 4.0}}),
        dict(serve_good, priority={"paid": {         # zero slo target
            "requests": 5, "completed": 5, "sheds": 0, "downgrades": 0,
            "p50_ms": 1.0, "p99_ms": 2.0, "goodput_rps": 4.0,
            "slo_ms": 0}}),
        dict(serve_good, priority={"paid": {         # unknown per-class key
            "requests": 5, "completed": 5, "sheds": 0, "downgrades": 0,
            "p50_ms": 1.0, "p99_ms": 2.0, "goodput_rps": 4.0,
            "tier": "bf16"}}),
        dict(serve_good, priority={"": {             # empty class name
            "requests": 5, "completed": 5, "sheds": 0, "downgrades": 0,
            "p50_ms": 1.0, "p99_ms": 2.0, "goodput_rps": 4.0}}),
    ]
    for obj in good:
        validate_line(obj, "self-test good")
    validate_serve_line(serve_good, "self-test serve good")
    validate_serve_line(dict(serve_good, mode="open", rate_rps=200.0,
                             batch_fill_mean=0.8), "self-test serve good2")
    validate_serve_line(dict(serve_good, warmup_s=0.42,
                             first_request_ms={"1": 2.5, "4": 3.75}),
                        "self-test serve good3")
    validate_serve_line(dict(serve_good, goodput_rps=5.5, slo_ms=50.0,
                             latency_by_class={
                                 "1": {"p50_ms": 1.5, "p99_ms": 8.0, "n": 40},
                                 "4": {"p50_ms": 2.5, "p99_ms": 9.0, "n": 7}}),
                        "self-test serve good4")
    validate_serve_line(dict(serve_good, tier="bf16"),
                        "self-test serve good5")
    validate_serve_line(dict(serve_good, tier="int8", divergence={
        "int8": {"p50": 0.004, "p99": 0.09, "n": 17, "violations": 0},
        "bf16": {"p50": 0.001, "p99": 0.01, "n": 3, "violations": 1}}),
        "self-test serve good6")
    validate_serve_line(dict(serve_good, router_policy="degrade", priority={
        "paid": {"requests": 8, "completed": 8, "sheds": 0,
                 "downgrades": 0, "p50_ms": 1.2, "p99_ms": 4.0,
                 "goodput_rps": 5.3, "slo_ms": 50.0},
        "best_effort": {"requests": 30, "completed": 26, "sheds": 4,
                        "downgrades": 19, "p50_ms": 2.0, "p99_ms": 9.0,
                        "goodput_rps": 15.0}}),
        "self-test serve good7")
    validate_serve_line(dict(serve_good, router_policy="shed"),
                        "self-test serve good8")
    for i, obj in enumerate(bad):
        try:
            validate_line(obj, "self-test bad[%d]" % i)
        except SchemaError:
            continue
        raise AssertionError("self-test: bad line %d passed: %r" % (i, obj))
    for i, obj in enumerate(serve_bad):
        try:
            validate_serve_line(obj, "self-test serve bad[%d]" % i)
        except SchemaError:
            continue
        raise AssertionError(
            "self-test: bad SERVE_BENCH line %d passed: %r" % (i, obj))
    trace_good = {"t": 0.125, "n": 3, "shapes": {"data": [8]},
                  "class": "open"}
    validate_trace_line(trace_good, "self-test trace good")
    validate_trace_line({"t": 0, "n": 1, "shapes": {"data": []},
                         "class": "closed"}, "self-test trace good2")
    trace_bad = [
        {},
        dict(trace_good, t=-1.0),                    # negative arrival
        dict(trace_good, n=0),                       # empty request
        dict(trace_good, n=2.5),                     # non-int count
        dict(trace_good, shapes={}),                 # no inputs
        dict(trace_good, shapes={"data": [8.5]}),    # float dim
        {k: v for k, v in trace_good.items() if k != "class"},
        dict(trace_good, extra=1),                   # unknown key
    ]
    for i, obj in enumerate(trace_bad):
        try:
            validate_trace_line(obj, "self-test trace bad[%d]" % i)
        except SchemaError:
            continue
        raise AssertionError(
            "self-test: bad trace record %d passed: %r" % (i, obj))


def main(argv):
    args = list(argv)
    if "--self-test" in args:
        args.remove("--self-test")
        self_test()
        print("self-test ok")
    trace_mode = "--trace" in args
    if trace_mode:
        args.remove("--trace")
    rc = 0
    for path in args:
        try:
            if trace_mode:
                n = validate_trace_file(path)
                print("%s: ok (%d trace records)" % (path, n))
                continue
            if path == "-":
                for n, line in enumerate(sys.stdin, 1):
                    line = line.strip()
                    if line.startswith(SERVE_PREFIX):
                        validate_serve_line(
                            json.loads(line[len(SERVE_PREFIX):]),
                            "<stdin>:%d" % n)
                    elif line.startswith("{"):
                        validate_line(json.loads(line), "<stdin>:%d" % n)
            else:
                validate_capture(path)
            print("%s: ok" % path)
        except (SchemaError, json.JSONDecodeError, OSError) as e:
            print("%s: FAIL: %s" % (path, e), file=sys.stderr)
            rc = 1
    return rc


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
