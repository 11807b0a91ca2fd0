#!/usr/bin/env python
"""Autotuning smoke (ISSUE 9) — run from ci/run_tests.sh unit tier.

End-to-end over real subprocesses, the way an operator would run it:

1. ``tools/loadgen.py --save-trace`` records a skewed traffic trace
   (request sizes 3/5/6 against the default 1,2,4,8 ladder — every
   request pads badly) and the trace passes the schema lint;
2. the ladder tuner's proposal from that trace scores a STRICTLY lower
   padding-waste x compile-count objective than the default ladder on
   the same trace (the ISSUE 9 acceptance);
3. ``tools/autotune.py search`` (measured dconv block-shape search on a
   CPU-sized problem, then the ladder search) persists winners, and a
   SECOND run of each against the warm store performs ZERO new
   measurements;
4. the dconv winner is never worse than the hand-tuned default on the
   microbench (the searcher measures the default first and keeps it on
   ties);
5. (ISSUE 18) two exhaustive-grid seeding runs under MXNET_COSTPLANE
   accumulate trial rows, then the learned cost model's
   predict-then-measure finds the known dconv winner deterministically
   (trial seconds replayed from the store) with at most HALF the grid's
   measured trials — the acceptance gate;
6. a CLI ``--strategy predict`` run at a fresh shape measures at most
   half its grid, surfaces ``trials_saved`` (AUTOTUNE line and bench
   telemetry block, schema-linted), stays never-worse, and a second run
   is a warm hit with zero measurements;
7. ``--all-kernels`` sweeps every runnable space — the new kernel spaces
   plus the non-kernel ``fused_step_layout`` — each recording an
   AUTOTUNE line, with one final telemetry block.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def run(cmd, env=None):
    print("+ %s" % " ".join(cmd), flush=True)
    proc = subprocess.run(cmd, capture_output=True, text=True, env=env,
                          cwd=REPO, timeout=600)
    sys.stdout.write(proc.stdout)
    sys.stderr.write(proc.stderr)
    if proc.returncode != 0:
        raise SystemExit("FAIL: %r exited %d" % (cmd, proc.returncode))
    return proc.stdout


def autotune_line(out):
    for line in out.splitlines():
        if line.startswith("AUTOTUNE "):
            return json.loads(line[len("AUTOTUNE "):])
    raise SystemExit("FAIL: no AUTOTUNE line in output")


def main():
    from ci.check_bench_schema import validate_trace_file

    tmp = tempfile.mkdtemp(prefix="mxnet_autotune_smoke_")
    trace = os.path.join(tmp, "trace.jsonl")
    env = dict(os.environ)
    env["MXNET_AUTOTUNE_CACHE"] = os.path.join(tmp, "autotune.json")
    py = sys.executable

    # 1: record traffic whose sizes (3/5/6) the default ladder pads badly
    run([py, os.path.join(REPO, "tools", "loadgen.py"), "--mode", "open",
         "--rate", "150", "--duration", "1.0", "--sizes", "3,5,6",
         "--batch-ladder", "1,2,4,8", "--save-trace", trace], env=env)
    n = validate_trace_file(trace)
    print("trace lint ok: %d records" % n)

    # 2: the proposal beats the default on its own trace (acceptance).
    # Replay with the SAME flush deadline the recording engine batched
    # under (loadgen's default --max-wait-ms 2), so the tuner models the
    # coalescing that actually produced — and would serve — this traffic
    from mxnet_tpu.autotune import ladder as lt

    wait_s = 0.002
    recs = lt.load_trace(trace)
    obj_default = lt.objective((1, 2, 4, 8), recs, max_wait_s=wait_s)
    tuned, rep = lt.propose(recs, max_wait_s=wait_s)
    print("ladder objective: default %.4f -> tuned %s %.4f"
          % (obj_default, tuned, rep["objective_tuned"]))
    assert rep["objective_tuned"] < obj_default, \
        "proposed ladder %s did not beat the default (%.4f >= %.4f)" % (
            tuned, rep["objective_tuned"], obj_default)

    at = os.path.join(REPO, "tools", "autotune.py")
    # 3a: measured dconv search (CPU-sized problem), never-worse winner
    out = autotune_line(run(
        [py, at, "search", "--kernel", "dconv_col_pallas",
         "--warmup", "1", "--repeat", "2"], env=env))
    assert out["measurements"] > 0 and not out["cached"]
    # never-worse is a BEHAVIORAL gate: a non-default winner must have
    # strictly beaten the measured default (best_s <= default_s holds by
    # construction, so asserting only that could never catch a searcher
    # that prefers a tying candidate over the hand-tuned default)
    from mxnet_tpu.autotune import get_space

    default_cfg = get_space("dconv_col_pallas").default
    assert out["config"] == default_cfg or out["best_s"] < out["default_s"], \
        "non-default winner must STRICTLY beat the measured default: %r" % out
    # 3b: warm store => zero new measurements
    out2 = autotune_line(run(
        [py, at, "search", "--kernel", "dconv_col_pallas",
         "--warmup", "1", "--repeat", "2"], env=env))
    assert out2["cached"] and out2["measurements"] == 0, out2
    assert out2["config"] == out["config"]

    # 3c: same persistence contract for the ladder search (again at the
    # recording engine's 2 ms flush deadline)
    out3 = autotune_line(run([py, at, "search", "--trace", trace,
                              "--max-wait-ms", "2"], env=env))
    assert not out3["cached"]
    assert out3["objective_tuned"] < out3["objective_default"], out3
    out4 = autotune_line(run([py, at, "search", "--trace", trace,
                              "--max-wait-ms", "2"], env=env))
    assert out4["cached"] and out4["measurements"] == 0, out4

    show = run([py, at, "show"], env=env)
    assert "dconv_col_pallas" in show and "bucket_ladder" in show

    # ------------------------------------------------------------------
    # ISSUE 18: learned cost model over the pipeline
    # ------------------------------------------------------------------
    def autotune_lines(out, kind=None):
        got = []
        for line in out.splitlines():
            if line.startswith("AUTOTUNE "):
                d = json.loads(line[len("AUTOTUNE "):])
                if kind is None or d.get("kind") == kind:
                    got.append(d)
        return got

    env18 = dict(env)
    env18["MXNET_COSTPLANE"] = "1"   # trial rows carry ledger features
    env18["MXNET_TELEMETRY"] = "1"   # counters + the trailing block

    # 5a: seed the store with exhaustive-grid trial rows at two shapes
    for n in ("384", "512"):
        seeded = autotune_line(run(
            [py, at, "search", "--kernel", "dconv_col_pallas", "--n", n,
             "--strategy", "grid", "--warmup", "0", "--repeat", "1"],
            env=env18))
        assert seeded["strategy"] == "grid" and not seeded["cached"], seeded

    # 5b: DETERMINISTIC acceptance gate — fit the model from the seeded
    # store, replay the recorded per-config seconds as the measurer, and
    # require predict-then-measure to reach an equal-or-better winner
    # than the exhaustive grid with <= 50% of its measured trials
    os.environ["MXNET_AUTOTUNE"] = "1"
    os.environ["MXNET_AUTOTUNE_CACHE"] = env["MXNET_AUTOTUNE_CACHE"]
    from mxnet_tpu.autotune import costmodel
    from mxnet_tpu.autotune import search as at_search
    from mxnet_tpu.autotune import store as at_store

    rows = costmodel.training_rows("dconv_col_pallas")
    assert len(rows) >= 2 * costmodel.MIN_ROWS, \
        "seeding left only %d training rows" % len(rows)
    model = costmodel.model_for("dconv_col_pallas")
    assert model is not None and model.ready
    sig512 = "N512-HW32-C16-i4"
    replay = {tuple(sorted(r["config"].items())): r["seconds"]
              for r in rows if r["sig"] == sig512}
    assert len(replay) >= 4, "expected a full seeded grid at N512: %r" % replay
    grid_best = min(replay.values())
    measured = []

    def replay_measure(cfg):
        measured.append(cfg)
        return replay[tuple(sorted(cfg.items()))]

    best, results, repd = at_search.predict_then_measure(
        get_space("dconv_col_pallas"), replay_measure,
        lambda c: model.predict_one(sig512, c,
                                    device_kind=at_store._device_kind()),
        ctx={"N": 512, "HW": 32, "C": 16, "itemsize": 4}, top_k=1)
    best_s = min(r["seconds"] for r in results)
    assert len(measured) <= repd["candidates"] // 2, \
        "predict measured %d of %d (> 50%%)" % (len(measured),
                                                repd["candidates"])
    assert best_s <= grid_best, \
        "predict winner %r (%.6f s) worse than grid best %.6f s" % (
            best, best_s, grid_best)
    print("model gate: winner %r in %d/%d measurements (grid best matched)"
          % (best, len(measured), repd["candidates"]))

    # 6: CLI predict leg at a FRESH shape: fewer measurements, the
    # trials_saved surface, never-worse, schema-linted telemetry block
    pred_out = run(
        [py, at, "search", "--kernel", "dconv_col_pallas", "--n", "256",
         "--strategy", "predict", "--top-k", "1",
         "--warmup", "0", "--repeat", "1"], env=env18)
    outp = autotune_lines(pred_out, kind="dconv")[0]
    assert outp["strategy"] == "predict", outp
    assert outp["measurements"] <= max(1, outp["grid"] // 2), outp
    assert outp["trials_saved"] == outp["grid"] - outp["measurements"], outp
    assert outp["config"] == default_cfg \
        or outp["best_s"] < outp["default_s"], \
        "predict winner must stay never-worse: %r" % outp
    tel = autotune_lines(pred_out, kind="telemetry")
    assert tel, "no telemetry block after a telemetry-enabled search"
    assert tel[0]["telemetry"]["trials_saved"] == outp["trials_saved"], tel
    from ci.check_bench_schema import validate_line

    validate_line({"metric": "autotune_smoke", "value": 1, "unit": "runs",
                   "telemetry": tel[0]["telemetry"]}, "autotune telemetry")
    # 6b: warm store again beats everything — zero measurements
    outw = autotune_line(run(
        [py, at, "search", "--kernel", "dconv_col_pallas", "--n", "256",
         "--strategy", "predict", "--top-k", "1",
         "--warmup", "0", "--repeat", "1"], env=env18))
    assert outw["cached"] and outw["measurements"] == 0, outw

    # 7: --all-kernels sweeps every runnable space (small shapes); the
    # new kernel spaces AND the non-kernel layout space all record lines
    sweep_out = run(
        [py, at, "search", "--all-kernels", "--warmup", "0", "--repeat",
         "1", "--n", "96", "--nms-boxes", "256", "--q-rows", "256",
         "--fs-steps", "2"], env=env18)
    swept = {d["kernel"]: d for d in autotune_lines(sweep_out)
             if "kernel" in d}
    for kern in ("nms_alive_pallas", "quantize_int8_pallas",
                 "dequantize_int8_pallas", "fused_step_layout"):
        assert kern in swept, "--all-kernels skipped %s" % kern
        assert swept[kern]["cached"] or swept[kern]["measurements"] > 0, \
            swept[kern]
    tel2 = autotune_lines(sweep_out, kind="telemetry")
    assert tel2 and "trials_saved" in tel2[0]["telemetry"], tel2
    validate_line({"metric": "autotune_sweep", "value": 1, "unit": "runs",
                   "telemetry": tel2[0]["telemetry"]}, "sweep telemetry")
    show2 = run([py, at, "show", "--features"], env=env)
    assert "fused_step_layout" in show2 and "nms_alive_pallas" in show2
    assert "trial rows:" in show2, "show --features lost the trial rows"
    print("check_autotune: OK")


if __name__ == "__main__":
    main()
