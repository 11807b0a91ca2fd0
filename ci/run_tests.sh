#!/bin/bash
# CI tiers — the reference's layout (ci/docker/runtime_functions.sh:491-599
# unit nosetests; tests/nightly/test_all.sh nightly tier; gpu tier re-runs
# the suite on device) mapped to this repo:
#
#   ./ci/run_tests.sh unit      fast unit tier (CPU, virtual 8-dev mesh)
#   ./ci/run_tests.sh nightly   multi-process dist cluster + example E2E +
#                               quality trainings (slow, CPU)
#   ./ci/run_tests.sh tpu       device tier, on a machine with a chip
#                               (one process per chip, from the repo root):
#                               chip_smoke.py, CPU-vs-TPU
#                               check_consistency, benches, mAP gates
#   ./ci/run_tests.sh all       unit + nightly
set -euo pipefail
SELF="$(cd "$(dirname "$0")" && pwd)/$(basename "$0")"
cd "$(dirname "$0")/.."

NIGHTLY_FILES=(
  tests/test_launch_dist.py
  tests/test_examples_classification.py
  tests/test_examples_detection.py
  tests/test_examples_rnn_sparse.py
  tests/test_examples_quant_dp.py
  tests/test_examples_misc.py
  tests/test_examples_nce_fcn_svm.py
  tests/test_example_deformable_rfcn.py
  tests/test_examples_round3.py
  tests/test_examples_round3b.py
  tests/test_examples_round4.py
  tests/test_examples_round5.py
  tests/test_tutorials.py
  tests/test_quality_map.py
  tests/test_quality_map_frcnn.py
  tests/test_quality_map_ssd.py
)

tier="${1:-unit}"
case "$tier" in
  unit)
    # bench-line schema lint (ISSUE 1): the bench line and its telemetry
    # block must stay machine-parseable for the driver
    python ci/check_bench_schema.py --self-test
    # serving smoke (ISSUE 2): tiny-symbol engine on CPU, closed+open load,
    # SERVE_BENCH lines must parse and pass the schema lint
    ./dev.sh python tools/loadgen.py --smoke \
      | python ci/check_bench_schema.py -
    # tracing smoke (ISSUE 4): serve a few requests + two train steps with
    # MXNET_TRACE=1, export, and validate the chrome trace (ts sanity, X
    # nesting, matched flow ids, cross-thread request trace)
    ./dev.sh python ci/check_trace.py --smoke
    # sharded fused step smoke (ISSUE 5): 2 train steps on an 8-host-device
    # dp mesh must be 1 compiled dispatch each with finite loss
    ./dev.sh python ci/check_mesh_fused.py
    # AOT cache smoke (ISSUE 6): warmup twice against one cache dir in
    # subprocesses — second run must be all cache hits and faster
    ./dev.sh python ci/check_aot_cache.py
    # graph-pass smoke (ISSUE 7): dead branch + duplicated subexpression +
    # constant subgraph must reduce to the hand-counted minimum node count
    # with forward parity against MXNET_GRAPH_PASSES=0
    ./dev.sh python ci/check_graph_passes.py
    # autotuning smoke (ISSUE 9): loadgen-recorded trace lints, the ladder
    # proposal beats the default on that trace, and a second autotune.py
    # run against the warm winner store performs zero new measurements
    ./dev.sh python ci/check_autotune.py
    # live ops plane smoke (ISSUE 10): Engine under MXNET_OPS_PORT=0 —
    # /metrics must parse as Prometheus text and carry the serving
    # counters, /healthz must flip 200->503 when the device loop is
    # frozen, /statusz JSON must round-trip, and the streaming SLO p99
    # must agree with loadgen's offline percentile on the same run
    ./dev.sh python ci/check_ops_server.py
    # source lint (ISSUE 8): mxlint over mxnet_tpu/ must be clean against
    # the committed baseline, and a file of seeded hazards must trip every
    # rule (new findings = nonzero exit; docs/ANALYSIS.md)
    ./dev.sh python ci/check_lint.py
    # numerics smoke (ISSUE 11): seeded precision hazards (bf16-accumulated
    # reduction, mixed-dtype binop, softmax fed an unbounded bf16 range,
    # non-bf16-exact float literal) must ALL trip, and the deploy-twin
    # predictor's cast plan must match the acceptance shape (majority
    # bf16_safe, reductions fp32_accum, unbounded exp/log fp32_only)
    ./dev.sh python ci/check_numerics.py
    # lock-discipline smoke (ISSUE 8): concurrent serving burst under
    # MXNET_LOCKCHECK=1 must record zero violations on the real engine,
    # and the seeded inversion/unguarded-mutation must both be detected
    ./dev.sh python ci/check_lockcheck.py
    # compile plane smoke (ISSUE 13): gate off = no rows, no ledger,
    # AOT-cache keys gate-invariant; gate on = the deploy twin yields
    # ledger rows at every compile site with real CPU-XLA flops/peak
    # numbers, and a seeded halved-flops baseline ledger makes
    # bench_compare --gate-cost exit nonzero while identical ledgers pass
    ./dev.sh python ci/check_costplane.py
    # training-health smoke (ISSUE 12): gate off = no staged stats, no
    # plane, no key marker, no dump; a seeded NaN divergence must trip the
    # verdict-class census + blessed-class violation counter and emit a
    # flightrec dump artifact naming the offending parameter group
    ./dev.sh python ci/check_trainhealth.py
    # precision-tier smoke (ISSUE 15): gate off = structural plans + AOT
    # keys byte-identical; the bf16 deploy twin must meet its rtol
    # contract vs fp32 AND show strictly lower ledger bytes_accessed; a
    # calibrated int8 twin meets tolerance, an uncalibrated one is
    # provably untouched
    ./dev.sh python ci/check_precision_tier.py
    # quality plane smoke (ISSUE 16): gate off = no plane, no shadow
    # thread, no quality stats, AOT keys gate-invariant; gate on at
    # sampling=1.0 = the bf16 deploy twin's shadow-sampled divergence rows
    # all sit inside the tier tolerance with zero violations and the
    # SERVE_BENCH line embeds the divergence block; a poisoned int8
    # calibration table (ranges 100x below live traffic) must trip both
    # the calibration-drift counter and a tolerance-violation flightrec
    # dump naming the tier and bucket
    ./dev.sh python ci/check_quality_plane.py
    # SLO-policy router smoke (ISSUE 17): MXNET_ROUTER_* must not move
    # AOT logical keys (off-path invariance); under the same mixed-
    # priority open-loop overload, degrade-first (best-effort rerouted to
    # the bf16 twin pool) must STRICTLY beat the single-engine and
    # shed-only baselines on paid-class goodput, hold the paid p99 target
    # and label downgraded replies with the serving tier; whole run under
    # MXNET_LOCKCHECK=1 with zero violations
    ./dev.sh python ci/check_router.py
    # pod observability smoke (ISSUE 19): MXNET_POD_METRICS unset leaves
    # the fit loop with no plane/thread/socket and no pod_* series; a
    # 2-process launch.py cluster must aggregate both ranks on /podz,
    # trip the ledger-divergence detector on a seeded fingerprint
    # mismatch with correlated (shared incident id) flightrec dumps on
    # both ranks, and raise a straggler verdict when rank 1 freezes
    ./dev.sh python ci/check_pod_obs.py
    # pod-scale fused training smoke (ISSUE 20): a 2-process launch.py
    # cluster joined into ONE 8-device dp mesh (fused step + ZeRO-1 over
    # the process boundary, per-rank half-batches, Gloo CPU collectives)
    # must match the single-process control bit-for-tolerance after a
    # mid-run straggler checkpoint-and-rejoin through MXNET_ELASTIC_DIR,
    # book its dp collectives as DCN bytes, and warm-restart from
    # per-rank AOT caches with zero fresh compiles and a clean non-empty
    # cross-rank ledger diff
    ./dev.sh python ci/check_pod_train.py
    # chip-smoke R-FCN leg at toy size (ISSUE 21): slow-marked, so the
    # tier-1 time limit is not spent on its XLA:CPU compile; the other
    # chip_smoke.py legs and refusals run with tests/ below
    ./dev.sh python -m pytest tests/test_chip_smoke.py -q -m slow
    # telemetry unit tests (tests/test_telemetry.py) run as part of tests/
    ignore=()
    for f in "${NIGHTLY_FILES[@]}"; do ignore+=(--ignore "$f"); done
    # -m 'not slow': the loadgen smoke above already covers the slow
    # subprocess serving test end-to-end
    exec ./dev.sh python -m pytest tests/ -q -m 'not slow' "${ignore[@]}"
    ;;
  nightly)
    exec ./dev.sh python -m pytest "${NIGHTLY_FILES[@]}" -q
    ;;
  tpu)
    # device tier.  Every command below fails when JAX finds no TPU
    # (chip_smoke.py and bench.py check the platform; under
    # MXNET_TEST_DEVICE=tpu a missing TPU fails the consistency sweep
    # instead of skipping it), so a broken install cannot come out green.
    # chip_smoke.py first: the quickest proof the system still starts on
    # the chip.
    python chip_smoke.py
    MXNET_TEST_DEVICE=tpu python -m pytest tests/test_consistency_tpu.py -q
    python bench.py
    MXNET_BENCH=resnet50 python bench.py
    # detection-quality gates on the chip (VERDICT r2 item 5, recalibrated
    # per ADVICE round 5): each recipe now runs at TWO fixed seeds and the
    # MEDIAN (== mean at n=2) is gated via ci/gate_map.py, replacing the
    # old single-run worst-seed-minus-20% floors (0.07/0.63/0.54/0.26)
    # that, over cross-seed variance as wide as 0.09..0.38, only caught
    # catastrophic (<=0.03) breakage and would pass a halved-mAP
    # regression.  Floors = mean(seed 0, seed 1 calibration, QUALITY.md §3
    # round-4/5 sweeps) − ~20%:
    #   R-FCN R-101  0.0900/0.2743 → mean 0.182 → floor 0.14
    #   FRCNN VGG16  0.8085/0.7883 → mean 0.798 → floor 0.64
    #   SSD-300      0.6802/0.9034 → mean 0.792 → floor 0.63
    #   SSD-512      0.8868/0.3357 → mean 0.611 → floor 0.49
    run_map_gate() {
      local floor="$1"; shift
      local vals=() log
      for seed in 0 1; do
        log="$(mktemp)"
        "$@" --seed "$seed" | tee "$log"
        vals+=("$(python ci/gate_map.py --extract "$log")")
        rm -f "$log"
      done
      python ci/gate_map.py --floor "$floor" "${vals[@]}"
    }
    run_map_gate 0.14 python examples/quality/eval_rfcn_map.py --resnet101 \
      --steps 3000 --live-bn
    run_map_gate 0.64 python examples/quality/eval_frcnn_map.py --vgg16 \
      --steps 3000
    run_map_gate 0.63 python examples/quality/eval_ssd_map.py --full \
      --steps 2000
    run_map_gate 0.49 python examples/quality/eval_ssd_map.py --full \
      --size 512 --steps 2000
    ;;
  all)
    "$SELF" unit
    "$SELF" nightly
    ;;
  *)
    echo "usage: $0 {unit|nightly|tpu|all}" >&2
    exit 2
    ;;
esac
