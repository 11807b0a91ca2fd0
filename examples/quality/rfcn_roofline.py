"""Roofline + batch-scaling measurement for the fused Deformable R-FCN step.

VERDICT round-2 item 1: ResNet-50 got an XLA cost analysis (flops, bytes,
peak temp) that proved it HBM-bound at ~100% of the hand-written ceiling;
the north-star step had nothing.  This script publishes the same numbers
for ``make_rfcn_train_step`` (batch 1..N) so "fast" is judged against the
chip's roofline, not just the 2018 GPU bar.

Usage (on a machine with a chip, from the repo root):
    python examples/quality/rfcn_roofline.py --batches 1 2 4
    python examples/quality/rfcn_roofline.py --batches 1 --ledger rfcn.jsonl

Prints, per batch size: cost-analysis flops/bytes, the implied MXU/HBM
time bounds (v5e: ~197 bf16 TFLOP/s, ~819 GB/s HBM), measured ms/step and
img/s.  Timing: chained steps with donated state, scalar-only fetch
(docs/PERF_NOTES.md "Measurement note").

``--ledger`` records each batch size's executable into a compile-plane
cost ledger (ISSUE 13; it enables ``MXNET_COSTPLANE`` for this process),
so the roofline workflow no longer hand-saves ``cost_analysis()`` JSON:
``tools/trace_summary.py --device-kind "TPU v5 lite" profile.json --ledger
rfcn.jsonl`` merges the measured module totals, and ``tools/bench_compare.py old.jsonl new.jsonl
--gate-cost`` turns a flop/peak regression between two builds into a CI
failure (docs/tutorials/performance.md).
"""
from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", ".."))

import numpy as np

V5E_BF16_TFLOPS = 197e12
V5E_HBM_BPS = 819e9


sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", "deformable_rfcn"))


def analyze(batch, image_shape, iters, windows, dtype="bfloat16",
            ledger=False):
    import jax

    import mxnet_tpu as mx
    from train_fused import build_net, make_rfcn_train_step, synthetic_coco

    mx.random.seed(0)
    rng = np.random.RandomState(0)
    net, shape, classes = build_net(True, image_shape, None)
    data, im_info, gt = synthetic_coco(rng, batch, shape, classes, net.max_gts)
    step, state = make_rfcn_train_step(net, batch, learning_rate=5e-4,
                                       momentum=0.9, compute_dtype=dtype)
    jstep = jax.jit(step, donate_argnums=(0,))
    key = jax.random.PRNGKey(0)
    d = jax.device_put(data)
    i = jax.device_put(im_info)
    g = jax.device_put(gt)

    t0 = time.time()
    lowered = jstep.lower(state, d, i, g, key)
    comp = lowered.compile()
    compile_s = time.time() - t0
    if ledger:
        # compile-plane row (ISSUE 13): the same extraction the library's
        # compile sites use, keyed stably by batch/shape/dtype so two
        # builds' ledgers diff row-for-row in bench_compare --gate-cost
        from mxnet_tpu.telemetry import costplane

        costplane.record_compile(
            "rfcn_train_step",
            ("rfcn_train_step", tuple(image_shape), dtype),
            "batch%d" % batch, comp, compile_s)
    ca = comp.cost_analysis()
    ca = ca[0] if isinstance(ca, (list, tuple)) else (ca or {})
    flops = float(ca.get("flops", 0.0))
    bytes_acc = float(ca.get("bytes accessed", 0.0))
    peak = None
    try:
        ma = comp.memory_analysis()
        peak = getattr(ma, "temp_size_in_bytes", None)
    except Exception:
        pass

    # timed chained steps on the ALREADY-COMPILED executable (jax's AOT path
    # doesn't seed the jit cache — calling jstep would recompile), state
    # donated, scalar fetch only
    state, loss, parts = comp(state, d, i, g, key)
    jax.block_until_ready(loss)
    best = None
    for w in range(windows):
        keys = [jax.random.fold_in(key, w * 1000 + it) for it in range(iters)]
        jax.block_until_ready(keys[-1])
        t0 = time.perf_counter()
        for it in range(iters):
            state, loss, parts = comp(state, d, i, g, keys[it])
        float(loss)
        dt = (time.perf_counter() - t0) / iters
        best = dt if best is None else min(best, dt)

    mxu_ms = flops / V5E_BF16_TFLOPS * 1e3
    hbm_ms = bytes_acc / V5E_HBM_BPS * 1e3
    return dict(batch=batch, compile_s=compile_s, flops=flops,
                bytes=bytes_acc, peak=peak, mxu_ms=mxu_ms, hbm_ms=hbm_ms,
                ms=best * 1e3, img_s=batch / best, loss=float(loss))


def main():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--batches", type=int, nargs="+", default=[1, 2])
    p.add_argument("--image-shape", type=int, nargs=2, default=[608, 1024])
    p.add_argument("--iters", type=int, default=10)
    p.add_argument("--windows", type=int, default=3)
    p.add_argument("--ledger", default=None,
                   help="record each executable into this compile-plane "
                        "cost ledger (sets MXNET_COSTPLANE/MXNET_COST_"
                        "LEDGER for this process; read it back with "
                        "trace_summary --ledger / bench_compare "
                        "--gate-cost)")
    args = p.parse_args()
    if args.ledger:
        os.environ["MXNET_COSTPLANE"] = "1"
        os.environ["MXNET_COST_LEDGER"] = args.ledger

    rows = []
    for b in args.batches:
        try:
            r = analyze(b, tuple(args.image_shape), args.iters, args.windows,
                        ledger=bool(args.ledger))
        except Exception as exc:  # OOM at larger batches is a finding, not a crash
            print("batch %d FAILED: %r" % (b, exc))
            continue
        rows.append(r)
        print("batch %d: compile %.0fs | %.2f TF, %.1f GB%s | bounds: MXU %.1f ms, "
              "HBM %.1f ms | measured %.1f ms/step = %.2f img/s | loss %.4f"
              % (r["batch"], r["compile_s"], r["flops"] / 1e12, r["bytes"] / 1e9,
                 (", peak temp %.1f GB" % (r["peak"] / 1e9)) if r["peak"] else "",
                 r["mxu_ms"], r["hbm_ms"], r["ms"], r["img_s"], r["loss"]),
              flush=True)
    if rows:
        b1 = rows[0]
        for r in rows[1:]:
            print("scaling: batch %d = %.2fx batch-%d throughput (linear would be %.1fx)"
                  % (r["batch"], r["img_s"] / b1["img_s"], b1["batch"],
                     r["batch"] / b1["batch"]), flush=True)


if __name__ == "__main__":
    main()
