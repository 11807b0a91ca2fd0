"""Benchmark: north-star throughput, single chip.  Prints ONE JSON line.

Default metric: **Deformable R-FCN (ResNet-101) training img/s** at COCO
shapes (608x1024, 80 classes) — the model family this reference fork exists
for (BASELINE.md north star; published ~3.8 img/s on the reference's
GPU setup, external Deformable-ConvNets repo).  The measured step is the
FULL detection train step — ResNet-101 + deformable res5, RPN,
MultiProposal, on-device targets, deformable PS-ROI heads, 4 losses,
momentum SGD — compiled into one XLA module
(examples/deformable_rfcn/train_fused.py).

``MXNET_BENCH=resnet50`` selects the classification headline instead
(ResNet-50 train, baseline 109 img/s on 1x K80,
`example/image-classification/README.md:145-156`);
``MXNET_BENCH=frcnn`` the Faster-RCNN VGG16 fused step (BASELINE config
2, `examples/rcnn/train_fused.py`).

The three model benches measure the chip and nothing else: with no TPU they
exit non-zero and print no result (the toy-trunk CPU runs live in
``tests/test_rfcn_fused.py`` / ``tests/test_frcnn_fused.py``).  Run from the
repo root, one process per chip; the parent of a bench must not have
touched JAX.
"""
import json
import os
import time

import numpy as np


def _emit(payload, attach_telemetry=True):
    """Print one bench JSON line; with MXNET_TELEMETRY enabled, attach
    the telemetry block (compile_s, peak_hbm_bytes, data_wait_frac, and —
    when a Module train loop ran — dispatches_per_step, the ISSUE 3 fused
    step's regression surface, plus trainhealth_drain_s, the ISSUE 12
    health plane's whole host-side overhead; see docs/OBSERVABILITY.md)
    and flush the JSONL event log.  The line's schema is linted by
    ci/check_bench_schema.py.

    ``attach_telemetry=False`` is for FOLLOW-UP rows in a multi-row run
    (the ISSUE 15 per-tier predictor rows): ``telemetry.summary()`` totals
    process-cumulative counters, so a second row would fold the first
    row's compile/memory into its own block and bench_compare would
    mis-attribute fp32 drift to the tier row — per-executable compile
    cost for twins lives in the costplane ledger instead."""
    from mxnet_tpu import telemetry

    if telemetry.enabled():
        if attach_telemetry:
            telemetry.sample_memory()
            payload["telemetry"] = telemetry.summary()
        telemetry.event("bench_result", **payload)
        telemetry.flush()
    print(json.dumps(payload))


def _require_tpu():
    """Exit non-zero, naming the device found, unless ``jax.devices()[0]``
    is a TPU — before any result line can be printed."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            "bench.py: needs a TPU, found platform=%r device_kind=%r "
            "(JAX_PLATFORMS=%r)" % (dev.platform, dev.device_kind,
                                    os.environ.get("JAX_PLATFORMS")))


def main():
    which = os.environ.get("MXNET_BENCH", "rfcn")
    if which == "frcnn":
        return main_frcnn()
    if which == "module":
        return main_module()
    if which == "predictor":
        return main_predictor()
    if which != "resnet50":
        return main_rfcn()
    import jax

    _require_tpu()
    dtype = os.environ.get("MXNET_BENCH_DTYPE", "bfloat16")
    # batch 448 saturates one v5e chip's HBM for ResNet-50 bf16 train
    # (480 falls off the memory cliff); fp32 activations are twice the size,
    # so the fp32 run halves the default batch
    default_batch = 448 if dtype != "float32" else 224
    batch = int(os.environ.get("MXNET_BENCH_BATCH", default_batch))
    iters = int(os.environ.get("MXNET_BENCH_ITERS", 20))
    image = 224

    import mxnet_tpu as mx  # noqa: F401
    from mxnet_tpu.gluon import loss as loss_mod
    from mxnet_tpu.gluon.functional import make_train_step
    from __graft_entry__ import _build_resnet

    # bf16 compute with fp32 master weights is the TPU-native training config
    # (MXU native dtype, halved HBM traffic); MXNET_BENCH_DTYPE=float32 gives
    # the fp32 number (with a halved default batch, above)
    net = _build_resnet(classes=1000, version=50, image_size=image)
    step, state, _meta = make_train_step(
        net, loss_mod.SoftmaxCrossEntropyLoss(), learning_rate=0.05, momentum=0.9,
        compute_dtype=None if dtype == "float32" else dtype,
    )
    from mxnet_tpu import telemetry

    # identity when MXNET_TELEMETRY is off; otherwise counts compiles and
    # attributes first-call wall time to jit_compile_seconds_total
    jstep = telemetry.instrument_step(
        jax.jit(step, donate_argnums=(0,)),
        name="resnet50_train_step", batch_size=batch)

    rng = np.random.RandomState(0)
    x = jax.device_put(rng.randn(batch, 3, image, image).astype(np.float32))
    y = jax.device_put(rng.randint(0, 1000, (batch,)).astype(np.float32))
    key = jax.random.PRNGKey(0)

    # warmup/compile
    state, loss = jstep(state, x, y, key)
    jax.block_until_ready(loss)

    best_dt = None
    for w in range(3):
        # keys precomputed OUTSIDE the timed window: an eager fold_in is
        # several host dispatches per step
        keys = [jax.random.fold_in(key, w * iters + i) for i in range(iters)]
        jax.block_until_ready(keys[-1])
        t0 = time.perf_counter()
        for i in range(iters):
            state, loss = jstep(state, x, y, keys[i])
        jax.block_until_ready(loss)
        dt = time.perf_counter() - t0
        best_dt = dt if best_dt is None else min(best_dt, dt)

    imgs_per_sec = batch * iters / best_dt
    baseline = 109.0  # 1x K80, batch 32
    _emit({
        "metric": "resnet50_train_imgs_per_sec",
        "value": round(imgs_per_sec, 2),
        "unit": "img/s",
        "vs_baseline": round(imgs_per_sec / baseline, 3),
    })


def main_rfcn():
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                    "examples", "deformable_rfcn"))
    from train_fused import run_bench

    _require_tpu()
    # batch 8 is the round-4 single-chip optimum (roofline:
    # examples/quality/rfcn_roofline.py — 33.8 img/s after the
    # deformable-conv one-hot-matmul rewrite moved batch 1 to 99% of its
    # HBM bound; batch 4: 32.0, batch 1: 23.5); scaling beyond this is
    # capped by near-linear bytes/step growth, see docs/PERF_NOTES.md
    batch = int(os.environ.get("MXNET_BENCH_BATCH", 8))
    iters = int(os.environ.get("MXNET_BENCH_ITERS", 10))
    imgs_per_sec, _ms, _loss = run_bench(
        resnet101=True, batch=batch, iters=iters, dtype="bfloat16",
        verbose=False)
    baseline = 3.8  # Deformable R-FCN reference throughput (BASELINE.md)
    _emit({
        "metric": "deformable_rfcn_r101_coco_train_imgs_per_sec",
        "value": round(imgs_per_sec, 2),
        "unit": "img/s",
        "vs_baseline": round(imgs_per_sec / baseline, 3),
    })


def main_module():
    """``MXNET_BENCH=module``: symbolic Module train-step microbench
    (ISSUE 3 fused executor).  A small MLP driven through the
    forward_backward/update loop; with MXNET_TELEMETRY=1 the emitted
    telemetry block carries ``dispatches_per_step`` — 1.0 on the fused path
    vs 2+P legacy (set MXNET_MODULE_FUSED_STEP=0 to measure the regression
    surface the fused path removes)."""
    import mxnet_tpu as mx
    from mxnet_tpu import module as mod_mod
    from mxnet_tpu.io import DataBatch

    batch = int(os.environ.get("MXNET_BENCH_BATCH", 64))
    iters = int(os.environ.get("MXNET_BENCH_ITERS", 50))
    rng = np.random.RandomState(0)
    X = rng.randn(batch, 128).astype(np.float32)
    y = rng.randint(0, 10, (batch,)).astype(np.float32)

    data = mx.sym.var("data")
    h = mx.sym.Activation(
        mx.sym.FullyConnected(data, name="fc1", num_hidden=256),
        name="a1", act_type="relu")
    h = mx.sym.Activation(
        mx.sym.FullyConnected(h, name="fc2", num_hidden=256),
        name="a2", act_type="relu")
    sym = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(h, name="fc3", num_hidden=10), name="softmax")

    mod = mod_mod.Module(sym)
    mod.bind(data_shapes=[("data", (batch, 128))],
             label_shapes=[("softmax_label", (batch,))])
    mod.init_params()
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.05,
                                         "momentum": 0.9})
    b = DataBatch(data=[mx.nd.array(X)], label=[mx.nd.array(y)])
    # trainhealth plane (ISSUE 12): with MXNET_TRAINHEALTH=1 this bench
    # drains like the fit loop would, so the emitted telemetry block's
    # trainhealth_drain_s measures the plane's whole per-step overhead
    # inside the timed loop (None when the gate is off)
    from mxnet_tpu import telemetry

    health = telemetry.trainhealth.plane()
    mod.forward_backward(b)
    mod.update()  # warmup/compile
    mod.get_outputs()[0].asnumpy()
    if health is not None:
        health.drain(mod, step=0)

    t0 = time.perf_counter()
    for i in range(iters):
        mod.forward_backward(b)
        mod.update()
        if health is not None:
            mod.get_outputs()[0].asnumpy()  # the fit loop's metric sync
            health.drain(mod, step=i + 1)
    mod.get_outputs()[0].asnumpy()  # sync the async dispatch chain
    dt = time.perf_counter() - t0
    _emit({
        "metric": "module_mlp_train_samples_per_sec",
        "value": round(batch * iters / dt, 2),
        "unit": "samples/s",
        "vs_baseline": None,
    })


def main_predictor():
    """``MXNET_BENCH=predictor``: symbolic inference-twin microbench
    (ISSUE 7 graph passes).  A two-head deploy graph — conv+BN trunk, then
    a classifier head AND an embedding head, each re-deriving the pooled
    trunk features through a shared helper (the standard exporter pattern:
    every head's builder recomputes its own normalize/flatten chain, so
    the captured graph carries duplicated subexpressions the passes merge;
    dropout nodes vanish from the eval plan and BatchNorms become affine).
    Driven through ``Predictor.forward`` — the serving shape the bucket
    ladder compiles.  With MXNET_TELEMETRY=1 the telemetry block carries
    ``graph_nodes_pre``/``graph_nodes_post``/``pass_time_s`` and
    ``compile_s`` (the first forward's trace+compile, via note_compile);
    run with MXNET_GRAPH_PASSES=0 to measure the unoptimized plan the
    passes replace (docs/PERF_NOTES.md "Graph passes").

    With ``MXNET_PRECISION_TIER=bf16|int8`` set (ISSUE 15) a SECOND line
    follows for that deploy twin (``Predictor.with_precision``) — each
    line carries the ``tier`` discriminator, so bench_compare diffs
    fp32-vs-fp32 and twin-vs-twin but never across tiers
    (docs/PERF_NOTES.md "Precision tiers")."""
    from mxnet_tpu.predictor import Predictor
    from mxnet_tpu.graph_passes import precision
    from mxnet_tpu.test_utils import deploy_twin_checkpoint

    batch = int(os.environ.get("MXNET_BENCH_BATCH", 16))
    iters = int(os.environ.get("MXNET_BENCH_ITERS", 200))
    image = 32

    # the two-head deploy graph lives in test_utils so the numerics CI
    # (ci/check_numerics.py, ISSUE 11) gates the exact topology benched here
    sym, params, input_shapes = deploy_twin_checkpoint(batch=batch,
                                                       image=image)
    rng = np.random.RandomState(0)

    from mxnet_tpu import telemetry

    pred = Predictor(sym, params, input_shapes)
    # the baseline row is ALWAYS the fp32 plan: with the tier env set, the
    # bind above already built the twin, so rebuild the fp32 sibling
    # explicitly (shared weight buffers either way)
    tier = precision.tier()
    if tier:
        pred = pred.with_precision(None)
    x = rng.rand(batch, 3, image, image).astype(np.float32)

    def run_one(p, label):
        t0 = time.perf_counter()
        p.forward(data=x)
        p.get_output(0)
        telemetry.note_compile(time.perf_counter() - t0,
                               fn="predictor_fwd_%s" % label)
        t0 = time.perf_counter()
        for _ in range(iters):
            p.forward(data=x)
        p.get_output(0)  # sync the async dispatch chain
        dt = time.perf_counter() - t0
        _emit({
            "metric": "predictor_cnn_infer_samples_per_sec",
            "value": round(batch * iters / dt, 2),
            "unit": "samples/s",
            "vs_baseline": None,
            "tier": label,
        }, attach_telemetry=(label == "fp32"))

    run_one(pred, "fp32")
    if tier:
        calibration = None
        if tier == "int8":
            calibration = precision.calibrate(
                pred, ({"data": rng.rand(batch, 3, image, image)
                        .astype(np.float32)} for _ in range(4)))
        run_one(pred.with_precision(tier, calibration), tier)


def main_frcnn():
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                    "examples", "rcnn"))
    from train_fused import run_bench

    _require_tpu()
    # batch 8 is the round-4 optimum (55.7 img/s; 16 plateaus at 57.3 —
    # docs/PERF_NOTES.md Faster-RCNN section)
    batch = int(os.environ.get("MXNET_BENCH_BATCH", 8))
    iters = int(os.environ.get("MXNET_BENCH_ITERS", 10))
    imgs_per_sec, _ms, _loss = run_bench(
        vgg16=True, batch=batch, iters=iters, dtype="bfloat16",
        verbose=False)
    # no published img/s in the reference tree for this recipe (the bar
    # is mAP 70.23, example/rcnn/README.md:38-42) — vs_baseline omitted
    _emit({
        "metric": "faster_rcnn_vgg16_voc_train_imgs_per_sec",
        "value": round(imgs_per_sec, 2),
        "unit": "img/s",
        "vs_baseline": None,
    })


if __name__ == "__main__":
    main()
