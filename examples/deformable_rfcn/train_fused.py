"""Deformable R-FCN (ResNet-101) — the north-star workload, jit-fused.

The reference fork exists to run this model (``/root/reference/README.md:1-7``);
its published throughput (~3.8 img/s on a K40, external Deformable-ConvNets
repo) is the BASELINE north-star bar.  Round 1 lost to it because the
detection step was eager + host-synced (host numpy proposal targets).  This
driver compiles the ENTIRE train step — ResNet-101 backbone, RPN,
MultiProposal, on-device anchor/proposal targets, deformable PS-ROI heads,
all four losses, and momentum SGD — into ONE XLA module, exactly like the
classification path's ``make_train_step`` (mxnet_tpu/gluon/functional.py).

Usage:
  python examples/deformable_rfcn/train_fused.py               # tiny CPU run
  python examples/deformable_rfcn/train_fused.py --resnet101 --bench \
      --image-shape 608 1024         # north-star measurement on the chip
"""
from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", ".."))

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu.gluon.functional import build_train_step
from mxnet_tpu.gluon.model_zoo.detection import DeformableRFCN, rfcn_resnet101


def synthetic_coco(rng, batch, image_shape, classes, max_gts):
    """One synthetic COCO-scale batch: bright rectangles on noise.

    Returns (data (B,3,H,W), im_info (B,3), gt (B,G,5) [-1-padded])."""
    h, w = image_shape
    data = (rng.rand(batch, 3, h, w) * 0.2).astype(np.float32)
    gt = np.full((batch, max_gts, 5), -1.0, np.float32)
    for b in range(batch):
        for j in range(rng.randint(1, min(max_gts, 8) + 1)):
            cls = rng.randint(0, classes)
            bw = rng.uniform(0.08, 0.5) * w
            bh = rng.uniform(0.08, 0.5) * h
            x1 = rng.uniform(0, w - bw)
            y1 = rng.uniform(0, h - bh)
            gt[b, j] = [cls, x1, y1, x1 + bw, y1 + bh]
            data[b, cls % 3, int(y1):int(y1 + bh), int(x1):int(x1 + bw)] += 0.8
    im_info = np.tile(np.array([h, w, 1.0], np.float32), (batch, 1))
    return data, im_info, gt


def synthetic_coco_device(key, batch, image_shape, classes, max_gts):
    """``synthetic_coco`` generated ON DEVICE from a PRNG key (all jnp; call
    inside jit).  Same construction — noise canvas, 1..min(G,8) rectangles
    of 0.08-0.5 relative size painted +0.8 onto channel ``cls % 3``, raw
    float coords in gt, -1 padding — but zero host work and zero H2D (a 608
    x1024 batch is 7.5 MB per step that never crosses the host link)."""
    import jax
    import jax.numpy as jnp

    h, w = image_shape
    kn, kg, kc, kw, kh, kx, ky = jax.random.split(key, 7)
    data = jax.random.uniform(kn, (batch, 3, h, w), jnp.float32) * 0.2
    n_boxes = jax.random.randint(kg, (batch,), 1, min(max_gts, 8) + 1)
    cls = jax.random.randint(kc, (batch, max_gts), 0, classes)
    bw = (jax.random.uniform(kw, (batch, max_gts)) * 0.42 + 0.08) * w
    bh = (jax.random.uniform(kh, (batch, max_gts)) * 0.42 + 0.08) * h
    x1 = jax.random.uniform(kx, (batch, max_gts)) * (w - bw)
    y1 = jax.random.uniform(ky, (batch, max_gts)) * (h - bh)
    valid = jnp.arange(max_gts)[None, :] < n_boxes[:, None]
    gt = jnp.where(
        valid[..., None],
        jnp.stack([cls.astype(jnp.float32), x1, y1, x1 + bw, y1 + bh], -1),
        -1.0)
    yy = jnp.arange(h, dtype=jnp.float32)[:, None]
    xx = jnp.arange(w, dtype=jnp.float32)[None, :]
    chan = jax.nn.one_hot(cls % 3, 3)                      # (B, G, 3)

    def paint(g, img):
        # int() truncation bounds, as the host generator paints
        m = ((yy >= jnp.floor(y1[:, g, None, None]))
             & (yy < jnp.floor(y1[:, g] + bh[:, g])[:, None, None])
             & (xx >= jnp.floor(x1[:, g, None, None]))
             & (xx < jnp.floor(x1[:, g] + bw[:, g])[:, None, None])
             & valid[:, g, None, None])
        return img + 0.8 * m[:, None] * chan[:, g, :, None, None]

    data = jax.lax.fori_loop(0, max_gts, paint, data)
    im_info = jnp.tile(jnp.array([h, w, 1.0], jnp.float32), (batch, 1))
    return data, im_info, gt


def _smooth_l1(pred, target, weight, sigma):
    """Weighted smooth-L1 via the registered op (ops/elemwise.py smooth_l1,
    reference mshadow_op.h smooth_l1_loss)."""
    from mxnet_tpu.ops.elemwise import smooth_l1

    return smooth_l1((pred - target) * weight, scalar=sigma)


def make_rfcn_train_step(net, batch, learning_rate=5e-4, momentum=0.9,
                         compute_dtype=None):
    """→ (step, state): ``step(state, data, im_info, gt, key, lr) ->
    (state, loss, parts)``, fully jittable, state donate-able; the step
    itself (learn/aux split, grad, momentum SGD, per-step ``lr``) is
    ``gluon.functional.build_train_step``'s, the loss below is the recipe's.
    It serves every two-stage detector whose net returns these eleven
    outputs: Faster R-CNN's class-specific regression only widens
    ``bbox_pred`` (examples/rcnn/train_fused.py).

    Mixed precision (``compute_dtype='bfloat16'``): parameters and image in
    bf16 for the conv trunk (MXU dtype, halved HBM traffic); box/coordinate
    math stays fp32 — gt/im_info/rois are never downcast, MultiProposal
    upcasts its inputs at entry (ops/detection.py multi_proposal), and the
    PS-ROI pooling computes sample coordinates in fp32.
    """
    import jax
    import jax.numpy as jnp

    Hf, Wf = net.feat_shape
    A = net.num_anchors
    a_total = Hf * Wf * A
    ncand = net.rpn_post_nms + net.max_gts

    def forward_loss(run, inputs, key):
        data, im_info, gt = inputs
        k1, k2, k3 = jax.random.split(key, 3)
        nz_rpn = jax.random.uniform(k1, (batch, a_total, 2), jnp.float32)
        nz_prop = jax.random.uniform(k2, (batch, ncand, 2), jnp.float32)
        x = data.astype(compute_dtype) if compute_dtype is not None else data
        outs = run((x, im_info, gt, nz_rpn, nz_prop), k3)
        (rpn_cls, rpn_bbox, rpn_label, rpn_bt, rpn_bw,
         _rois, label, bbox_target, bbox_weight, cls_score, bbox_pred) = (
            jnp.asarray(o).astype(jnp.float32) for o in outs)

        with jax.named_scope("loss"):
            # RPN losses (reference train_end2end loss heads; anchor order
            # h·(W·A)+w·A+a matches rpn_anchor_target / MultiProposal)
            logits = rpn_cls.reshape(batch, 2, A, Hf, Wf).transpose(0, 3, 4, 2, 1)
            logits = logits.reshape(batch, a_total, 2)
            valid = rpn_label >= 0
            lab = jnp.maximum(rpn_label, 0.0).astype(jnp.int32)
            logp = jax.nn.log_softmax(logits, axis=-1)
            ce = -jnp.take_along_axis(logp, lab[..., None], axis=-1)[..., 0]
            rpn_cls_loss = jnp.where(valid, ce, 0.0).sum() / jnp.maximum(
                valid.sum(), 1)
            bp = rpn_bbox.reshape(batch, A, 4, Hf, Wf).transpose(0, 3, 4, 1, 2)
            bp = bp.reshape(batch, a_total, 4)
            rpn_bbox_loss = _smooth_l1(bp, rpn_bt, rpn_bw, 3.0).sum() / (
                net.rpn_batch * batch)

            # R-CNN head losses (class-agnostic bbox, R-FCN convention)
            logp2 = jax.nn.log_softmax(cls_score, axis=-1)
            rcnn_cls_loss = -jnp.take_along_axis(
                logp2, label.astype(jnp.int32)[:, None], axis=1).mean()
            rcnn_bbox_loss = _smooth_l1(bbox_pred, bbox_target, bbox_weight, 1.0
                                        ).sum() / label.shape[0]

            total = rpn_cls_loss + rpn_bbox_loss + rcnn_cls_loss + rcnn_bbox_loss
            parts = jnp.stack([rpn_cls_loss, rpn_bbox_loss, rcnn_cls_loss,
                               rcnn_bbox_loss])
        return total, parts

    core, state, _ = build_train_step(net, forward_loss, learning_rate,
                                      momentum, compute_dtype)

    def step(state, data, im_info, gt, key, lr=learning_rate):
        return core(state, (data, im_info, gt), key, lr)

    return step, state


def build_net(resnet101, image_shape=None, classes=None, frozen_bn=True):
    """→ (net, image_shape, classes): the full ResNet-101 north-star model,
    or the tiny-trunk CPU configuration with the same graph."""
    if resnet101:
        shape = tuple(image_shape or (608, 1024))
        classes = classes or 80
        net = rfcn_resnet101(classes=classes, image_shape=shape, max_gts=16,
                             frozen_bn=frozen_bn)
    else:
        shape = tuple(image_shape or (64, 96))
        classes = classes or 3
        # anchor scales sized for the tiny image (stride 16 ⇒ 16/32-px boxes)
        net = DeformableRFCN(
            classes=classes, image_shape=shape, units=(1, 1, 1, 1),
            scales=(1, 2), ratios=(0.5, 1, 2), rpn_pre_nms=200,
            rpn_post_nms=32, batch_rois=16, rpn_batch=32, max_gts=8,
            frozen_bn=frozen_bn)
    net.initialize()
    net.init_params()  # tiny dummy pass; H/W-independent param shapes
    return net, shape, classes


def run_bench(resnet101, batch=1, iters=10, image_shape=None, classes=None,
              dtype=None, lr=5e-4, windows=3, verbose=True):
    """Timed chained-step bench (state stays on device; one scalar fetch per
    window).  → (img_per_sec, ms_per_step, final_loss).  This is THE repo
    headline measurement — bench.py calls it."""
    import jax

    mx.random.seed(0)
    rng = np.random.RandomState(0)
    net, shape, classes = build_net(resnet101, image_shape, classes)
    data, im_info, gt = synthetic_coco(rng, batch, shape, classes, net.max_gts)
    step, state = make_rfcn_train_step(
        net, batch, learning_rate=lr, momentum=0.9, compute_dtype=dtype)
    jstep = jax.jit(step, donate_argnums=(0,))
    key = jax.random.PRNGKey(0)
    d = jax.device_put(data)
    i = jax.device_put(im_info)
    g = jax.device_put(gt)
    t0 = time.time()
    state, loss, parts = jstep(state, d, i, g, key)
    jax.block_until_ready(loss)
    compile_s = time.time() - t0
    # no-op unless MXNET_TELEMETRY is set: feeds bench.py's telemetry block
    mx.telemetry.note_compile(compile_s, fn="rfcn_fused_step")
    if verbose:
        print("compile+first step: %.1fs  loss=%.4f" % (compile_s, float(loss)))
    best = None
    for w in range(windows):
        # keys precomputed OUTSIDE the timed window: an eager fold_in is
        # several host dispatches per step (measured in the step trace)
        keys = [jax.random.fold_in(key, w * 1000 + it) for it in range(iters)]
        jax.block_until_ready(keys[-1])
        t0 = time.perf_counter()
        for it in range(iters):
            state, loss, parts = jstep(state, d, i, g, keys[it])
        float(loss)  # sync via the scalar; state never leaves the device
        dt = (time.perf_counter() - t0) / iters
        best = dt if best is None else min(best, dt)
    return batch / best, best * 1e3, float(loss)


def main():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--resnet101", action="store_true",
                   help="full ResNet-101 trunk (default: tiny units for CPU)")
    p.add_argument("--image-shape", type=int, nargs=2, default=None)
    p.add_argument("--batch-size", type=int, default=1)
    p.add_argument("--classes", type=int, default=None)
    p.add_argument("--steps", type=int, default=12)
    p.add_argument("--lr", type=float, default=5e-4)
    p.add_argument("--dtype", default=None,
                   help="compute dtype (bfloat16 on TPU; fp32 default)")
    p.add_argument("--bench", action="store_true")
    p.add_argument("--bench-iters", type=int, default=10)
    args = p.parse_args()

    import jax

    on_tpu = jax.devices()[0].platform == "tpu"
    if args.dtype is None and args.bench and on_tpu:
        args.dtype = "bfloat16"

    if args.bench:
        img_s, ms, loss = run_bench(
            args.resnet101, batch=args.batch_size, iters=args.bench_iters,
            image_shape=args.image_shape, classes=args.classes,
            dtype=args.dtype, lr=args.lr)
        print("rfcn_fused_bench: batch=%d dtype=%s  %.2f img/s (%.0f ms/step)"
              "  loss=%.4f"
              % (args.batch_size, args.dtype or "float32", img_s, ms, loss))
        return

    mx.random.seed(0)
    rng = np.random.RandomState(0)
    net, shape, classes = build_net(args.resnet101, args.image_shape, args.classes)
    data, im_info, gt = synthetic_coco(rng, args.batch_size, shape, classes,
                                       net.max_gts)
    step, state = make_rfcn_train_step(
        net, args.batch_size, learning_rate=args.lr, momentum=0.9,
        compute_dtype=args.dtype)
    jstep = jax.jit(step, donate_argnums=(0,))
    key = jax.random.PRNGKey(0)

    first = last = None
    for s in range(args.steps):
        data, im_info, gt = synthetic_coco(rng, args.batch_size, shape,
                                           classes, net.max_gts)
        state, loss, parts = jstep(state, data, im_info, gt,
                                   jax.random.fold_in(key, s))
        l = float(loss)
        pr = [float(x) for x in np.asarray(parts)]
        print("step %2d  loss=%.4f  (rpn_cls %.3f rpn_bbox %.3f "
              "rcnn_cls %.3f rcnn_bbox %.3f)" % (s, l, *pr))
        if first is None:
            first = l
        last = l
    assert np.isfinite(last), "loss diverged"
    assert last < first, "loss did not decrease (first=%.4f last=%.4f)" % (first, last)
    print("DEFORMABLE-RFCN FUSED TRAIN OK")


if __name__ == "__main__":
    main()
