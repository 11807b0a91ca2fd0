"""SSD-300/512 fused train + inference benches — reference
``example/ssd/{train.py,benchmark_score.py}`` (published bar: VGG16 SSD
300² at 95 FPS, batch 16, TITAN X — ``example/ssd/README.md:44-50``).

One XLA module per direction, exactly like the R-FCN north star:
- train step: VGG16-reduced forward, on-device MultiBoxTarget (bipartite
  match + negative mining), CE + smooth-L1, momentum SGD, donated state;
- score step: forward + softmax + MultiBoxDetection (decode + per-class
  blocked NMS over all 8732/24564 anchors).

Usage:
  ./dev.sh python examples/ssd/train_fused.py                 # CPU smoke
  python examples/ssd/train_fused.py --size 300 --bench       # chip bench
  python examples/ssd/train_fused.py --size 512 --bench
"""
from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", ".."))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu.gluon.functional import (build_train_step, functionalize,
                                        merge_params, param_names)
from vgg_ssd import SSD300, SSD512, VGGSSD


def synthetic_voc(rng, batch, size, classes, max_gts=8):
    """Bright rectangles on noise; labels (B, G, 5) [cls, x1..y2] in [0,1]
    corner format (MultiBoxTarget's convention), -1-padded."""
    data = (rng.rand(batch, 3, size, size) * 0.2).astype(np.float32)
    gt = np.full((batch, max_gts, 5), -1.0, np.float32)
    for b in range(batch):
        for j in range(rng.randint(1, 5)):
            cls = rng.randint(0, classes)
            w, h = rng.uniform(0.1, 0.5, 2)
            x1, y1 = rng.uniform(0, 1 - w), rng.uniform(0, 1 - h)
            gt[b, j] = [cls, x1, y1, x1 + w, y1 + h]
            px = (np.array([x1, y1, x1 + w, y1 + h]) * size).astype(int)
            data[b, cls % 3, px[1]:px[3], px[0]:px[2]] += 0.8
    return data, gt


def make_ssd_train_step(net, anchors, batch, learning_rate=1e-3,
                        momentum=0.9, compute_dtype=None):
    """→ (step, state): one-XLA-module SSD train step, ``step(state, data,
    gt, key, lr) -> (state, loss, parts)``; state donate-able.  The loss is
    the recipe's, the step ``gluon.functional.build_train_step``'s."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops.detection import multibox_target
    from mxnet_tpu.ops.elemwise import smooth_l1

    anc = jnp.asarray(anchors)[None]  # (1, A, 4) fp32 — never downcast

    def forward_loss(run, inputs, key):
        data, gt = inputs
        x = data.astype(compute_dtype) if compute_dtype is not None else data
        cls_preds, box_preds = run((x,), key)
        cls_preds = cls_preds.astype(jnp.float32)
        box_preds = box_preds.astype(jnp.float32)
        with jax.named_scope("loss"):
            # on-device targets (reference MultiBoxTarget semantics:
            # bipartite match + 0.5 IoU, 3:1 negative mining); cls_preds
            # (B, C+1, A)
            bt, bm, ct = multibox_target(
                anc, gt, cls_preds.transpose(0, 2, 1),
                negative_mining_ratio=3.0)
            valid = (ct >= 0).astype(jnp.float32)
            logp = jax.nn.log_softmax(cls_preds, axis=-1)
            ce = -jnp.take_along_axis(
                logp, jnp.maximum(ct, 0).astype(jnp.int32)[..., None], axis=-1
            )[..., 0] * valid
            npos = jnp.maximum(
                bm.reshape(bm.shape[0], -1, 4)[..., 0].sum(), 1.0)
            cls_loss = ce.sum() / npos
            loc_loss = smooth_l1((box_preds - bt) * bm, scalar=1.0).sum() / npos
        return cls_loss + loc_loss, jnp.stack([cls_loss, loc_loss])

    core, state, _ = build_train_step(net, forward_loss, learning_rate,
                                      momentum, compute_dtype)

    def step(state, data, gt, key, lr=learning_rate):
        return core(state, (data, gt), key, lr)

    return step, state


def make_score_step(net, anchors, compute_dtype=None):
    """→ score(params, x): forward + decode + NMS, one XLA module
    (reference benchmark_score.py measures exactly this)."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops.detection import multibox_detection

    apply, names, vals, _aux = functionalize(net, train=False)
    cdtype = jnp.dtype(compute_dtype) if compute_dtype is not None else None
    anc = jnp.asarray(anchors)[None]

    def score(pvals, x, key):
        if cdtype is not None:
            pvals = [v.astype(cdtype) if jnp.issubdtype(v.dtype, jnp.floating)
                     else v for v in pvals]
            x = x.astype(cdtype)
        (cls_preds, box_preds), _ = apply(pvals, (x,), key)
        cls_prob = jax.nn.softmax(cls_preds.astype(jnp.float32), axis=-1)
        return multibox_detection(
            cls_prob.transpose(0, 2, 1), box_preds.astype(jnp.float32), anc,
            nms_threshold=0.45, nms_topk=400)

    return score, vals


def run_bench(size=300, classes=20, train_batch=8, score_batch=16, iters=10,
              windows=3, dtype=None, verbose=True):
    import jax

    mx.random.seed(0)
    rng = np.random.RandomState(0)
    cfg = SSD300 if size == 300 else SSD512
    net = VGGSSD(classes, cfg)
    net.initialize(mx.init.Xavier())
    net(mx.nd.zeros((1, 3, size, size)))  # materialize params
    anchors = net.make_anchors(size)
    if verbose:
        print("ssd%d: %d anchors, %d params" % (
            size, len(anchors),
            sum(int(np.prod(p.shape)) for p in
                net.collect_params().values() for p in [p.data()])))

    results = {}
    # -- train step ------------------------------------------------------
    step, state = make_ssd_train_step(net, anchors, train_batch,
                                      compute_dtype=dtype)
    jstep = jax.jit(step, donate_argnums=(0,))
    data, gt = synthetic_voc(rng, train_batch, size, classes)
    d, g = jax.device_put(data), jax.device_put(gt)
    key = jax.random.PRNGKey(0)
    t0 = time.time()
    state, loss, parts = jstep(state, d, g, key)
    jax.block_until_ready(loss)
    if verbose:
        print("train compile+first: %.1fs loss=%.3f" % (time.time() - t0, float(loss)))
    best = None
    for w in range(windows):
        keys = [jax.random.fold_in(key, w * 100 + i) for i in range(iters)]
        jax.block_until_ready(keys[-1])
        t0 = time.perf_counter()
        for i in range(iters):
            state, loss, parts = jstep(state, d, g, keys[i])
        float(loss)
        dt = (time.perf_counter() - t0) / iters
        best = dt if best is None else min(best, dt)
    results["train"] = (train_batch / best, best * 1e3, float(loss))

    # -- score (inference+NMS) step — the reference's 95-FPS metric ------
    score, _fresh = make_score_step(net, anchors, compute_dtype=dtype)
    jscore = jax.jit(score)
    svals = [jax.device_put(v) for v in _merge_vals(net, state)]
    xs = jax.device_put(synthetic_voc(rng, score_batch, size, classes)[0])
    out = jscore(svals, xs, key)
    float(out[0, 0, 0])  # scalar sync
    bests = None
    for w in range(windows):
        t0 = time.perf_counter()
        for i in range(iters):
            out = jscore(svals, xs, key)
        float(out[0, 0, 0])
        dt = (time.perf_counter() - t0) / iters
        bests = dt if bests is None else min(bests, dt)
    results["score"] = (score_batch / bests, bests * 1e3)
    return results


def _merge_vals(net, state):
    """Reassemble functionalize's value list (learnables + aux running
    stats) from a trained train-step state."""
    learn, _mom, aux = state
    return merge_params(*param_names(net), learn, aux)


def main():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--size", type=int, default=300, choices=(300, 512))
    p.add_argument("--classes", type=int, default=20)
    p.add_argument("--train-batch", type=int, default=None)
    p.add_argument("--score-batch", type=int, default=16)
    p.add_argument("--iters", type=int, default=10)
    p.add_argument("--bench", action="store_true")
    p.add_argument("--steps", type=int, default=8)
    args = p.parse_args()

    import jax

    on_tpu = jax.devices()[0].platform == "tpu"
    dtype = "bfloat16" if on_tpu else None

    if args.bench:
        tb = args.train_batch or (8 if args.size == 300 else 4)
        r = run_bench(size=args.size, classes=args.classes, train_batch=tb,
                      score_batch=args.score_batch, iters=args.iters,
                      dtype=dtype)
        print("ssd%d_bench: train %.1f img/s (%.0f ms/step, batch %d) | "
              "score+nms %.1f img/s (%.0f ms, batch %d) vs reference bar "
              "95 FPS @300^2"
              % (args.size, r["train"][0], r["train"][1], tb,
                 r["score"][0], r["score"][1], args.score_batch))
        return

    # CPU smoke: tiny size but the REAL graph; loss must decrease
    mx.random.seed(0)
    rng = np.random.RandomState(0)
    size, classes, batch = 128, 3, 2
    cfg = dict(SSD300, tail=0,
               sizes=SSD300["sizes"][:4], ratios=SSD300["ratios"][:4])
    net = VGGSSD(classes, cfg)
    net.initialize(mx.init.Xavier())
    net(mx.nd.zeros((1, 3, size, size)))
    anchors = net.make_anchors(size)
    step, state = make_ssd_train_step(net, anchors, batch, learning_rate=5e-3)
    jstep = jax.jit(step, donate_argnums=(0,))
    key = jax.random.PRNGKey(0)
    first = last = None
    for s in range(args.steps):
        data, gt = synthetic_voc(rng, batch, size, classes)
        state, loss, parts = jstep(state, data, gt, jax.random.fold_in(key, s))
        l = float(loss)
        print("step %d loss=%.4f (cls %.3f loc %.3f)"
              % (s, l, *[float(x) for x in np.asarray(parts)]))
        first = first if first is not None else l
        last = l
    assert np.isfinite(last) and last < first, (first, last)
    print("SSD FUSED TRAIN OK")


if __name__ == "__main__":
    main()
