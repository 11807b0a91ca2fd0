"""Plain reference of the Deformable R-FCN train step (float32, jax.numpy).

Imports nothing of the program.  It states, from the published recipe
(msracver/Deformable-ConvNets ``resnet_v1_101_coco_trainval_rfcn_dcn_end2end``
and the reference fork's contrib operators), what one training step computes:

    ResNet-v1 trunk (stride on the first 1x1, frozen BatchNorm, gradient cut
    below res3) -> res5 at dilation 2 with deformable 3x3 convolutions
    (4 deformable groups, offsets from a 3x3 convolution) -> RPN ->
    MultiProposal (decode, clip, min-size, top-k, greedy NMS, 300 rois) ->
    anchor targets and proposal targets sampled by rank over uniform noise ->
    deformable PS-ROI pooling (offsets pooled from a 1x1 branch) -> four
    losses -> gradients (jax.grad) -> SGD with momentum.

Every operator is the straightforward one: bilinear gathers, a sequential
greedy NMS, no kernels, no one-hot matrices, no batching tricks.  The batch is
walked in blocks of images whose gradients add up, so that float32 fits the
chip: frozen BatchNorm leaves the images independent, and the only numbers
shared over the batch (the loss denominators) do not depend on the weights.

The detection operators follow the numpy statements in
``tests/test_detection.py`` (np_deformable_conv, np_deformable_psroi,
np_multi_proposal); the two target operators follow the semantics in
``mxnet_tpu/ops/rcnn_targets.py``'s docstrings (the rcnn example's
assign_anchor / sample_rois with rank-over-noise subsampling).
"""
import functools
import json

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import precision as P

BN_EPS = 1e-5


# ---------------------------------------------------------------------------
# parameters: names, shapes and kinds, from the configuration alone
# ---------------------------------------------------------------------------

def param_spec(cfg):
    """-> [(name, shape, kind)] in a fixed order.  ``kind`` tells the weight
    maker what a leaf is: conv | head | bias | gamma | gamma_res | beta | mean | var |
    offset_w | offset_b | trans_w.  Names are the program's parameter names without
    their model prefix, so the runner can hand each leaf to its place."""
    spec = []
    k2 = cfg["pooled_size"] ** 2
    A = len(cfg["anchor_scales"]) * len(cfg["anchor_ratios"])
    C1 = cfg["classes"] + 1

    def bn(prefix, i, c):
        # a block's last BatchNorm scales the residual branch: its gamma is
        # a kind of its own so that the weight maker can keep 33 blocks of
        # un-normalised (frozen) statistics from growing without bound
        for leaf, kind in (("gamma", "gamma_res" if i == 2 else "gamma"),
                           ("beta", "beta"),
                           ("running_mean", "mean"), ("running_var", "var")):
            spec.append(("%sbatchnorm%d_%s" % (prefix, i, leaf), (c,), kind))

    spec.append(("conv2d0_weight", (64, 3, 7, 7), "conv"))
    bn("", 0, 64)
    cin = 64
    for s, (units, c) in enumerate(zip(cfg["units"], (256, 512, 1024, 2048)), 2):
        deform = s == 5
        mid = c // 4
        for u in range(1, units + 1):
            pre = "res%d_unit%d_" % (s, u)
            spec.append((pre + "conv2d0_weight", (mid, cin, 1, 1), "conv"))
            if deform:
                spec.append((pre + "deformableconv2d0_weight",
                             (mid, mid, 3, 3), "conv"))
                spec.append((pre + "deformableconv2d0_offset_weight",
                             (2 * 9 * cfg["deformable_groups"], mid, 3, 3),
                             "offset_w"))
                spec.append((pre + "deformableconv2d0_offset_bias",
                             (2 * 9 * cfg["deformable_groups"],), "offset_b"))
                spec.append((pre + "conv2d1_weight", (c, mid, 1, 1), "conv"))
            else:
                spec.append((pre + "conv2d1_weight", (mid, mid, 3, 3), "conv"))
                spec.append((pre + "conv2d2_weight", (c, mid, 1, 1), "conv"))
            bn(pre, 0, mid)
            bn(pre, 1, mid)
            bn(pre, 2, c)
            if u == 1:
                spec.append((pre + "conv2d%d_weight" % (2 if deform else 3),
                             (c, cin, 1, 1), "conv"))
                bn(pre, 3, c)
            cin = c
    new = cfg["conv_new_filters"]
    for name, shape, kind in (
            ("rpn_conv", (512, 1024, 3, 3), "conv"),
            ("rpn_cls", (2 * A, 512, 1, 1), "head"),
            ("rpn_bbox", (4 * A, 512, 1, 1), "head"),
            ("conv_new", (new, 2048, 1, 1), "conv"),
            ("rfcn_cls", (C1 * k2, new, 1, 1), "head"),
            ("rfcn_bbox", (8 * k2, new, 1, 1), "head"),
            ("rfcn_trans", (2 * k2, new, 1, 1), "trans_w")):
        spec.append((name + "_weight", shape, kind))
        spec.append((name + "_bias", shape[:1], "bias"))
    return spec


def is_aux(name):
    return name.endswith("running_mean") or name.endswith("running_var")


# ---------------------------------------------------------------------------
# operators
# ---------------------------------------------------------------------------

def frozen_bn(x, p, prefix):
    scale = p[prefix + "gamma"] / jnp.sqrt(p[prefix + "running_var"] + BN_EPS)
    return (x - p[prefix + "running_mean"]) * scale + p[prefix + "beta"]


def bilinear_rows(table, y, x, H, W):
    """``table`` (H*W, C); ``y``, ``x`` (N,) real coordinates, clamped to the
    plane -> (N, C): the four-corner bilinear sample."""
    y = jnp.clip(y, 0.0, H - 1.0)
    x = jnp.clip(x, 0.0, W - 1.0)
    y0 = jnp.floor(y)
    x0 = jnp.floor(x)
    ly = (y - y0)[:, None]
    lx = (x - x0)[:, None]
    y0 = y0.astype(jnp.int32)
    x0 = x0.astype(jnp.int32)
    y1 = jnp.minimum(y0 + 1, H - 1)
    x1 = jnp.minimum(x0 + 1, W - 1)

    def at(yy, xx):
        return jnp.take(table, yy * W + xx, axis=0)

    return (at(y0, x0) * (1 - ly) * (1 - lx) + at(y0, x1) * (1 - ly) * lx
            + at(y1, x0) * ly * (1 - lx) + at(y1, x1) * ly * lx)


def deformable_conv3x3(x, offset, w, prec, dilation, groups):
    """3x3, stride 1, pad = dilation.  ``x`` (B,H,W,C); ``offset`` (B,H,W,
    groups*18) with channel g*18 + 2*tap + (0: dy, 1: dx); ``w`` (O,C,3,3).
    A sample outside [0,H) x [0,W) contributes nothing."""
    B, H, W, C = x.shape
    cg = C // groups
    hh = jnp.arange(H, dtype=jnp.float32)[:, None, None]
    ww = jnp.arange(W, dtype=jnp.float32)[None, :, None]
    tap_y = jnp.asarray([(t // 3 - 1) * dilation for t in range(9)], jnp.float32)
    tap_x = jnp.asarray([(t % 3 - 1) * dilation for t in range(9)], jnp.float32)
    off = offset.reshape(B, H, W, groups, 9, 2)

    def one(xb, ob):          # one image
        def group(tab, og):   # tab (H*W, cg), og (H, W, 9, 2)
            y = hh + tap_y + og[..., 0]
            xx = ww + tap_x + og[..., 1]
            live = (y >= 0) & (y < H) & (xx >= 0) & (xx < W)
            v = bilinear_rows(tab, y.reshape(-1), xx.reshape(-1), H, W)
            return v.reshape(H, W, 9, cg) * live[..., None]
        tabs = xb.reshape(H * W, groups, cg).transpose(1, 0, 2)
        col = jax.vmap(group)(tabs, ob.transpose(2, 0, 1, 3, 4))
        return col.transpose(1, 2, 3, 0, 4).reshape(H, W, 9, C)

    col = jax.vmap(one)(x, off)                                   # B,H,W,9,C
    wt = w.reshape(w.shape[0], C, 9).transpose(0, 2, 1)           # O,9,C
    return P.einsum("bhwtc,otc->bhwo", col, wt, prec)


def deformable_psroi(data, rois, trans, output_dim, k, scale, trans_std,
                     spp=4):
    """``data`` (B,H,W,output_dim*k*k) with channel (ct*k + gh)*k + gw;
    ``rois`` (R,5) [image, x1, y1, x2, y2]; ``trans`` (R,2,k,k) or None.
    -> (R, output_dim, k, k): per bin the mean of the live samples of a
    spp x spp grid, shifted by ``trans`` * trans_std * roi size."""
    B, H, W, _ = data.shape
    R = rois.shape[0]
    g2 = k * k
    table = data.reshape(B, H * W, output_dim, g2).transpose(0, 3, 1, 2)
    table = table.reshape(B * g2 * H * W, output_dim)
    b = rois[:, 0].astype(jnp.int32)
    xs = jnp.round(rois[:, 1]) * scale - 0.5
    ys = jnp.round(rois[:, 2]) * scale - 0.5
    xe = (jnp.round(rois[:, 3]) + 1.0) * scale - 0.5
    ye = (jnp.round(rois[:, 4]) + 1.0) * scale - 0.5
    rw = jnp.maximum(xe - xs, 0.1)
    rh = jnp.maximum(ye - ys, 0.1)
    bw, bh = rw / k, rh / k
    if trans is None:
        tx = ty = jnp.zeros((R, k, k), jnp.float32)
    else:
        tx = trans[:, 0] * trans_std
        ty = trans[:, 1] * trans_std
    ph = jnp.arange(k, dtype=jnp.float32)
    s = jnp.arange(spp, dtype=jnp.float32)
    # (R, ph, pw, ih, iw)
    wst = ph[None, None, :] * bw[:, None, None] + xs[:, None, None] \
        + tx * rw[:, None, None]
    hst = ph[None, :, None] * bh[:, None, None] + ys[:, None, None] \
        + ty * rh[:, None, None]
    w_ = wst[..., None, None] + s[None, None, None, None, :] \
        * (bw / spp)[:, None, None, None, None]
    h_ = hst[..., None, None] + s[None, None, None, :, None] \
        * (bh / spp)[:, None, None, None, None]
    w_ = jnp.broadcast_to(w_, (R, k, k, spp, spp))
    h_ = jnp.broadcast_to(h_, (R, k, k, spp, spp))
    live = (w_ >= -0.5) & (w_ <= W - 0.5) & (h_ >= -0.5) & (h_ <= H - 0.5)
    ghw = (jnp.arange(k)[:, None] * k + jnp.arange(k)[None, :])
    plane = (b[:, None, None] * g2 + ghw[None]) * (H * W)           # R,k,k

    y = jnp.clip(h_, 0.0, H - 1.0)
    x = jnp.clip(w_, 0.0, W - 1.0)
    y0 = jnp.floor(y)
    x0 = jnp.floor(x)
    ly = (y - y0)[..., None]
    lx = (x - x0)[..., None]
    y0 = y0.astype(jnp.int32)
    x0 = x0.astype(jnp.int32)
    y1 = jnp.minimum(y0 + 1, H - 1)
    x1 = jnp.minimum(x0 + 1, W - 1)
    base = plane[..., None, None]

    def at(yy, xx):
        return jnp.take(table, base + yy * W + xx, axis=0)

    v = (at(y0, x0) * (1 - ly) * (1 - lx) + at(y0, x1) * (1 - ly) * lx
         + at(y1, x0) * ly * (1 - lx) + at(y1, x1) * ly * lx)
    v = v * live[..., None]                               # R,k,k,spp,spp,OD
    cnt = live.sum(axis=(3, 4)).astype(jnp.float32)[..., None]
    out = jnp.where(cnt > 0, v.sum(axis=(3, 4)) / jnp.maximum(cnt, 1.0), 0.0)
    return out.transpose(0, 3, 1, 2)


def base_anchors(stride, scales, ratios):
    """The classic RPN enumeration: ratios outside, scales inside, sizes
    snapped by floor(. + 0.5)."""
    size = float(stride * stride)
    ctr = 0.5 * (stride - 1.0)
    out = []
    for r in ratios:
        nw = np.floor(np.sqrt(np.floor(size / r)) + 0.5)
        nh = np.floor(nw * r + 0.5)
        for s in scales:
            ws, hs = nw * s, nh * s
            out.append([ctr - 0.5 * (ws - 1), ctr - 0.5 * (hs - 1),
                        ctr + 0.5 * (ws - 1), ctr + 0.5 * (hs - 1)])
    return np.asarray(out, np.float32)


def all_anchors(Hf, Wf, stride, scales, ratios):
    """(Hf*Wf*A, 4), index h*(Wf*A) + w*A + a."""
    base = base_anchors(stride, scales, ratios)
    sx = np.arange(Wf, dtype=np.float32) * stride
    sy = np.arange(Hf, dtype=np.float32) * stride
    shift = np.stack(np.broadcast_arrays(
        sx[None, :, None], sy[:, None, None], sx[None, :, None],
        sy[:, None, None]), -1)                                    # Hf,Wf,1,4
    return jnp.asarray((shift + base[None, None]).reshape(-1, 4))


def iou_plus_one(a, b):
    """Dense IoU (Na, Nb) with the +1 pixel convention.  Coordinate by
    coordinate: a trailing axis of 2 would be padded to a whole tile."""
    ax1, ay1, ax2, ay2 = (a[:, i, None] for i in range(4))
    bx1, by1, bx2, by2 = (b[None, :, i] for i in range(4))
    area_a = (ax2 - ax1 + 1.0) * (ay2 - ay1 + 1.0)
    area_b = (bx2 - bx1 + 1.0) * (by2 - by1 + 1.0)
    w = jnp.maximum(jnp.minimum(ax2, bx2) - jnp.maximum(ax1, bx1) + 1.0, 0.0)
    h = jnp.maximum(jnp.minimum(ay2, by2) - jnp.maximum(ay1, by1) + 1.0, 0.0)
    inter = w * h
    union = area_a + area_b - inter
    return jnp.where(union <= 0, 0.0, inter / jnp.maximum(union, 1e-12))


def greedy_nms_alive(boxes, thresh):
    """Sequential greedy NMS over score-ordered boxes: box i survives iff no
    surviving j < i overlaps it by more than ``thresh``."""
    n = boxes.shape[0]
    over = iou_plus_one(boxes, boxes) > thresh
    later = jnp.arange(n)

    def body(i, dead):
        kills = over[i] & (later > i) & ~dead[i]
        return dead | kills

    return ~lax.fori_loop(0, n, body, jnp.zeros((n,), bool))


def proposals_one(prob_fg, deltas, info, anchors, cfg):
    """``prob_fg`` (Hf,Wf,A), ``deltas`` (Hf,Wf,A,4) -> (post, 4) rois."""
    stride = cfg["feature_stride"]
    Hf, Wf, _ = prob_fg.shape
    a = anchors.reshape(Hf, Wf, -1, 4)
    w = a[..., 2] - a[..., 0] + 1.0
    h = a[..., 3] - a[..., 1] + 1.0
    cx = a[..., 0] + 0.5 * (w - 1.0)
    cy = a[..., 1] + 0.5 * (h - 1.0)
    pcx = deltas[..., 0] * w + cx
    pcy = deltas[..., 1] * h + cy
    pw = jnp.exp(deltas[..., 2]) * w
    ph = jnp.exp(deltas[..., 3]) * h
    im_h, im_w, im_scale = info[0], info[1], info[2]
    x1 = jnp.clip(pcx - 0.5 * (pw - 1.0), 0.0, im_w - 1.0)
    y1 = jnp.clip(pcy - 0.5 * (ph - 1.0), 0.0, im_h - 1.0)
    x2 = jnp.clip(pcx + 0.5 * (pw - 1.0), 0.0, im_w - 1.0)
    y2 = jnp.clip(pcy + 0.5 * (ph - 1.0), 0.0, im_h - 1.0)
    outside = (jnp.arange(Hf)[:, None, None] >= jnp.ceil(im_h / stride)) \
        | (jnp.arange(Wf)[None, :, None] >= jnp.ceil(im_w / stride))
    score = jnp.where(outside, -1.0, prob_fg)
    ms = cfg["rpn_min_size"] * im_scale
    tiny = ((x2 - x1 + 1.0) < ms) | ((y2 - y1 + 1.0) < ms)
    x1 = jnp.where(tiny, x1 - ms / 2, x1)
    y1 = jnp.where(tiny, y1 - ms / 2, y1)
    x2 = jnp.where(tiny, x2 + ms / 2, x2)
    y2 = jnp.where(tiny, y2 + ms / 2, y2)
    score = jnp.where(tiny, -1.0, score).reshape(-1)
    boxes = jnp.stack([x1, y1, x2, y2], -1).reshape(-1, 4)
    pre = min(cfg["rpn_pre_nms"], boxes.shape[0])
    post = cfg["rpn_post_nms"]
    order = jnp.argsort(-score, stable=True)[:pre]
    ordered = boxes[order]
    alive = greedy_nms_alive(ordered, cfg["rpn_nms_thresh"])
    keep = jnp.argsort(~alive, stable=True)[:post]
    n = jnp.maximum(jnp.minimum(alive.sum(), post), 1)
    slot = jnp.arange(post)
    return ordered[keep[jnp.where(slot < n, slot, slot % n)]]


def bbox_transform(ex, gt):
    ew = ex[:, 2] - ex[:, 0] + 1.0
    eh = ex[:, 3] - ex[:, 1] + 1.0
    ecx = ex[:, 0] + 0.5 * (ew - 1.0)
    ecy = ex[:, 1] + 0.5 * (eh - 1.0)
    gw = gt[:, 2] - gt[:, 0] + 1.0
    gh = gt[:, 3] - gt[:, 1] + 1.0
    gcx = gt[:, 0] + 0.5 * (gw - 1.0)
    gcy = gt[:, 1] + 0.5 * (gh - 1.0)
    return jnp.stack([(gcx - ecx) / (ew + 1e-14), (gcy - ecy) / (eh + 1e-14),
                      jnp.log(jnp.maximum(gw / ew, 1e-12)),
                      jnp.log(jnp.maximum(gh / eh, 1e-12))], axis=1)


def rank_select(mask, noise, limit):
    """Keep at most ``limit`` of the True entries: those of least noise.
    -> (kept mask, the order that lists candidates by noise)."""
    n = mask.shape[0]
    order = jnp.argsort(jnp.where(mask, noise, 2.0), stable=True)
    rank = jnp.zeros((n,), jnp.int32).at[order].set(jnp.arange(n, dtype=jnp.int32))
    return mask & (rank < limit), order


def anchor_targets_one(gt, info, nz, anchors, cfg):
    """assign_anchor: label in {-1, 0, 1}, box targets and weights, for the
    anchors inside the image; fg >= 0.7 IoU or a gt's best, bg < 0.3; at
    most rpn_batch/2 fg, the rest bg, both sampled by noise rank."""
    total = anchors.shape[0]
    batch = cfg["rpn_batch"]
    max_fg = int(round(batch * 0.5))
    inside = (anchors[:, 0] >= 0) & (anchors[:, 1] >= 0) \
        & (anchors[:, 2] < info[1]) & (anchors[:, 3] < info[0])
    gt_valid = gt[:, 0] >= 0
    num_gt = gt_valid.sum()
    iou = iou_plus_one(anchors, gt[:, 1:5])
    iou = jnp.where(gt_valid[None, :] & inside[:, None], iou, -1.0)
    argmax = jnp.argmax(iou, axis=1)
    max_iou = jnp.maximum(jnp.max(iou, axis=1), 0.0)
    fg = inside & (max_iou >= 0.7) & (num_gt > 0)
    best = jnp.zeros((total,), jnp.int32).at[jnp.argmax(iou, axis=0)].add(
        gt_valid.astype(jnp.int32)) > 0
    fg = fg | (best & inside)
    fg_kept, _ = rank_select(fg, nz[:, 0], max_fg)
    n_fg = fg_kept.sum()
    bg = inside & (max_iou < 0.3) & ~fg & (num_gt > 0)
    bg = jnp.where(num_gt > 0, bg, inside)
    bg_kept, _ = rank_select(bg, nz[:, 1], batch - jnp.minimum(n_fg, max_fg))
    label = jnp.where(fg_kept, 1.0, jnp.where(bg_kept, 0.0, -1.0))
    tgt = bbox_transform(anchors, gt[jnp.clip(argmax, 0, gt.shape[0] - 1), 1:5])
    w = fg_kept[:, None].astype(jnp.float32)
    return label, tgt * w, jnp.broadcast_to(w, (total, 4))


def proposal_targets_one(b, rois, gt, nz, cfg):
    """sample_rois: candidates are the proposals and the gt boxes; fg >= 0.5
    IoU (at most a quarter of batch_rois), bg below; slots past the sampled
    ones repeat the sampled bg (or fg).  Class-agnostic box targets."""
    per_im = cfg["batch_rois"]
    fg_per_im = int(round(cfg["fg_fraction"] * per_im))
    G = gt.shape[0]
    post = rois.shape[0]
    gt_valid = gt[:, 0] >= 0
    num_gt = gt_valid.sum()
    cand = jnp.concatenate([rois, gt[:, 1:5]], axis=0)
    cand_valid = jnp.concatenate([jnp.ones((post,), bool), gt_valid])
    iou = jnp.where(gt_valid[None, :], iou_plus_one(cand, gt[:, 1:5]), -1.0)
    argmax = jnp.clip(jnp.argmax(iou, axis=1), 0, G - 1)
    max_iou = jnp.maximum(jnp.max(iou, axis=1), 0.0)
    fg = cand_valid & (max_iou >= 0.5) & (num_gt > 0)
    fg_kept, fg_order = rank_select(fg, nz[:, 0], fg_per_im)
    n_fg = jnp.minimum(fg_kept.sum(), fg_per_im)
    bg = cand_valid & (max_iou < 0.5)
    bg_kept, bg_order = rank_select(bg, nz[:, 1], per_im - n_fg)
    n_bg = jnp.minimum(bg_kept.sum(), per_im - n_fg)
    slots = jnp.arange(per_im)
    pad = jnp.where(n_bg > 0, bg_order[(slots - n_fg) % jnp.maximum(n_bg, 1)],
                    fg_order[slots % jnp.maximum(n_fg, 1)])
    idx = jnp.where(slots < n_fg, fg_order[slots], pad)
    sel = cand[idx]
    sel_gt = argmax[idx]
    is_fg = fg[idx]
    label = jnp.where(is_fg, gt[sel_gt, 0] + 1.0, 0.0)
    tgt = bbox_transform(sel, gt[sel_gt, 1:5])
    onehot = jax.nn.one_hot(jnp.minimum(label, 1.0).astype(jnp.int32), 2)
    w = is_fg[:, None, None] * onehot[:, :, None]
    bt = (w * tgt[:, None, :]).reshape(per_im, 8)
    bw = jnp.broadcast_to(w, (per_im, 2, 4)).reshape(per_im, 8)
    rows = jnp.concatenate([jnp.full((per_im, 1), b, jnp.float32), sel], axis=1)
    return rows, label, bt, bw


def smooth_l1(x, sigma):
    s2 = sigma * sigma
    ax = jnp.abs(x)
    return jnp.where(ax < 1.0 / s2, 0.5 * s2 * x * x, ax - 0.5 / s2)


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------

def bottleneck(x, p, pre, prec, stride, dilation, deform, down, groups):
    y = P.conv(x, p[pre + "conv2d0_weight"], prec, stride=stride)
    y = jax.nn.relu(frozen_bn(y, p, pre + "batchnorm0_"))
    if deform:
        d = pre + "deformableconv2d0_"
        off = P.conv(y, p[d + "offset_weight"], prec, pad=dilation,
                     dilation=dilation) + p[d + "offset_bias"]
        y = deformable_conv3x3(y, off, p[d + "weight"], prec, dilation, groups)
        last, sc = "conv2d1_weight", "conv2d2_weight"
    else:
        y = P.conv(y, p[pre + "conv2d1_weight"], prec, pad=dilation,
                   dilation=dilation)
        last, sc = "conv2d2_weight", "conv2d3_weight"
    y = jax.nn.relu(frozen_bn(y, p, pre + "batchnorm1_"))
    y = frozen_bn(P.conv(y, p[pre + last], prec), p, pre + "batchnorm2_")
    if down:
        x = frozen_bn(P.conv(x, p[pre + sc], prec, stride=stride), p,
                      pre + "batchnorm3_")
    return jax.nn.relu(y + x)


def stage(x, p, s, units, prec, stride, cfg):
    deform = s == 5
    for u in range(1, units + 1):
        x = bottleneck(x, p, "res%d_unit%d_" % (s, u), prec,
                       stride if u == 1 else 1, 2 if deform else 1, deform,
                       u == 1, cfg["deformable_groups"])
    return x


def conv_bias(x, p, name, prec, pad=0):
    return P.conv(x, p[name + "_weight"], prec, pad=pad) + p[name + "_bias"]


def block_losses(p, data, im_info, gt, nz_rpn, nz_prop, denom, cfg, prec):
    """The four loss sums of a block of images, each already divided by the
    whole batch's denominator.  ``data`` (nb,3,H,W); ``denom`` = (valid RPN
    labels of the batch, rpn_batch * batch, rois of the batch)."""
    nb = data.shape[0]
    k = cfg["pooled_size"]
    A = len(cfg["anchor_scales"]) * len(cfg["anchor_ratios"])
    C1 = cfg["classes"] + 1
    ss = 1.0 / cfg["feature_stride"]
    x = jnp.transpose(data, (0, 2, 3, 1))
    x = P.conv(x, p["conv2d0_weight"], prec, stride=2, pad=3)
    x = P.max_pool_3x3_s2(jax.nn.relu(frozen_bn(x, p, "batchnorm0_")))
    u = cfg["units"]
    c2 = lax.stop_gradient(stage(x, p, 2, u[0], prec, 1, cfg))
    c4 = stage(stage(c2, p, 3, u[1], prec, 2, cfg), p, 4, u[2], prec, 2, cfg)
    c5 = stage(c4, p, 5, u[3], prec, 1, cfg)
    Hf, Wf = c4.shape[1:3]
    anchors = all_anchors(Hf, Wf, cfg["feature_stride"], cfg["anchor_scales"],
                          cfg["anchor_ratios"])

    t = jax.nn.relu(conv_bias(c4, p, "rpn_conv", prec, pad=1))
    rpn_cls = conv_bias(t, p, "rpn_cls", prec)        # nb,Hf,Wf,2A: bg | fg
    rpn_box = conv_bias(t, p, "rpn_bbox", prec)       # nb,Hf,Wf,4A: a*4 + c
    logits = jnp.stack([rpn_cls[..., :A], rpn_cls[..., A:]], -1)   # ..,A,2
    prob_fg = jax.nn.softmax(logits, axis=-1)[..., 1]
    deltas = rpn_box.reshape(nb, Hf, Wf, A, 4)
    rois = lax.stop_gradient(jax.vmap(
        lambda s, d, i: proposals_one(s, d, i, anchors, cfg))(
            prob_fg, deltas, im_info))                             # nb,post,4

    label, bt, bw = jax.vmap(
        lambda g, i, n: anchor_targets_one(g, i, n, anchors, cfg))(
            gt, im_info, nz_rpn)
    logits = logits.reshape(nb, -1, 2)
    valid = label >= 0
    logp = jax.nn.log_softmax(logits, axis=-1)
    ce = -jnp.take_along_axis(
        logp, jnp.maximum(label, 0.0).astype(jnp.int32)[..., None], -1)[..., 0]
    rpn_cls_loss = jnp.where(valid, ce, 0.0).sum() / denom[0]
    rpn_box_loss = smooth_l1((deltas.reshape(nb, -1, 4) - bt) * bw, 3.0
                             ).sum() / denom[1]

    rows, rlabel, rbt, rbw = jax.vmap(
        lambda b, r, g, n: proposal_targets_one(b, r, g, n, cfg))(
            jnp.arange(nb, dtype=jnp.float32), rois, gt, nz_prop)
    rows = rows.reshape(-1, 5)
    feat = jax.nn.relu(conv_bias(c5, p, "conv_new", prec))
    cls_maps = conv_bias(feat, p, "rfcn_cls", prec)
    box_maps = conv_bias(feat, p, "rfcn_bbox", prec)
    trans_maps = conv_bias(feat, p, "rfcn_trans", prec)
    trans = deformable_psroi(trans_maps, rows, None, 2, k, ss, 0.0)
    cls = deformable_psroi(cls_maps, rows, trans, C1, k, ss, 0.1)
    box = deformable_psroi(box_maps, rows, trans, 8, k, ss, 0.1)
    cls_score = cls.reshape(cls.shape[0], C1, -1).mean(axis=2)
    box_pred = box.reshape(box.shape[0], 8, -1).mean(axis=2)
    logp2 = jax.nn.log_softmax(cls_score, axis=-1)
    rcnn_cls_loss = -jnp.take_along_axis(
        logp2, rlabel.reshape(-1).astype(jnp.int32)[:, None], 1).sum() / denom[2]
    rcnn_box_loss = smooth_l1(
        (box_pred - rbt.reshape(-1, 8)) * rbw.reshape(-1, 8), 1.0
    ).sum() / denom[2]
    parts = jnp.stack([rpn_cls_loss, rpn_box_loss, rcnn_cls_loss,
                       rcnn_box_loss])
    return parts.sum(), parts


def step_noise(key, batch, n_anchors, n_cand):
    """The step's sampling noise, drawn as the recipe draws it: the key is
    split in three, the first two give the uniforms."""
    k1, k2, _ = jax.random.split(key, 3)
    return (jax.random.uniform(k1, (batch, n_anchors, 2), jnp.float32),
            jax.random.uniform(k2, (batch, n_cand, 2), jnp.float32))


class Reference:
    """Momentum SGD over ``block_losses``, the batch walked in blocks.

    ``images`` picks the rows whose gradient is used and the loss is the
    mean over them: the whole batch for the reference, a part of it for the
    planted faults (half of the batch left out; one chip's shard alone)."""

    def __init__(self, cfg, params, prec="float32", block=1):
        self.cfg = cfg
        self.prec = prec
        self.block = block
        self.names = [n for n, _, _ in param_spec(cfg)]
        self.learn_names = [n for n in self.names if not is_aux(n)]
        self.p = dict(params)
        self.mom = None
        H, W = cfg["image_shape"]
        s = cfg["feature_stride"]
        self.anchors = all_anchors(H // s, W // s, s, cfg["anchor_scales"],
                                   cfg["anchor_ratios"])

        self._grad_block, self._labels = _programs(json.dumps(cfg, sort_keys=True), prec)
        self._add = _add

    def grads(self, data, im_info, gt, key, images=None):
        """-> (loss, parts (4,), grads dict) of one step at the present
        parameters."""
        cfg = self.cfg
        B = data.shape[0]
        rows = list(range(B)) if images is None else list(images)
        nz_rpn, nz_prop = step_noise(key, B, self.anchors.shape[0],
                                     cfg["rpn_post_nms"] + gt.shape[1])
        idx = jnp.asarray(rows)
        labels = self._labels(gt[idx], im_info[idx], nz_rpn[idx], self.anchors)
        n = len(rows)
        denom = (jnp.maximum((labels >= 0).sum(), 1).astype(jnp.float32),
                 jnp.float32(cfg["rpn_batch"] * n),
                 jnp.float32(cfg["batch_rois"] * n))
        learn = {k: self.p[k] for k in self.learn_names}
        aux = {k: v for k, v in self.p.items() if is_aux(k)}
        parts = g = None
        for i in range(0, n, self.block):
            sel = jnp.asarray(rows[i:i + self.block])
            pi, gi = self._grad_block(learn, aux, data[sel], im_info[sel],
                                      gt[sel], nz_rpn[sel], nz_prop[sel], denom)
            parts = pi if parts is None else parts + pi
            g = gi if g is None else self._add(g, gi)
        return parts.sum(), parts, g

    def step(self, data, im_info, gt, key, images=None):
        """One SGD-momentum step. -> (loss, parts, grads)."""
        loss, parts, g = self.grads(data, im_info, gt, key, images)
        lr, m = self.cfg["learning_rate"], self.cfg["momentum"]
        if self.mom is None:
            self.mom = {k: jnp.zeros_like(v) for k, v in g.items()}
        self.mom, new = _sgd(self.mom, g, {k: self.p[k] for k in g}, lr, m)
        self.p.update(new)
        return loss, parts, g


@functools.lru_cache(maxsize=None)
def _programs(cfg_json, prec):
    """The jitted block gradient and anchor labels of one configuration and
    precision, made once however many References are built."""
    cfg = json.loads(cfg_json)

    def grad_block(learn, aux, *args):
        def f(learn):
            return block_losses({**learn, **aux}, *args, cfg, prec)
        (_, parts), g = jax.value_and_grad(f, has_aux=True)(learn)
        return parts, g

    labels = jax.vmap(
        lambda g, i, n, anchors: anchor_targets_one(g, i, n, anchors, cfg)[0],
        in_axes=(0, 0, 0, None))
    return jax.jit(grad_block), jax.jit(labels)


@jax.jit
def _add(a, b):
    return jax.tree_util.tree_map(jnp.add, a, b)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _sgd(mom, g, p, lr, m):
    mom = {k: m * mom[k] + g[k] for k in g}
    return mom, {k: p[k] - lr * mom[k] for k in g}
