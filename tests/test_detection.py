"""Detection op tests — numpy oracles implementing the reference kernels'
documented semantics (reference tests live in
tests/python/unittest/test_operator.py::test_roipooling / test_proposal etc.;
oracles here are written from the algorithm, independent of both codebases).
"""
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu.test_utils import assert_almost_equal


# ---------------------------------------------------------------------------
# numpy oracles
# ---------------------------------------------------------------------------


def np_roi_pooling(data, rois, pooled, scale):
    B, C, H, W = data.shape
    PH, PW = pooled
    R = rois.shape[0]
    out = np.zeros((R, C, PH, PW), data.dtype)
    for r in range(R):
        b = int(rois[r, 0])
        xs = int(round(rois[r, 1] * scale))
        ys = int(round(rois[r, 2] * scale))
        xe = int(round(rois[r, 3] * scale))
        ye = int(round(rois[r, 4] * scale))
        rh, rw = max(ye - ys + 1, 1), max(xe - xs + 1, 1)
        for ph in range(PH):
            for pw in range(PW):
                hs = min(max(int(np.floor(ph * rh / PH)) + ys, 0), H)
                he = min(max(int(np.ceil((ph + 1) * rh / PH)) + ys, 0), H)
                ws = min(max(int(np.floor(pw * rw / PW)) + xs, 0), W)
                we = min(max(int(np.ceil((pw + 1) * rw / PW)) + xs, 0), W)
                if he <= hs or we <= ws:
                    continue
                out[r, :, ph, pw] = data[b, :, hs:he, ws:we].max(axis=(1, 2))
    return out


def np_bilinear(plane, y, x):
    H, W = plane.shape
    y, x = min(max(y, 0.0), H - 1.0), min(max(x, 0.0), W - 1.0)
    y0, x0 = int(np.floor(y)), int(np.floor(x))
    y1, x1 = min(y0 + 1, H - 1), min(x0 + 1, W - 1)
    ly, lx = y - y0, x - x0
    return (
        plane[y0, x0] * (1 - ly) * (1 - lx)
        + plane[y0, x1] * (1 - ly) * lx
        + plane[y1, x0] * ly * (1 - lx)
        + plane[y1, x1] * ly * lx
    )


def np_roi_align(data, rois, pooled, scale, ratio):
    B, C, H, W = data.shape
    PH, PW = pooled
    R = rois.shape[0]
    out = np.zeros((R, C, PH, PW), np.float64)
    for r in range(R):
        b = int(rois[r, 0])
        x1, y1, x2, y2 = rois[r, 1:] * scale
        rw, rh = max(x2 - x1, 1.0), max(y2 - y1, 1.0)
        bh, bw = rh / PH, rw / PW
        gh = ratio if ratio > 0 else int(np.ceil(rh / PH))
        gw = ratio if ratio > 0 else int(np.ceil(rw / PW))
        for ph in range(PH):
            for pw in range(PW):
                acc = np.zeros(C)
                for iy in range(gh):
                    yy = y1 + ph * bh + (iy + 0.5) * bh / gh
                    for ix in range(gw):
                        xx = x1 + pw * bw + (ix + 0.5) * bw / gw
                        if yy < -1.0 or yy > H or xx < -1.0 or xx > W:
                            continue
                        acc += np.array([np_bilinear(data[b, c], yy, xx) for c in range(C)])
                out[r, :, ph, pw] = acc / (gh * gw)
    return out


def np_psroi_pooling(data, rois, scale, output_dim, pooled, group):
    B, C, H, W = data.shape
    R = rois.shape[0]
    out = np.zeros((R, output_dim, pooled, pooled), np.float64)
    for r in range(R):
        b = int(rois[r, 0])
        xs = round(rois[r, 1]) * scale
        ys = round(rois[r, 2]) * scale
        xe = (round(rois[r, 3]) + 1.0) * scale
        ye = (round(rois[r, 4]) + 1.0) * scale
        rw, rh = max(xe - xs, 0.1), max(ye - ys, 0.1)
        bh, bw = rh / pooled, rw / pooled
        for ct in range(output_dim):
            for ph in range(pooled):
                for pw in range(pooled):
                    hs = min(max(int(np.floor(ph * bh + ys)), 0), H)
                    he = min(max(int(np.ceil((ph + 1) * bh + ys)), 0), H)
                    ws = min(max(int(np.floor(pw * bw + xs)), 0), W)
                    we = min(max(int(np.ceil((pw + 1) * bw + xs)), 0), W)
                    gh = min(max(ph * group // pooled, 0), group - 1)
                    gw = min(max(pw * group // pooled, 0), group - 1)
                    c = (ct * group + gh) * group + gw
                    if he <= hs or we <= ws:
                        continue
                    out[r, ct, ph, pw] = data[b, c, hs:he, ws:we].mean()
    return out


def np_deformable_psroi(data, rois, trans, scale, output_dim, group, pooled, part, spp, trans_std, no_trans):
    B, C, H, W = data.shape
    R = rois.shape[0]
    out = np.zeros((R, output_dim, pooled, pooled), np.float64)
    num_classes = 1 if no_trans else trans.shape[1] // 2
    cpc = output_dim // num_classes
    for r in range(R):
        b = int(rois[r, 0])
        xs = round(rois[r, 1]) * scale - 0.5
        ys = round(rois[r, 2]) * scale - 0.5
        xe = (round(rois[r, 3]) + 1.0) * scale - 0.5
        ye = (round(rois[r, 4]) + 1.0) * scale - 0.5
        rw, rh = max(xe - xs, 0.1), max(ye - ys, 0.1)
        bh, bw = rh / pooled, rw / pooled
        sub_h, sub_w = bh / spp, bw / spp
        for ct in range(output_dim):
            cls = ct // cpc
            for ph in range(pooled):
                for pw in range(pooled):
                    p_h = int(np.floor(float(ph) / pooled * part))
                    p_w = int(np.floor(float(pw) / pooled * part))
                    tx = 0.0 if no_trans else trans[r, cls * 2, p_h, p_w] * trans_std
                    ty = 0.0 if no_trans else trans[r, cls * 2 + 1, p_h, p_w] * trans_std
                    wst = pw * bw + xs + tx * rw
                    hst = ph * bh + ys + ty * rh
                    gh = min(max(ph * group // pooled, 0), group - 1)
                    gw = min(max(pw * group // pooled, 0), group - 1)
                    c = (ct * group + gh) * group + gw
                    acc, cnt = 0.0, 0
                    for ih in range(spp):
                        for iw in range(spp):
                            w_ = wst + iw * sub_w
                            h_ = hst + ih * sub_h
                            if w_ < -0.5 or w_ > W - 0.5 or h_ < -0.5 or h_ > H - 0.5:
                                continue
                            acc += np_bilinear(data[b, c], h_, w_)
                            cnt += 1
                    out[r, ct, ph, pw] = 0.0 if cnt == 0 else acc / cnt
    return out


def np_deformable_conv(data, offset, weight, bias, kernel, stride, dilate, pad, groups, dg):
    B, C, H, W = data.shape
    F = weight.shape[0]
    kh, kw = kernel
    sh, sw = stride
    dh, dw = dilate
    ph, pw = pad
    Ho = (H + 2 * ph - (dh * (kh - 1) + 1)) // sh + 1
    Wo = (W + 2 * pw - (dw * (kw - 1) + 1)) // sw + 1
    col = np.zeros((B, C, kh * kw, Ho, Wo))
    for b in range(B):
        for c in range(C):
            g = c // (C // dg)
            for i in range(kh):
                for j in range(kw):
                    t = i * kw + j
                    for ho in range(Ho):
                        for wo in range(Wo):
                            oy = offset[b, g * 2 * kh * kw + 2 * t, ho, wo]
                            ox = offset[b, g * 2 * kh * kw + 2 * t + 1, ho, wo]
                            y = ho * sh - ph + i * dh + oy
                            x = wo * sw - pw + j * dw + ox
                            if y < 0 or y >= H or x < 0 or x >= W:
                                continue
                            col[b, c, t, ho, wo] = np_bilinear(data[b, c], y, x)
    cpg = C // groups
    fpg = F // groups
    out = np.zeros((B, F, Ho, Wo))
    for b in range(B):
        for g in range(groups):
            w_ = weight[g * fpg:(g + 1) * fpg].reshape(fpg, -1)
            c_ = col[b, g * cpg:(g + 1) * cpg].reshape(cpg * kh * kw, -1)
            out[b, g * fpg:(g + 1) * fpg] = (w_ @ c_).reshape(fpg, Ho, Wo)
    if bias is not None:
        out += bias[None, :, None, None]
    return out


def np_multi_proposal(cls_prob, bbox_pred, im_info, stride, scales, ratios, pre_nms, post_nms, thresh, min_size):
    # anchors
    base = np.array([0, 0, stride - 1, stride - 1], np.float32)
    w0 = base[2] - base[0] + 1
    h0 = base[3] - base[1] + 1
    cx, cy = base[0] + 0.5 * (w0 - 1), base[1] + 0.5 * (h0 - 1)
    size = w0 * h0
    anchors = []
    for r in ratios:
        sr = np.floor(size / r)
        nw = np.floor(np.sqrt(sr) + 0.5)
        nh = np.floor(nw * r + 0.5)
        for s in scales:
            ws, hs = nw * s, nh * s
            anchors.append([cx - 0.5 * (ws - 1), cy - 0.5 * (hs - 1), cx + 0.5 * (ws - 1), cy + 0.5 * (hs - 1)])
    anchors = np.array(anchors, np.float32)
    A = anchors.shape[0]
    B, _, Hf, Wf = cls_prob.shape
    rois_all, scores_all = [], []
    for b in range(B):
        im_h, im_w, im_scale = im_info[b]
        props = []
        for h in range(Hf):
            for w in range(Wf):
                for a in range(A):
                    box = anchors[a] + np.array([w * stride, h * stride, w * stride, h * stride])
                    bw = box[2] - box[0] + 1
                    bh = box[3] - box[1] + 1
                    bcx = box[0] + 0.5 * (bw - 1)
                    bcy = box[1] + 0.5 * (bh - 1)
                    dx, dy, dw_, dh_ = bbox_pred[b, 4 * a:4 * a + 4, h, w]
                    pcx, pcy = dx * bw + bcx, dy * bh + bcy
                    pw_, ph_ = np.exp(dw_) * bw, np.exp(dh_) * bh
                    x1 = np.clip(pcx - 0.5 * (pw_ - 1), 0, im_w - 1)
                    y1 = np.clip(pcy - 0.5 * (ph_ - 1), 0, im_h - 1)
                    x2 = np.clip(pcx + 0.5 * (pw_ - 1), 0, im_w - 1)
                    y2 = np.clip(pcy + 0.5 * (ph_ - 1), 0, im_h - 1)
                    score = cls_prob[b, A + a, h, w]
                    if h >= int(im_h / stride) or w >= int(im_w / stride):
                        score = -1.0
                    ms = min_size * im_scale
                    if (x2 - x1 + 1) < ms or (y2 - y1 + 1) < ms:
                        x1, y1, x2, y2 = x1 - ms / 2, y1 - ms / 2, x2 + ms / 2, y2 + ms / 2
                        score = -1.0
                    props.append([x1, y1, x2, y2, score])
        props = np.array(props, np.float32)
        order = np.argsort(-props[:, 4], kind="stable")[: min(pre_nms, len(props))]
        ordered = props[order]
        # greedy NMS, +1 areas
        area = (ordered[:, 2] - ordered[:, 0] + 1) * (ordered[:, 3] - ordered[:, 1] + 1)
        suppressed = np.zeros(len(ordered), bool)
        keep = []
        for i in range(len(ordered)):
            if len(keep) >= post_nms:
                break
            if suppressed[i]:
                continue
            keep.append(i)
            xx1 = np.maximum(ordered[i, 0], ordered[i + 1:, 0])
            yy1 = np.maximum(ordered[i, 1], ordered[i + 1:, 1])
            xx2 = np.minimum(ordered[i, 2], ordered[i + 1:, 2])
            yy2 = np.minimum(ordered[i, 3], ordered[i + 1:, 3])
            inter = np.maximum(0, xx2 - xx1 + 1) * np.maximum(0, yy2 - yy1 + 1)
            iou = inter / (area[i] + area[i + 1:] - inter)
            suppressed[i + 1:] |= iou > thresh
        out = np.zeros((post_nms, 5), np.float32)
        osc = np.zeros((post_nms, 1), np.float32)
        for i in range(post_nms):
            idx = keep[i] if i < len(keep) else keep[i % len(keep)]
            out[i, 0] = b
            out[i, 1:] = ordered[idx, :4]
            osc[i, 0] = ordered[idx, 4]
        rois_all.append(out)
        scores_all.append(osc)
    return np.concatenate(rois_all), np.concatenate(scores_all)


# ---------------------------------------------------------------------------
# tests
# ---------------------------------------------------------------------------


def test_roi_pooling():
    data = np.random.randn(2, 3, 12, 9).astype(np.float32)
    rois = np.array(
        [
            [0, 0, 0, 16, 16],
            [1, 2, 3, 15, 13],
            [0, 7, 3, 24, 22],  # exceeds the map after scaling
            [1, 5, 5, 5, 5],  # degenerate single-pixel roi
        ],
        np.float32,
    )
    out = nd.ROIPooling(nd.array(data), nd.array(rois), pooled_size=(3, 3), spatial_scale=0.5).asnumpy()
    exp = np_roi_pooling(data, rois, (3, 3), 0.5)
    assert_almost_equal(out, exp, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("ratio", [2, -1])
def test_roi_align(ratio):
    data = np.random.randn(2, 4, 10, 10).astype(np.float32)
    rois = np.array([[0, 1, 1, 8, 8], [1, 0, 0, 18, 12], [0, 3.3, 2.2, 6.1, 7.9]], np.float32)
    out = nd.contrib.ROIAlign(
        nd.array(data), nd.array(rois), pooled_size=(2, 2), spatial_scale=0.5, sample_ratio=ratio
    ).asnumpy()
    exp = np_roi_align(data, rois, (2, 2), 0.5, ratio)
    assert_almost_equal(out, exp, rtol=1e-4, atol=1e-5)


def test_psroi_pooling():
    group, od = 3, 4
    data = np.random.randn(2, group * group * od, 9, 9).astype(np.float32)
    rois = np.array([[0, 0, 0, 14, 14], [1, 2, 4, 17, 15]], np.float32)
    out = nd.contrib.PSROIPooling(
        nd.array(data), nd.array(rois), spatial_scale=0.5, output_dim=od, pooled_size=group, group_size=group
    ).asnumpy()
    exp = np_psroi_pooling(data, rois, 0.5, od, group, group)
    assert_almost_equal(out, exp, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("no_trans", [True, False])
def test_deformable_psroi_pooling(no_trans):
    group = pooled = part = 3
    od = 2
    data = np.random.randn(2, group * group * od, 9, 9).astype(np.float32)
    rois = np.array([[0, 0, 0, 14, 14], [1, 2, 4, 17, 15]], np.float32)
    trans = (np.random.rand(2, 2, part, part).astype(np.float32) - 0.5)
    out = nd.contrib.DeformablePSROIPooling(
        nd.array(data),
        nd.array(rois),
        nd.array(trans),
        spatial_scale=0.5,
        output_dim=od,
        group_size=group,
        pooled_size=pooled,
        part_size=part,
        sample_per_part=2,
        trans_std=0.1,
        no_trans=no_trans,
    ).asnumpy()
    exp = np_deformable_psroi(data, rois, trans, 0.5, od, group, pooled, part, 2, 0.1, no_trans)
    assert_almost_equal(out, exp, rtol=1e-4, atol=1e-5)


def test_deformable_convolution_matches_conv_at_zero_offset():
    data = np.random.randn(1, 4, 7, 7).astype(np.float32)
    weight = np.random.randn(6, 4, 3, 3).astype(np.float32)
    bias = np.random.randn(6).astype(np.float32)
    offset = np.zeros((1, 2 * 9, 5, 5), np.float32)
    out = nd.contrib.DeformableConvolution(
        nd.array(data), nd.array(offset), nd.array(weight), nd.array(bias), kernel=(3, 3), num_filter=6
    ).asnumpy()
    ref = nd.Convolution(
        nd.array(data), nd.array(weight), nd.array(bias), kernel=(3, 3), num_filter=6
    ).asnumpy()
    assert_almost_equal(out, ref, rtol=1e-4, atol=1e-4)


def test_deformable_convolution():
    B, C, H, W = 2, 4, 6, 5
    kernel, stride, dilate, pad = (3, 3), (2, 2), (1, 1), (1, 1)
    dg = 2
    Ho = (H + 2 - 3) // 2 + 1
    Wo = (W + 2 - 3) // 2 + 1
    data = np.random.randn(B, C, H, W).astype(np.float32)
    weight = np.random.randn(4, C, 3, 3).astype(np.float32)
    offset = np.random.randn(B, 2 * dg * 9, Ho, Wo).astype(np.float32)
    out = nd.contrib.DeformableConvolution(
        nd.array(data), nd.array(offset), nd.array(weight),
        kernel=kernel, num_filter=4, stride=stride, dilate=dilate, pad=pad,
        num_deformable_group=dg, no_bias=True,
    ).asnumpy()
    exp = np_deformable_conv(data, offset, weight, None, kernel, stride, dilate, pad, 1, dg)
    assert_almost_equal(out, exp, rtol=1e-3, atol=1e-4)


def test_deformable_convolution_grad():
    # jax AD of the gather formulation vs finite differences (replaces the
    # reference's hand-written deformable_col2im backward)
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops.registry import get as get_op

    op = get_op("_contrib_DeformableConvolution")
    data = np.random.randn(1, 2, 5, 5).astype(np.float32)
    weight = np.random.randn(3, 2, 3, 3).astype(np.float32)
    offset = 0.3 * np.random.randn(1, 18, 5, 5).astype(np.float32)

    def f(d, o, w):
        return op.fn(d, o, w, None, kernel=(3, 3), num_filter=3, pad=(1, 1), no_bias=True).sum()

    g_data, g_off, g_w = jax.grad(f, argnums=(0, 1, 2))(data, offset, weight)
    eps = np.float32(1e-2)  # float32 finite differences
    for arr, g, name in [(data, g_data, "data"), (offset, g_off, "offset"), (weight, g_w, "weight")]:
        idx = tuple(np.unravel_index(np.argmax(np.abs(np.asarray(g))), arr.shape))
        p = arr.copy()
        p[idx] += eps
        m = arr.copy()
        m[idx] -= eps
        args_p = [p if name == "data" else data, p if name == "offset" else offset, p if name == "weight" else weight]
        num = (f(*args_p) - f(*[m if name == "data" else data, m if name == "offset" else offset, m if name == "weight" else weight])) / (2 * eps)
        assert_almost_equal(np.asarray(g)[idx], np.asarray(num), rtol=2e-2, atol=1e-2, names=(name, "fd"))


def test_roi_pooling_grouped_path_matches_ungrouped():
    """The gather-free grouped path (``rois_per_image`` hint, the
    Faster-RCNN head's layout) must match the general path bit-for-bit in
    forward and gradients for batch-major rois."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops.detection import roi_pooling

    rng = np.random.RandomState(5)
    B, C, H, W, Rb = 3, 8, 12, 16, 10
    R = B * Rb
    data = jnp.asarray(rng.rand(B, C, H, W).astype(np.float32))
    rois = np.zeros((R, 5), np.float32)
    rois[:, 0] = np.repeat(np.arange(B), Rb)
    rois[:, 1:3] = rng.rand(R, 2) * 100
    rois[:, 3:5] = rois[:, 1:3] + rng.rand(R, 2) * 100 + 8
    kw = dict(pooled_size=4, spatial_scale=1 / 8)
    base = roi_pooling(data, jnp.asarray(rois), **kw)
    grouped = roi_pooling(data, jnp.asarray(rois), rois_per_image=Rb, **kw)
    np.testing.assert_array_equal(np.asarray(base), np.asarray(grouped))
    g0 = jax.grad(lambda d: (roi_pooling(d, jnp.asarray(rois), **kw) ** 2
                             ).sum())(data)
    g1 = jax.grad(lambda d: (roi_pooling(d, jnp.asarray(rois),
                                         rois_per_image=Rb, **kw) ** 2
                             ).sum())(data)
    np.testing.assert_allclose(np.asarray(g0), np.asarray(g1),
                               rtol=1e-6, atol=1e-6)


def test_deformable_convolution_matmul_path():
    """The separable one-hot-matmul sampling path (engaged above the
    N·H·W size threshold; the TPU north-star res5 runs through it) must
    match the numpy oracle in forward and finite differences in grad."""
    import jax
    from mxnet_tpu.ops.registry import get as get_op

    np.random.seed(7)
    op = get_op("_contrib_DeformableConvolution")
    B, C, H, W, dg, F = 1, 4, 28, 28, 2, 4
    # K2·Ho·Wo·H·W = 9·784·784 ≈ 5.5M ≥ 2^22 → matmul path
    data = np.random.randn(B, C, H, W).astype(np.float32)
    weight = np.random.randn(F, C, 3, 3).astype(np.float32)
    offset = 0.5 * np.random.randn(B, 2 * dg * 9, H, W).astype(np.float32)
    out = np.asarray(op.fn(data, offset, weight, None, kernel=(3, 3),
                           num_filter=F, pad=(1, 1),
                           num_deformable_group=dg, no_bias=True))
    exp = np_deformable_conv(data, offset, weight, None, (3, 3), (1, 1),
                             (1, 1), (1, 1), 1, dg)
    assert_almost_equal(out, exp, rtol=1e-3, atol=1e-4)

    def f(d, o, w):
        return op.fn(d, o, w, None, kernel=(3, 3), num_filter=F,
                     pad=(1, 1), num_deformable_group=dg, no_bias=True).sum()

    g_data, g_off, g_w = jax.grad(f, argnums=(0, 1, 2))(data, offset, weight)
    eps = np.float32(1e-2)
    for arr, g, name in [(data, g_data, "data"), (offset, g_off, "offset"),
                         (weight, g_w, "weight")]:
        idx = tuple(np.unravel_index(
            np.argmax(np.abs(np.asarray(g))), arr.shape))
        p = arr.copy(); p[idx] += eps
        m = arr.copy(); m[idx] -= eps
        pick = lambda v: (v if name == "data" else data,
                          v if name == "offset" else offset,
                          v if name == "weight" else weight)
        num = (f(*pick(p)) - f(*pick(m))) / (2 * eps)
        assert_almost_equal(np.asarray(g)[idx], np.asarray(num),
                            rtol=2e-2, atol=1e-2, names=(name, "fd"))


def test_deformable_convolution_vmem_guard_fallback(monkeypatch):
    """ADVICE round 5: with the estimated backward footprint over the VMEM
    budget, the auto branch must take the plain XLA scan directly (no
    platform_dependent / no Pallas build attempt) and produce identical
    values.  The guard consult and the path taken are both asserted."""
    import jax

    from mxnet_tpu.ops import pallas_kernels
    from mxnet_tpu.ops.registry import get as get_op

    np.random.seed(8)
    op = get_op("_contrib_DeformableConvolution")
    B, C, H, W, dg, F = 1, 4, 28, 28, 2, 4  # matmul path (≥ 2^22)
    data = np.random.randn(B, C, H, W).astype(np.float32)
    weight = np.random.randn(F, C, 3, 3).astype(np.float32)
    offset = 0.5 * np.random.randn(B, 2 * dg * 9, H, W).astype(np.float32)
    kw = dict(kernel=(3, 3), num_filter=F, pad=(1, 1),
              num_deformable_group=dg, no_bias=True)

    verdicts = []
    real_fits = pallas_kernels.dconv_fits_vmem
    monkeypatch.setattr(
        pallas_kernels, "dconv_fits_vmem",
        lambda *a: verdicts.append(real_fits(*a)) or verdicts[-1])
    pd_calls = []
    real_pd = jax.lax.platform_dependent

    def spy_pd(*a, **k):
        pd_calls.append(1)
        return real_pd(*a, **k)

    monkeypatch.setattr(jax.lax, "platform_dependent", spy_pd)

    monkeypatch.delenv("MXNET_DCONV_VMEM_MB", raising=False)
    base = np.asarray(op.fn(data, offset, weight, None, **kw))
    assert verdicts == [True] and pd_calls  # fused path considered

    verdicts.clear()
    pd_calls.clear()
    monkeypatch.setenv("MXNET_DCONV_VMEM_MB", "0.001")  # force fallback
    fell_back = np.asarray(op.fn(data, offset, weight, None, **kw))
    assert verdicts == [False] and not pd_calls  # xla_col taken directly
    assert_almost_equal(base, fell_back, rtol=1e-6, atol=0)


def test_multi_proposal():
    np.random.seed(3)
    B, A, Hf, Wf = 2, 9, 4, 4
    stride = 16
    scales, ratios = (8, 16, 32), (0.5, 1, 2)
    cls_prob = np.random.rand(B, 2 * A, Hf, Wf).astype(np.float32)
    bbox_pred = (0.2 * np.random.randn(B, 4 * A, Hf, Wf)).astype(np.float32)
    im_info = np.array([[64, 64, 1.5], [48, 64, 2.0]], np.float32)
    post = 8
    rois, scores = nd.contrib.MultiProposal(
        nd.array(cls_prob), nd.array(bbox_pred), nd.array(im_info),
        feature_stride=stride, scales=scales, ratios=ratios,
        rpn_pre_nms_top_n=60, rpn_post_nms_top_n=post, threshold=0.7,
        rpn_min_size=8, output_score=True,
    )
    exp_rois, exp_scores = np_multi_proposal(
        cls_prob, bbox_pred, im_info, stride, scales, ratios, 60, post, 0.7, 8
    )
    assert_almost_equal(rois.asnumpy(), exp_rois, rtol=1e-4, atol=1e-4)
    assert_almost_equal(scores.asnumpy(), exp_scores, rtol=1e-4, atol=1e-4)


def test_proposal_symbol():
    # symbolic-path smoke: Proposal inside a Symbol graph
    from mxnet_tpu import sym

    cls = sym.Variable("cls")
    bbox = sym.Variable("bbox")
    info = sym.Variable("info")
    p = sym.contrib.MultiProposal(cls, bbox, info, rpn_post_nms_top_n=4, rpn_pre_nms_top_n=12,
                                  scales=(8,), ratios=(1.0,), feature_stride=16)
    exe = p.simple_bind(mx.cpu(), cls=(1, 2, 3, 3), bbox=(1, 4, 3, 3), info=(1, 3))
    exe.arg_dict["cls"][:] = nd.array(np.random.rand(1, 2, 3, 3).astype(np.float32))
    exe.arg_dict["bbox"][:] = nd.array(0.1 * np.random.randn(1, 4, 3, 3).astype(np.float32))
    exe.arg_dict["info"][:] = nd.array(np.array([[48, 48, 1.0]], np.float32))
    out = exe.forward()[0]
    assert out.shape == (4, 5)
    assert np.isfinite(out.asnumpy()).all()


def np_greedy_nms_alive(boxes, thresh, plus_one=1.0, valid=None, ids=None,
                        force_suppress=True):
    """Sequential greedy NMS survivor mask — oracle for the blocked kernel."""
    N = len(boxes)
    alive = np.ones(N, bool) if valid is None else valid.copy()
    area = np.maximum(boxes[:, 2] - boxes[:, 0] + plus_one, 0) * np.maximum(
        boxes[:, 3] - boxes[:, 1] + plus_one, 0)
    for i in range(N):
        if not alive[i]:
            continue
        tl = np.maximum(boxes[i, :2], boxes[:, :2])
        br = np.minimum(boxes[i, 2:], boxes[:, 2:])
        wh = np.maximum(br - tl + plus_one, 0)
        inter = wh[:, 0] * wh[:, 1]
        union = area[i] + area - inter
        iou = np.where(union <= 0, 0, inter / np.maximum(union, 1e-12))
        sup = (np.arange(N) > i) & (iou > thresh)
        if ids is not None and not force_suppress:
            sup &= ids == ids[i]
        alive &= ~sup
    return alive


@pytest.mark.parametrize("n,tile", [(37, 256), (300, 64), (1000, 256), (6000, 256)])
def test_nms_blocked_matches_sequential_greedy(n, tile):
    """The blocked NMS (N/tile sequential steps) must produce byte-identical
    survivor sets to the sequential greedy scan at every size incl. the
    reference's rpn_pre_nms_top_n=6000 (multi_proposal.cc:221-273)."""
    import jax.numpy as jnp
    from mxnet_tpu.ops.detection import _nms_alive_blocked

    rng = np.random.RandomState(n)
    # heavy-overlap regime: many suppression chains cross tile boundaries
    ctr = rng.rand(n, 2) * 80
    wh = rng.rand(n, 2) * 60 + 10
    boxes = np.concatenate([ctr - wh / 2, ctr + wh / 2], 1).astype(np.float32)
    ref = np_greedy_nms_alive(boxes, 0.7, plus_one=1.0)
    got = np.asarray(_nms_alive_blocked(jnp.asarray(boxes), 0.7, tile=tile, plus_one=1.0))
    assert (ref == got).all()


def test_nms_blocked_ids_and_valid():
    """Per-class suppression + pre-dead rows (box_nms / MultiBoxDetection path)."""
    import jax.numpy as jnp
    from mxnet_tpu.ops.detection import _nms_alive_blocked

    rng = np.random.RandomState(11)
    n = 700
    ctr = rng.rand(n, 2) * 100
    wh = rng.rand(n, 2) * 30 + 2
    boxes = np.concatenate([ctr - wh / 2, ctr + wh / 2], 1).astype(np.float32)
    ids = rng.randint(0, 4, n).astype(np.float32)
    valid = rng.rand(n) > 0.2
    ref = np_greedy_nms_alive(boxes, 0.5, plus_one=0.0, valid=valid, ids=ids,
                              force_suppress=False)
    got = np.asarray(_nms_alive_blocked(
        jnp.asarray(boxes), 0.5, tile=128, plus_one=0.0,
        valid=jnp.asarray(valid), ids=jnp.asarray(ids), force_suppress=False))
    assert (ref == got).all()


def test_nms_blocked_empty():
    import jax.numpy as jnp
    from mxnet_tpu.ops.detection import _nms_alive_blocked

    assert _nms_alive_blocked(jnp.zeros((0, 4)), 0.5).shape == (0,)
    out = nd.contrib.box_nms(nd.array(np.zeros((1, 0, 6), np.float32)))
    assert out.shape == (1, 0, 6)


def test_deformable_psroi_matmul_path_matches_gather_path():
    """The one-hot-matmul hot path (engaged above the size threshold,
    detection.py) must match the gather path in forward AND gradients —
    the TPU headline runs through it."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops import detection as D

    rng = np.random.RandomState(0)
    B, OD, g = 2, 6, 3
    C, H, W = OD * g * g, 12, 16
    data = jnp.asarray(rng.rand(B, C, H, W).astype(np.float32))
    R = 40
    rois = np.zeros((R, 5), np.float32)
    rois[:, 0] = rng.randint(0, B, R)
    rois[:, 1:3] = rng.rand(R, 2) * 100
    rois[:, 3:5] = rois[:, 1:3] + rng.rand(R, 2) * 120 + 8
    trans = jnp.asarray(0.3 * rng.randn(R, 2, 3, 3).astype(np.float32))
    kw = dict(spatial_scale=1 / 8, output_dim=OD, group_size=g,
              pooled_size=3, part_size=3, trans_std=0.1)
    small = D.deformable_psroi_pooling(data, jnp.asarray(rois), trans, **kw)
    # tile ROIs 40x to cross the 1<<16 threshold -> matmul path
    roisL = jnp.asarray(np.tile(rois, (40, 1)))
    transL = jnp.asarray(np.tile(np.asarray(trans), (40, 1, 1, 1)))
    big = D.deformable_psroi_pooling(data, roisL, transL, **kw)
    np.testing.assert_allclose(np.asarray(big[:R]), np.asarray(small),
                               rtol=1e-5, atol=1e-5)

    f_small = lambda d, t: jnp.sum(
        D.deformable_psroi_pooling(d, jnp.asarray(rois), t, **kw) ** 2)
    f_big = lambda d, t: jnp.sum(
        D.deformable_psroi_pooling(d, roisL, t, **kw)[:R] ** 2)
    gs = jax.grad(f_small, argnums=(0, 1))(data, trans)
    gb = jax.grad(f_big, argnums=(0, 1))(data, transL)
    np.testing.assert_allclose(np.asarray(gs[0]), np.asarray(gb[0]),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(gs[1]), np.asarray(gb[1][:R]),
                               rtol=1e-4, atol=1e-5)


def test_deformable_psroi_grouped_path_matches_ungrouped():
    """The block-diagonal batch-major path (``rois_per_image`` hint, the
    O(B) batch-scaling fix) must match the general path bit-for-bit in
    forward and gradients for grouped rois."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops import detection as D

    rng = np.random.RandomState(1)
    B, OD, g = 3, 6, 3
    C, H, W = OD * g * g, 12, 16
    data = jnp.asarray(rng.rand(B, C, H, W).astype(np.float32))
    Rb = 40
    R = B * Rb
    rois = np.zeros((R, 5), np.float32)
    rois[:, 0] = np.repeat(np.arange(B), Rb)  # batch-major grouping
    rois[:, 1:3] = rng.rand(R, 2) * 100
    rois[:, 3:5] = rois[:, 1:3] + rng.rand(R, 2) * 120 + 8
    trans = jnp.asarray(0.3 * rng.randn(R, 2, 3, 3).astype(np.float32))
    roisj = jnp.asarray(rois)
    kw = dict(spatial_scale=1 / 8, output_dim=OD, group_size=g,
              pooled_size=3, part_size=3, trans_std=0.1)
    # R*K*PH*PW*spp2*cpc = 120*1*9*16*6 = 103,680 >= 1<<16 = 65,536 -> both
    # runs take the matmul path (shrinking OD below 4 would drop under the
    # threshold and test the gather path vacuously)
    plain = D.deformable_psroi_pooling(data, roisj, trans, **kw)
    grouped = D.deformable_psroi_pooling(data, roisj, trans,
                                         rois_per_image=Rb, **kw)
    np.testing.assert_allclose(np.asarray(grouped), np.asarray(plain),
                               rtol=1e-5, atol=1e-6)

    f_p = lambda d, t: jnp.sum(
        D.deformable_psroi_pooling(d, roisj, t, **kw) ** 2)
    f_g = lambda d, t: jnp.sum(
        D.deformable_psroi_pooling(d, roisj, t, rois_per_image=Rb, **kw) ** 2)
    gp = jax.grad(f_p, argnums=(0, 1))(data, trans)
    gg = jax.grad(f_g, argnums=(0, 1))(data, trans)
    np.testing.assert_allclose(np.asarray(gg[0]), np.asarray(gp[0]),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(gg[1]), np.asarray(gp[1]),
                               rtol=1e-4, atol=1e-5)
    # a wrong rois_per_image (not matching R) safely falls back to general
    fallback = D.deformable_psroi_pooling(data, roisj, trans,
                                          rois_per_image=7, **kw)
    np.testing.assert_allclose(np.asarray(fallback), np.asarray(plain),
                               rtol=1e-6, atol=0)


def test_grouped_roi_hint_misuse_raises_in_debug_mode():
    """VERDICT r4 item 7: the ``rois_per_image`` grouped layout is a trusted
    hint on the fused path, but the synchronous debug engine (the
    reference's ``MXNET_ENGINE_TYPE=NaiveEngine`` story) validates it —
    shuffled/interleaved rois raise instead of silently pooling from the
    wrong image."""
    from mxnet_tpu import engine

    data = np.random.randn(2, 8, 8, 8).astype(np.float32)
    good = np.array(
        [[0, 0, 0, 7, 7], [0, 1, 1, 6, 6], [1, 0, 0, 7, 7], [1, 2, 2, 5, 5]],
        np.float32)
    bad = good[[2, 1, 0, 3]]  # interleaved batch indices
    kw = dict(pooled_size=(2, 2), spatial_scale=1.0, rois_per_image=2)

    # fused/trusted path: no validation, no cost — documents the contract
    nd.ROIPooling(nd.array(data), nd.array(bad), **kw).asnumpy()

    engine.naive_engine(True)
    try:
        # correct grouping passes and matches the ungrouped result
        out = nd.ROIPooling(nd.array(data), nd.array(good), **kw).asnumpy()
        exp = nd.ROIPooling(nd.array(data), nd.array(good),
                            pooled_size=(2, 2), spatial_scale=1.0).asnumpy()
        assert_almost_equal(out, exp, rtol=1e-6, atol=0)
        with pytest.raises(ValueError, match="batch-major"):
            nd.ROIPooling(nd.array(data), nd.array(bad), **kw)
        # an all-ZEROS (unfilled) batch_idx column is NOT misuse — the
        # documented contract lets positional groupers leave it at 0
        zeroed = good.copy(); zeroed[:, 0] = 0
        nd.ROIPooling(nd.array(data), nd.array(zeroed), **kw).asnumpy()
        # but only the zero constant is exempt: a constant NONZERO column
        # carries real indices (every roi claims image 1) inconsistent
        # with r // Rb, and must raise like any filled column (ADVICE r5)
        ones = good.copy(); ones[:, 0] = 1
        with pytest.raises(ValueError, match="batch-major"):
            nd.ROIPooling(nd.array(data), nd.array(ones), **kw)
        # same contract on the deformable pooling's hint
        drois = np.array([[1, 0, 0, 14, 14], [0, 2, 4, 17, 15]], np.float32)
        with pytest.raises(ValueError, match="batch-major"):
            nd.contrib.DeformablePSROIPooling(
                nd.array(data), nd.array(drois), spatial_scale=0.5,
                output_dim=2, group_size=2, pooled_size=2, no_trans=True,
                rois_per_image=1)
    finally:
        engine.naive_engine(False)


# -- the rank-one one-hot path of DeformablePSROIPooling (ISSUE 29) ----------
# rois (x1, y1, x2, y2) on a 7 x 9 map at spatial_scale 1, pooled 3, spp 2:
# one hangs over each edge, one lies wholly outside (every sample dead), one
# puts samples exactly at -0.5 and one at W - 0.5 / H - 0.5 (all live), one
# is narrower than the 0.1 floor in both directions, one sits inside.  Sizes
# are multiples of 3 so that sample positions are exact in float32 and in
# the reference's float64 alike (a third would round across a boundary)
_PSROI_EDGE_ROIS = np.array([
    [-5, 1, 3, 6], [5, 2, 13, 4], [2, -4, 7, 4], [1, 4, 6, 12],
    [20, 15, 31, 23], [0, 0, 11, 8], [3, 1, 14, 12], [4, 3, 3, 2],
    [2, 1, 7, 6]], np.float32)
_PSROI_MAP = (7, 9)


def _psroi_edge_case(grouped, dtype, tiles=20, seed=0):
    """-> (data, unique rois, trans of the unique rois, tiled rois, tiled
    trans, kw, rows of the tiled run that hold the unique rois, the tiled
    run's ``rois_per_image``).  The tiled run is over the one-hot
    threshold, the unique rois under it."""
    import jax.numpy as jnp

    rng = np.random.RandomState(seed)
    B, OD, g = 2, 6, 3
    H, W = _PSROI_MAP
    U = len(_PSROI_EDGE_ROIS)
    uniq = np.zeros((B * U, 5), np.float32)
    uniq[:, 0] = np.repeat(np.arange(B), U)
    uniq[:, 1:] = np.tile(_PSROI_EDGE_ROIS, (B, 1))
    trans = (0.3 * rng.randn(B * U, 2, g, g)).astype(np.float32)
    if grouped:   # batch-major: image b's rows are its unique rois, tiled
        big = uniq.reshape(B, 1, U, 5).repeat(tiles, 1).reshape(-1, 5)
        big_t = trans.reshape(B, 1, U, 2, g, g).repeat(tiles, 1).reshape(
            -1, 2, g, g)
        rows = (np.arange(B)[:, None] * tiles * U + np.arange(U)).reshape(-1)
    else:         # interleaved images: only the batch_idx column says which
        big, big_t = np.tile(uniq, (tiles, 1)), np.tile(trans, (tiles, 1, 1, 1))
        rows = np.arange(B * U)
    data = jnp.asarray(rng.randn(B, OD * g * g, H, W).astype(np.float32)
                       ).astype(dtype)
    kw = dict(spatial_scale=1.0, output_dim=OD, group_size=g, pooled_size=g,
              part_size=g, sample_per_part=2, trans_std=0.1)
    assert len(big) * g * g * 4 * OD >= 1 << 16 > len(uniq) * g * g * 4 * OD
    return (data, jnp.asarray(uniq), jnp.asarray(trans).astype(dtype),
            jnp.asarray(big), jnp.asarray(big_t).astype(dtype), kw,
            rows, tiles * U if grouped else 0)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("no_trans", [True, False])
@pytest.mark.parametrize("grouped", [True, False])
def test_deformable_psroi_onehot_matches_per_sample_reference(
        grouped, no_trans, dtype):
    """The one-hot path builds each bin's accumulation matrix as an outer
    product of a per-row and a per-column weight vector; the reference walks
    every sample and its four corners in a plain loop."""
    import jax.numpy as jnp
    from mxnet_tpu.ops import detection as D

    data, uniq, trans, big, big_t, kw, rows, rpi = _psroi_edge_case(
        grouped, jnp.dtype(dtype))
    out = D.deformable_psroi_pooling(data, big, big_t, no_trans=no_trans,
                                     rois_per_image=rpi, **kw)
    assert out.dtype == data.dtype
    got = np.asarray(out.astype(jnp.float32))[rows]
    exp = np_deformable_psroi(
        np.asarray(data.astype(jnp.float32), np.float64), np.asarray(uniq),
        np.asarray(trans.astype(jnp.float32), np.float64), 1.0, 6, 3, 3, 3,
        2, 0.1, no_trans)
    tol = 1e-5 if dtype == "float32" else 2e-2   # bf16: A, data and output
    assert_almost_equal(got, exp, rtol=tol, atol=tol)
    outside = np.flatnonzero(np.asarray(uniq)[:, 1] == 20)
    assert len(outside) == 2 and not got[outside].any()   # cnt 0 -> 0
    if no_trans:
        # the inclusive boundary: bin (0, 0) of the roi at (0, 0) averages
        # samples at -0.5 and 1.5 on both axes, all four live
        d = np.asarray(data.astype(jnp.float32), np.float64)
        exp00 = np.mean([np_bilinear(d[0, 0], y, x)
                         for y in (-0.5, 1.0) for x in (-0.5, 1.5)])
        assert abs(got[5, 0, 0, 0] - exp00) <= tol * max(1.0, abs(exp00))


@pytest.mark.parametrize("grouped", [True, False])
def test_deformable_psroi_onehot_grads_match_gather_path(grouped):
    """Gradients to the data and to the offsets: the rank-one one-hot path
    (tiled rois, over the threshold) against the gather path (the unique
    rois alone), on rois that hang over every edge."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops import detection as D

    data, uniq, trans, big, big_t, kw, rows, rpi = _psroi_edge_case(
        grouped, jnp.float32, seed=1)
    cot = jnp.asarray(np.random.RandomState(2).randn(len(uniq), 6, 3, 3)
                      .astype(np.float32))
    U = len(_PSROI_EDGE_ROIS)
    f_small = lambda d, t: jnp.sum(cot * D.deformable_psroi_pooling(
        d, uniq, t, rois_per_image=U if grouped else 0, **kw))
    f_big = lambda d, t: jnp.sum(cot * D.deformable_psroi_pooling(
        d, big, t, rois_per_image=rpi, **kw)[rows])
    gs = jax.grad(f_small, argnums=(0, 1))(data, trans)
    gb = jax.grad(f_big, argnums=(0, 1))(data, big_t)
    assert float(jnp.abs(gs[1]).max()) > 1.0      # the offsets matter
    np.testing.assert_allclose(np.asarray(gb[0]), np.asarray(gs[0]),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(gb[1])[rows], np.asarray(gs[1]),
                               rtol=1e-4, atol=2e-5)


def _psroi_structure_case(grouped):
    """A one-hot-path call whose every axis length differs from spp² = 16
    and whose roi count exceeds the data's channels."""
    import jax.numpy as jnp
    from mxnet_tpu.ops import detection as D

    rng = np.random.RandomState(0)
    B, OD, g, H, W, Rb = 2, 6, 3, 12, 20, 120
    R = B * Rb
    rois = np.zeros((R, 5), np.float32)
    rois[:, 0] = np.repeat(np.arange(B), Rb)
    rois[:, 1:3] = rng.rand(R, 2) * 100
    rois[:, 3:5] = rois[:, 1:3] + rng.rand(R, 2) * 120 + 8
    rois = jnp.asarray(rois)
    data = jnp.asarray(rng.rand(B, OD * g * g, H, W).astype(np.float32))
    trans = jnp.asarray(0.3 * rng.randn(R, 2, g, g).astype(np.float32))
    fn = lambda d, t: D.deformable_psroi_pooling(
        d, rois, t, spatial_scale=1 / 8, output_dim=OD, group_size=g,
        pooled_size=g, part_size=g, sample_per_part=4, trans_std=0.1,
        rois_per_image=Rb if grouped else 0)
    return fn, data, trans, R * H * W


def _all_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _all_eqns(sub)


@pytest.mark.parametrize("grouped", [True, False])
def test_deformable_psroi_onehot_contracts_no_sample_axis(grouped):
    """The accumulation matrix is an outer product: forward and backward
    hold no matrix product or convolution that contracts over the spp²
    samples of a bin (the rank-16 build that ran at 0.4 % of the MXU)."""
    import jax
    import jax.numpy as jnp

    fn, data, trans, _ = _psroi_structure_case(grouped)
    loss = lambda d, t: jnp.sum(fn(d, t) ** 2)
    jaxpr = jax.make_jaxpr(jax.value_and_grad(loss, argnums=(0, 1)))(
        data, trans)
    dots = 0
    for eqn in _all_eqns(jaxpr.jaxpr):
        assert not eqn.primitive.name.startswith("conv_general"), eqn
        if eqn.primitive.name == "dot_general":
            dots += 1
            (lc, _), _ = eqn.params["dimension_numbers"]
            shape = eqn.invars[0].aval.shape
            assert 16 not in [shape[i] for i in lc], eqn
    assert dots >= 3    # the plane product and its two transposes


@pytest.mark.parametrize("grouped", [True, False])
def test_deformable_psroi_onehot_saves_no_accumulation_matrix(grouped):
    """No (R, H·W) array is a residual of the forward: each bin's matrix is
    rebuilt in the backward (jax.checkpoint), so device memory does not grow
    with bins x rois x map."""
    import jax

    fn, data, trans, rhw = _psroi_structure_case(grouped)
    _, f_vjp = jax.vjp(fn, data, trans)
    sizes = [int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(f_vjp)
             if hasattr(x, "shape")]
    assert sizes and max(sizes) < rhw, (sorted(sizes)[-3:], rhw)


def test_dconv_col_pallas_matches_xla_formulation():
    """Round-5 fused dconv sampling kernel: VMEM-resident A (and dA) must
    equal the XLA one-hot-matmul formulation, values and all four grads
    (d_ly, d_lx, d_lf, d_ft), channels-major on both sides (PR 32:
    ``ft^T (BG, C, HW)`` in, ``col^T (BG, C, N)`` out) — interpret mode
    here; the chip consistency tier covers the compiled kernel."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops.pallas_kernels import dconv_col_pallas
    from mxnet_tpu.test_utils import dconv_dense_reference

    BG, N, H, W, C = 3, 70, 9, 11, 16   # N not a block multiple
    HW = H * W
    rng = np.random.RandomState(0)
    y0 = jnp.asarray(rng.randint(0, H - 1, (BG, N)).astype(np.int32))
    y1 = jnp.minimum(y0 + 1, H - 1)
    x0 = jnp.asarray(rng.randint(0, W - 1, (BG, N)).astype(np.int32))
    x1 = jnp.minimum(x0 + 1, W - 1)
    ly = jnp.asarray(rng.rand(BG, N).astype(np.float32))
    lx = jnp.asarray(rng.rand(BG, N).astype(np.float32))
    lf = jnp.asarray((rng.rand(BG, N) > 0.2).astype(np.float32))
    ft = jnp.asarray(rng.randn(BG, C, HW).astype(np.float32))

    def ref(*a):
        return dconv_dense_reference(*a, (H, W))

    r = ref(y0, y1, x0, x1, ly, lx, lf, ft)
    o = dconv_col_pallas(y0, y1, x0, x1, ly, lx, lf, ft, (H, W), True)
    assert o.shape == r.shape == (BG, C, N)
    np.testing.assert_allclose(np.asarray(o), np.asarray(r),
                               rtol=1e-5, atol=1e-5)

    g = jnp.asarray(rng.randn(BG, C, N).astype(np.float32))
    fr = lambda *a: jnp.sum(ref(y0, y1, x0, x1, *a) * g)
    fp = lambda *a: jnp.sum(
        dconv_col_pallas(y0, y1, x0, x1, *a, (H, W), True) * g)
    gr = jax.grad(fr, argnums=(0, 1, 2, 3))(ly, lx, lf, ft)
    gp = jax.grad(fp, argnums=(0, 1, 2, 3))(ly, lx, lf, ft)
    for i in range(4):
        assert gp[i].shape == gr[i].shape
        np.testing.assert_allclose(np.asarray(gp[i]), np.asarray(gr[i]),
                                   rtol=1e-4, atol=1e-4)


def test_deformable_conv_impl_env_override():
    """MXNET_DCONV_IMPL=pallas runs the fused kernel (interpret on CPU)
    and must match the default XLA path on the big-path shapes."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops import detection as D

    rng = np.random.RandomState(1)
    # N*H*W = (9*32*32)*(32*32) = 9.4M >= 1<<22: the ONE-HOT path (where
    # the impl dispatch lives), not the small-shape gather fallback
    B, C, H, W = 1, 8, 32, 32
    F = 8
    data = jnp.asarray(rng.randn(B, C, H, W).astype(np.float32))
    off = jnp.asarray(0.4 * rng.randn(B, 2 * 9 * 2, H, W).astype(np.float32))
    wt = jnp.asarray(rng.randn(F, C, 3, 3).astype(np.float32) * 0.1)
    kw = dict(kernel=(3, 3), num_filter=F, pad=(1, 1),
              num_deformable_group=2, no_bias=True)
    cot = jnp.asarray(rng.randn(B, F, H, W).astype(np.float32))

    def out_and_grads():
        def loss(*a):
            return jnp.sum(D.deformable_convolution(*a, **kw) * cot)
        return (D.deformable_convolution(data, off, wt, **kw),
                *jax.grad(loss, argnums=(0, 1, 2))(data, off, wt))

    base = out_and_grads()
    os.environ["MXNET_DCONV_IMPL"] = "pallas"
    try:
        pal = out_and_grads()
    finally:
        del os.environ["MXNET_DCONV_IMPL"]
    # the XLA scan and the kernel hand over the same channels-major columns
    # (PR 32): values, then the gradients to data, offsets and weights
    for name, p, b in zip(("out", "d_data", "d_offset", "d_weight"), pal,
                          base):
        np.testing.assert_allclose(np.asarray(p), np.asarray(b), rtol=1e-4,
                                   atol=1e-4, err_msg=name)


@pytest.mark.parametrize("groups", [1, 2])
def test_grouped_col_product_vjp_matches_einsum(groups):
    """``_grouped_col_product`` writes its own cotangents (the columns' one
    as ``(b, g, k, p)``, no transpose): value and both gradients equal the
    plain einsum's, grouped or not."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops.detection import _grouped_col_product

    rng = np.random.RandomState(4)
    B, G, Fg, K, Pn = 3, groups, 5, 18, 40
    wmat = jnp.asarray(rng.randn(G, Fg, K).astype(np.float32))
    col = jnp.asarray(rng.randn(B, G, K, Pn).astype(np.float32))
    cot = jnp.asarray(rng.randn(B, G, Fg, Pn).astype(np.float32))
    plain = lambda w, c: jnp.einsum("gfk,bgkp->bgfp", w, c)
    got = jax.vjp(_grouped_col_product, wmat, col)
    want = jax.vjp(plain, wmat, col)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                               rtol=1e-6, atol=1e-6)
    for g, w in zip(got[1](cot), want[1](cot)):
        assert g.shape == w.shape and g.dtype == w.dtype
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("impl", ["pallas", "xla"])
def test_deformable_conv_transposes_no_columns(impl, monkeypatch):
    """The mechanism of PR 32: features and columns stay channels-major from
    the data to the grouped product, so the traced forward + backward of
    ``deformable_convolution`` on the one-hot path holds no ``transpose`` of
    an array as large as the columns (``B x C x N``), through the kernel
    pair and through the XLA scan alike.  Before, the kernel's ``(BG, N,
    cpg)`` columns were transposed into ``(B, C, K2, Ho, Wo)`` and their
    gradient back: two per layer.  What is left are features-sized
    (``B x C x HW``, a ninth of the columns for a 3x3 kernel)."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops import detection as D

    monkeypatch.setenv("MXNET_DCONV_IMPL", impl)
    B, C, H, W, F, DG = 2, 8, 24, 32, 8, 2
    N = 9 * H * W
    assert N * H * W >= 1 << 22  # the one-hot path, where the kernels are
    kw = dict(kernel=(3, 3), num_filter=F, pad=(1, 1),
              num_deformable_group=DG, no_bias=True)
    shapes = ((B, C, H, W), (B, 2 * 9 * DG, H, W), (F, C, 3, 3))

    def loss(data, off, wt):
        return jnp.sum(D.deformable_convolution(data, off, wt, **kw))

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(
        *(jnp.zeros(s, jnp.float32) for s in shapes))
    eqns = list(_all_eqns(jaxpr.jaxpr))
    if impl == "pallas":
        kernels = {e.params["name"] for e in eqns
                   if e.primitive.name == "pallas_call"}
        assert kernels == {"dconv_col_pallas_fwd", "dconv_col_pallas_bwd"}
    sizes = [int(np.prod(e.invars[0].aval.shape)) for e in eqns
             if e.primitive.name == "transpose"]
    assert sizes, "the features' transposes of the backward are still there"
    # columns-sized is B*C*N; the threshold is half of that, the features
    # B*C*HW = 2/9 of it
    assert max(sizes) < N * C, sorted(sizes)[-4:]
