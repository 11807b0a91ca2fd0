"""Detection operators — the north-star op set (SURVEY §2.1 contrib ops).

TPU-native re-designs of the reference's CPU/CUDA detection kernels
(``src/operator/contrib/{roi_align,psroi_pooling,deformable_psroi_pooling,
deformable_convolution-inl,multi_proposal,multibox_prior,multibox_target,
multibox_detection,bounding_box-inl}``, ``src/operator/roi_pooling.cc``).

Design rules (SURVEY §7.3 "dynamic shapes on TPU"):

* Every output has a **static shape**; variable-count results (NMS survivors,
  valid detections) are carried as fixed-capacity arrays + masks/sentinels,
  exactly matching the reference's padded outputs where it has them
  (Proposal pads by cycling kept boxes, MultiBoxDetection pads with -1 rows).
* Irregular reads are **bilinear/integer gathers** built from broadcasted
  iotas + masks; XLA fuses the mask+reduce so no (R,C,H,W,PH,PW) tensor is
  ever materialized.
* Greedy NMS runs **blocked**: N/tile sequential steps, each settling one
  score-ordered tile by fixed-point iteration over a dense (tile, tile) IoU
  matrix, then one (tile, N) sweep over later boxes — identical survivors to
  the sequential greedy scan, but the sequential depth at the reference's
  ``rpn_pre_nms_top_n=6000`` drops from 6000 to ~24 (``_nms_alive_blocked``).
* The deformable-conv hot loop lands on the MXU: bilinear im2col gather
  followed by one big (C·K²)×F matmul, grouped when num_group>1.

All gradients come from jax AD of these same formulations (the reference
hand-writes every backward kernel, e.g. deformable_col2im's atomic scatter —
here XLA emits the scatter-add from the gather's transpose automatically).
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np

from .registry import register, stable_eager


def _pair(v):
    if isinstance(v, (int, float)):
        return (int(v), int(v))
    v = tuple(int(x) for x in v)
    return v * 2 if len(v) == 1 else v


def _check_grouped_layout(batch_idx, B, Rb, op):
    """Debug-mode validation of the ``rois_per_image`` layout contract.

    The grouped pooling paths TRUST that roi r belongs to image r // Rb and
    ignore the batch_idx column (a traced value cannot be asserted inside
    jit).  Under the synchronous debug engine (``MXNET_ENGINE_TYPE=
    NaiveEngine`` / ``engine.naive_engine()`` — the reference's debug story,
    ``docs/faq/env_var.md:52-56``) values are concrete, so the contract IS
    checkable: a batch_idx column that carries real indices inconsistent
    with r // Rb raises here instead of silently pooling from the wrong
    image.  A CONSTANT column (callers that group positionally and leave
    batch_idx at 0 — valid per the "column is ignored" contract) passes.
    Zero cost on the fused path — the check short-circuits unless debug
    mode is on, and a tracer (still possible under ``disable_jit`` inside
    e.g. ``jax.grad``) skips it.
    """
    from .. import engine

    if not engine.is_naive():
        return
    try:
        idx = np.asarray(batch_idx).reshape(B, Rb)
    except Exception:  # tracer or abstract value — nothing to check
        return
    if (idx == 0).all():
        # all-zeros column: the caller grouped positionally and never
        # filled batch_idx — consistent with the documented "column is
        # ignored" contract, no evidence of misuse.  Only the ZERO constant
        # is exempt: a constant NONZERO column carries real indices (every
        # roi claims image k) and must agree with r // Rb like any other
        # filled column (ADVICE round 5)
        return
    expect = np.broadcast_to(np.arange(B)[:, None], (B, Rb))
    if not np.array_equal(idx, expect):
        bad = int(np.argmax((idx != expect).reshape(-1)))
        raise ValueError(
            "%s: rois_per_image=%d promises batch-major grouped rois "
            "(roi r belongs to image r // %d), but roi %d has batch_idx "
            "%d, expected %d. Pass rois straight from MultiProposal/"
            "proposal_target, or drop the rois_per_image hint."
            % (op, Rb, Rb, bad, int(idx.reshape(-1)[bad]),
               int(expect.reshape(-1)[bad])))


# ---------------------------------------------------------------------------
# bilinear sampling helpers
# ---------------------------------------------------------------------------


def _bilinear(plane, y, x):
    """Bilinear sample ``plane`` (H, W) at float coords, reference snap rule:
    neighbors clamp to the last row/col (roi_align.cc:276-284), so positions
    in (H-1, H) degrade to 1-D interpolation along the other axis.  Caller
    masks fully-out-of-range samples."""
    H, W = plane.shape
    y = jnp.clip(y, 0.0, H - 1.0)
    x = jnp.clip(x, 0.0, W - 1.0)
    y0 = jnp.floor(y).astype(jnp.int32)
    x0 = jnp.floor(x).astype(jnp.int32)
    y1 = jnp.minimum(y0 + 1, H - 1)
    x1 = jnp.minimum(x0 + 1, W - 1)
    ly = y - y0.astype(plane.dtype)
    lx = x - x0.astype(plane.dtype)
    v00 = plane[y0, x0]
    v01 = plane[y0, x1]
    v10 = plane[y1, x0]
    v11 = plane[y1, x1]
    return (
        v00 * (1 - ly) * (1 - lx)
        + v01 * (1 - ly) * lx
        + v10 * ly * (1 - lx)
        + v11 * ly * lx
    )


# vectorized over arbitrarily-shaped coord arrays, channel-major plane stack
_bilinear_hw = jax.vmap(_bilinear, in_axes=(0, None, None))  # over channels


# ---------------------------------------------------------------------------
# ROIPooling (reference src/operator/roi_pooling.cc:62-130)
# ---------------------------------------------------------------------------


@register("ROIPooling", alias=["_contrib_ROIPooling"])
def roi_pooling(data, rois, *, pooled_size, spatial_scale, rois_per_image=0):
    """Max pooling over ROI bins (reference src/operator/roi_pooling.cc:62).

    data: (B, C, H, W); rois: (R, 5) [batch_idx, x1, y1, x2, y2] in image
    coords.  Integer rounding semantics: roi corners are ``round(coord *
    spatial_scale)``, bins are [floor(ph·bs), ceil((ph+1)·bs)) clipped to the
    map, empty bins output 0 (roi_pooling.cc:69-117).

    ``rois_per_image`` (static, optional): caller's guarantee that rois are
    batch-major grouped (the MultiProposal / proposal_target layout) —
    image axes then align by indexing and the per-roi ``data[batch_idx]``
    gather disappears.  The chip profile of the batch-4 Faster-RCNN step
    showed that gather as a sequential while + ~1.3 GB of feature-map
    copies (~65 ms/step of a 120 ms step); the grouped path is the same
    separable masked-max with zero gathers.  Like the deformable pooling's
    hint, this TRUSTS the layout and ignores the batch_idx column; under
    the synchronous debug engine (``MXNET_ENGINE_TYPE=NaiveEngine``) the
    contract is validated and misuse raises (``_check_grouped_layout``).
    """
    PH, PW = _pair(pooled_size)
    B, C, H, W = data.shape
    R = rois.shape[0]
    f32 = data.dtype
    # bin-boundary math always runs fp32 (deformable_psroi_pooling's
    # discipline): bf16 products near integers floor/ceil differently per
    # backend, shifting integer bin extents wholesale
    cf = jnp.float32
    rois = rois.astype(cf)

    batch_idx = rois[:, 0].astype(jnp.int32)
    xs = jnp.round(rois[:, 1] * spatial_scale).astype(jnp.int32)
    ys = jnp.round(rois[:, 2] * spatial_scale).astype(jnp.int32)
    xe = jnp.round(rois[:, 3] * spatial_scale).astype(jnp.int32)
    ye = jnp.round(rois[:, 4] * spatial_scale).astype(jnp.int32)
    roi_h = jnp.maximum(ye - ys + 1, 1).astype(cf)  # (R,)
    roi_w = jnp.maximum(xe - xs + 1, 1).astype(cf)
    bs_h = roi_h / PH
    bs_w = roi_w / PW

    ph = jnp.arange(PH, dtype=cf)
    pw = jnp.arange(PW, dtype=cf)
    # bin bounds per (R, PH) before roi offset, then clipped into the map
    hstart = jnp.floor(ph[None, :] * bs_h[:, None]).astype(jnp.int32) + ys[:, None]
    hend = jnp.ceil((ph[None, :] + 1) * bs_h[:, None]).astype(jnp.int32) + ys[:, None]
    wstart = jnp.floor(pw[None, :] * bs_w[:, None]).astype(jnp.int32) + xs[:, None]
    wend = jnp.ceil((pw[None, :] + 1) * bs_w[:, None]).astype(jnp.int32) + xs[:, None]
    hstart, hend = jnp.clip(hstart, 0, H), jnp.clip(hend, 0, H)
    wstart, wend = jnp.clip(wstart, 0, W), jnp.clip(wend, 0, W)

    hh = jnp.arange(H)
    ww = jnp.arange(W)
    mask_h = (hh[None, None, :] >= hstart[:, :, None]) & (hh[None, None, :] < hend[:, :, None])  # (R,PH,H)
    mask_w = (ww[None, None, :] >= wstart[:, :, None]) & (ww[None, None, :] < wend[:, :, None])  # (R,PW,W)

    neg = jnp.array(-np.inf, f32)
    Rb = int(rois_per_image)
    if Rb > 0 and R == B * Rb:
        # grouped path: roi r belongs to image r // Rb — pure indexing
        _check_grouped_layout(batch_idx, B, Rb, "ROIPooling")
        mh = mask_h.reshape(B, Rb, PH, H)
        mw = mask_w.reshape(B, Rb, PW, W)
        # separable masked max, image axes aligned; XLA fuses select+reduce
        t = jnp.where(mh[:, :, :, None, :, None], data[:, None, None], neg
                      ).max(axis=4)                       # (B,Rb,PH,C,W)
        o = jnp.where(mw[:, :, None, None, :], t[..., None, :], neg
                      ).max(axis=5)                       # (B,Rb,PH,C,PW)
        out = o.transpose(0, 1, 3, 2, 4).reshape(R, C, PH, PW)
    else:
        def one_roi(b, mh, mw):
            feat = data[b]  # (C, H, W)
            # separable masked max: over H then W; XLA fuses select+reduce
            t = jnp.where(mh[:, None, :, None], feat[None], neg).max(axis=2)  # (PH,C,W)
            o = jnp.where(mw[:, None, None, :], t[None], neg).max(axis=3)  # (PW,PH,C)
            return o.transpose(2, 1, 0)  # (C, PH, PW)

        out = jax.vmap(one_roi)(batch_idx, mask_h, mask_w)  # (R, C, PH, PW)
    empty = (hend <= hstart)[:, None, :, None] | (wend <= wstart)[:, None, None, :]
    return jnp.where(empty, jnp.zeros((), f32), out)


# ---------------------------------------------------------------------------
# ROIAlign (reference src/operator/contrib/roi_align.cc:141-236)
# ---------------------------------------------------------------------------


@register("_contrib_ROIAlign", alias=["ROIAlign"])
def roi_align(data, rois, *, pooled_size, spatial_scale, sample_ratio=-1):
    """Average of bilinear samples per bin (reference roi_align.cc:141).

    No coordinate rounding; roi sizes floored at 1; per-bin grid is
    ``sample_ratio`` when > 0 else ``ceil(roi_size / pooled_size)`` — the
    adaptive case is realized as a static sample grid (capped at the grid a
    map-spanning roi needs) with count masking, so shapes stay static.
    """
    PH, PW = _pair(pooled_size)
    B, C, H, W = data.shape
    f32 = data.dtype
    # sample-coordinate math always fp32 (see roi_pooling note)
    cf = jnp.float32
    rois = rois.astype(cf)

    batch_idx = rois[:, 0].astype(jnp.int32)
    x1 = rois[:, 1] * spatial_scale
    y1 = rois[:, 2] * spatial_scale
    x2 = rois[:, 3] * spatial_scale
    y2 = rois[:, 4] * spatial_scale
    roi_w = jnp.maximum(x2 - x1, 1.0)
    roi_h = jnp.maximum(y2 - y1, 1.0)
    bs_h = roi_h / PH
    bs_w = roi_w / PW

    if sample_ratio > 0:
        gh_max = gw_max = int(sample_ratio)
        grid_h = jnp.full_like(roi_h, sample_ratio)
        grid_w = jnp.full_like(roi_w, sample_ratio)
    else:
        # static cap: a roi spanning the whole map needs ceil(H/PH) samples
        gh_max = int(np.ceil(H / PH)) + 1
        gw_max = int(np.ceil(W / PW)) + 1
        grid_h = jnp.clip(jnp.ceil(bs_h), 1, gh_max)
        grid_w = jnp.clip(jnp.ceil(bs_w), 1, gw_max)

    iy = jnp.arange(gh_max, dtype=cf)
    ix = jnp.arange(gw_max, dtype=cf)

    def one_roi(b, ys, xs, bh, bw, gh, gw):
        feat = data[b]  # (C,H,W)
        # sample coords (PH, gh_max) / (PW, gw_max), fp32
        py = ys + jnp.arange(PH, dtype=cf)[:, None] * bh + (iy[None, :] + 0.5) * bh / gh
        px = xs + jnp.arange(PW, dtype=cf)[:, None] * bw + (ix[None, :] + 0.5) * bw / gw
        # inclusion rule y ∈ [-1, H] (roi_align.cc bilinear pre-check)
        my = (iy[None, :] < gh) & (py >= -1.0) & (py <= H)  # (PH, gh_max)
        mx = (ix[None, :] < gw) & (px >= -1.0) & (px <= W)  # (PW, gw_max)
        # outer product of sample axes: gather at all (y, x) pairs
        yy = jnp.broadcast_to(py.reshape(PH, gh_max, 1, 1), (PH, gh_max, PW, gw_max))
        xx = jnp.broadcast_to(px.reshape(1, 1, PW, gw_max), (PH, gh_max, PW, gw_max))
        v = _bilinear_hw(feat, yy.reshape(-1), xx.reshape(-1)).reshape(C, PH, gh_max, PW, gw_max)
        m = (my[:, :, None, None] & mx[None, None, :, :]).astype(v.dtype)
        s = (v * m[None]).sum(axis=(2, 4))  # (C, PH, PW)
        return (s / (gh * gw).astype(v.dtype)).astype(f32)

    return jax.vmap(one_roi)(batch_idx, y1, x1, bs_h, bs_w, grid_h, grid_w)


# ---------------------------------------------------------------------------
# PSROIPooling (reference src/operator/contrib/psroi_pooling.cc:57-120)
# ---------------------------------------------------------------------------


@register("_contrib_PSROIPooling", alias=["PSROIPooling"])
def psroi_pooling(data, rois, *, spatial_scale, output_dim, pooled_size, group_size=0):
    """Position-sensitive ROI average pooling (R-FCN; psroi_pooling.cc:57).

    Bin (ph, pw) of output channel c averages input channel
    ``(c·group+gh)·group+gw`` over the bin's integer positions; roi corners
    round to ints then scale; sizes floored at 0.1; empty bins → 0.
    """
    PH = PW = int(pooled_size)
    group = int(group_size) if group_size else PH
    B, C, H, W = data.shape
    f32 = data.dtype
    OD = int(output_dim)
    # bin-boundary math always fp32 (see roi_pooling note)
    cf = jnp.float32
    rois = rois.astype(cf)

    batch_idx = rois[:, 0].astype(jnp.int32)
    xs = jnp.round(rois[:, 1]) * spatial_scale
    ys = jnp.round(rois[:, 2]) * spatial_scale
    xe = (jnp.round(rois[:, 3]) + 1.0) * spatial_scale
    ye = (jnp.round(rois[:, 4]) + 1.0) * spatial_scale
    roi_w = jnp.maximum(xe - xs, 0.1)
    roi_h = jnp.maximum(ye - ys, 0.1)
    bs_h = roi_h / PH
    bs_w = roi_w / PW

    ph = jnp.arange(PH, dtype=cf)
    pw = jnp.arange(PW, dtype=cf)
    hstart = jnp.clip(jnp.floor(ph[None, :] * bs_h[:, None] + ys[:, None]).astype(jnp.int32), 0, H)
    hend = jnp.clip(jnp.ceil((ph[None, :] + 1) * bs_h[:, None] + ys[:, None]).astype(jnp.int32), 0, H)
    wstart = jnp.clip(jnp.floor(pw[None, :] * bs_w[:, None] + xs[:, None]).astype(jnp.int32), 0, W)
    wend = jnp.clip(jnp.ceil((pw[None, :] + 1) * bs_w[:, None] + xs[:, None]).astype(jnp.int32), 0, W)

    # channel map: out channel c at bin (ph, pw) reads input channel
    gh = np.clip((np.arange(PH) * group) // PH, 0, group - 1)
    gw = np.clip((np.arange(PW) * group) // PW, 0, group - 1)
    cin = ((np.arange(OD)[:, None, None] * group + gh[None, :, None]) * group + gw[None, None, :])
    cin = jnp.asarray(cin)  # (OD, PH, PW)

    hh = jnp.arange(H)
    ww = jnp.arange(W)
    mask_h = (hh[None, None, :] >= hstart[:, :, None]) & (hh[None, None, :] < hend[:, :, None])
    mask_w = (ww[None, None, :] >= wstart[:, :, None]) & (ww[None, None, :] < wend[:, :, None])

    # masked bin sums as two einsum contractions (MXU-friendly), then ÷ area.
    # Contract H/W away on the full channel dim FIRST (O(C·PH·PW) result),
    # then gather the position-sensitive channel per bin — avoids
    # materializing a (OD,PH,PW,H,W) gather per ROI that XLA can't fuse
    # into the contraction.
    p_idx = jnp.arange(PH)[None, :, None]
    q_idx = jnp.arange(PW)[None, None, :]

    def one(b, mh, mw):
        s_all = jnp.einsum("chw,ph,qw->cpq", data[b], mh.astype(f32), mw.astype(f32))
        return s_all[cin, p_idx, q_idx]  # (OD, PH, PW)

    out = jax.vmap(one)(batch_idx, mask_h, mask_w)  # (R, OD, PH, PW)
    cnt_h = (hend - hstart)[:, None, :, None].astype(cf)
    cnt_w = (wend - wstart)[:, None, None, :].astype(cf)
    area = cnt_h * cnt_w
    return jnp.where(area > 0, out.astype(cf) / jnp.maximum(area, 1.0),
                     jnp.zeros((), cf)).astype(f32)


# ---------------------------------------------------------------------------
# DeformablePSROIPooling (reference contrib/deformable_psroi_pooling.cc:66-170)
# ---------------------------------------------------------------------------


@register("_contrib_DeformablePSROIPooling", alias=["DeformablePSROIPooling"])
def deformable_psroi_pooling(
    data,
    rois,
    trans=None,
    *,
    spatial_scale,
    output_dim,
    group_size,
    pooled_size,
    part_size=0,
    sample_per_part=4,
    trans_std=0.0,
    no_trans=False,
    rois_per_image=0,
):
    """Deformable position-sensitive ROI pooling (Deformable R-FCN).

    Reference deformable_psroi_pooling.cc:95-170: rois round to ints, map to
    [round(x)·s − 0.5, (round(x)+1)·s − 0.5]; each bin takes a static
    sample_per_part × sample_per_part grid of bilinear samples, shifted by
    ``trans`` offsets (scaled by trans_std and roi size); samples outside
    (−0.5, size−0.5) are dropped; output is sum / live-count (0 if none).

    ``rois_per_image`` (static, optional): caller's guarantee that rois are
    batch-major grouped — roi r belongs to image r // rois_per_image (the
    MultiProposal / proposal_target layout).  Enables the block-diagonal
    batched formulation: the one-hot accumulation matrix becomes
    (B, R/B, H·W) instead of (R, B·H·W), cutting the A-matrix build and
    the MXU matmuls from O(B²) to O(B).  This was the batch>1 scaling
    killer at north-star shapes (roofline: batch 4 measured 2.2× the HBM
    bound with the ungrouped form).

    WARNING: the grouped path TRUSTS this layout and ignores the rois'
    batch_idx column — interleaved or shuffled rois with ``rois_per_image``
    set silently pool from the wrong image (a traced value can't be
    asserted).  Only pass it when the rois come straight from
    MultiProposal/proposal_target or an equivalently grouped source; a
    value that doesn't divide R falls back to the general path.  Under the
    synchronous debug engine (``MXNET_ENGINE_TYPE=NaiveEngine``) the
    contract is validated and misuse raises (``_check_grouped_layout``).
    """
    PH = PW = int(pooled_size)
    group = int(group_size)
    part = int(part_size) if part_size else PH
    spp = int(sample_per_part)
    OD = int(output_dim)
    B, C, H, W = data.shape
    # coordinate math always runs fp32 — bf16 sample positions quantize to
    # ~0.25 px at COCO feature extents; values stay in the data dtype
    f32 = jnp.float32

    batch_idx = rois[:, 0].astype(jnp.int32)
    rois = rois.astype(f32)
    xs = jnp.round(rois[:, 1]) * spatial_scale - 0.5
    ys = jnp.round(rois[:, 2]) * spatial_scale - 0.5
    xe = (jnp.round(rois[:, 3]) + 1.0) * spatial_scale - 0.5
    ye = (jnp.round(rois[:, 4]) + 1.0) * spatial_scale - 0.5
    roi_w = jnp.maximum(xe - xs, 0.1)
    roi_h = jnp.maximum(ye - ys, 0.1)
    bs_h = roi_h / PH
    bs_w = roi_w / PW
    sub_h = bs_h / spp
    sub_w = bs_w / spp

    num_classes = 1 if no_trans or trans is None else trans.shape[1] // 2
    ch_per_class = OD // num_classes
    R = rois.shape[0]
    g2 = group * group
    if C != OD * g2:
        raise ValueError(
            "DeformablePSROIPooling: data has %d channels, needs output_dim"
            "*group_size^2 = %d*%d = %d" % (C, OD, g2, OD * g2))

    # The position-sensitive channel map is separable: channel index =
    # c·g² + gh(ph)·g + gw(pw).  Lay the data out as (B, ncls, g², H, W,
    # cpc) so one 5-index gather per corner fetches a CONTIGUOUS
    # ``ch_per_class``-vector per sample — sample coordinates depend only on
    # the trans class, never the within-class channel.  This cuts gather
    # count ~cpc× vs a scalar-per-channel gather (measured 0.5 s → the
    # whole-step bottleneck at north-star shapes, 81-class cls pooling).
    datag = data.reshape(B, num_classes, ch_per_class, g2, H, W)
    datag = datag.transpose(0, 1, 3, 4, 5, 2)  # (B, ncls, g2, H, W, cpc)

    ghs = np.clip((np.arange(PH) * group) // PH, 0, group - 1)
    gws = np.clip((np.arange(PW) * group) // PW, 0, group - 1)
    ghw = jnp.asarray(ghs[:, None] * group + gws[None, :])  # (PH, PW)
    # part cell per bin
    part_h = np.asarray((np.arange(PH) * part) // PH)  # (PH,)
    part_w = np.asarray((np.arange(PW) * part) // PW)

    r1 = (slice(None), None, None, None)  # (R,) -> (R,1,1,1)
    K = num_classes

    if no_trans or trans is None:
        tx = jnp.zeros((R, K, PH, PW), f32)
        ty = jnp.zeros((R, K, PH, PW), f32)
    else:
        # trans (R, 2K, part, part) -> per-class per-bin offsets (R,K,PH,PW)
        t = trans.reshape(R, K, 2, part, part)
        tx = t[:, :, 0][:, :, part_h][:, :, :, part_w] * trans_std
        ty = t[:, :, 1][:, :, part_h][:, :, :, part_w] * trans_std
    wst = jnp.arange(PW, dtype=f32)[None, None, None, :] * bs_w[r1] + xs[r1] + tx * roi_w[r1]
    hst = jnp.arange(PH, dtype=f32)[None, None, :, None] * bs_h[r1] + ys[r1] + ty * roi_h[r1]

    def axis_samples(start, sub, size):
        """One axis of a bin's sample grid, on (R, K, PH, PW, spp): the two
        corner indices, the second corner's weight and the live mask.  The
        spp × spp grid is the product of its two axes (a sample's row
        depends on its row index only, its column on its column index, as in
        deformable_psroi_pooling.cc), and so is everything built from it."""
        s = start[..., None] + jnp.arange(spp, dtype=f32) * sub[:, None, None, None, None]
        # inclusive boundary: sample at exactly ±0.5 survives (reference
        # skips only w < −0.5 / w > W−0.5, deformable_psroi_pooling.cc:159)
        live = ((s >= -0.5) & (s <= size - 0.5)).astype(f32)
        sc = jnp.clip(s, 0.0, size - 1.0)
        i0 = jnp.floor(sc).astype(jnp.int32)
        return i0, jnp.minimum(i0 + 1, size - 1), sc - i0.astype(f32), live

    y0, y1, ly, lfy = axis_samples(hst, sub_h, H)
    x0, x1, lx, lfx = axis_samples(wst, sub_w, W)
    cnt = (lfy.sum(-1) * lfx.sum(-1))[..., None]  # (R, K, PH, PW, 1)

    Rb = int(rois_per_image)
    grouped = Rb > 0 and R == B * Rb
    if grouped:
        _check_grouped_layout(batch_idx, B, Rb, "DeformablePSROIPooling")
    if R * K * PH * PW * spp * spp * ch_per_class >= (1 << 16):
        # -- rank-one one-hot matmul path (TPU hot path) ------------------
        # Per bin (k, ph, pw): accumulate every (roi, sample)'s live-masked
        # bilinear footprint into a dense accumulation matrix A and multiply
        # by that bin's flattened plane.  Both directions are MXU matmuls —
        # no gather OR scatter touches HBM (the scatter-add XLA derives from
        # a gather formulation measured ~580 ms/step at north-star shapes).
        #
        # A sample's 4-corner footprint is separable,
        #   [(1−ly)e_{y0} + ly·e_{y1}] ⊗ [(1−lx)e_{x0} + lx·e_{x1}],
        # and so is the grid of samples, hence their sum is RANK ONE:
        #   A[r] = (Σ_i lfy_i·Y_i) ⊗ (Σ_j lfx_j·X_j) = ay[r] ⊗ ax[r]
        # — one broadcast multiply (a batch of spp²-deep products lowers to
        # convolutions that run at 0.4 % of the MXU).  The one-hot compares
        # run over H and W separately, spp deep, for all bins at once.
        # Grouped (batch-major) rois additionally make the plane matmul
        # block-diagonal: (B, Rb, H·W) per-image blocks instead of one
        # (R, B·H·W) matrix — O(B), not O(B²), in batch.
        hw = H * W
        NB = K * PH * PW
        lead = (B, Rb) if grouped else (R,)

        # remat here too: AD would save the (NB, R, spp, size) one-hot
        # masks; the samples they are rebuilt from are spp numbers a bin
        @functools.partial(jax.checkpoint, static_argnums=(4,))
        def axis_weights(i0, i1, l, lf, size):
            """(R,K,PH,PW,spp) samples -> (NB,) + lead + (size,): each
            bin's live bilinear weight on every index of the axis."""
            def bins(a):
                return a.transpose(1, 2, 3, 0, 4).reshape((NB,) + lead + (spp, 1))
            iota = jnp.arange(size, dtype=jnp.int32)
            return (bins(lf * (1.0 - l)) * (bins(i0) == iota)
                    + bins(lf * l) * (bins(i1) == iota)).sum(axis=-2)

        if grouped:
            ay = axis_weights(y0, y1, ly, lfy, H)
        else:
            # the batch offset rides in the row index (gy = b·H + y, flat
            # position gy·W + x ≡ b·hw + y·W + x — matches plane layout)
            yoff = (batch_idx * H)[:, None, None, None, None]
            ay = axis_weights(y0 + yoff, y1 + yoff, ly, lfy, B * H)
        ax = axis_weights(x0, x1, lx, lfx, W)

        # per-bin flattened planes from the position-sensitive channel map:
        # grouped (NB, B, H·W, cpc), ungrouped (NB, B·H·W, cpc)
        kb = np.repeat(np.arange(K), PH * PW)
        gb = np.tile(np.asarray(ghs[:, None] * group + gws[None, :]).reshape(-1), K)
        planes = datag.transpose(1, 2, 0, 3, 4, 5).reshape(K, g2, B, hw, ch_per_class)
        planes = planes[kb, gb]  # (NB, B, hw, cpc)
        if not grouped:
            # B already precedes hw, so the flat index stays b·hw + y·W + x
            planes = planes.reshape(NB, B * hw, ch_per_class)

        # fp32 inputs must not silently drop to the TPU's default bf16
        # matmul passes (~5e-3 pooled-score error, measured); ay, ax and
        # their product are always f32
        prec = (jax.lax.Precision.HIGHEST
                if datag.dtype == jnp.float32 else None)

        # remat: without it, AD saves each bin's A as a residual (~0.7 GB
        # over 147 bins at north-star shapes); rebuilding it in the backward
        # is one fused multiply
        @jax.checkpoint
        def one_bin(args):
            ayb, axb, plane = args
            a = (ayb[..., :, None] * axb[..., None, :]).astype(datag.dtype)
            # grouped (B,Rb,hw) @ (B,hw,cpc); ungrouped (R,B·hw) @ (B·hw,cpc)
            return jnp.matmul(a.reshape(lead + (-1,)), plane, precision=prec)

        # full unroll for typical bin counts (NB=49): measured A/B at the
        # batch-8 north star — unroll=NB 33.8 img/s vs unroll=7 32.8 (~3%;
        # the scans are mostly overlapped with backbone compute, so the
        # win is scheduling freedom at the margins, not the op-lane time).
        # Unusual group sizes keep a partial unroll to bound code size.
        unroll = NB if NB <= 64 else 7
        _, s = jax.lax.scan(
            lambda _, args: (None, one_bin(args)), None, (ay, ax, planes),
            unroll=unroll)  # (NB,) + lead + (cpc,)
        s = (s.reshape(K, PH, PW, R, ch_per_class)
             .transpose(3, 0, 1, 2, 4))  # (R, K, PH, PW, cpc)
    else:
        # -- gather path (small problems / CPU) ---------------------------
        # one gather per corner of each of the spp × spp samples; the
        # batch index rides in the gather (a vmapped ``data[b]`` would
        # materialize an (R, C, H, W) copy — 11.6 GB at COCO eval scale).
        # With the grouped hint the index comes from the layout (r // Rb),
        # NOT the batch_idx column — the one-hot path above ignores the
        # column, and both paths must agree for the same inputs (a
        # positional grouper that left the column at 0 would otherwise get
        # different pooling depending on problem size).
        row_img = (jnp.arange(R, dtype=jnp.int32) // Rb) if grouped else batch_idx
        b_idx = row_img[:, None, None, None, None, None]
        k_idx = jnp.arange(K)[None, :, None, None, None, None]
        g_idx = ghw[None, None, :, :, None, None]
        # rows of the sample grid on axis 4, columns on axis 5
        yy0, yy1, lyn = y0[..., :, None], y1[..., :, None], ly[..., :, None, None]
        xx0, xx1, lxn = x0[..., None, :], x1[..., None, :], lx[..., None, :, None]
        v = (
            datag[b_idx, k_idx, g_idx, yy0, xx0] * (1 - lyn) * (1 - lxn)
            + datag[b_idx, k_idx, g_idx, yy0, xx1] * (1 - lyn) * lxn
            + datag[b_idx, k_idx, g_idx, yy1, xx0] * lyn * (1 - lxn)
            + datag[b_idx, k_idx, g_idx, yy1, xx1] * lyn * lxn
        )  # (R, K, PH, PW, spp, spp, cpc)
        lf = lfy[..., :, None, None] * lfx[..., None, :, None]
        s = (v * lf).sum(axis=(4, 5))  # (R, K, PH, PW, cpc)

    out = jnp.where(cnt > 0, s.astype(f32) / jnp.maximum(cnt, 1.0),
                    jnp.zeros((), f32))
    # (R, K, PH, PW, cpc) -> (R, K·cpc = OD, PH, PW), in the data dtype
    return out.transpose(0, 1, 4, 2, 3).reshape(R, OD, PH, PW).astype(data.dtype)


@jax.custom_vjp
def _grouped_col_product(wmat, col):
    """``out[b, g, f, p] = sum_k wmat[g, f, k] * col[b, g, k, p]``, the
    deformable convolution's grouped product over its channels-major
    columns.

    The products are the einsum's own; what is fixed here is how the two
    cotangents are formed, so that the columns (179 MB a res5 layer) are
    never relaid (PR 32; read off the step's executable compiled for a
    v5e).  JAX's transpose rule for a ``dot_general`` puts the contracted
    axis last, ``(g, b, p, k)``, and transposes that into ``col``'s
    ``(b, g, k, p)``, which XLA's TPU compiler does not fold into the
    product (a ``copy`` of its own): with the image as a batch axis of the
    product the columns' cotangent comes out as ``(b, g, k, p)`` to begin
    with.  And with the columns as the RIGHT operand of the weights'
    cotangent the compiler relays them twice, ``(k, b, p)`` and then
    channels-minor; as the left operand it reads them as they lie."""
    return jnp.einsum("gfk,bgkp->bgfp", wmat, col)


def _grouped_col_product_fwd(wmat, col):
    return _grouped_col_product(wmat, col), (wmat, col)


def _grouped_col_product_bwd(res, ct):
    wmat, col = res
    # d_wmat[g, f, k] = sum over b, p of col[b, g, k, p] * ct[b, g, f, p],
    # formed as (g, k, f): the transpose is weights-sized
    d_wmat = jax.lax.dot_general(
        col, ct, (((0, 3), (0, 3)), ((1,), (1,)))).transpose(0, 2, 1)
    # d_col[b, g, k, p] = sum over f of wmat[g, f, k] * ct[b, g, f, p]
    wb = jnp.broadcast_to(wmat, col.shape[:1] + wmat.shape)
    d_col = jax.lax.dot_general(wb, ct, (((2,), (2,)), ((0, 1), (0, 1))))
    return d_wmat.astype(wmat.dtype), d_col.astype(col.dtype)


_grouped_col_product.defvjp(_grouped_col_product_fwd,
                            _grouped_col_product_bwd)


def _defconv_inputs(attrs):
    base = ["data", "offset", "weight"]
    return base if attrs.get("no_bias") else base + ["bias"]


def _defconv_params(attrs, shapes):
    d = shapes["data"]
    kh, kw = _pair(attrs["kernel"])
    ng = attrs.get("num_group", 1)
    return {
        "weight": (attrs["num_filter"], d[1] // ng, kh, kw),
        "bias": (attrs["num_filter"],),
    }


@register(
    "_contrib_DeformableConvolution",
    alias=["DeformableConvolution"],
    inputs_fn=_defconv_inputs,
    infer_params=_defconv_params,
)
def deformable_convolution(
    data,
    offset,
    weight,
    bias=None,
    *,
    kernel,
    num_filter,
    stride=(1, 1),
    dilate=(1, 1),
    pad=(0, 0),
    num_group=1,
    num_deformable_group=1,
    no_bias=False,
    workspace=1024,
    layout=None,
):
    """Deformable convolution v1 (reference deformable_convolution-inl.h:99,
    im2col at offset positions deformable_im2col.h:264-316).

    Each kernel tap (i, j) at output (ho, wo) samples the input bilinearly at
    ``(ho·stride − pad + i·dilate + Δy, ...)`` where Δ comes from ``offset``
    (B, 2·DG·K², Ho, Wo); out-of-map samples are 0; positions past the last
    row/col snap to it.  The gathered column tensor hits the MXU as one
    (C·K²)→F matmul per group — XLA autodiffs the gather into the
    scatter-add the reference hand-writes as deformable_col2im.
    """
    kh, kw = _pair(kernel)
    sh, sw = _pair(stride)
    dh, dw = _pair(dilate)
    ph, pw = _pair(pad)
    B, C, H, W = data.shape
    F = int(num_filter)
    G = int(num_group)
    DG = int(num_deformable_group)
    Ho = (H + 2 * ph - (dh * (kh - 1) + 1)) // sh + 1
    Wo = (W + 2 * pw - (dw * (kw - 1) + 1)) // sw + 1
    f32 = data.dtype
    K2 = kh * kw

    # base sampling positions, tap order (i·kw + j) as in deformable_im2col
    ii = jnp.arange(kh)
    jj = jnp.arange(kw)
    tap_dy = (ii[:, None] * dh).repeat(kw, axis=1).reshape(-1)  # (K2,)
    tap_dx = jnp.tile(jj * dw, kh)  # (K2,)
    grid_y = (jnp.arange(Ho) * sh - ph)[:, None]  # (Ho,1)
    grid_x = (jnp.arange(Wo) * sw - pw)[None, :]  # (1,Wo)

    N = K2 * Ho * Wo
    cpg = C // DG
    if N * H * W >= (1 << 22):
        # -- separable one-hot matmul path (TPU hot path) -----------------
        # The per-channel bilinear gather profiled at ~64 ms/step of the
        # batch-4 north-star step (3 res5 deformable convs × fwd+bwd, the
        # bf16[B·K2·HoWo, cpg] sampling fusions — gathers run ~30 GB/s vs
        # the 819 GB/s HBM peak).  Same trick as deformable_psroi_pooling:
        # the bilinear footprint is separable, so per (image, group) the
        # sample matrix A[n, h·W+w] = yw[n,h]·xw[n,w] is a rank-1 product
        # of one-hot lerp factors and ``col = A @ feat`` rides the MXU —
        # both directions are matmuls, no gather/scatter.  A is rebuilt in
        # the backward (remat) instead of saved.  Features and columns stay
        # channels-major, the data's own order: ``col^T = feat^T @ A^T`` is
        # (cpg, N) per (image, group), so the (B, C, K2, Ho, Wo) columns are
        # a reshape of it and nothing columns-sized is transposed (PR 32).
        off = offset.reshape(B, DG, K2, 2, Ho, Wo)
        sy = grid_y[None, None, None] + tap_dy[None, None, :, None, None] + off[:, :, :, 0]
        sx = grid_x[None, None, None] + tap_dx[None, None, :, None, None] + off[:, :, :, 1]
        live = (sy >= 0) & (sy < H) & (sx >= 0) & (sx < W)
        cf = jnp.float32  # coordinate math in fp32 (house rule)
        syc = jnp.clip(sy.astype(cf), 0.0, H - 1.0).reshape(B, DG, N)
        sxc = jnp.clip(sx.astype(cf), 0.0, W - 1.0).reshape(B, DG, N)
        y0 = jnp.floor(syc).astype(jnp.int32)
        x0 = jnp.floor(sxc).astype(jnp.int32)
        y1 = jnp.minimum(y0 + 1, H - 1)
        x1 = jnp.minimum(x0 + 1, W - 1)
        ly = syc - y0.astype(cf)          # lerp factors stay fp32; only A
        lx = sxc - x0.astype(cf)          # downcasts for the plane matmul
        lf = live.reshape(B, DG, N).astype(cf)
        iota_y = jnp.arange(H, dtype=jnp.int32)
        iota_x = jnp.arange(W, dtype=jnp.int32)
        prec = jax.lax.Precision.HIGHEST if f32 == jnp.float32 else None

        @jax.checkpoint
        def one_bg(args):
            yb0, yb1, xb0, xb1, lyb, lxb, lfb, ftt = args
            yv = ((1.0 - lyb)[:, None] * (yb0[:, None] == iota_y)
                  + lyb[:, None] * (yb1[:, None] == iota_y))      # (N, H)
            xv = lfb[:, None] * (
                (1.0 - lxb)[:, None] * (xb0[:, None] == iota_x)
                + lxb[:, None] * (xb1[:, None] == iota_x))        # (N, W)
            a = jnp.einsum("nh,nw->nhw", yv, xv,
                           precision=jax.lax.Precision.HIGHEST)
            # defensive: pin f32 accumulation for bf16 inputs on every
            # backend (the MXU's native behavior; XLA:CPU may otherwise
            # accumulate bf16).  NOT testable via the consistency tier —
            # its bf16 variant of this path is excluded for the unrelated
            # floor()-bin-flip reason (test_consistency_tpu.py case note).
            return jax.lax.dot_general(
                ftt, a.reshape(N, H * W).astype(f32),
                (((1,), (1,)), ((), ())), precision=prec,
                preferred_element_type=jnp.float32).astype(f32)   # (cpg, N)

        flat = lambda a: a.reshape(B * DG, N)
        ftm = data.reshape(B * DG, cpg, H * W)

        def xla_col():
            _, col = jax.lax.scan(
                lambda _, args: (None, one_bg(args)), None,
                (flat(y0), flat(y1), flat(x0), flat(x1), flat(ly),
                 flat(lx), flat(lf), ftm),
                unroll=min(B * DG, 16))
            return col

        def pallas_col(interpret=False):
            # fused VMEM-resident A (and dA) — the round-5 kernel: the
            # XLA path materializes the rank-1 sample matrix in HBM
            # (~106 MB bf16 fwd + ~213 MB f32 dA per (image, group) at
            # north-star shapes); keeping both in VMEM measured
            # fwd+bwd 34.7 -> 21.2 ms standalone, bitwise-equal output
            # (pallas_kernels.dconv_col_pallas, custom VJP)
            from .pallas_kernels import dconv_col_pallas

            return dconv_col_pallas(
                flat(y0), flat(y1), flat(x0), flat(x1), flat(ly),
                flat(lx), flat(lf), ftm, (H, W), interpret)

        impl = os.environ.get("MXNET_DCONV_IMPL", "auto")
        if impl == "xla":
            col = xla_col()
        elif impl == "pallas":
            # forced: pallas everywhere; the interpret choice follows the
            # LOWERING platform (same rule as MXNET_NMS_IMPL)
            col = jax.lax.platform_dependent(
                tpu=lambda: pallas_col(False),
                default=lambda: pallas_col(True))
        else:
            # auto: fused kernel on TPU only when its backward working set
            # fits VMEM — above the limit (large feature maps) Mosaic would
            # hard-fail the kernel build, so fall back to the XLA scan
            # (ADVICE round 5; pallas_kernels.dconv_bwd_vmem_bytes)
            from .pallas_kernels import dconv_fits_vmem

            if dconv_fits_vmem(H * W, cpg, jnp.dtype(f32).itemsize):
                col = jax.lax.platform_dependent(
                    tpu=lambda: pallas_col(False), default=xla_col)
            else:
                col = xla_col()
        col = col.reshape(B, C, K2, Ho, Wo)  # from (B·DG, cpg, N)
    else:
        # -- gather path (small problems / CPU) ---------------------------
        def one_image(img, off):
            # off: (2*DG*K2, Ho, Wo) → (DG, K2, 2, Ho, Wo); [.., 0] = Δy
            off = off.reshape(DG, K2, 2, Ho, Wo)
            sy = grid_y[None, None] + tap_dy[None, :, None, None] + off[:, :, 0]
            sx = grid_x[None, None] + tap_dx[None, :, None, None] + off[:, :, 1]
            live = (sy >= 0) & (sy < H) & (sx >= 0) & (sx < W)

            def per_group(g):
                planes = jax.lax.dynamic_slice_in_dim(img, g * cpg, cpg, axis=0)
                v = jax.vmap(lambda p: _bilinear(p, sy[g], sx[g]))(planes)
                return jnp.where(live[g][None], v, jnp.zeros((), f32))

            return jnp.concatenate([per_group(g) for g in range(DG)], axis=0)

        col = jax.vmap(one_image)(data, offset)  # (B, C, K2, Ho, Wo)
    # grouped matmul on the MXU
    wmat = weight.reshape(G, F // G, (C // G) * K2)
    col = col.reshape(B, G, (C // G) * K2, Ho * Wo)
    out = _grouped_col_product(wmat, col).reshape(B, F, Ho, Wo)
    if bias is not None and not no_bias:
        out = out + bias[None, :, None, None]
    return out


# ---------------------------------------------------------------------------
# Proposal / MultiProposal (reference contrib/multi_proposal.cc, proposal.cc)
# ---------------------------------------------------------------------------


def _generate_base_anchors(stride, scales, ratios):
    """Classic RPN anchor enumeration (multi_proposal-inl.h:186-226): for each
    ratio then scale, snap w/h via the floor(.+0.5) rule around the stride
    box's center."""
    base = np.array([0, 0, stride - 1, stride - 1], np.float32)
    w = base[2] - base[0] + 1.0
    h = base[3] - base[1] + 1.0
    x_ctr = base[0] + 0.5 * (w - 1.0)
    y_ctr = base[1] + 0.5 * (h - 1.0)
    size = w * h
    out = []
    for r in ratios:
        size_ratio = np.floor(size / r)
        new_w = np.floor(np.sqrt(size_ratio) + 0.5)
        new_h = np.floor(new_w * r + 0.5)
        for s in scales:
            ws, hs = new_w * s, new_h * s
            out.append(
                [
                    x_ctr - 0.5 * (ws - 1.0),
                    y_ctr - 0.5 * (hs - 1.0),
                    x_ctr + 0.5 * (ws - 1.0),
                    y_ctr + 0.5 * (hs - 1.0),
                ]
            )
    return np.array(out, np.float32)  # (A, 4)


def _iou_mat(a_boxes, a_area, b_boxes, b_area, plus_one=0.0):
    """Dense IoU matrix (A, B) between two corner-box sets."""
    tl = jnp.maximum(a_boxes[:, None, :2], b_boxes[None, :, :2])
    br = jnp.minimum(a_boxes[:, None, 2:], b_boxes[None, :, 2:])
    wh = jnp.maximum(br - tl + plus_one, 0.0)
    inter = wh[..., 0] * wh[..., 1]
    union = a_area[:, None] + b_area[None, :] - inter
    return jnp.where(union <= 0, 0.0, inter / jnp.maximum(union, 1e-12))


def _nms_alive_blocked(boxes, thresh, tile=256, plus_one=1.0, valid=None,
                       ids=None, force_suppress=True):
    """Full greedy-NMS survivor mask over score-ordered (N, 4) boxes.

    Semantics are exactly the sequential greedy scan (reference
    multi_proposal.cc:221-273): box i survives iff no surviving j < i has
    IoU(i, j) > thresh.  The TPU restructuring cuts sequential depth from N
    single-box steps to N/tile block steps: each block settles its own
    members by iterating the suppression map to its (unique, greedy) fixed
    point with dense (tile, tile) IoU matrices, then kills later boxes with
    one (tile, N) IoU sweep.  At the reference's rpn_pre_nms_top_n=6000 this
    is ~24 sequential steps instead of 6000 (VERDICT round-1 weak item 4).

    ``valid`` optionally marks rows dead from the start (they neither
    suppress nor survive).  ``ids`` (with ``force_suppress=False``) restricts
    suppression to equal-id pairs — the per-class NMS of box_nms /
    MultiBoxDetection.  Returns a bool (N,) mask.

    On TPU at production sizes this dispatches to the Pallas kernel
    (``pallas_kernels.nms_alive_pallas`` — identical survivors, measured
    ~2.3x faster, docs/PERF_NOTES.md "Pallas head-to-head"); the choice
    rides ``lax.platform_dependent`` so a CPU lowering in a TPU process
    (the consistency tier) still gets the XLA formulation.
    ``MXNET_NMS_IMPL=xla|pallas`` overrides the auto choice.
    """
    N = boxes.shape[0]
    if N == 0:
        return jnp.zeros((0,), bool)
    impl = os.environ.get("MXNET_NMS_IMPL", "auto")
    # the kernel needs a static threshold; a traced thresh can't take the
    # pallas path (np.float32 etc. coerce fine)
    static_thresh = not isinstance(thresh, jax.core.Tracer)
    if impl == "pallas" and not static_thresh:
        import warnings

        warnings.warn("MXNET_NMS_IMPL=pallas ignored: NMS threshold is a "
                      "traced value; using the XLA formulation")
    if impl != "xla" and static_thresh:

        def pallas_path(interpret):
            from .pallas_kernels import nms_alive_pallas

            v = jnp.ones((N,), bool) if valid is None else valid
            return nms_alive_pallas(
                boxes, v, ids, thresh=float(thresh),
                plus_one=float(plus_one), force_suppress=force_suppress,
                interpret=interpret)

        if impl == "pallas":
            # forced: pallas on every platform, but the interpret choice
            # must follow the LOWERING platform, not default_backend() — a
            # CPU-placed NMS in a TPU process (eval decode under
            # jax.default_device(cpu), the consistency tier's CPU leg)
            # cannot lower a Mosaic kernel
            return jax.lax.platform_dependent(
                tpu=lambda: pallas_path(False),
                default=lambda: pallas_path(True))
        if N >= 1024:
            return jax.lax.platform_dependent(
                tpu=lambda: pallas_path(False),
                default=lambda: _nms_alive_blocked_xla(
                    boxes, thresh, tile, plus_one, valid, ids,
                    force_suppress))
    return _nms_alive_blocked_xla(boxes, thresh, tile, plus_one, valid, ids,
                                  force_suppress)


def _nms_alive_blocked_xla(boxes, thresh, tile, plus_one, valid, ids,
                           force_suppress):
    """The XLA formulation of the blocked greedy scan (docstring above)."""
    N = boxes.shape[0]
    T = int(min(tile, N))
    nb = -(-N // T)
    Np = nb * T
    boxes_p = jnp.pad(boxes, ((0, Np - N), (0, 0)))
    alive = jnp.arange(Np) < N
    if valid is not None:
        alive = alive & jnp.pad(valid, (0, Np - N))
    ids_p = None if (ids is None or force_suppress) else jnp.pad(ids, (0, Np - N))
    # degenerate (inverted) boxes count as zero area (reference BoxArea rule)
    area = jnp.maximum(boxes_p[:, 2] - boxes_p[:, 0] + plus_one, 0.0) * jnp.maximum(
        boxes_p[:, 3] - boxes_p[:, 1] + plus_one, 0.0)
    idx = jnp.arange(Np)
    intra_lt = jnp.arange(T)[:, None] < jnp.arange(T)[None, :]  # [j, i] j<i

    def block(k, alive):
        tb = jax.lax.dynamic_slice_in_dim(boxes_p, k * T, T, axis=0)
        tarea = jax.lax.dynamic_slice_in_dim(area, k * T, T, axis=0)
        ta = jax.lax.dynamic_slice_in_dim(alive, k * T, T, axis=0)
        # sup[j, i]: j would suppress i (j earlier in score order)
        sup = (_iou_mat(tb, tarea, tb, tarea, plus_one) > thresh) & intra_lt
        if ids_p is not None:
            tid = jax.lax.dynamic_slice_in_dim(ids_p, k * T, T, axis=0)
            sup = sup & (tid[:, None] == tid[None, :])

        # fixed point of cur[i] = ta[i] & ~∃j (sup[j,i] & cur[j]); the greedy
        # survivor set is its unique fixpoint (induction over i), reached in
        # ≤T iterations (typically ~log); while_loop is fine here — proposal
        # coordinates carry no gradient (reference Proposal is non-diff too)
        def w_cond(st):
            prev, cur = st
            return jnp.any(prev != cur)

        def w_body(st):
            _, cur = st
            return cur, ta & ~jnp.any(sup & cur[:, None], axis=0)

        first = ta & ~jnp.any(sup & ta[:, None], axis=0)
        _, cur = jax.lax.while_loop(w_cond, w_body, (ta, first))

        # settled tile survivors kill any later box they overlap
        cross = (_iou_mat(tb, tarea, boxes_p, area, plus_one) > thresh) & cur[:, None]
        if ids_p is not None:
            cross = cross & (tid[:, None] == ids_p[None, :])
        hit = jnp.any(cross, axis=0)
        alive = alive & ~((idx >= (k + 1) * T) & hit)
        return jax.lax.dynamic_update_slice_in_dim(alive, cur, k * T, axis=0)

    alive = jax.lax.fori_loop(0, nb, block, alive)
    return alive[:N]


def _nms_fixed(boxes, thresh, max_keep, tile=512):
    """Greedy NMS over score-ordered (N, 4) boxes, +1 area convention
    (multi_proposal.cc:221-273).  Returns (keep_idx (max_keep,), out_size):
    the first ``max_keep`` survivors in score order.  Runs as blocked NMS
    (``_nms_alive_blocked``) — N/tile sequential steps, not N."""
    N = boxes.shape[0]
    alive = _nms_alive_blocked(boxes, thresh, tile=tile, plus_one=1.0)
    # survivors in index (= score) order, then first max_keep
    order = jnp.argsort(~alive, stable=True)
    keep = order[:max_keep].astype(jnp.int32)
    cnt = jnp.minimum(alive.sum().astype(jnp.int32), max_keep)
    return keep, cnt


def _proposal_one_image(scores_fg, deltas, im_info, anchors, stride, pre_nms, post_nms, thresh, min_size):
    """Single-image RPN proposal pipeline; all shapes static."""
    A4 = anchors.shape[0]
    A = A4
    Hf, Wf = scores_fg.shape[1:]
    f32 = scores_fg.dtype

    # anchor grid in reference enumeration order: index = h·(W·A) + w·A + a
    shift_x = jnp.arange(Wf, dtype=f32) * stride
    shift_y = jnp.arange(Hf, dtype=f32) * stride
    boxes = jnp.stack(
        [
            jnp.broadcast_to(shift_x[None, :, None] + anchors[None, None, :, 0], (Hf, Wf, A)),
            jnp.broadcast_to(shift_y[:, None, None] + anchors[None, None, :, 1], (Hf, Wf, A)),
            jnp.broadcast_to(shift_x[None, :, None] + anchors[None, None, :, 2], (Hf, Wf, A)),
            jnp.broadcast_to(shift_y[:, None, None] + anchors[None, None, :, 3], (Hf, Wf, A)),
        ],
        axis=-1,
    )  # (Hf, Wf, A, 4)

    # deltas (4A, Hf, Wf) laid out a*4+c → (Hf, Wf, A, 4)
    d = deltas.reshape(A, 4, Hf, Wf).transpose(2, 3, 0, 1)
    widths = boxes[..., 2] - boxes[..., 0] + 1.0
    heights = boxes[..., 3] - boxes[..., 1] + 1.0
    ctr_x = boxes[..., 0] + 0.5 * (widths - 1.0)
    ctr_y = boxes[..., 1] + 0.5 * (heights - 1.0)
    pred_ctr_x = d[..., 0] * widths + ctr_x
    pred_ctr_y = d[..., 1] * heights + ctr_y
    pred_w = jnp.exp(d[..., 2]) * widths
    pred_h = jnp.exp(d[..., 3]) * heights
    im_h, im_w, im_scale = im_info[0], im_info[1], im_info[2]
    x1 = jnp.clip(pred_ctr_x - 0.5 * (pred_w - 1.0), 0.0, im_w - 1.0)
    y1 = jnp.clip(pred_ctr_y - 0.5 * (pred_h - 1.0), 0.0, im_h - 1.0)
    x2 = jnp.clip(pred_ctr_x + 0.5 * (pred_w - 1.0), 0.0, im_w - 1.0)
    y2 = jnp.clip(pred_ctr_y + 0.5 * (pred_h - 1.0), 0.0, im_h - 1.0)

    score = scores_fg.transpose(1, 2, 0)  # (Hf, Wf, A)
    # mask padded rows/cols beyond the real (unpadded) feature extent
    real_h = jnp.ceil(im_h / stride).astype(jnp.int32)
    real_w = jnp.ceil(im_w / stride).astype(jnp.int32)
    pad_mask = (jnp.arange(Hf)[:, None, None] >= real_h) | (jnp.arange(Wf)[None, :, None] >= real_w)
    score = jnp.where(pad_mask, -1.0, score)

    # FilterBox: expand + kill tiny boxes (multi_proposal.cc:147-161)
    ms = min_size * im_scale
    iw = x2 - x1 + 1.0
    ih = y2 - y1 + 1.0
    tiny = (iw < ms) | (ih < ms)
    half = ms / 2.0
    x1 = jnp.where(tiny, x1 - half, x1)
    y1 = jnp.where(tiny, y1 - half, y1)
    x2 = jnp.where(tiny, x2 + half, x2)
    y2 = jnp.where(tiny, y2 + half, y2)
    score = jnp.where(tiny, -1.0, score)

    props = jnp.stack([x1, y1, x2, y2, score], axis=-1).reshape(-1, 5)  # (H·W·A, 5)
    N = props.shape[0]
    K1 = min(pre_nms, N) if pre_nms > 0 else N
    order = jnp.argsort(-props[:, 4], stable=True)[:K1]
    ordered = props[order]  # (K1, 5)

    keep, out_size = _nms_fixed(ordered[:, :4], thresh, post_nms)
    out_size = jnp.maximum(out_size, 1)
    slots = jnp.arange(post_nms)
    idx = keep[jnp.where(slots < out_size, slots, slots % out_size)]
    rois = ordered[idx, :4]
    rscore = ordered[idx, 4:5]
    return rois, rscore


@register("_contrib_MultiProposal", alias=["MultiProposal"])
@stable_eager
def multi_proposal(
    cls_prob,
    bbox_pred,
    im_info,
    *,
    rpn_pre_nms_top_n=6000,
    rpn_post_nms_top_n=300,
    threshold=0.7,
    rpn_min_size=16,
    scales=(4, 8, 16, 32),
    ratios=(0.5, 1, 2),
    feature_stride=16,
    output_score=False,
    iou_loss=False,
):
    """Batched RPN proposal generation (reference multi_proposal.cc:290-460):
    decode anchor deltas, clip, kill sub-min-size boxes, sort, greedy NMS,
    emit exactly ``rpn_post_nms_top_n`` rois per image (padded by cycling the
    kept boxes).  Returns (B·post, 5) rois [batch_idx, x1, y1, x2, y2] and,
    if output_score, (B·post, 1) scores."""
    if iou_loss:
        raise NotImplementedError("iou_loss=True branch is not supported on TPU build")
    # box/score math always runs fp32: bf16 scores (8 mantissa bits) collapse
    # the pre-NMS top-k into index-order ties, and bf16 box coords quantise
    # to 4-px steps at 1000-px extents (mixed-precision trunks feed bf16 in)
    cls_prob = cls_prob.astype(jnp.float32)
    bbox_pred = bbox_pred.astype(jnp.float32)
    im_info = im_info.astype(jnp.float32)
    anchors = jnp.asarray(_generate_base_anchors(feature_stride, scales, ratios))
    B = cls_prob.shape[0]
    A = anchors.shape[0]
    scores_fg = cls_prob[:, A:, :, :]  # (B, A, Hf, Wf)
    post = int(rpn_post_nms_top_n)

    rois, rscore = jax.vmap(
        lambda s, d, i: _proposal_one_image(
            s, d, i, anchors, float(feature_stride), int(rpn_pre_nms_top_n), post, float(threshold), float(rpn_min_size)
        )
    )(scores_fg, bbox_pred, im_info)
    batch_col = jnp.repeat(jnp.arange(B, dtype=rois.dtype), post)[:, None]
    out = jnp.concatenate([batch_col, rois.reshape(B * post, 4)], axis=1)
    if output_score:
        return out, rscore.reshape(B * post, 1)
    return out


@register("_contrib_Proposal", alias=["Proposal"])
@stable_eager
def proposal(cls_prob, bbox_pred, im_info, *, rpn_pre_nms_top_n=6000, rpn_post_nms_top_n=300,
             threshold=0.7, rpn_min_size=16, scales=(4, 8, 16, 32), ratios=(0.5, 1, 2),
             feature_stride=16, output_score=False, iou_loss=False):
    """Single-image Proposal (reference contrib/proposal.cc) — the batch-1
    case of MultiProposal with identical numerics."""
    return multi_proposal(
        cls_prob, bbox_pred, im_info,
        rpn_pre_nms_top_n=rpn_pre_nms_top_n, rpn_post_nms_top_n=rpn_post_nms_top_n,
        threshold=threshold, rpn_min_size=rpn_min_size, scales=scales, ratios=ratios,
        feature_stride=feature_stride, output_score=output_score, iou_loss=iou_loss,
    )


# ---------------------------------------------------------------------------
# MultiBox trio (SSD; reference contrib/multibox_{prior,target,detection}.cc)
# ---------------------------------------------------------------------------


@register("_contrib_MultiBoxPrior", alias=["MultiBoxPrior"])
def multibox_prior(data, *, sizes=(1.0,), ratios=(1.0,), clip=False, steps=(-1.0, -1.0), offsets=(0.5, 0.5)):
    """SSD anchor generation (reference multibox_prior.cc:30-70): per cell,
    ``sizes`` boxes at ratio 1 (width aspect-corrected by H/W), then
    ``ratios[1:]`` at sizes[0]; corner format, normalized coords; optional
    clip to [0, 1].  Output (1, H·W·A, 4)."""
    H, W = data.shape[2], data.shape[3]
    if H <= 0 or W <= 0:
        raise ValueError(
            "MultiBoxPrior: feature map is %dx%d — input too small for this "
            "many downsampling stages" % (H, W)
        )
    sizes = tuple(float(s) for s in (sizes if isinstance(sizes, (tuple, list)) else (sizes,)))
    ratios = tuple(float(r) for r in (ratios if isinstance(ratios, (tuple, list)) else (ratios,)))
    step_y = 1.0 / H if steps[0] <= 0 else float(steps[0])
    step_x = 1.0 / W if steps[1] <= 0 else float(steps[1])
    off_y, off_x = float(offsets[0]), float(offsets[1])

    # per-cell half-extents, order: sizes@ratio1 then sizes[0]@ratios[1:]
    hw = [(s * H / W / 2.0, s / 2.0) for s in sizes]
    hw += [(sizes[0] * H / W * np.sqrt(r) / 2.0, sizes[0] / np.sqrt(r) / 2.0) for r in ratios[1:]]
    half = jnp.asarray(np.array(hw, np.float32))  # (A, 2) [w, h]

    cy = ((jnp.arange(H, dtype=jnp.float32) + off_y) * step_y)[:, None, None]
    cx = ((jnp.arange(W, dtype=jnp.float32) + off_x) * step_x)[None, :, None]
    zeros = jnp.zeros((H, W, half.shape[0]), jnp.float32)
    out = jnp.stack(
        [cx - half[None, None, :, 0] + zeros, cy - half[None, None, :, 1] + zeros,
         cx + half[None, None, :, 0] + zeros, cy + half[None, None, :, 1] + zeros],
        axis=-1,
    ).reshape(1, -1, 4)
    if clip:
        out = jnp.clip(out, 0.0, 1.0)
    return out


def _box_iou_corner(a, b):
    """IoU of (N,4)×(M,4) corner boxes, no +1 (multibox_target-inl.h:158)."""
    tl = jnp.maximum(a[:, None, :2], b[None, :, :2])
    br = jnp.minimum(a[:, None, 2:], b[None, :, 2:])
    wh = jnp.maximum(br - tl, 0.0)
    inter = wh[..., 0] * wh[..., 1]
    area_a = (a[:, 2] - a[:, 0]) * (a[:, 3] - a[:, 1])
    area_b = (b[:, 2] - b[:, 0]) * (b[:, 3] - b[:, 1])
    union = area_a[:, None] + area_b[None, :] - inter
    return jnp.where(union <= 0, 0.0, inter / jnp.maximum(union, 1e-12))


@register("_contrib_MultiBoxTarget", alias=["MultiBoxTarget"])
@stable_eager
def multibox_target(
    anchor,
    label,
    cls_pred,
    *,
    overlap_threshold=0.5,
    ignore_label=-1.0,
    negative_mining_ratio=-1.0,
    negative_mining_thresh=0.5,
    minimum_negative_samples=0,
    variances=(0.1, 0.1, 0.2, 0.2),
):
    """SSD training-target assignment (reference multibox_target.cc:72-270).

    Stage 1 bipartite matching: repeatedly take the globally best (anchor, gt)
    pair; stage 2 threshold matching for the rest; stage 3 hard-negative
    mining ranked by background prob.  Outputs (loc_target (B, A·4),
    loc_mask (B, A·4), cls_target (B, A)); cls 0 = background,
    ignore_label = don't-care.
    """
    A = anchor.shape[-2]
    anchors = anchor.reshape(A, 4)
    B, L, LW = label.shape
    C = cls_pred.shape[1]
    vx, vy, vw, vh = (float(v) for v in variances)
    f32 = anchors.dtype
    big_neg = jnp.asarray(-1e30, f32)

    def one(lab, cpred):
        valid_seen = jnp.cumprod(lab[:, 0] != -1.0) == 1  # valid prefix (reference stops at first -1)
        gt_valid = valid_seen  # (L,)
        num_valid = gt_valid.sum()
        ious = _box_iou_corner(anchors, lab[:, 1:5])  # (A, L)
        ious = jnp.where(gt_valid[None, :], ious, 0.0)

        # stage 1: bipartite — at most min(A, L) rounds; L is small & static
        def body(_, st):
            anchor_matched, gt_matched, match_gt, match_iou = st
            m = jnp.where(anchor_matched[:, None] | gt_matched[None, :], -1.0, ious)
            flat = jnp.argmax(m)
            i, k = flat // L, flat % L
            ok = m[i, k] > 1e-6
            anchor_matched = anchor_matched.at[i].set(anchor_matched[i] | ok)
            gt_matched = gt_matched.at[k].set(gt_matched[k] | ok)
            match_gt = match_gt.at[i].set(jnp.where(ok, k, match_gt[i]))
            match_iou = match_iou.at[i].set(jnp.where(ok, m[i, k], match_iou[i]))
            return anchor_matched, gt_matched, match_gt, match_iou

        st = (
            jnp.zeros((A,), bool),
            ~gt_valid,  # invalid gts count as already matched
            jnp.full((A,), -1, jnp.int32),
            jnp.full((A,), -1.0, f32),
        )
        anchor_matched, _, match_gt, match_iou = jax.lax.fori_loop(0, min(A, L), body, st)
        positive = anchor_matched

        # stage 2: threshold matching for unmatched anchors
        best_gt = jnp.argmax(ious, axis=1).astype(jnp.int32)
        best_iou = jnp.take_along_axis(ious, best_gt[:, None], axis=1)[:, 0]
        if overlap_threshold > 0:
            thr_pos = (~positive) & (best_iou > overlap_threshold) & (num_valid > 0)
            match_gt = jnp.where(positive, match_gt, jnp.where(thr_pos, best_gt, match_gt))
            match_iou = jnp.where(positive, match_iou, jnp.where(thr_pos, best_iou, match_iou))
            positive = positive | thr_pos
        num_positive = positive.sum()

        # stage 3: negatives
        cand_iou = jnp.where(positive, match_iou, best_iou)  # max-iou per anchor
        if negative_mining_ratio > 0:
            prob_bg = jax.nn.softmax(cpred, axis=0)[0]  # (A,)
            cand = (~positive) & (cand_iou < negative_mining_thresh)
            num_neg = jnp.minimum(
                jnp.maximum(
                    (num_positive * negative_mining_ratio).astype(jnp.int32),
                    jnp.int32(minimum_negative_samples),
                ),
                (A - num_positive).astype(jnp.int32),
            )
            # pick num_neg hardest (lowest background prob) among candidates
            key = jnp.where(cand, -prob_bg, big_neg)
            order = jnp.argsort(-key, stable=True)  # candidates by descending -prob
            rank = jnp.zeros((A,), jnp.int32).at[order].set(jnp.arange(A, dtype=jnp.int32))
            negative = cand & (rank < num_neg)
        else:
            negative = ~positive
        negative = negative & (num_valid > 0)
        positive = positive & (num_valid > 0)

        # targets
        safe_gt = jnp.clip(match_gt, 0, L - 1)
        g = lab[safe_gt]  # (A, LW)
        aw = anchors[:, 2] - anchors[:, 0]
        ah = anchors[:, 3] - anchors[:, 1]
        ax = (anchors[:, 0] + anchors[:, 2]) * 0.5
        ay = (anchors[:, 1] + anchors[:, 3]) * 0.5
        gw = g[:, 3] - g[:, 1]
        gh = g[:, 4] - g[:, 2]
        gx = (g[:, 1] + g[:, 3]) * 0.5
        gy = (g[:, 2] + g[:, 4]) * 0.5
        loc = jnp.stack(
            [
                (gx - ax) / aw / vx,
                (gy - ay) / ah / vy,
                jnp.log(jnp.maximum(gw / aw, 1e-12)) / vw,
                jnp.log(jnp.maximum(gh / ah, 1e-12)) / vh,
            ],
            axis=-1,
        )  # (A, 4)
        pos4 = positive[:, None]
        loc_target = jnp.where(pos4, loc, 0.0).reshape(-1)
        loc_mask = jnp.broadcast_to(pos4, (A, 4)).astype(f32).reshape(-1)
        cls_t = jnp.where(
            positive,
            g[:, 0] + 1.0,
            jnp.where(negative, 0.0, jnp.asarray(float(ignore_label), f32)),
        )
        return loc_target, loc_mask, cls_t

    loc_t, loc_m, cls_t = jax.vmap(one)(label, cls_pred)
    return loc_t, loc_m, cls_t


@register("_contrib_MultiBoxDetection", alias=["MultiBoxDetection"])
@stable_eager
def multibox_detection(
    cls_prob,
    loc_pred,
    anchor,
    *,
    clip=True,
    threshold=0.01,
    background_id=0,
    nms_threshold=0.5,
    force_suppress=False,
    variances=(0.1, 0.1, 0.2, 0.2),
    nms_topk=-1,
):
    """SSD decode + per-class NMS (reference multibox_detection.cc:83-190).

    Output (B, A, 6) rows [class_id, score, x1, y1, x2, y2]; valid detections
    sorted by score descending, suppressed rows keep coords but class −1,
    absent rows all −1."""
    B, C, A = cls_prob.shape
    vx, vy, vw, vh = (float(v) for v in variances)
    anchors = anchor.reshape(A, 4)
    f32 = cls_prob.dtype

    aw = anchors[:, 2] - anchors[:, 0]
    ah = anchors[:, 3] - anchors[:, 1]
    ax = (anchors[:, 0] + anchors[:, 2]) * 0.5
    ay = (anchors[:, 1] + anchors[:, 3]) * 0.5

    def one(cp, lp):
        score = jnp.max(cp[1:], axis=0)  # (A,) over non-background classes
        cid = jnp.argmax(cp[1:], axis=0).astype(f32)  # 0-based class id
        cid = jnp.where(score < threshold, -1.0, cid)
        lp = lp.reshape(A, 4)
        ox = lp[:, 0] * vx * aw + ax
        oy = lp[:, 1] * vy * ah + ay
        ow = jnp.exp(lp[:, 2] * vw) * aw * 0.5
        oh = jnp.exp(lp[:, 3] * vh) * ah * 0.5
        x1, y1, x2, y2 = ox - ow, oy - oh, ox + ow, oy + oh
        if clip:
            x1, y1, x2, y2 = (jnp.clip(v, 0.0, 1.0) for v in (x1, y1, x2, y2))
        valid = cid >= 0
        # sort valid detections by score desc (invalid sink to the end)
        key = jnp.where(valid, score, -jnp.inf)
        order = jnp.argsort(-key, stable=True)
        cid, score, x1, y1, x2, y2, valid = (v[order] for v in (cid, score, x1, y1, x2, y2, valid))
        if nms_topk > 0:
            valid = valid & (jnp.arange(A) < nms_topk)
            cid = jnp.where(valid, cid, jnp.where(cid >= 0, -1.0, cid))
        boxes = jnp.stack([x1, y1, x2, y2], axis=-1)

        if 0 < nms_threshold <= 1:
            alive = _nms_alive_blocked(
                boxes, nms_threshold, plus_one=0.0, valid=cid >= 0,
                ids=cid, force_suppress=force_suppress)
            cid = jnp.where(alive | (cid < 0), cid, -1.0)

        row = jnp.stack([cid, score, x1, y1, x2, y2], axis=-1)
        return jnp.where(valid[:, None], row, -1.0)

    return jax.vmap(one)(cls_prob, loc_pred)


# ---------------------------------------------------------------------------
# Generic box ops (reference contrib/bounding_box-inl.h)
# ---------------------------------------------------------------------------


def _to_corner(box):
    x, y, w, h = box[..., 0], box[..., 1], box[..., 2], box[..., 3]
    return jnp.stack([x - w / 2, y - h / 2, x + w / 2, y + h / 2], axis=-1)


def _to_center(box):
    x1, y1, x2, y2 = box[..., 0], box[..., 1], box[..., 2], box[..., 3]
    return jnp.stack([(x1 + x2) / 2, (y1 + y2) / 2, x2 - x1, y2 - y1], axis=-1)


@register("_contrib_box_iou", alias=["box_iou"])
def box_iou(lhs, rhs, *, format="corner"):
    """Pairwise IoU (reference bounding_box-inl.h BoxOverlapForward):
    lhs (..., N, 4) × rhs (..., M, 4) → (..., N, M)."""
    if format == "center":
        lhs, rhs = _to_corner(lhs), _to_corner(rhs)
    lflat = lhs.reshape(-1, lhs.shape[-2], 4)
    rflat = rhs.reshape(-1, rhs.shape[-2], 4)
    out = jax.vmap(_box_iou_corner)(lflat, rflat)
    return out.reshape(*lhs.shape[:-2], lhs.shape[-2], rhs.shape[-2]) if lhs.ndim > 2 else out[0]


@register("_contrib_box_nms", alias=["box_nms", "_contrib_box_non_maximum_suppression"])
@stable_eager
def box_nms(
    data,
    *,
    overlap_thresh=0.5,
    valid_thresh=0.0,
    topk=-1,
    coord_start=2,
    score_index=1,
    id_index=-1,
    force_suppress=False,
    in_format="corner",
    out_format="corner",
):
    """Generic NMS (reference bounding_box-inl.h BoxNMSForward): input
    (..., N, K) rows with a score, optional class id, and 4 coords; output
    same shape, rows sorted by score desc, suppressed/invalid rows −1."""
    shape = data.shape
    N, K = shape[-2], shape[-1]
    if N == 0:
        return data
    flat = data.reshape(-1, N, K)
    cs, si = int(coord_start), int(score_index)

    def one(d):
        score = d[:, si]
        valid = score > valid_thresh
        key = jnp.where(valid, score, -jnp.inf)
        order = jnp.argsort(-key, stable=True)
        d = d[order]
        score = d[:, si]
        valid = valid[order]
        if topk > 0:
            valid = valid & (jnp.arange(N) < topk)
        boxes = d[:, cs:cs + 4]
        if in_format == "center":
            boxes = _to_corner(boxes)
        ids = d[:, id_index] if id_index >= 0 else None
        alive = _nms_alive_blocked(
            boxes, overlap_thresh, plus_one=0.0, valid=valid,
            ids=ids, force_suppress=force_suppress or id_index < 0)
        out = d
        if out_format != in_format:
            conv = _to_corner if out_format == "corner" else _to_center
            out = out.at[:, cs:cs + 4].set(conv(out[:, cs:cs + 4]))
        return jnp.where((alive & valid)[:, None], out, -1.0)

    return jax.vmap(one)(flat).reshape(shape)


@register("_contrib_bipartite_matching", alias=["bipartite_matching"])
@stable_eager
def bipartite_matching(data, *, threshold, is_ascend=False, topk=-1):
    """Greedy bipartite matching (reference bounding_box-inl.h
    BipartiteMatchingForward): data (..., N, M) scores; repeatedly take the
    global best pair.  Returns (row_match (..., N), col_match (..., M))."""
    shape = data.shape
    N, M = shape[-2], shape[-1]
    flat = data.reshape(-1, N, M)
    sign = 1.0 if is_ascend else -1.0

    def one(d):
        score = d * sign  # minimize

        def body(_, st):
            rows, cols, s = st
            flatidx = jnp.argmin(s)
            i, j = flatidx // M, flatidx % M
            ok = (s[i, j] < jnp.inf) & (
                (d[i, j] >= threshold) if not is_ascend else (d[i, j] <= threshold)
            )
            rows = rows.at[i].set(jnp.where(ok, j, rows[i]))
            cols = cols.at[j].set(jnp.where(ok, i, cols[j]))
            s = s.at[i, :].set(jnp.where(ok, jnp.inf, s[i, :]))
            s = s.at[:, j].set(jnp.where(ok, jnp.inf, s[:, j]))
            return rows, cols, s

        k = min(N, M) if topk <= 0 else min(topk, min(N, M))
        rows = jnp.full((N,), -1.0, d.dtype)
        cols = jnp.full((M,), -1.0, d.dtype)
        rows, cols, _ = jax.lax.fori_loop(0, k, body, (rows, cols, score))
        return rows, cols

    r, c = jax.vmap(one)(flat)
    return r.reshape(*shape[:-1]), c.reshape(*shape[:-2], M)
