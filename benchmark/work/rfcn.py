"""Deformable R-FCN: its convolutions, and the least time of its deformable
im2col (the operator behind ``dconv_col_pallas_fwd`` / ``_bwd``)."""
from benchmark.work import conv


def layers(cfg):
    """Every convolution of Deformable R-FCN, one image: name, forward MACs,
    and whether a backward pass goes through it (conv1 and res2 are fixed
    and the gradient is cut above them)."""
    H, W = cfg["image_shape"]
    A = len(cfg["anchor_scales"]) * len(cfg["anchor_ratios"])
    k2 = cfg["pooled_size"] ** 2
    out = [conv("conv1", (H // 2, W // 2), 64, 3, 7, False)]
    hw = (H // 4, W // 4)
    cin = 64
    strides = (1, 2, 2, 1)
    for s, (units, c, st) in enumerate(
            zip(cfg["units"], (256, 512, 1024, 2048), strides), 2):
        mid = c // 4
        trained = s >= 3
        for u in range(1, units + 1):
            if u == 1:
                hw = (hw[0] // st, hw[1] // st)     # stride on the first 1x1
                out.append(conv("res%d_u%d_sc" % (s, u), hw, c, cin, 1, trained))
            pre = "res%d_u%d_" % (s, u)
            out.append(conv(pre + "a", hw, mid, cin, 1, trained))
            out.append(conv(pre + "b", hw, mid, mid, 3, trained))
            if s == 5:
                out.append(conv(pre + "offset", hw,
                                18 * cfg["deformable_groups"], mid, 3, trained))
            out.append(conv(pre + "c", hw, c, mid, 1, trained))
            cin = c
        if s == 4:
            feat = hw
    out.append(conv("rpn_conv", feat, 512, 1024, 3))
    out.append(conv("rpn_cls", feat, 2 * A, 512, 1))
    out.append(conv("rpn_bbox", feat, 4 * A, 512, 1))
    new = cfg["conv_new_filters"]
    out.append(conv("conv_new", hw, new, 2048, 1))
    out.append(conv("rfcn_cls", hw, (cfg["classes"] + 1) * k2, new, 1))
    out.append(conv("rfcn_bbox", hw, 8 * k2, new, 1))
    out.append(conv("rfcn_trans", hw, 2 * k2, new, 1))
    return out


def dconv_min_seconds(cfg, images, peak):
    """The least time ``images`` images' deformable im2col (forward and
    backward, every deformable layer) could take on a chip with ``peak``.

    The operator: for each of H*W*9 sample points and each of C channels, a
    four-corner bilinear sample (4 multiply-adds: 8 FLOPs).  Forward reads
    the features and the offsets and writes the columns; backward reads the
    columns' gradient and the features, writes the features' gradient and
    the offsets' (about twice the arithmetic).  Features and columns in the
    compute type, offsets in float32.
    -> (seconds, "bytes" or "flops": which bound it)."""
    H, W = cfg["image_shape"]
    hw = (H // cfg["feature_stride"]) * (W // cfg["feature_stride"])
    C = 2048 // 4
    layers = cfg["units"][3]
    el = 2 if cfg["compute_dtype"] == "bfloat16" else 4
    cols = hw * 9 * C
    feat = hw * C * el
    offs = hw * 18 * cfg["deformable_groups"] * 4
    flops = (8 + 16) * cols
    byts = (feat + offs + cols * el) + (cols * el + feat + feat + offs)
    n = images * layers
    t_flops = n * flops / peak["bf16_flops_per_s"]
    t_bytes = n * byts / peak["hbm_bytes_per_s"]
    return max(t_flops, t_bytes), ("bytes" if t_bytes >= t_flops else "flops")
