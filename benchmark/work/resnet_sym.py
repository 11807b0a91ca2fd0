"""The symbolic pre-activation ResNet of examples/image-classification."""
from benchmark.work import conv


def layers(cfg):
    """Every convolution and the dense layer of the symbolic pre-activation
    ResNet (stride on the 3x3 of a bottleneck), one image."""
    H, W = cfg["image_shape"][1:]
    f = cfg["filter_list"]
    out = [conv("conv0", (H // 2, W // 2), f[0], cfg["image_shape"][0], 7)]
    hw = (H // 4, W // 4)
    cin = f[0]
    for i, units in enumerate(cfg["units"]):
        c = f[i + 1]
        mid = c // 4
        for u in range(1, units + 1):
            st = 2 if (u == 1 and i > 0) else 1
            pre = "stage%d_unit%d_" % (i + 1, u)
            out.append(conv(pre + "conv1", hw, mid, cin, 1))
            hw2 = (hw[0] // st, hw[1] // st)
            out.append(conv(pre + "conv2", hw2, mid, mid, 3))
            out.append(conv(pre + "conv3", hw2, c, mid, 1))
            if u == 1:
                out.append(conv(pre + "sc", hw2, c, cin, 1))
            hw, cin = hw2, c
    out.append(conv("fc1", (1, 1), cfg["classes"], cin, 1))
    return out
