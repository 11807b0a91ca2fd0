"""benchmark/work/ against hand-worked counts (shapes only)."""
import json
import os
import sys

import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
sys.path.insert(0, REPO)

from benchmark import work  # noqa: E402


def _cfg(name):
    with open(os.path.join(REPO, "benchmark", "configs", name + ".json")) as f:
        return json.load(f)


def test_resnet50_forward_is_about_4_1_gmac():
    cfg = _cfg("resnet50_sym_imagenet")
    layers = {l["name"]: l["macs"] for l in work.counter(cfg).layers(cfg)}
    # conv0: 112*112 outputs x 64 filters x 3*7*7
    assert layers["conv0"] == 112 * 112 * 64 * 147
    # stage2 unit1: the 1x1 runs at 56x56, the strided 3x3 at 28x28
    assert layers["stage2_unit1_conv1"] == 56 * 56 * 128 * 256
    assert layers["stage2_unit1_conv2"] == 28 * 28 * 128 * 128 * 9
    assert layers["stage2_unit1_sc"] == 28 * 28 * 512 * 256
    assert layers["fc1"] == 1000 * 2048
    assert len(layers) == 1 + 16 * 3 + 4 + 1
    assert work.forward_macs(cfg) == pytest.approx(4.09e9, rel=0.01)
    # forward + backward: three passes of 2 FLOPs a MAC
    assert work.train_flops_per_item(cfg) == 6 * work.forward_macs(cfg)


def test_rfcn_counts_no_backward_below_res3():
    cfg = _cfg("rfcn_r101_coco")
    layers = {l["name"]: l for l in work.counter(cfg).layers(cfg)}
    assert layers["conv1"]["macs"] == 304 * 512 * 64 * 147
    assert not layers["conv1"]["trained"] and not layers["res2_u3_c"]["trained"]
    assert layers["res3_u1_a"]["trained"]
    # res4 at 38x64, stride on the first 1x1
    assert layers["res4_u1_a"]["macs"] == 38 * 64 * 256 * 512
    # res5 stays at stride 16: deformable 3x3 512->512 and its offset branch
    assert layers["res5_u2_b"]["macs"] == 38 * 64 * 512 * 512 * 9
    assert layers["res5_u2_offset"]["macs"] == 38 * 64 * 72 * 512 * 9
    assert layers["rfcn_cls"]["macs"] == 38 * 64 * 81 * 49 * 256
    fwd = work.forward_macs(cfg)
    frozen = sum(l["macs"] for l in layers.values() if not l["trained"])
    assert work.train_flops_per_item(cfg) == 6 * (fwd - frozen) + 2 * frozen
    assert 130e9 < fwd < 150e9


def test_dconv_roofline_is_bound_by_bytes():
    cfg = _cfg("rfcn_r101_coco")
    peak = work.peaks("TPU v5 lite")
    seconds, bound = work.counter(cfg).dconv_min_seconds(cfg, 8, peak)
    hw, C = 38 * 64, 512
    cols = hw * 9 * C
    fwd = hw * C * 2 + hw * 72 * 4 + cols * 2
    bwd = cols * 2 + 2 * hw * C * 2 + hw * 72 * 4
    assert bound == "bytes"
    assert seconds == pytest.approx(8 * 3 * (fwd + bwd) / 819e9)


@pytest.mark.parametrize("name", ["rfcn_r101_coco", "resnet50_sym_imagenet"])
def test_a_counter_is_the_file_the_configuration_names(name):
    cfg = _cfg(name)
    assert os.path.isfile(os.path.join(
        REPO, "benchmark", "work", cfg["work"] + ".py"))
    mod = work.counter(cfg)
    assert mod.__name__ == "benchmark.work." + cfg["work"]
    assert work.forward_macs(cfg) == sum(l["macs"] for l in mod.layers(cfg))


def test_rfcn_head_is_counted_at_the_width_the_file_states():
    cfg = _cfg("rfcn_r101_coco")
    assert cfg["conv_new_filters"] == 256       # what the program builds
    layers = {l["name"]: l["macs"] for l in work.counter(cfg).layers(cfg)}
    assert layers["conv_new"] == 38 * 64 * 256 * 2048
    assert layers["rfcn_trans"] == 38 * 64 * 98 * 256
    wide = dict(cfg, conv_new_filters=cfg["source_widths"]["conv_new_filters"])
    assert work.forward_macs(wide) - work.forward_macs(cfg) == \
        38 * 64 * (1024 - 256) * (2048 + 81 * 49 + 8 * 49 + 98)
