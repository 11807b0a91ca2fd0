"""Cross-backend consistency: CPU vs TPU forward+backward per op.

Port of the reference's ``check_consistency`` discipline
(``python/mxnet/test_utils.py:1207`` — the same symbol is run on a context
list and outputs/gradients are cross-compared with dtype-aware tolerances;
the GPU test tier re-runs the whole unit suite this way, SURVEY §4.1).

Here the context list is {CPU backend, TPU chip}: each case is a pure jax
function run jitted on both backends under ``default_matmul_precision
('highest')`` (numerics comparison, not a speed test), comparing outputs
and — for float inputs — VJP gradients against a fixed cotangent.

Runs on a machine with a chip, from the repo root:
``MXNET_TEST_DEVICE=tpu python -m pytest tests/test_consistency_tpu.py``.
In the CPU tier (``MXNET_TEST_DEVICE`` unset; ``conftest.py`` forces
``JAX_PLATFORMS=cpu``) every case skips.  Under ``MXNET_TEST_DEVICE=tpu`` a
missing TPU is a FAILURE of every case, never a skip: a broken install
must not come out green.
"""
import functools
import os

import numpy as np
import pytest

import mxnet_tpu as mx  # noqa: F401 — populates the registry
from mxnet_tpu.ops import registry


def _tpu_device():
    import jax

    for d in jax.devices():
        if d.platform == "tpu":
            return d
    return None


def _cpu_device():
    import jax

    return jax.devices("cpu")[0]


_DEVICE_TIER = os.environ.get("MXNET_TEST_DEVICE", "").startswith("tpu")
requires_tpu = pytest.mark.skipif(
    not _DEVICE_TIER, reason="CPU tier (MXNET_TEST_DEVICE=tpu not set)")

_R = np.random.RandomState(7)


def _d(*shape, lo=-1.0, hi=1.0):
    return (_R.rand(*shape) * (hi - lo) + lo).astype(np.float32)


def _run(dev, fn, args, with_grad, dtype=None):
    import jax
    import jax.numpy as jnp

    def cast(a):
        a = np.asarray(a)
        if dtype is not None and np.issubdtype(a.dtype, np.floating):
            return a.astype(jnp.dtype(dtype))  # ml_dtypes bfloat16 via jnp
        return a

    ja = [jax.device_put(cast(a), dev) for a in args]
    with jax.default_matmul_precision("highest"):
        if not with_grad:
            out = jax.jit(fn)(*ja)
            return [np.asarray(o) for o in jax.tree_util.tree_leaves(out)], []

        def scalarized(*xs):
            out = fn(*xs)
            leaves = jax.tree_util.tree_leaves(out)
            # fixed deterministic cotangent: sum of o * cos(iota)
            s = 0.0
            for o in leaves:
                if jnp.issubdtype(o.dtype, jnp.floating):
                    w = jnp.cos(jnp.arange(o.size, dtype=jnp.float32)).reshape(o.shape)
                    s = s + jnp.sum(o.astype(jnp.float32) * w)
            return s, leaves

        grad_fn = jax.grad(scalarized, argnums=tuple(
            i for i, a in enumerate(args)
            if np.issubdtype(np.asarray(a).dtype, np.floating)), has_aux=True)
        grads, leaves = jax.jit(grad_fn)(*ja)
        return ([np.asarray(o) for o in leaves],
                [np.asarray(g) for g in grads])


def _check(fn, args, with_grad=True, rtol=2e-3, atol=2e-3, dtype=None):
    cpu_out, cpu_g = _run(_cpu_device(), fn, args, with_grad, dtype)
    tpu_out, tpu_g = _run(_tpu_device(), fn, args, with_grad, dtype)
    for i, (c, t) in enumerate(zip(cpu_out, tpu_out)):
        np.testing.assert_allclose(
            np.asarray(t, np.float32), np.asarray(c, np.float32),
            rtol=rtol, atol=atol, err_msg="output %d" % i)
    for i, (c, t) in enumerate(zip(cpu_g, tpu_g)):
        np.testing.assert_allclose(
            np.asarray(t, np.float32), np.asarray(c, np.float32),
            rtol=rtol, atol=atol, err_msg="grad %d" % i)


def _op(name, **attrs):
    fn = registry.get(name)
    return functools.partial(fn, **attrs) if attrs else fn


# --------------------------------------------------------------------------
# the sweep: (id, fn, args, with_grad, tolerances)
#
# ``bf16=True`` (or a tolerance dict) additionally emits a bfloat16 variant
# of the case — the dtype production actually trains in (VERDICT round-2
# item 2; reference check_consistency includes fp16 the same way,
# test_utils.py:470,1207).  bf16 tolerances default to 4e-2: inputs are
# rounded to 8 mantissa bits on BOTH backends, so remaining divergence is
# accumulation order, but one bf16 ulp at |x|~1 is 2^-8 ≈ 4e-3 and errors
# compound through reductions.
# --------------------------------------------------------------------------
BF16_TOL = dict(rtol=4e-2, atol=4e-2)


def _cases():
    C = []

    def add(name, fn, args, with_grad=True, bf16=None, **tol):
        C.append(pytest.param(fn, args, with_grad, dict(tol), id=name))
        if bf16:
            btol = dict(BF16_TOL)
            if isinstance(bf16, dict):
                btol.update(bf16)
            btol["dtype"] = "bfloat16"
            C.append(pytest.param(fn, args, with_grad, btol, id=name + "_bf16"))

    # elemwise / math (12)
    for u in ["sigmoid", "tanh", "exp", "log", "sqrt", "square", "erf",
              "softsign", "log1p", "rsqrt", "sin", "arctan"]:
        x = _d(4, 5, lo=0.2, hi=2.0)
        add(u, _op(u), [x], bf16=u in ("sigmoid", "tanh", "exp", "erf"))
    # binary + broadcast (6)
    add("broadcast_add", _op("broadcast_add"), [_d(3, 1, 4), _d(1, 2, 4)], bf16=True)
    add("broadcast_mul", _op("broadcast_mul"), [_d(3, 1, 4), _d(1, 2, 4)], bf16=True)
    add("broadcast_div", _op("broadcast_div"), [_d(3, 1, 4), _d(1, 2, 4, lo=0.5, hi=2.0)])
    add("broadcast_maximum", _op("broadcast_maximum"), [_d(3, 4), _d(3, 4)])
    add("dot", _op("dot"), [_d(6, 7), _d(7, 5)], bf16=True)
    add("batch_dot", _op("batch_dot"), [_d(3, 4, 5), _d(3, 5, 6)], bf16=True)
    # reductions (6)
    add("sum_axis", _op("sum", axis=1), [_d(4, 5, 6)], bf16=True)
    add("mean", _op("mean", axis=(0, 2)), [_d(4, 5, 6)], bf16=True)
    add("max", _op("max", axis=1), [_d(4, 5, 6)], bf16=True)
    add("prod", _op("prod", axis=2), [_d(3, 4, 5, lo=0.5, hi=1.5)], bf16=True)
    add("norm", _op("norm"), [_d(4, 5)], bf16=True)
    add("topk", _op("topk", k=3, axis=-1, ret_typ="value"), [_d(4, 9)], False)
    # nn core (12)
    add("Convolution", _op("Convolution", kernel=(3, 3), num_filter=8, pad=(1, 1)),
        [_d(2, 4, 9, 9), _d(8, 4, 3, 3), _d(8)], bf16=True)
    add("Convolution_stride", _op("Convolution", kernel=(3, 3), num_filter=6,
                                  stride=(2, 2), no_bias=True),
        [_d(2, 3, 11, 11), _d(6, 3, 3, 3)], bf16=True)
    add("Deconvolution", _op("Deconvolution", kernel=(2, 2), num_filter=5,
                             stride=(2, 2), no_bias=True),
        [_d(2, 3, 5, 5), _d(3, 5, 2, 2)], bf16=True)
    add("FullyConnected", _op("FullyConnected", num_hidden=7),
        [_d(4, 10), _d(7, 10), _d(7)], bf16=True)
    add("Pooling_max", _op("Pooling", kernel=(2, 2), pool_type="max", stride=(2, 2)),
        [_d(2, 3, 8, 8)], bf16=True)
    add("Pooling_avg", _op("Pooling", kernel=(3, 3), pool_type="avg", pad=(1, 1)),
        [_d(2, 3, 8, 8)], bf16=True)
    add("softmax", _op("softmax", axis=-1), [_d(4, 9)], bf16=True)
    add("log_softmax", _op("log_softmax", axis=-1), [_d(4, 9)], bf16=True)
    add("Activation_relu", _op("Activation", act_type="relu"), [_d(4, 5)], bf16=True)
    add("LeakyReLU_elu", _op("LeakyReLU", act_type="elu", slope=0.3), [_d(4, 5)])
    add("LayerNorm", _op("LayerNorm"), [_d(4, 6), _d(6, lo=0.5, hi=1.5), _d(6)], bf16=True)
    add("L2Normalization", _op("L2Normalization"), [_d(3, 4, 5)], bf16=True)
    # BatchNorm fwd (aux mutation excluded from grad comparison)
    bn = _op("BatchNorm", fix_gamma=False)
    add("BatchNorm", lambda x, g, b, mm, mv: bn(x, g, b, mm, mv)[0],
        [_d(3, 4, 5, 5), _d(4, lo=0.5, hi=1.5), _d(4),
         np.zeros(4, np.float32), np.ones(4, np.float32)], bf16=True)
    # shape / indexing (8)
    add("transpose", _op("transpose", axes=(0, 2, 1)), [_d(3, 4, 5)])
    add("Reshape", _op("Reshape", shape=(0, -1)), [_d(3, 4, 5)])
    add("take", _op("take"), [_d(5, 4), np.array([0, 3, 1], np.float32)])
    add("gather_nd", _op("gather_nd"),
        [_d(4, 5), np.array([[0, 2], [1, 3]], np.float32)])
    add("Embedding", _op("Embedding", input_dim=10, output_dim=4),
        [np.array([1, 4, 7], np.float32), _d(10, 4)])
    add("one_hot", _op("one_hot", depth=6), [np.array([0, 3, 5], np.float32)], False)
    add("where", _op("where"),
        [(_d(3, 4) > 0).astype(np.float32), _d(3, 4), _d(3, 4)])
    add("Concat", _op("Concat", dim=1), [_d(2, 3), _d(2, 4)])
    # sequence / rnn-ish (3)
    add("SequenceMask", _op("SequenceMask", use_sequence_length=True, value=-1.0),
        [_d(5, 3, 2), np.array([2, 5, 1], np.float32)])
    add("SwapAxis", _op("SwapAxis", dim1=0, dim2=2), [_d(3, 4, 5)])
    add("slice_axis", _op("slice_axis", axis=1, begin=1, end=4), [_d(3, 5, 2)])
    # losses (3)
    add("smooth_l1", _op("smooth_l1", scalar=2.0), [_d(4, 5)], bf16=True)
    add("softmax_cross_entropy", _op("softmax_cross_entropy"),
        [_d(4, 6), np.array([0, 2, 5, 1], np.float32)], bf16=True)
    add("SoftmaxOutput", _op("SoftmaxOutput"),
        [_d(4, 6), np.array([0, 2, 5, 1], np.float32)], False, bf16=True)
    # detection set (10) — the north-star ops
    rois = np.concatenate([
        np.zeros((8, 1), np.float32),
        np.sort(_R.rand(8, 2, 2).astype(np.float32) * 12, axis=1).reshape(8, 4)],
        axis=1)
    rois[:, 3:] += 2.0
    add("ROIPooling", _op("ROIPooling", pooled_size=(3, 3), spatial_scale=0.5),
        [_d(1, 4, 10, 10), rois], bf16=True)
    add("ROIPooling_grouped",  # the Faster-RCNN head's gather-free path
        _op("ROIPooling", pooled_size=(3, 3), spatial_scale=0.5,
            rois_per_image=8),
        [_d(1, 4, 10, 10), rois], bf16=True)
    add("ROIAlign", _op("_contrib_ROIAlign", pooled_size=(3, 3),
                        spatial_scale=0.5, sample_ratio=2),
        [_d(1, 4, 10, 10), rois], bf16=True)
    add("PSROIPooling", _op("_contrib_PSROIPooling", spatial_scale=0.5,
                            output_dim=2, pooled_size=3),
        [_d(1, 18, 10, 10), rois], bf16=True)
    add("DefPSROIPooling_gather",
        _op("_contrib_DeformablePSROIPooling", spatial_scale=0.5, output_dim=2,
            group_size=3, pooled_size=3, part_size=3, trans_std=0.1),
        [_d(1, 18, 10, 10), rois, 0.2 * _d(8, 2, 3, 3)], bf16=True)
    bigrois = np.tile(rois, (40, 1))
    add("DefPSROIPooling_matmul",
        _op("_contrib_DeformablePSROIPooling", spatial_scale=0.5, output_dim=2,
            group_size=3, pooled_size=3, part_size=3, trans_std=0.1),
        [_d(1, 18, 10, 10), bigrois, 0.2 * _d(320, 2, 3, 3)], bf16=True)
    add("DeformableConvolution",
        _op("_contrib_DeformableConvolution", kernel=(3, 3), num_filter=6,
            pad=(1, 1), num_deformable_group=2, no_bias=True),
        [_d(1, 4, 8, 8), 0.5 * _d(1, 36, 8, 8), _d(6, 4, 3, 3)], bf16=True)
    add("DeformableConvolution_matmul",  # K2·Ho·Wo·H·W ≥ 2^22 → the
        # separable one-hot-matmul sampling path (the res5 hot path).
        # fp32 only: with 7k offset-driven samples, bf16-rounded offsets
        # flip floor() bins for ~2% of samples vs the f32 oracle (the same
        # score-discontinuity rationale that excludes bf16 MultiProposal)
        _op("_contrib_DeformableConvolution", kernel=(3, 3), num_filter=6,
            pad=(1, 1), num_deformable_group=2, no_bias=True),
        [_d(1, 4, 28, 28), 0.5 * _d(1, 36, 28, 28), _d(6, 4, 3, 3)],
        bf16=False)
    add("MultiProposal",
        _op("_contrib_MultiProposal", rpn_pre_nms_top_n=60, rpn_post_nms_top_n=12,
            scales=(4, 8), ratios=(0.5, 1, 2), feature_stride=16, rpn_min_size=4),
        [np.sort(_R.rand(1, 12, 5, 7).astype(np.float32), axis=1),  # 2A=12
         0.1 * _d(1, 24, 5, 7), np.array([[80, 112, 1.0]], np.float32)], False)
    # (no bf16 MultiProposal/box_nms variants: bf16-rounded scores collapse
    # into exact ties and CPU/TPU break them in different orders — discrete
    # keep-set divergence no numeric tolerance can absorb, like plain topk)
    nmsdat = np.concatenate([
        _R.randint(0, 3, (1, 64, 1)).astype(np.float32),
        _R.rand(1, 64, 1).astype(np.float32),
        np.sort(_R.rand(1, 64, 2, 2) * 20, axis=2).reshape(1, 64, 4).astype(np.float32),
    ], axis=2)
    add("box_nms", _op("_contrib_box_nms", overlap_thresh=0.5, coord_start=2,
                       score_index=1, id_index=0), [nmsdat], False)
    # big-N variant: N>=1024 routes through the Pallas NMS kernel on TPU
    # (ops/pallas_kernels.nms_alive_pallas) while CPU stays on the XLA
    # formulation — this case cross-checks the two implementations on the
    # actual hardware dispatch boundary
    nmsbig = np.concatenate([
        _R.randint(0, 8, (1, 1536, 1)).astype(np.float32),
        _R.rand(1, 1536, 1).astype(np.float32),
        np.sort(_R.rand(1, 1536, 2, 2) * 300, axis=2).reshape(1, 1536, 4).astype(np.float32),
    ], axis=2)
    add("box_nms_pallas_dispatch",
        _op("_contrib_box_nms", overlap_thresh=0.5, coord_start=2,
            score_index=1, id_index=0), [nmsbig], False)
    add("box_iou", _op("_contrib_box_iou"),
        [np.sort(_R.rand(6, 2, 2) * 10, axis=1).reshape(6, 4).astype(np.float32),
         np.sort(_R.rand(4, 2, 2) * 10, axis=1).reshape(4, 4).astype(np.float32)], bf16=True)
    anchors = np.sort(_R.rand(1, 20, 2, 2), axis=2).reshape(1, 20, 4).astype(np.float32)
    lab = np.full((1, 3, 5), -1.0, np.float32)
    lab[0, 0] = [1, 0.1, 0.1, 0.6, 0.7]
    add("MultiBoxTarget", _op("_contrib_MultiBoxTarget"),
        [anchors, lab, _d(1, 2, 20)], False, bf16=True)
    # rcnn targets (2)
    gt = np.full((1, 4, 5), -1.0, np.float32)
    gt[0, 0] = [0, 4, 4, 40, 40]
    gt[0, 1] = [2, 20, 10, 70, 60]
    add("rpn_anchor_target",
        _op("_contrib_rpn_anchor_target", feat_height=5, feat_width=6,
            feature_stride=16, scales=(2, 4), ratios=(0.5, 1, 2), batch_rois=32),
        [gt, np.array([[80, 96, 1.0]], np.float32)], False, bf16=True)
    prois = np.concatenate([
        np.zeros((20, 1), np.float32),
        np.sort(_R.rand(20, 2, 2) * 60, axis=1).reshape(20, 4).astype(np.float32)],
        axis=1)
    add("proposal_target",
        _op("_contrib_proposal_target", num_classes=4, batch_images=1,
            batch_rois=8), [prois, gt], False, bf16=True)
    # linalg (3)
    spd = _d(4, 4)
    spd = spd @ spd.T + 4 * np.eye(4, dtype=np.float32)
    add("linalg_potrf", _op("_linalg_potrf"), [spd])
    add("linalg_gemm2", _op("_linalg_gemm2"), [_d(3, 4), _d(4, 5)], bf16=True)
    add("linalg_sumlogdiag", _op("_linalg_sumlogdiag"), [spd])
    return C


@requires_tpu
@pytest.mark.parametrize("fn,args,with_grad,tol", _cases())
def test_cpu_tpu_consistency(fn, args, with_grad, tol):
    if _tpu_device() is None:
        import jax

        pytest.fail("MXNET_TEST_DEVICE=tpu but JAX reports no TPU: %r"
                    % (jax.devices(),))
    _check(fn, args, with_grad=with_grad, **tol)
