"""Pallas TPU kernel tests (interpret mode on CPU; the same kernels compile
for real TPU — verified bit-accurate vs the jnp formulation on hardware)."""
import numpy as np
import pytest
import jax.numpy as jnp

from mxnet_tpu.ops.pallas_kernels import (
    dequantize_int8_pallas, quantize_int8_pallas, supported,
)


def test_supported_predicate():
    assert supported((16, 256), np.float32)
    assert supported((8, 128), np.float32)
    assert not supported((3, 5), np.float32)  # not tile aligned
    assert not supported((16, 256), np.int32)  # wrong dtype


def test_quantize_matches_jnp_formula():
    rng = np.random.RandomState(0)
    x = (rng.randn(16, 256) * 3).astype(np.float32)
    rr = jnp.asarray(np.abs(x).max())
    q = quantize_int8_pallas(jnp.asarray(x), rr, interpret=True)
    scale = 127.0 / float(rr)
    ref = (np.sign(x) * np.minimum(np.abs(x) * scale + 0.5, 127.0)).astype(np.int8)
    np.testing.assert_array_equal(np.asarray(q), ref)


def test_dequantize_roundtrip():
    rng = np.random.RandomState(1)
    x = (rng.randn(32, 128) * 5).astype(np.float32)
    rr = jnp.asarray(np.abs(x).max())
    q = quantize_int8_pallas(jnp.asarray(x), rr, interpret=True)
    back = dequantize_int8_pallas(q, rr, interpret=True)
    assert np.abs(np.asarray(back) - x).max() < float(rr) / 127 * 1.01


def test_3d_shape_and_uneven_rows():
    rng = np.random.RandomState(2)
    x = (rng.randn(3, 8, 384) * 2).astype(np.float32)  # 9216 = 72 tiles
    assert supported(x.shape, x.dtype)
    rr = jnp.asarray(np.abs(x).max())
    q = quantize_int8_pallas(jnp.asarray(x), rr, interpret=True)
    assert q.shape == x.shape and q.dtype == jnp.int8


# ---------------------------------------------------------------------------
# Blocked greedy NMS kernel (VERDICT r2 item 3)
# ---------------------------------------------------------------------------

def _rand_boxes(rng, *lead, n, extent=800.0):
    ctr = rng.uniform(0, extent, lead + (n, 2))
    wh = rng.uniform(8, 250, lead + (n, 2))
    return np.concatenate([ctr - wh / 2, ctr + wh / 2], -1).astype(np.float32)


def test_nms_pallas_matches_xla_blocked():
    import jax
    from mxnet_tpu.ops.detection import _nms_alive_blocked
    from mxnet_tpu.ops.pallas_kernels import nms_alive_pallas

    rng = np.random.RandomState(0)
    for n in (100, 300, 700):  # below, at, and across the 256 tile
        boxes = jnp.asarray(_rand_boxes(rng, n=n))
        valid = jnp.asarray(rng.rand(n) > 0.1)
        ref = np.asarray(_nms_alive_blocked(boxes, 0.5, valid=valid))
        got = np.asarray(nms_alive_pallas(boxes, valid, None, thresh=0.5,
                                          interpret=True))
        np.testing.assert_array_equal(ref, got)


def test_nms_pallas_per_class_ids():
    from mxnet_tpu.ops.detection import _nms_alive_blocked
    from mxnet_tpu.ops.pallas_kernels import nms_alive_pallas

    rng = np.random.RandomState(1)
    n = 400
    boxes = jnp.asarray(_rand_boxes(rng, n=n))
    valid = jnp.asarray(rng.rand(n) > 0.05)
    ids = jnp.asarray(rng.randint(0, 6, n))
    ref = np.asarray(_nms_alive_blocked(
        boxes, 0.5, valid=valid, ids=ids, force_suppress=False, plus_one=0.0))
    got = np.asarray(nms_alive_pallas(
        boxes, valid, ids, thresh=0.5, plus_one=0.0, force_suppress=False,
        interpret=True))
    np.testing.assert_array_equal(ref, got)


def test_nms_pallas_vmap_hits_batched_grid():
    import jax
    from mxnet_tpu.ops.detection import _nms_alive_blocked
    from mxnet_tpu.ops.pallas_kernels import nms_alive_pallas

    rng = np.random.RandomState(2)
    B, n = 3, 512
    boxes = jnp.asarray(_rand_boxes(rng, B, n=n))
    valid = jnp.asarray(rng.rand(B, n) > 0.1)
    got = np.asarray(jax.vmap(
        lambda b, v: nms_alive_pallas(b, v, None, thresh=0.5,
                                      interpret=True))(boxes, valid))
    ref = np.stack([np.asarray(_nms_alive_blocked(
        boxes[i], 0.5, valid=valid[i])) for i in range(B)])
    np.testing.assert_array_equal(ref, got)


def test_nms_pallas_grad_is_zero_not_error():
    """The survivor mask is piecewise-constant: grad through a consumer
    must flow through box VALUES only (same as the XLA bool-mask path)."""
    import jax
    from mxnet_tpu.ops.pallas_kernels import nms_alive_pallas

    rng = np.random.RandomState(3)
    n = 300
    boxes = jnp.asarray(_rand_boxes(rng, n=n))
    valid = jnp.ones((n,), bool)

    def loss(b):
        alive = nms_alive_pallas(b, valid, None, thresh=0.5, interpret=True)
        return jnp.where(alive[:, None], b, 0.0).sum()

    g = np.asarray(jax.grad(loss)(boxes))
    alive = np.asarray(nms_alive_pallas(boxes, valid, None, thresh=0.5,
                                        interpret=True))
    np.testing.assert_array_equal(
        g, np.broadcast_to(np.where(alive[:, None], 1.0, 0.0), g.shape))


def test_dispatch_env_override(monkeypatch):
    """MXNET_NMS_IMPL=pallas routes _nms_alive_blocked through the kernel
    on CPU (interpret); =xla keeps the jnp path; results identical."""
    from mxnet_tpu.ops import detection

    rng = np.random.RandomState(4)
    boxes = jnp.asarray(_rand_boxes(rng, n=200))
    monkeypatch.setenv("MXNET_NMS_IMPL", "xla")
    ref = np.asarray(detection._nms_alive_blocked(boxes, 0.6))
    monkeypatch.setenv("MXNET_NMS_IMPL", "pallas")
    got = np.asarray(detection._nms_alive_blocked(boxes, 0.6))
    np.testing.assert_array_equal(ref, got)


def test_dconv_vmem_guard(monkeypatch):
    """ADVICE round 5: the fused-dconv auto branch must keep known-good
    north-star shapes on the kernel but push conv4-scale feature maps
    (whose backward working set hard-fails Mosaic) to the XLA scan."""
    from mxnet_tpu.ops.pallas_kernels import (dconv_bwd_vmem_bytes,
                                              dconv_fits_vmem)

    monkeypatch.delenv("MXNET_DCONV_VMEM_MB", raising=False)
    # north-star res5: 38x64 map, cpg=512 — measured working, stays fused
    assert dconv_fits_vmem(38 * 64, 512, 2)
    assert dconv_fits_vmem(38 * 64, 512, 4)
    # conv4-scale: 76x128 map — the hard-fail case, falls back
    assert not dconv_fits_vmem(76 * 128, 512, 2)
    assert dconv_bwd_vmem_bytes(76 * 128, 512, 2) > (24 << 20)
    # env override wins in both directions
    monkeypatch.setenv("MXNET_DCONV_VMEM_MB", "1024")
    assert dconv_fits_vmem(76 * 128, 512, 2)
    monkeypatch.setenv("MXNET_DCONV_VMEM_MB", "1")
    assert not dconv_fits_vmem(38 * 64, 64, 2)


def test_batched_kernels_run_per_dp_shard_under_a_visible_mesh():
    """GSPMD cannot partition a Mosaic kernel (on chips the lowering raises
    and asks for a shard_map — PR 21's four-chip run).  Traced under
    ``jax.set_mesh`` with a dp axis, the batched NMS and dconv kernels wrap
    themselves in a shard_map over it; results equal the plain call and
    stay dp-sharded.  With no visible mesh the call is plain."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from mxnet_tpu import parallel
    from mxnet_tpu.ops import pallas_kernels as pk

    mesh = parallel.make_mesh({"dp": 8})
    shard = lambda a: jax.device_put(a, NamedSharding(mesh, P("dp")))
    rng = np.random.RandomState(0)

    B, N = 8, 300
    ctr, wh = rng.rand(B, N, 2) * 500, rng.rand(B, N, 2) * 100 + 8
    boxes = np.concatenate([ctr - wh / 2, ctr + wh / 2], -1).astype(np.float32)
    valid = np.ones((B, N), bool)
    nms = jax.vmap(lambda b, v: pk.nms_alive_pallas(
        b, v, None, thresh=0.7, interpret=True))
    assert "shard_map" not in str(jax.make_jaxpr(nms)(boxes, valid))
    want = jax.jit(nms)(boxes, valid)
    with jax.set_mesh(mesh):
        assert "shard_map" in str(jax.make_jaxpr(nms)(boxes, valid))
        got = jax.jit(nms)(shard(boxes), shard(valid))
    assert got.sharding.spec == P("dp")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    BG, C, H, W = 8, 8, 5, 7
    n = 9 * H * W
    sy = rng.uniform(0, H - 1, (BG, n)).astype(np.float32)
    sx = rng.uniform(0, W - 1, (BG, n)).astype(np.float32)
    y0, x0 = np.floor(sy).astype(np.int32), np.floor(sx).astype(np.int32)
    y1, x1 = np.minimum(y0 + 1, H - 1), np.minimum(x0 + 1, W - 1)
    lf = (rng.rand(BG, n) > 0.1).astype(np.float32)
    ft = rng.randn(BG, H * W, C).astype(np.float32)

    def loss(ly, lx, lf, ft):
        col = pk.dconv_col_pallas(y0, y1, x0, x1, ly, lx, lf, ft, (H, W),
                                  True)
        return jnp.sum(col * jnp.cos(jnp.arange(
            col.size, dtype=jnp.float32)).reshape(col.shape))

    grad = jax.grad(loss, argnums=(0, 1, 2, 3))
    args = (sy - y0, sx - x0, lf, ft)
    want = jax.jit(grad)(*args)
    with jax.set_mesh(mesh):
        # forward and backward kernel, one shard_map each
        assert str(jax.make_jaxpr(grad)(*args)).count("shard_map") == 2
        got = jax.jit(grad)(*map(shard, args))
    for g, w in zip(got, want):
        assert g.sharding.spec == P("dp")
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
