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
    ft = rng.randn(BG, C, H * W).astype(np.float32)

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


# -- the band-limited dconv kernel pair (PR 27) ------------------------------
# name: (map, offsets' magnitude or None for map-wide, row block or None,
# channels)
_DCONV_REGIMES = {
    # the base grid of a dilated 3x3 alone: every lerp weight 0 or 1
    "zero_offsets": ((24, 32), 0.0, None, 16),
    "small_offsets": ((24, 32), 0.7, None, 16),
    # band = the whole map: the dense step
    "map_wide": ((24, 32), None, None, 16),
    # most samples pushed outside: clipped to the edge, lf = 0
    "outside": ((10, 16), 12.0, None, 16),
    # N = 891 is no multiple of 64, and blocks of 64 straddle taps of 99
    # rows; W no power of two, HW no multiple of 128
    "ragged_straddling": ((9, 11), 1.5, 64, 16),
    # wider than one step of the loop, narrower than half the map
    "two_steps": ((40, 32), 2.5, None, 16),
    # the channels-major layout's edges (PR 32): N = 1728 is 13.5 blocks of
    # the default 128, so the last block's padded lanes of col^T are sliced
    # off and the padded columns of g^T meet lf = 0; C = 20 is no multiple
    # of the sublane tile, let alone of 128
    "ragged_lanes": ((12, 16), 1.0, None, 20),
}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("regime", sorted(_DCONV_REGIMES))
def test_dconv_band_matches_dense(regime, dtype, monkeypatch):
    """Forward and all four gradients of the band-limited kernels against
    the dense formulation in the channels-major layout (``col^T``, ``g^T``,
    ``ft^T`` and ``d_ft^T``), whatever the band: one step, several, the
    whole map; padded rows; a block that straddles two taps; a ragged map;
    a ragged last block of lanes."""
    import jax

    from mxnet_tpu.ops import pallas_kernels as pk
    from mxnet_tpu.test_utils import (dconv_dense_reference,
                                      dconv_sample_inputs)

    hw, offset, nblk, C = _DCONV_REGIMES[regime]
    if nblk:
        monkeypatch.setattr(pk, "_DCONV_NBLK", nblk)
    rng = np.random.RandomState(3)
    BG = 2
    y0, y1, x0, x1, ly, lx, lf = map(
        jnp.asarray, dconv_sample_inputs(rng, BG, hw, offset))
    share = float(pk.dconv_band_share(y0, y1, hw, pk._DCONV_NBLK))
    if regime == "map_wide":
        assert share > 0.9
    elif regime == "two_steps":
        n_chunks = pk._dconv_chunks(hw[0] * hw[1])
        assert pk._DCONV_STEP / n_chunks < share < 0.5
    if regime == "ragged_lanes":
        assert y0.shape[1] % pk._DCONV_NBLK and C % 8
    ft = jnp.asarray(rng.randn(BG, C, hw[0] * hw[1]).astype(np.float32)
                     ).astype(dtype)
    cot = jnp.asarray(rng.randn(BG, C, y0.shape[1]).astype(np.float32))

    def run(fn):
        def loss(*a):
            return jnp.sum(fn(*a).astype(jnp.float32) * cot)
        out = fn(ly, lx, lf, ft)
        grads = jax.grad(loss, argnums=(0, 1, 2, 3))(ly, lx, lf, ft)
        return [np.asarray(v.astype(jnp.float32)) for v in (out,) + grads]

    got = run(lambda *a: pk.dconv_col_pallas(y0, y1, x0, x1, *a, hw, True))
    want = run(lambda *a: dconv_dense_reference(y0, y1, x0, x1, *a, hw))
    # bf16: one rounding of the output / of d_ft, and the dense path's AD
    # rounds dA where the kernel keeps it f32
    tol = 2.0 ** -7 if dtype == "bfloat16" else 1e-5
    assert got[0].shape == (BG, C, y0.shape[1])
    assert got[4].shape == ft.shape
    for name, g, w in zip(("col", "d_ly", "d_lx", "d_lf", "d_ft"), got, want):
        assert g.shape == w.shape, name
        assert np.abs(g - w).max() <= tol * max(np.abs(w).max(), 1.0), name


def test_dconv_band_holds_every_corner_and_padding_never_widens_it():
    """Property of ``_dconv_band``: each corner of each row lies in its
    block's ``[lo, hi]`` chunks, and the rows padded on to the last block
    leave its band what its live rows alone make it."""
    from mxnet_tpu.ops import pallas_kernels as pk
    from mxnet_tpu.test_utils import dconv_sample_inputs

    rng = np.random.RandomState(5)
    chunk = pk._DCONV_CHUNK
    for hw, offset, nblk in (((24, 32), 0.7, 80), ((13, 21), 3.0, 48),
                             ((24, 32), None, 80), ((9, 11), 40.0, 64)):
        H, W = hw
        y0, y1, x0, x1 = dconv_sample_inputs(rng, 3, hw, offset)[:4]
        N = y0.shape[1]
        assert N % nblk  # the last block is padded
        n_pad = -(-N // nblk) * nblk
        lo, hi = (np.asarray(v) for v in pk._dconv_band(
            pk._dconv_pad(jnp.asarray(y0), n_pad, "edge")[:, 0],
            pk._dconv_pad(jnp.asarray(y1), n_pad, "edge")[:, 0],
            W, H * W, nblk))
        assert lo.shape == hi.shape == (3, n_pad // nblk)
        blk = np.arange(N) // nblk
        assert (lo[:, blk] * chunk <= y0 * W + x0).all()
        assert (y1 * W + x1 < (hi[:, blk] + 1) * chunk).all()
        assert (0 <= lo).all() and (hi < pk._dconv_chunks(H * W)).all()
        tail = slice(N - N % nblk, N)
        np.testing.assert_array_equal(
            lo[:, -1], y0[:, tail].min(1) * W // chunk)
        np.testing.assert_array_equal(
            hi[:, -1], (y1[:, tail].max(1) * W + W - 1) // chunk)


def test_dconv_band_share_at_the_cells_shape():
    """``dconv_band_share``: 1.0 when every block's samples span the map,
    at most 5 of 19 chunks at R-FCN res5's shape under offsets below one
    cell (two output rows of a dilated tap touch five feature rows)."""
    from mxnet_tpu.ops.pallas_kernels import dconv_band_share
    from mxnet_tpu.test_utils import dconv_sample_inputs

    hw = (38, 64)
    rng = np.random.RandomState(0)
    y0, y1 = dconv_sample_inputs(rng, 2, hw, None)[:2]
    assert float(dconv_band_share(y0, y1, hw, 128)) > 0.999
    y0, y1 = dconv_sample_inputs(rng, 2, hw, 1.0)[:2]
    share = float(dconv_band_share(y0, y1, hw, 128))
    assert 2 / 19 < share <= 5 / 19
    # a block of the whole tap sees the whole map
    assert float(dconv_band_share(y0, y1, hw, 38 * 64)) == 1.0
