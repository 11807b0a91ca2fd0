"""KVStore + parallel tests.

Mirrors reference ``tests/python/unittest/test_kvstore.py`` semantics (init /
push aggregation / pull / updater / compression) and adds mesh/collective and
ring-attention checks on the virtual 8-device CPU mesh (conftest.py), the
local stand-in for the reference's N-process fake cluster (SURVEY §4.1).
"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import kvstore as kv_mod
from mxnet_tpu import parallel

SHAPE = (4, 4)
KEYS = [5, 7, 11]


def init_kv(kv_type="local"):
    kv = kv_mod.create(kv_type)
    kv.init(3, mx.nd.zeros(SHAPE))
    kv.init(KEYS, [mx.nd.zeros(SHAPE)] * len(KEYS))
    return kv


def check_diff_to_scalar(A, x):
    assert np.sum(np.abs(A.asnumpy() - x)) == 0, (A.asnumpy(), x)


class TestKVStore:
    def test_single_kv_pair(self):
        kv = init_kv()
        kv.push(3, mx.nd.ones(SHAPE) * 4)
        out = mx.nd.empty(SHAPE)
        kv.pull(3, out=out)
        check_diff_to_scalar(out, 4)

    def test_list_kv_pair(self):
        kv = init_kv()
        kv.push(KEYS, [mx.nd.ones(SHAPE) * (k + 1) for k in range(len(KEYS))])
        out = [mx.nd.empty(SHAPE) for _ in KEYS]
        kv.pull(KEYS, out=out)
        for k, o in enumerate(out):
            check_diff_to_scalar(o, k + 1)

    def test_aggregator(self):
        """Per-device value lists are summed (reference test_kvstore.py
        test_aggregator, 4 'devices')."""
        kv = init_kv()
        num_devs = 4
        vals = [mx.nd.ones(SHAPE)] * num_devs
        kv.push(3, vals)
        outs = [mx.nd.empty(SHAPE) for _ in range(num_devs)]
        kv.pull(3, out=outs)
        for o in outs:
            check_diff_to_scalar(o, num_devs)

    def test_updater(self):
        kv = init_kv()

        def updater(key, recv, stored):
            stored += recv * 2

        kv.set_updater(updater)
        kv.push(3, mx.nd.ones(SHAPE))
        out = mx.nd.empty(SHAPE)
        kv.pull(3, out=out)
        check_diff_to_scalar(out, 2)
        kv.push(3, [mx.nd.ones(SHAPE)] * 4)
        kv.pull(3, out=out)
        check_diff_to_scalar(out, 2 + 8)

    def test_optimizer_in_store(self):
        kv = init_kv()
        kv.set_optimizer(mx.optimizer.create("sgd", learning_rate=0.1))
        kv.push(3, mx.nd.ones(SHAPE))
        out = mx.nd.empty(SHAPE)
        kv.pull(3, out=out)
        # w = 0 - 0.1 * grad(=1) = -0.1 (wd=0 default)
        np.testing.assert_allclose(out.asnumpy(), -0.1 * np.ones(SHAPE), rtol=1e-6)

    def test_gradient_compression(self):
        """2-bit quantization with error feedback
        (reference tests/nightly/dist_sync_kvstore.py:232)."""
        kv = init_kv()
        kv.set_gradient_compression({"type": "2bit", "threshold": 0.5})
        kv.push(3, mx.nd.ones(SHAPE) * 0.3)  # below threshold → 0, residual 0.3
        out = mx.nd.empty(SHAPE)
        kv.pull(3, out=out)
        check_diff_to_scalar(out, 0)
        kv.push(3, mx.nd.ones(SHAPE) * 0.3)  # residual 0.3+0.3 ≥ 0.5 → +0.5
        kv.pull(3, out=out)
        check_diff_to_scalar(out, 0.5)

    def test_row_sparse_pull(self):
        kv = kv_mod.create("local")
        w = np.random.rand(6, 3).astype(np.float32)
        kv.init("w", mx.nd.array(w))
        rid = mx.nd.array([0, 3], dtype="int32")
        out = mx.nd.empty((2, 3))
        kv.row_sparse_pull("w", out=out, row_ids=rid)
        np.testing.assert_allclose(out.asnumpy(), w[[0, 3]])

    def test_uninit_push_raises(self):
        kv = kv_mod.create("local")
        with pytest.raises(KeyError):
            kv.push(99, mx.nd.ones(SHAPE))

    def test_factory_rejects_unknown(self):
        with pytest.raises(ValueError):
            kv_mod.create("bogus")

    def test_save_load_optimizer_states(self, tmp_path):
        kv = init_kv()
        kv.set_optimizer(mx.optimizer.create("adam", learning_rate=0.01))
        kv.push(3, mx.nd.ones(SHAPE))
        f = str(tmp_path / "opt.states")
        kv.save_optimizer_states(f)
        kv2 = init_kv()
        kv2.set_optimizer(mx.optimizer.create("adam", learning_rate=0.01))
        kv2.load_optimizer_states(f)
        assert set(kv2._updater.states.keys()) == set(kv._updater.states.keys())


class TestMesh:
    def test_make_mesh_default(self):
        mesh = parallel.make_mesh()
        assert mesh.axis_names == ("dp",)
        assert mesh.devices.size == 8

    def test_make_mesh_2d(self):
        mesh = parallel.make_mesh(dp=2, tp=4)
        assert mesh.shape["dp"] == 2 and mesh.shape["tp"] == 4
        # canonical ordering: dp before tp
        assert mesh.axis_names == ("dp", "tp")

    def test_make_mesh_infer(self):
        mesh = parallel.make_mesh(dp=-1, tp=2)
        assert mesh.shape["dp"] == 4

    def test_shard_and_replicate(self):
        mesh = parallel.make_mesh(dp=8)
        x = mx.nd.ones((16, 4))
        xs = parallel.shard(x, ("dp", None), mesh=mesh)
        assert xs.shape == (16, 4)
        np.testing.assert_allclose(xs.asnumpy(), np.ones((16, 4)))
        xr = parallel.replicate(x, mesh=mesh)
        assert xr.asnumpy().shape == (16, 4)

    def test_shard_params_rules(self):
        mesh = parallel.make_mesh(dp=2, tp=4)
        params = {"dense0_weight": mx.nd.ones((8, 8)), "dense0_bias": mx.nd.ones((8,))}
        out = parallel.shard_params(params, mesh=mesh, rules=[("weight", (None, "tp"))])
        assert out["dense0_weight"].shape == (8, 8)
        assert out["dense0_bias"].shape == (8,)


class TestCollectives:
    def test_allreduce_in_shard_map(self):
        import jax.numpy as jnp
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        mesh = parallel.make_mesh(dp=8)

        def step(x):
            return parallel.allreduce(x, "dp")

        fn = shard_map(step, mesh=mesh, in_specs=P("dp"), out_specs=P("dp"))
        x = jnp.arange(8.0)
        out = fn(x)
        np.testing.assert_allclose(np.asarray(out), np.full(8, x.sum()))

    def test_pmean_and_reduce_scatter(self):
        import jax.numpy as jnp
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        mesh = parallel.make_mesh(dp=8)
        x = jnp.arange(16.0).reshape(8, 2)

        fn = shard_map(lambda v: parallel.pmean(v, "dp"), mesh=mesh, in_specs=P("dp"), out_specs=P("dp"))
        out = np.asarray(fn(x))
        np.testing.assert_allclose(out, np.tile(x.mean(axis=0), (8, 1)))

        fn2 = shard_map(
            lambda v: parallel.reduce_scatter(v, "dp", axis=0),
            mesh=mesh,
            in_specs=P(None),
            out_specs=P("dp"),
        )
        y = jnp.ones((8, 8))
        out2 = np.asarray(fn2(y))
        np.testing.assert_allclose(out2, 8 * np.ones((8, 8)))


class TestRingAttention:
    def _reference_attention(self, q, k, v, causal=False):
        scale = 1.0 / np.sqrt(q.shape[-1])
        s = np.einsum("bhqd,bhkd->bhqk", q, k) * scale
        if causal:
            S = q.shape[2]
            mask = np.tril(np.ones((S, S), bool))
            s = np.where(mask[None, None], s, -1e30)
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        return np.einsum("bhqk,bhkd->bhqd", p, v)

    @pytest.mark.parametrize("causal", [False, True])
    def test_ring_matches_dense(self, causal):
        mesh = parallel.make_mesh(sp=8)
        B, H, S, D = 2, 2, 32, 8
        rng = np.random.RandomState(0)
        q = rng.randn(B, H, S, D).astype(np.float32)
        k = rng.randn(B, H, S, D).astype(np.float32)
        v = rng.randn(B, H, S, D).astype(np.float32)
        out = parallel.ring_self_attention(q, k, v, mesh=mesh, causal=causal)
        expect = self._reference_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), expect, rtol=2e-4, atol=2e-5)


class TestReviewRegressions:
    """Regressions for code-review findings (layout, prefetch, symbolblock)."""

    def test_nhwc_conv_matches_nchw(self):
        from mxnet_tpu import gluon

        np.random.seed(0)
        x = np.random.randn(2, 8, 8, 3).astype(np.float32)  # NHWC
        c_last = gluon.nn.Conv2D(4, 3, layout="NHWC", in_channels=3)
        c_last.initialize()
        out = c_last(mx.nd.array(x))
        assert out.shape == (2, 6, 6, 4)
        # same weights, channel-first path
        w = c_last.weight.data().asnumpy()  # (O, Kh, Kw, I)
        b = c_last.bias.data().asnumpy()
        c_first = gluon.nn.Conv2D(4, 3, layout="NCHW", in_channels=3)
        c_first.initialize()
        c_first.weight.set_data(mx.nd.array(np.transpose(w, (0, 3, 1, 2))))
        c_first.bias.set_data(mx.nd.array(b))
        out2 = c_first(mx.nd.array(np.transpose(x, (0, 3, 1, 2))))
        np.testing.assert_allclose(
            out.asnumpy(), np.transpose(out2.asnumpy(), (0, 2, 3, 1)), rtol=1e-4, atol=1e-5
        )

    def test_nhwc_pooling(self):
        from mxnet_tpu import gluon

        x = np.arange(2 * 4 * 4 * 3, dtype=np.float32).reshape(2, 4, 4, 3)
        p = gluon.nn.MaxPool2D((2, 2), layout="NHWC")
        out = p(mx.nd.array(x)).asnumpy()
        ref = x.reshape(2, 2, 2, 2, 2, 3).max(axis=(2, 4))
        np.testing.assert_allclose(out, ref)

    def test_bad_layout_rejected(self):
        from mxnet_tpu import gluon

        with pytest.raises(ValueError):
            gluon.nn.Conv2D(4, 3, layout="NCWH")

    def test_dataloader_prefetch_zero(self):
        from mxnet_tpu import gluon

        ds = gluon.data.ArrayDataset(np.arange(10, dtype=np.float32))
        loader = gluon.data.DataLoader(ds, batch_size=2, num_workers=2, prefetch=0)
        seen = [b.asnumpy() for b in loader]
        assert len(seen) == 5

    def test_symbolblock_param_names_unprefixed(self, tmp_path):
        from mxnet_tpu import gluon
        import mxnet_tpu.symbol as sym

        data = sym.var("data")
        out = sym.FullyConnected(data, name="fc", num_hidden=3)
        blk = gluon.SymbolBlock(out, [data])
        names = set(blk.collect_params().keys())
        assert "fc_weight" in names and "fc_bias" in names, names
