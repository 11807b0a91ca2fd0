"""The selected-key attention's Pallas kernel pair (ops/pallas_attention.py)
on the CPU in interpret mode, against the XLA walk of ops/transformer.py that
it replaces on a TPU: every output and every gradient of
``IndexerSparseAttention``, what crosses the kernel boundary, the declared
costs, and the shapes that stay on the walk.
"""
import os
import sys

import numpy as np
import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, REPO)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from mxnet_tpu.ops import pallas_attention as pa  # noqa: E402
from mxnet_tpu.ops import pallas_kernels as pk  # noqa: E402
from mxnet_tpu.ops import transformer as tr  # noqa: E402

D, HI, DI = 128, 4, 16
GRADS = ("query", "key", "value", "index_query", "index_key", "index_weight")


def _inputs(S, Hq, Hkv, dtype, seed=0, q_scale=1.0, dead_keys=None):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    unit = lambda x: x * jax.lax.rsqrt(  # noqa: E731
        jnp.mean(x * x, axis=-1, keepdims=True))
    args = [q_scale * unit(jax.random.normal(ks[0], (S, Hq, D))),
            unit(jax.random.normal(ks[1], (S, Hkv, D))),
            jax.random.normal(ks[2], (S, Hkv, D)),
            jax.random.normal(ks[3], (S, HI, DI)),
            jax.random.normal(ks[4], (S, DI)),
            jax.random.normal(ks[5], (S, HI))]
    if dead_keys is not None:
        # index scores are sums of relu(.) x weight: positive weights and
        # all-positive index vectors score every key above 0, a zero index
        # key scores exactly 0, so no row's top-k reaches the dead keys
        args[3], args[4], args[5] = (jnp.abs(a) + 0.1 for a in args[3:])
        args[4] = args[4].at[dead_keys].set(0.0)
    return [a.astype(dtype) for a in args], jax.random.normal(ks[6],
                                                              (S, Hq, D))


def _run(mode, args, cot, topk, block, span):
    def loss(*a):
        out = tr._sparse_attention(*a, topk, block, span, True, mode)
        return jnp.sum(out[0].astype(jnp.float32) * cot) + 2.0 * out[1], out
    (_, out), grads = jax.jit(jax.value_and_grad(
        loss, argnums=tuple(range(6)), has_aux=True))(*args)
    return out, grads


# name: S, Hq, Hkv, dtype, topk, block, span, extra inputs
CASES = {
    # every row has fewer causal keys than topk: everything is selected
    "all_selected_f32": (256, 8, 1, jnp.float32, 256, 64, 256, {}),
    "two_spans_group8_f32": (512, 8, 1, jnp.float32, 96, 64, 256, {}),
    "two_spans_group8_bf16": (512, 8, 1, jnp.bfloat16, 96, 64, 256, {}),
    "group1_two_heads_f32": (512, 2, 2, jnp.float32, 96, 64, 256, {}),
    "group2_bf16": (512, 4, 2, jnp.bfloat16, 64, 32, 256, {}),
    # tiles of 128 keys; keys 128..255 are never selected, so from the third
    # span on every block walks a tile with an empty selection
    "empty_tile_f32": (512, 4, 1, jnp.float32, 48, 32, 128,
                       {"dead_keys": slice(128, 256)}),
    # |q| |k| / sqrt(d) = 45: exp() of the unshifted scores would leave
    # bfloat16's and float32's useful range, the shift has to be the same in
    # the forward, the saved log-sum and the backward
    "large_scores_f32": (512, 8, 1, jnp.float32, 96, 64, 256,
                         {"q_scale": 4.0}),
    "large_scores_bf16": (512, 8, 1, jnp.bfloat16, 96, 64, 256,
                          {"q_scale": 4.0}),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_pair_is_the_walk(case):
    """o, kl, selected, causal, the packed selection and all six gradients:
    the kernel pair (interpreted) against the XLA walk."""
    S, Hq, Hkv, dtype, topk, block, span, extra = CASES[case]
    args, cot = _inputs(S, Hq, Hkv, dtype, **extra)
    assert pa.sparse_attn_supported(block, Hq, Hkv, D, span, dtype)
    want, want_g = _run("xla", args, cot, topk, block, span)
    got, got_g = _run("interpret", args, cot, topk, block, span)
    # what the selection decides is exact
    assert int(got[2]) == int(want[2]) and int(got[3]) == S * (S + 1) // 2
    np.testing.assert_array_equal(got[4], want[4])
    if case.startswith("all_selected"):
        assert int(got[2]) == int(got[3])
    if case.startswith("empty_tile"):
        bits = np.asarray(got[4])
        assert not bits[:, 4:8].any() and bits[300:, 8:].any()
    # float32: rounding order alone; bfloat16: both round the weights to 8
    # bits as MXU operands (the kernel after normalising them) and sum the
    # keys' gradients over the blocks in bfloat16
    tol = 2e-5 if dtype == jnp.float32 else 2.0 ** -6
    f32 = lambda x: np.asarray(x.astype(jnp.float32), np.float64)  # noqa: E731
    for name, a, b in (("out", got[0], want[0]), ("kl", got[1], want[1]),
                       *zip(GRADS, got_g, want_g)):
        a, b = f32(a), f32(b)
        assert np.isfinite(a).all(), name
        assert np.abs(a - b).max() <= tol * np.abs(b).max(), (
            name, np.abs(a - b).max(), np.abs(b).max())


def _eqns_outside_kernels(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue            # what is inside lives in VMEM
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns_outside_kernels(sub)


def _layer_jaxpr(mode, S=768, Hq=8, Hkv=1, block=32, span=256, d=D,
                 dtype=jnp.bfloat16):
    shapes = ((S, Hq, d), (S, Hkv, d), (S, Hkv, d), (S, HI, DI), (S, DI),
              (S, HI))

    def loss(*a):
        out = tr._sparse_attention(*a, 64, block, span, False, mode)
        return jnp.sum(out[0].astype(jnp.float32)) + out[1]

    return jax.make_jaxpr(jax.grad(loss, argnums=tuple(range(6))))(
        *(jnp.zeros(s, dtype) for s in shapes))


def _most_per_key(jaxpr, ends):
    """Over the arrays that the equations outside the kernels read and write
    and that have an axis as long as some span's keys: the most elements a
    key."""
    return max(int(np.prod(v.aval.shape)) // max(set(v.aval.shape) & ends)
               for e in _eqns_outside_kernels(jaxpr.jaxpr)
               for v in list(e.invars) + list(e.outvars)
               if hasattr(v, "aval") and hasattr(v.aval, "shape")
               and set(v.aval.shape) & ends)


def test_no_heads_block_keys_array_outside_the_kernels():
    """The mechanism: forward + backward of one layer through the kernel
    pair hold no (heads, block, keys) array outside a ``pallas_call``: no
    array with a key axis has ``Hq x block`` elements a key or more; the walk
    holds them (``e``, ``ds``).  What is left with a key axis: queries, keys
    and values themselves (``Hq d`` a row at most), the (block, keys) index
    scores, selection and target, the indexer's (block, HI, keys) products."""
    S, Hq, block, span = 1536, 8, 256, 512
    ends = set(range(span, S + 1, span))
    assert not ends & {D, DI, HI, Hq, block}     # a key axis is recognisable
    assert max(Hq * D, block * HI) < Hq * block
    kernel = _layer_jaxpr("interpret", S=S, Hq=Hq, block=block, span=span)
    names = {e.params["name"] for e in _eqns_outside_kernels(kernel.jaxpr)
             if e.primitive.name == "pallas_call"}
    assert names == {"sparse_attn_pallas_fwd", "sparse_attn_pallas_bwd"}
    assert _most_per_key(kernel, ends) < Hq * block
    walk = _layer_jaxpr("xla", S=S, Hq=Hq, block=block, span=span)
    assert _most_per_key(walk, ends) == Hq * block


def test_traced_costs_count_one_call_a_span_and_the_keys_walked():
    """A call is traced for each span, not for each block (the blocks of a
    span are one ``lax.map``): under ``grad`` the forward twice (the
    ``custom_vjp``'s primal, which is dropped, and its forward rule), the
    backward once; each priced at the keys the span's blocks walk in the
    mean: whole tiles up to each block's last query.  (Layers of one shape
    share a span's trace, ``transformer._attend_span``: the count is of
    traces, so it starts from empty caches.)"""
    jax.clear_caches()
    pk.reset_traced_costs()
    S, Hq, Hkv, block, span = 768, 8, 1, 32, 256
    _layer_jaxpr("interpret")
    costs = pk.traced_costs()
    spans = S // span
    walked = [pa.sparse_attn_walked(end, span, block)
              for end in range(span, S + 1, span)]
    assert walked == [256, 512, 768]      # tiles of 256: a span is one tile
    assert pa.sparse_attn_walked(16384, 2048, 256) == 16384 - 1024 + 256
    for name, traces, products, fn in (
            ("sparse_attn_pallas_fwd", 2, 3, pa.cost_sparse_attn_fwd),
            ("sparse_attn_pallas_bwd", 1, 5, pa.cost_sparse_attn_bwd)):
        assert costs[name]["calls"] == traces * spans
        assert costs[name]["shapes"] == 1
        assert costs[name]["shape"] == [Hkv, Hq // Hkv * block, D]
        assert costs[name]["flops"] == sum(
            products * 2 * block * Hq * D * w for w in walked) // spans
        assert costs[name]["bytes_accessed"] == sum(
            fn(block, w, Hq, Hkv, D)["bytes_accessed"]
            for w in walked) // spans
        assert name in pk.cost_fns()
    pk.reset_traced_costs()


def test_layers_share_a_spans_trace_but_not_another_scoring_function(
        monkeypatch):
    """A second layer of the same shapes traces no kernel again (trace and
    lowering are paid on every run); a replaced ``_index_scores`` is a new
    trace, because the scoring function is one of the span's static
    arguments."""
    jax.clear_caches()
    pk.reset_traced_costs()
    _layer_jaxpr("interpret")
    first = pk.traced_costs()["sparse_attn_pallas_fwd"]["calls"]
    _layer_jaxpr("interpret")
    assert pk.traced_costs()["sparse_attn_pallas_fwd"]["calls"] == first
    real = tr._index_scores
    monkeypatch.setattr(tr, "_index_scores",
                        lambda *a, **kw: real(*a, **kw))
    _layer_jaxpr("interpret")
    assert pk.traced_costs()["sparse_attn_pallas_fwd"]["calls"] == 2 * first
    pk.reset_traced_costs()


@pytest.mark.parametrize("why,kw", [
    ("head size not a multiple of 128", {"d": 64}),
    ("block under the int8 mask's sublane tile", {"block": 16}),
    ("span not whole key tiles", {"span": 192, "S": 384, "block": 32}),
])
def test_shapes_the_kernels_cannot_take_walk(why, kw):
    """Asked for the kernel pair, such shapes still take the XLA walk: no
    ``pallas_call`` is traced and the results are the walk's, bit for bit."""
    S, block, span, d = (kw.get("S", 512), kw.get("block", 64),
                         kw.get("span", 256), kw.get("d", D))
    assert not pa.sparse_attn_supported(block, 4, 2, d, span, jnp.float32)
    jaxpr = _layer_jaxpr("interpret", S=S, Hq=4, Hkv=2, block=block,
                         span=span, d=d, dtype=jnp.float32)
    assert not [e for e in _eqns_outside_kernels(jaxpr.jaxpr)
                if e.primitive.name == "pallas_call"]
    ks = jax.random.split(jax.random.PRNGKey(3), 6)
    args = [jax.random.normal(k, s) for k, s in zip(ks, (
        (S, 4, d), (S, 2, d), (S, 2, d), (S, HI, DI), (S, DI), (S, HI)))]
    cot = jnp.ones((S, 4, d))
    for a, b in zip(jax.tree_util.tree_leaves(
                        _run("interpret", args, cot, 48, block, span)),
                    jax.tree_util.tree_leaves(
                        _run("xla", args, cot, 48, block, span))):
        np.testing.assert_array_equal(a, b)


def test_the_operator_walks_on_a_cpu_and_says_which_shapes_it_takes():
    """The registered operator chooses by the lowering platform: lowered for
    the CPU, kernel-sized shapes carry no Mosaic call (the kernel branch is
    traced, and dropped); the cell's shapes are the kernels'."""
    assert pa.sparse_attn_supported(256, 32, 4, 128, 2048, jnp.bfloat16)
    assert not pa.sparse_attn_supported(256, 32, 4, 128, 2048, jnp.float16)
    assert pa.sparse_attn_tile(2048) == 512 and pa.sparse_attn_tile(384) == 128
    assert pa.sparse_attn_vmem_bytes(256, 32, 4, 128, 2) <= pa._VMEM_LIMIT
    assert not pa.sparse_attn_fits_vmem(2048, 32, 4, 128, 2)
    args, _ = _inputs(256, 8, 1, jnp.float32)
    fn = jax.jit(lambda *a: tr.indexer_sparse_attention(
        *a, topk=64, block=64, span=256))
    text = fn.lower(*args).as_text()
    assert "tpu_custom_call" not in text
    want = tr._sparse_attention(*args, 64, 64, 256, False, "xla")
    for a, b in zip(fn(*args), want):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)


# -- the kernels at the cell's shapes, compiled for a described v5e -----------
@pytest.fixture(scope="module")
def one_v5e():
    """A TPU v5e that is described, not attached: its compiler refuses what
    interpret mode lets through (unaligned slices, too much VMEM)."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever the plugin raises
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("keys", [2048, 16384])
def test_the_pair_compiles_for_a_v5e_at_the_cells_shapes(one_v5e, keys):
    """32 / 4 heads of 128, blocks of 256 queries, tiles of 512 keys, the
    first and the last span: Mosaic takes both kernels within the
    ``vmem_limit_bytes`` they ask for."""
    Hkv, g, B, d = 4, 8, 256, 128

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_v5e)

    q = arg((Hkv, g * B, d), jnp.bfloat16)
    kv = arg((keys, Hkv * d), jnp.bfloat16)
    mask = arg((B, keys), jnp.int8)
    row = arg((Hkv, g * B), jnp.float32)
    last = arg((), jnp.int32)
    fwd = jax.jit(pa.sparse_attn_fwd).lower(
        q, kv, kv, mask, arg((Hkv,), jnp.float32), last).compile()
    bwd = jax.jit(pa.sparse_attn_bwd).lower(
        q, kv, kv, mask, row, row, q, last).compile()
    assert "sparse_attn_pallas_fwd" in fwd.as_text()
    assert "sparse_attn_pallas_bwd" in bwd.as_text()
