"""The attention kernel pairs of ops/pallas_attention.py on the CPU in
interpret mode, against the XLA walks of ops/transformer.py that they
replace on a TPU: the selected-key pair (every output and every gradient of
``IndexerSparseAttention``) and the dense causal pair (``CausalAttention``,
``LatentAttention``); what crosses the kernel boundary, the declared costs,
and the shapes that stay on the walk.
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


# -- dense causal attention: the pair of CausalAttention / LatentAttention -----
def _causal_inputs(N, S, Hq, Hkv, d, dv, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    unit = lambda x: x * jax.lax.rsqrt(  # noqa: E731
        jnp.mean(x * x, axis=-1, keepdims=True))
    q = unit(jax.random.normal(ks[0], (N, S, Hq, d)))
    k = unit(jax.random.normal(ks[1], (N, S, Hkv, d)))
    v = jax.random.normal(ks[2], (N, S, Hkv, dv))
    return ([a.astype(dtype) for a in (q, k, v)],
            jax.random.normal(ks[3], (N, S, Hq, dv)))


def _causal_run(mode, args, cot, block=64, span=128):
    def loss(q, k, v):
        o = tr._causal_attention(q, k, v, block, span, mode)
        return jnp.sum(o.astype(jnp.float32) * cot), o
    (_, o), grads = jax.jit(jax.value_and_grad(
        loss, argnums=(0, 1, 2), has_aux=True))(*args)
    return (o,) + grads


# name: N, S, Hq, Hkv, d, dv, dtype; S = 384 is three tiles of 128 keys: the
# first block skips two of them, every block masks its diagonal tile, the
# last walks two whole tiles below it
CAUSAL_CASES = {
    "one_document_128_128_f32": (1, 384, 2, 2, 128, 128, jnp.float32),
    "one_document_128_128_bf16": (1, 384, 2, 2, 128, 128, jnp.bfloat16),
    "two_documents_f32": (2, 384, 2, 2, 128, 128, jnp.float32),
    "latent_192_128_f32": (2, 384, 2, 2, 192, 128, jnp.float32),
    "latent_192_128_bf16": (2, 384, 2, 2, 192, 128, jnp.bfloat16),
    "grouped_4_over_2_f32": (1, 384, 4, 2, 128, 128, jnp.float32),
    "grouped_4_over_2_bf16": (1, 384, 4, 2, 64, 128, jnp.bfloat16),
}


@pytest.mark.parametrize("case", sorted(CAUSAL_CASES))
def test_causal_pair_is_the_walk(case):
    """o, dq, dk, dv of ``CausalAttention``: the kernel pair (interpreted)
    against the XLA walk; with two documents a large value planted in the
    other document's keys and values moves nothing."""
    N, S, Hq, Hkv, d, dv, dtype = CAUSAL_CASES[case]
    args, cot = _causal_inputs(N, S, Hq, Hkv, d, dv, dtype)
    assert pa.causal_attn_why_not(S, Hq, Hkv, d, dv, dtype) is None
    assert pa.causal_attn_tiles(S) == (128, 128)
    want = _causal_run("xla", args, cot)
    got = _causal_run("interpret", args, cot)
    # float32: rounding order alone; bfloat16: both round the weights to 8
    # bits as MXU operands, the kernel after normalising them
    tol = 2e-5 if dtype == jnp.float32 else 2.0 ** -6
    f32 = lambda x: np.asarray(x.astype(jnp.float32), np.float64)  # noqa: E731
    for name, a, b in zip(("o", "dq", "dk", "dv"), got, want):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        a, b = f32(a), f32(b)
        assert np.isfinite(a).all(), name
        assert np.abs(a - b).max() <= tol * np.abs(b).max(), (
            name, np.abs(a - b).max(), np.abs(b).max())
    if N == 2:
        q, k, v = args
        planted = [q, k.at[0].set(50.0), v.at[0].set(1e4)]
        other = _causal_run("interpret", planted, cot.at[0].set(0.0))
        mine = _causal_run("interpret", args, cot.at[0].set(0.0))
        np.testing.assert_array_equal(other[0][1], mine[0][1])
        for a, b in zip(other[1:], mine[1:]):
            np.testing.assert_array_equal(a[1], b[1])


def test_latent_attention_through_the_pair_is_the_walk():
    """``LatentAttention``'s own vjp (it keeps its input, o and the rows'
    log-sums and rebuilds q, k, v) through the pair against the walk: the
    output and the gradient of the input and of every weight."""
    N, S, D, H, nope, rope, vd, latent = 2, 384, 64, 2, 128, 64, 128, 32
    sizes = (H, nope, rope, vd, 10000.0, 1e-6)
    ks = jax.random.split(jax.random.PRNGKey(2), 6)
    data = jax.random.normal(ks[0], (N, S, D))
    weights = (0.1 * jax.random.normal(ks[1], (H * (nope + rope), D)),
               0.1 * jax.random.normal(ks[2], (latent + rope, D)),
               1.0 + 0.1 * jax.random.normal(ks[3], (latent,)),
               0.1 * jax.random.normal(ks[4], (H * (nope + vd), latent)))
    cot = jax.random.normal(ks[5], (N, S, H, vd))
    pos = jnp.arange(S, dtype=jnp.int32)

    def run(mode):
        def loss(data, *w):
            o = tr._latent_attention(sizes, 64, 128, mode, data, pos, *w)
            return jnp.sum(o * cot), o
        (_, o), grads = jax.jit(jax.value_and_grad(
            loss, argnums=tuple(range(5)), has_aux=True))(data, *weights)
        return (o,) + grads

    for a, b in zip(run("interpret"), run("xla")):
        assert np.abs(a - b).max() <= 5e-5 * np.abs(b).max()


def _causal_jaxpr(mode, N=1, S=1024, Hq=2, Hkv=2, d=128, dv=128,
                  dtype=jnp.bfloat16, block=256, span=512):
    def loss(q, k, v):
        return jnp.sum(tr._causal_attention(q, k, v, block, span, mode)
                       .astype(jnp.float32))

    return jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(
        jnp.zeros((N, S, Hq, d), dtype), jnp.zeros((N, S, Hkv, d), dtype),
        jnp.zeros((N, S, Hkv, dv), dtype))


@pytest.mark.parametrize("why,shape,tiles", [
    ("value heads of 96 are not whole lanes", {"dv": 96}, (64, 128)),
    ("a sequence of 320 is not whole tiles", {"S": 320}, (32, 64)),
    ("float16 is neither float32 nor bfloat16", {"dtype": jnp.float16},
     (64, 128)),
    ("score heads of 96 are not a multiple of 64", {"d": 96}, (64, 128)),
    ("3 query heads do not group over 2 key heads", {"Hq": 3}, None),
    ("a working set of", {"S": 65536}, None),
])
def test_shapes_the_causal_pair_cannot_take_walk(why, shape, tiles):
    """``causal_attn_why_not`` names the reason, and asked for the pair such
    shapes trace no ``pallas_call``: the walk runs (``tiles``: the walk's
    block and span; None where the walk refuses the shapes too, or they are
    too large to trace here)."""
    shape = dict(dict(S=512, Hq=2, Hkv=2, d=128, dv=128, dtype=jnp.float32),
                 **shape)
    asked = [shape[n] for n in ("S", "Hq", "Hkv", "d", "dv", "dtype")]
    said = pa.causal_attn_why_not(*asked)
    assert said is not None and why in said, said
    assert not pa.causal_attn_supported(*asked)
    if tiles:
        jaxpr = _causal_jaxpr("interpret", block=tiles[0], span=tiles[1],
                              **shape)
        assert not [e for e in _eqns_outside_kernels(jaxpr.jaxpr)
                    if e.primitive.name == "pallas_call"]


def test_causal_costs_count_the_products_walked_and_layers_share_a_trace():
    """``traced_costs()``: 2 products a walked pair forward and 5 backward,
    over whole live tiles; a call is a TRACE of the jitted kernel call: one
    for a shape, whether the ``custom_vjp``'s primal, its forward rule or a
    second layer asks for it."""
    jax.clear_caches()
    pk.reset_traced_costs()
    N, S, H, d, dv = 1, 2048, 2, 128, 128
    _causal_jaxpr("interpret", S=S)
    _causal_jaxpr("interpret", S=S)
    costs = pk.traced_costs()
    bq, bk = pa.causal_attn_tiles(S)
    assert (bq, bk) == (1024, 1024)
    assert pa.causal_attn_tiles(4096 + 512) == (512, 512)
    pairs = pa.causal_attn_walked(S)
    assert pairs == 3 * bq * bk          # two diagonal tiles and one below
    assert pa.causal_attn_walked(8192, (512, 512)) == 136 * 512 * 512
    for name, products_d in (("causal_attn_pallas_fwd", d + dv),
                             ("causal_attn_pallas_bwd", 3 * d + 2 * dv)):
        assert costs[name]["calls"] == 1 and costs[name]["shapes"] == 1
        assert costs[name]["shape"] == [N, H, S, d]
        assert costs[name]["flops"] == 2 * N * H * pairs * products_d
        assert name in pk.cost_fns()
    pk.reset_traced_costs()


def _lowered_for_tpu(fn, *args):
    return jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()


def _arrays_outside_custom_calls(text):
    """(shape, type) of every tensor a line of the lowered module names,
    Mosaic's kernel bodies (``tpu_custom_call`` backend configs) left out."""
    import re

    for line in text.splitlines():
        if "tpu_custom_call" in line:
            line = re.sub(r'backend_config = "[^"]*"', "", line)
        for dims, typ in re.findall(r"tensor<((?:\d+x)+)(\w+)>", line):
            yield tuple(int(x) for x in dims.split("x")[:-1]), typ, line


@pytest.mark.parametrize("operator", ["CausalAttention", "LatentAttention"])
def test_no_block_weights_and_no_float32_key_sums_in_the_tpu_step(operator):
    """The mechanism.  Forward + backward of one layer, lowered for a TPU:
    the two custom calls are there, and outside them no array has both a
    block of rows and a keys-long axis (the walk's (N, heads, block, keys)
    weights), and no float32 array is as large as the keys' or values'
    gradient (the walk's (N, keys, heads, d) sums over the blocks).  The
    same step lowered for the CPU holds both: the walk."""
    N, S, H, block, span = 2, 1024, 2, 256, 512
    ends = set(range(span, S + 1, span))
    if operator == "CausalAttention":
        d, dv = 128, 128
        args = [jnp.zeros((N, S, H, w), jnp.bfloat16) for w in (d, d, dv)]
        fn = lambda *a: tr.causal_attention(*a, block=block, span=span)  # noqa: E731
    else:
        d, dv, D, latent = 192, 128, 64, 32
        args = [jnp.zeros(s, jnp.bfloat16) for s in (
            (N, S, D), (H * d, D), (latent + 64, D), (latent,),
            (H * (128 + dv), latent))]
        pos = jnp.arange(S, dtype=jnp.int32)
        fn = lambda x, *w: tr.latent_attention(  # noqa: E731
            x, pos, *w, num_heads=H, qk_nope_dim=128, qk_rope_dim=64,
            v_dim=dv, block=block, span=span)

    def step(*a):
        return jax.grad(lambda *a: jnp.sum(fn(*a).astype(jnp.float32)),
                        argnums=tuple(range(len(a))))(*a)

    def weights_and_sums(text):
        weights = sums = 0
        for shape, typ, line in _arrays_outside_custom_calls(text):
            if block in shape[:-1] and shape[-1] in ends:
                weights += 1
            if (typ == "f32" and len(shape) == 4 and shape[1] in ends
                    and shape[2:] in ((H, d), (H, dv))
                    and "= stablehlo.add " in line):
                sums += 1
        return weights, sums

    tpu = _lowered_for_tpu(step, *args)
    assert "causal_attn_pallas_fwd" in tpu and "causal_attn_pallas_bwd" in tpu
    assert weights_and_sums(tpu) == (0, 0)
    cpu = jax.jit(step).lower(*args).as_text()
    assert "tpu_custom_call" not in cpu
    walk_weights, walk_sums = weights_and_sums(cpu)
    assert walk_weights > 0 and walk_sums > 0


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


@pytest.mark.parametrize("cell,shape", [
    ("moonlight_16b_a3b.train_s8k", (2, 8192, 16, 192, 128)),
    ("ouro_2_6b.train_s4k", (1, 4096, 16, 128, 128)),
])
def test_the_causal_pair_compiles_for_a_v5e_at_the_cells_shapes(one_v5e, cell,
                                                                shape):
    """16 heads with their own keys, bfloat16, the tuned tiles: Mosaic takes
    both kernels (the 192-wide contraction, the whole float32 ``dq`` of one
    head in VMEM) within the ``vmem_limit_bytes`` they ask for."""
    N, S, H, d, dv = shape
    assert pa.causal_attn_supported(S, H, H, d, dv, jnp.bfloat16), cell

    def arg(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_v5e)

    q, v = arg((N, H, S, d)), arg((N, H, S, dv))
    row = arg((N, H, S), jnp.float32)
    fwd = jax.jit(pa.causal_attn_fwd).lower(
        q, q, v, arg((N, H), jnp.float32)).compile()
    bwd = jax.jit(pa.causal_attn_bwd).lower(q, q, v, row, row, v).compile()
    assert "causal_attn_pallas_fwd" in fwd.as_text()
    assert "causal_attn_pallas_bwd" in bwd.as_text()


def _pallas_calls_under(jaxpr, inside=()):
    """(name, the primitives it sits under) of every ``pallas_call``."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn.params["name"], inside
            continue
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _pallas_calls_under(
                        sub, inside + (eqn.primitive.name,))


def test_the_looped_rematted_step_traces_the_pair_once_inside_the_scan():
    """``OuroLM`` at a toy size with heads of 128: two layers applied four
    times as one ``lax.scan``, each application rematted.  The whole Adam
    step traces the kernels once a shape and rule, not once a layer or a
    pass (both layers and the recomputed forward share them), and every ``pallas_call`` of the
    step sits inside the scans (the TPU branch of ``platform_dependent``;
    lowered for the CPU the step holds no Mosaic call)."""
    from mxnet_tpu.gluon.functional import make_train_step
    from mxnet_tpu.gluon.model_zoo.text import OuroLM, OuroLMLoss

    cfg = {"hidden_size": 256, "num_hidden_layers": 2, "total_ut_steps": 4,
           "num_attention_heads": 2, "num_key_value_heads": 2,
           "head_dim": 128, "intermediate_size": 128, "vocab_size": 64,
           "rms_norm_eps": 1e-6, "rope_theta": 1000000, "rope_scaling": None,
           "use_sliding_window": False, "tie_word_embeddings": False,
           "layer_types": ["full_attention"] * 48}
    net = OuroLM.from_config(cfg, attn_block=64, attn_span=128, loss_block=64)
    net.initialize()
    step, state, _ = make_train_step(
        net, OuroLMLoss(0.1), learning_rate=1e-3, optimizer="adam",
        beta1=0.9, beta2=0.95)
    tokens = jnp.zeros((1, 128), jnp.int32)
    jax.clear_caches()
    pk.reset_traced_costs()
    args = (state, (tokens, tokens), tokens, jax.random.PRNGKey(0))
    traced = jax.jit(step).trace(*args)
    costs = pk.traced_costs()
    # the forward twice: the ``custom_vjp``'s primal (the first trace of the
    # rematted body) and its forward rule, as the selected-key pair's
    for name, traces in (("causal_attn_pallas_fwd", 2),
                         ("causal_attn_pallas_bwd", 1)):
        assert costs[name]["calls"] == traces, (name, costs[name])
        assert costs[name]["shape"] == [1, 2, 128, 128]
    calls = list(_pallas_calls_under(traced.jaxpr.jaxpr))
    names = [n for n, _ in calls]
    # two layers: forward, recomputed forward and backward of each
    assert names.count("causal_attn_pallas_fwd") == 4
    assert names.count("causal_attn_pallas_bwd") == 2
    assert all("scan" in inside for _, inside in calls), calls
    assert "tpu_custom_call" not in traced.lower().as_text()
    pk.reset_traced_costs()
