"""Pallas TPU kernels for the selected-key attention of one block of queries
(``ops/transformer.py`` ``IndexerSparseAttention``).

The XLA walk writes a block's unnormalised weights ``e`` and their gradient
``ds``, arrays of (heads, block, keys), to HBM and reads them back some nine
times a layer.  Here they live in VMEM for one (key tile, key-value head):
``sparse_attn_pallas_fwd`` and ``sparse_attn_pallas_bwd`` walk a block of
queries over tiles of ``_TILE`` keys, and only ``(heads, rows, head_dim)``-
and ``(block, keys)``-shaped arrays cross the kernel boundary.

Layouts.  The queries of one key-value head come as one row block: ``q``
``(Hkv, g * B, d)``, row ``i * B + r`` the ``i``-th query head of the group
at query ``r`` of the block, so a head group's scores are one ``(g B, d) x
(d, tile)`` product.  Keys and values are the operator's own ``(keys, Hkv
d)`` view, the selection ``(B, keys)`` int8 (causal mask folded in; 1/64
of one crossing of ``e`` at 32 heads in bfloat16).  Per-row statistics
cross the boundary lane-broadcast, ``(Hkv, g B, 128)`` float32: a column
vector costs a whole lane tile in VMEM either way.

The grid ends each block's walk at the tile of its last query (``nlive``,
scalar prefetch): tiles above it are not fetched and not computed, their
outputs written as zeros.

The softmax shift is the walk's: ``|q| max_s |k_s| d^-1/2`` a row, an upper
bound of the row's scores.  The forward makes two sweeps over the key tiles:
the first sums ``e`` and ``e v``; the second, with every row's sum known,
recomputes the scores and writes ``target``, the mean over the heads of the
probabilities.  The backward recomputes them once more from the saved
log-sum ``lse = shift + log z``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from .pallas_kernels import _MIN_SUBLANES, _record_cost, register_cost

__all__ = ["sparse_attn_fwd", "sparse_attn_bwd", "sparse_attn_supported",
           "sparse_attn_vmem_bytes", "sparse_attn_fits_vmem",
           "sparse_attn_tile", "sparse_attn_walked"]

_LANE = 128
# keys a grid step: the (g B, tile) float32 scores, probabilities and their
# gradients of one key-value head are 4 MB each at 2048 rows.  On the chip
# (PERF.md section 6, PR 34) a block of the cell's last span takes 0.81 +
# 1.55 ms forward + backward at 256, 0.69 + 1.12 at 512, 0.66 + 1.08 at 1024,
# where the whole tiles up to a block's last query are 3 % more keys
_TILE = 512
# The kernels hold every head's queries, statistics and float32 accumulators
# of the block (the key tiles walk outermost so that ``target`` can sum over
# the heads in VMEM), which at 32 heads of 128 and 256 queries is more than
# the 16 MB a Mosaic kernel gets by default: both calls pass
# ``vmem_limit_bytes`` (v5e: 128 MiB of VMEM), and the guard judges the
# estimate below against the same number.
_VMEM_LIMIT = 64 << 20


def sparse_attn_tile(span):
    """Keys a grid step, for spans of ``span`` keys: ``_TILE``, or the half
    or quarter of it that divides the span."""
    return next((t for t in (_TILE, _TILE // 2) if span % t == 0), _LANE)


def sparse_attn_vmem_bytes(B, Hq, Hkv, d, itemsize, tile=_TILE):
    """Estimated VMEM working set of the BACKWARD kernel, the larger: q, do
    and dq blocks (two buffers each), the lane-broadcast statistics, the
    float32 dq accumulator, and five ``(g B, tile)`` float32 planes (scores,
    probabilities, their gradients, the mask) of one key-value head."""
    R = Hq // Hkv * B
    return (3 * 2 * Hq * B * d * itemsize          # q, do, dq blocks
            + 2 * Hkv * R * _LANE * 4              # lse | delta
            + Hq * B * d * 4                       # dq accumulator
            + 5 * R * tile * 4                     # planes of one head group
            + 2 * 2 * (2 * tile * d * itemsize + B * tile * 5))


def sparse_attn_fits_vmem(B, Hq, Hkv, d, itemsize, tile=_TILE):
    return sparse_attn_vmem_bytes(B, Hq, Hkv, d, itemsize, tile) <= _VMEM_LIMIT


def sparse_attn_supported(B, Hq, Hkv, d, span, dtype):
    """Whether the kernel pair takes a block of ``B`` queries of ``Hq`` /
    ``Hkv`` heads of ``d`` against keys in multiples of ``span``: lanes full
    (``d`` a multiple of 128), the block whole sublane tiles of the compute
    type (and of the int8 selection), the span whole key tiles, and the
    working set within ``_VMEM_LIMIT``."""
    dtype = jnp.dtype(dtype)
    if dtype not in (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16)):
        return False
    sub = max(_MIN_SUBLANES[dtype], _MIN_SUBLANES[jnp.dtype(jnp.int8)])
    return (d % _LANE == 0 and B % sub == 0 and span % _LANE == 0
            and Hq % Hkv == 0
            and sparse_attn_fits_vmem(B, Hq, Hkv, d, dtype.itemsize,
                                      sparse_attn_tile(span)))


def sparse_attn_walked(end, span, block):
    """Mean keys a block of the span ending at ``end`` walks: whole tiles up
    to its last query."""
    T = sparse_attn_tile(span)
    lasts = range(end - span + block - 1, end, block)
    return sum((t // T + 1) * T for t in lasts) // len(lasts)


# -- declared costs: the products actually walked; bytes of q, k, v, o (or
# their gradients), the mask and target once each ----------------------------
@register_cost("sparse_attn_pallas_fwd")
def cost_sparse_attn_fwd(rows, keys, hq, hkv, d, itemsize=2):
    """Scores twice (the second sweep) and the value product, over ``keys``
    walked; q and o, k and v, the int8 mask, target and the statistics."""
    return {"flops": 3 * 2 * rows * hq * d * keys,
            "bytes_accessed": (2 * rows * hq * d * itemsize
                               + 2 * keys * hkv * d * itemsize
                               + rows * keys * (1 + 4)
                               + rows * hq * _LANE * 4)}


@register_cost("sparse_attn_pallas_bwd")
def cost_sparse_attn_bwd(rows, keys, hq, hkv, d, itemsize=2):
    """Five products a (query, key, head): scores, ``do v``, ``dv``, ``dq``,
    ``dk``; q, do and dq, k, v, dk and dv, the mask, target, statistics."""
    return {"flops": 5 * 2 * rows * hq * d * keys,
            "bytes_accessed": (3 * rows * hq * d * itemsize
                               + 4 * keys * hkv * d * itemsize
                               + rows * keys * (1 + 4)
                               + rows * hq * _LANE * 4)}


def _dot(a, b, contract):
    return lax.dot_general(a, b, ((contract, ((), ()))),
                           preferred_element_type=jnp.float32)


def _masked(x, m_ref, g):
    """(g B, T) x the block's (B, T) mask, the same for every head."""
    B, T = m_ref.shape
    m = m_ref[...].astype(jnp.float32)
    return (x.reshape(g, B, T) * m[None]).reshape(g * B, T)


def _head_sum(p, g):
    """(g B, T) -> (B, T): the sum over the group's heads."""
    return jnp.sum(p.reshape(g, p.shape[0] // g, p.shape[1]), axis=0)


def _lane_chunks(x):
    """(R, T) -> (R, 128): the T / 128 lane tiles added up (no lane moves)."""
    out = x[:, :_LANE]
    for c in range(1, x.shape[1] // _LANE):
        out = out + x[:, c * _LANE:(c + 1) * _LANE]
    return out


def _add_target(tgt_ref, t, h, last_h, inv_heads):
    """The key tile's target block gathers the key-value heads' sums of
    probabilities, ``h`` the innermost grid axis: set, add, then the mean."""
    from jax.experimental import pallas as pl

    @pl.when(h == 0)
    def _():
        tgt_ref[...] = t

    @pl.when(h > 0)
    def _():
        tgt_ref[...] += t

    @pl.when(h == last_h)
    def _():
        tgt_ref[...] *= inv_heads


def _fwd_kernel(g, scale, inv_heads):
    from jax.experimental import pallas as pl

    def kern(nlive_ref, q_ref, kmax_ref, k_ref, v_ref, m_ref,
             o_ref, lse_ref, tgt_ref, acc_ref, z_ref, c_ref):
        ph, j, h = pl.program_id(0), pl.program_id(1), pl.program_id(2)
        last_j, last_h = pl.num_programs(1) - 1, pl.num_programs(2) - 1
        live = j < nlive_ref[0]

        @pl.when((ph == 0) & (j == 0))
        def _():
            acc_ref[h] = jnp.zeros(acc_ref.shape[1:], jnp.float32)
            z_ref[h] = jnp.zeros(z_ref.shape[1:], jnp.float32)
            # the row's shift, scaled: |q| max|k| / sqrt(d), on every lane
            qf = q_ref[h].astype(jnp.float32)
            c_ref[h] = jnp.broadcast_to(
                jnp.sqrt(jnp.sum(qf * qf, axis=-1, keepdims=True))
                * (kmax_ref[h][:, :1] * scale), c_ref.shape[1:])

        @pl.when((ph == 0) & live)
        def _():
            s = _dot(q_ref[h], k_ref[...], ((1,), (1,)))        # (R, T)
            e = _masked(jnp.exp(s * scale - c_ref[h][:, :1]), m_ref, g)
            z_ref[h] += _lane_chunks(e)
            acc_ref[h] += _dot(e.astype(v_ref.dtype), v_ref[...],
                               ((1,), (0,)))

        @pl.when((ph == 0) & (j == last_j))
        def _():
            z = jnp.sum(z_ref[h], axis=-1, keepdims=True)       # (R, 1)
            o_ref[h] = (acc_ref[h] / z).astype(o_ref.dtype)
            lse = c_ref[h] + jnp.log(z)
            c_ref[h] = lse
            lse_ref[h] = lse

        @pl.when((ph == 1) & live)
        def _():
            s = _dot(q_ref[h], k_ref[...], ((1,), (1,)))
            _add_target(tgt_ref, _head_sum(_masked(
                jnp.exp(s * scale - c_ref[h][:, :1]), m_ref, g), g),
                h, last_h, inv_heads)

        @pl.when((ph == 1) & jnp.logical_not(live) & (h == 0))
        def _():
            tgt_ref[...] = jnp.zeros(tgt_ref.shape, jnp.float32)

    return kern


def _bwd_kernel(g, scale, inv_heads):
    from jax.experimental import pallas as pl

    def kern(nlive_ref, q_ref, do_ref, st_ref, k_ref, v_ref, m_ref,
             dq_ref, dk_ref, dv_ref, tgt_ref, acc_ref):
        j, h = pl.program_id(0), pl.program_id(1)
        last_j, last_h = pl.num_programs(0) - 1, pl.num_programs(1) - 1
        live = j < nlive_ref[0]
        cdt = q_ref.dtype

        @pl.when(j == 0)
        def _():
            acc_ref[h] = jnp.zeros(acc_ref.shape[1:], jnp.float32)

        @pl.when(live)
        def _():
            q, do, k, v = q_ref[h], do_ref[h], k_ref[...], v_ref[...]
            st = st_ref[h]
            s = _dot(q, k, ((1,), (1,)))                        # (R, T)
            p = _masked(jnp.exp(s * scale - st[:, :1]), m_ref, g)
            _add_target(tgt_ref, _head_sum(p, g), h, last_h, inv_heads)
            dv_ref[...] = _dot(p.astype(cdt), do,
                               ((0,), (0,))).astype(dv_ref.dtype)
            # o = p v:  dp = do . v,  ds = p (dp - do . o) / sqrt(d)
            dp = _dot(do, v, ((1,), (1,)))
            ds = (p * (dp - st[:, 1:2]) * scale).astype(cdt)
            acc_ref[h] += _dot(ds, k, ((1,), (0,)))
            dk_ref[...] = _dot(ds, q, ((0,), (0,))).astype(dk_ref.dtype)

        @pl.when(jnp.logical_not(live))
        def _():
            dk_ref[...] = jnp.zeros(dk_ref.shape, dk_ref.dtype)
            dv_ref[...] = jnp.zeros(dv_ref.shape, dv_ref.dtype)

            @pl.when(h == 0)
            def _():
                tgt_ref[...] = jnp.zeros(tgt_ref.shape, jnp.float32)

        @pl.when(j == last_j)
        def _():
            dq_ref[h] = acc_ref[h].astype(dq_ref.dtype)

    return kern


def _params(interpret):
    from jax.experimental.pallas import tpu as pltpu

    if interpret:
        return {"interpret": True}
    return {"compiler_params": pltpu.CompilerParams(
        vmem_limit_bytes=_VMEM_LIMIT)}


def _shapes(q, k, mask, tile):
    Hkv, R, d = q.shape
    B, Sk = mask.shape
    if k.shape != (Sk, Hkv * d) or R % B or Sk % tile or tile % _LANE:
        raise ValueError("q %r, k %r, mask %r and tiles of %d keys do not "
                         "fit together" % (q.shape, k.shape, mask.shape, tile))
    return Hkv, R, d, B, Sk


def _whole(shape):
    """The array as one block, fetched once and resident over the grid."""
    from jax.experimental import pallas as pl

    return pl.BlockSpec(shape, lambda *_: (0,) * len(shape))


def _live_tile(j, nlive_ref):
    """Key tile ``j``, or the block's last live one: a repeated block index
    is not fetched again."""
    return jnp.minimum(j, nlive_ref[0] - 1)


def sparse_attn_fwd(q, k, v, mask, kmax, last, *, tile=_TILE, walked=None,
                    interpret=False):
    """One block's attention over its selected keys.

    ``q`` (Hkv, g B, d) (module docstring), ``k`` / ``v`` (Sk, Hkv d),
    ``mask`` (B, Sk) int8, 1 where the query reads the key, ``kmax`` (Hkv,)
    float32 ``max_s |k_s|`` of each key-value head, ``last`` () int32 the
    position of the block's last query: keys from the tile after it are
    neither fetched nor computed.  ``walked``: the keys a call walks, for the
    declared cost alone (default ``Sk``).
    -> o (Hkv, g B, d) in q's type; lse (Hkv, g B) float32, each row's shift
    plus the log of its weights' sum; target (B, Sk) float32, the mean over
    the heads of the probabilities (0 off the mask)."""
    Hkv, R, d, B, Sk = _shapes(q, k, mask, tile)
    _record_cost("sparse_attn_pallas_fwd", cost_sparse_attn_fwd(
        B, walked or Sk, Hkv * (R // B), Hkv, d, q.dtype.itemsize), q.shape)
    return _fwd_call(q, k, v, mask, kmax, last, tile=tile,
                     interpret=interpret)


# jitted: one trace of the kernel and one Mosaic lowering for each distinct
# shape (a span's), however often a step calls it; both are paid on every run
@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def _fwd_call(q, k, v, mask, kmax, last, *, tile, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    Hkv, R, d, B, Sk = _shapes(q, k, mask, tile)
    g, T = R // B, tile
    nlive = (last.astype(jnp.int32) // T + 1).reshape(1)
    kmax_b = jnp.broadcast_to(kmax.astype(jnp.float32)[:, None, None],
                              (Hkv, 1, _LANE))
    kv_spec = pl.BlockSpec((T, d), lambda ph, j, h, n: (_live_tile(j, n), h))
    o, lse, target = pl.pallas_call(
        _fwd_kernel(g, d ** -0.5, 1.0 / (Hkv * g)),
        out_shape=(jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((Hkv, R, _LANE), jnp.float32),
                   jax.ShapeDtypeStruct((B, Sk), jnp.float32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(2, Sk // T, Hkv),
            in_specs=[_whole(q.shape), _whole(kmax_b.shape), kv_spec, kv_spec,
                      pl.BlockSpec((B, T),
                                   lambda ph, j, h, n: (0, _live_tile(j, n)))],
            # the first sweep writes no target: its block stays tile 0,
            # which the second sweep's first steps fill before it moves on
            out_specs=(_whole(q.shape), _whole((Hkv, R, _LANE)),
                       pl.BlockSpec((B, T), lambda ph, j, h, n: (0, j * ph))),
            scratch_shapes=[pltpu.VMEM(q.shape, jnp.float32),
                            pltpu.VMEM((Hkv, R, _LANE), jnp.float32),
                            pltpu.VMEM((Hkv, R, _LANE), jnp.float32)]),
        name="sparse_attn_pallas_fwd",
        **_params(interpret),
    )(nlive, q, kmax_b, k, v, mask)
    return o, lse[..., 0], target


def sparse_attn_bwd(q, k, v, mask, lse, delta, do, last, *, tile=_TILE,
                    walked=None, interpret=False):
    """The block's gradients, its probabilities recomputed from ``lse``
    (:func:`sparse_attn_fwd`'s).  ``do`` (Hkv, g B, d) the output's
    cotangent in q's layout, ``delta`` (Hkv, g B) float32 ``sum_d do o``.
    -> dq (Hkv, g B, d), dk, dv (Sk, Hkv d) in q's type (zeros above the
    block's last query), target (B, Sk) float32 as the forward's."""
    Hkv, R, d, B, Sk = _shapes(q, k, mask, tile)
    _record_cost("sparse_attn_pallas_bwd", cost_sparse_attn_bwd(
        B, walked or Sk, Hkv * (R // B), Hkv, d, q.dtype.itemsize), q.shape)
    return _bwd_call(q, k, v, mask, lse, delta, do.astype(q.dtype), last,
                     tile=tile, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def _bwd_call(q, k, v, mask, lse, delta, do, last, *, tile, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    Hkv, R, d, B, Sk = _shapes(q, k, mask, tile)
    g, T = R // B, tile
    nlive = (last.astype(jnp.int32) // T + 1).reshape(1)
    # lane 0 the log-sum, lane 1 do . o: one lane-tiled array for both
    stats = jnp.concatenate(
        [lse[..., None], delta[..., None],
         jnp.zeros((Hkv, R, _LANE - 2), jnp.float32)], axis=-1)
    kv_in = pl.BlockSpec((T, d), lambda j, h, n: (_live_tile(j, n), h))
    kv_out = pl.BlockSpec((T, d), lambda j, h, n: (j, h))
    dq, dk, dv, target = pl.pallas_call(
        _bwd_kernel(g, d ** -0.5, 1.0 / (Hkv * g)),
        out_shape=(jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct((B, Sk), jnp.float32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(Sk // T, Hkv),
            in_specs=[_whole(q.shape), _whole(q.shape), _whole(stats.shape),
                      kv_in, kv_in,
                      pl.BlockSpec((B, T),
                                   lambda j, h, n: (0, _live_tile(j, n)))],
            out_specs=(_whole(q.shape), kv_out, kv_out,
                       pl.BlockSpec((B, T), lambda j, h, n: (0, j))),
            scratch_shapes=[pltpu.VMEM(q.shape, jnp.float32)]),
        name="sparse_attn_pallas_bwd",
        **_params(interpret),
    )(nlive, q, do, stats, k, v, mask)
    return dq, dk, dv, target
