"""Pallas TPU kernels for attention (``ops/transformer.py``): the selected-
key pair of ``IndexerSparseAttention`` (PR 34), and below it the dense causal
pair of ``CausalAttention`` / ``LatentAttention`` (PR 36).  Both run where a
step is lowered for a TPU at shapes their ``*_supported`` takes; the XLA walks
run everywhere else (a CPU, toy shapes) and are the kernels' oracles.

Selected keys.  The walk writes a block's unnormalised weights ``e`` and their
gradient ``ds``, (heads, block, keys), to HBM and reads them back some nine
times a layer.  Here they live in VMEM for one (key tile, key-value head):
``sparse_attn_pallas_fwd`` / ``_bwd`` walk a block of queries over tiles of
``_TILE`` keys; only ``(heads, rows, head_dim)``- and ``(block, keys)``-shaped
arrays cross.  ``q`` is ``(Hkv, g * B, d)``, row ``i * B + r`` the ``i``-th
query head of the group at query ``r``: a head group's scores are one ``(g B,
d) x (d, tile)`` product.  Keys and values are the operator's ``(keys, Hkv
d)`` view, the selection ``(B, keys)`` int8 (causal mask folded in).  Per-row
statistics cross lane-broadcast, ``(Hkv, g B, 128)`` float32.  The grid ends
a block's walk at the tile of its last query (``nlive``, scalar prefetch).
The forward sweeps the tiles twice: sums of ``e`` and ``e v``, then, every
row's sum known, ``target``, the heads' mean probabilities.

Dense causal.  ``causal_attn_pallas_fwd`` / ``_bwd`` take heads-major ``q``
(N, Hq, S, d), ``k`` (N, Hkv, S, d), ``v`` (N, Hkv, S, dv) and walk a table of
the live (query block, key tile) pairs of one (document, head): no step above
the diagonal, the mask formed from positions in VMEM on the diagonal's tiles
alone.  The forward is one sweep; a row's log-sum ``lse`` (N, Hq, S) float32
is all the backward needs besides q, k, v, do and ``do . o``.  The backward
holds a key tile while the query blocks under it pass: ``dk`` / ``dv`` sum in
float32 in VMEM, as does one head's whole ``dq``; five products a pair.
Both pairs shift the softmax by the walk's bound ``|q| max_s |k_s| d^-1/2``.
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


# -- dense causal attention (``CausalAttention`` / ``LatentAttention``) --------
# Query blocks and key tiles of one (document, query head).  The grid's last
# axis walks a TABLE of the live (block, tile) pairs (scalar prefetch): a tile
# wholly above a block's last query is no grid step at all.  Tiles change no
# result; the chip's sweep is in PERF.md section 6 (PR 36).
_CAUSAL_BQ = 1024
_CAUSAL_BK = 1024


def causal_attn_tiles(S):
    """(queries a block, keys a tile) for a sequence of ``S``: the tuned
    sizes, or the largest halves of them that divide ``S``."""
    fit = lambda top: next(  # noqa: E731
        (t for t in (top, top // 2, top // 4) if S % t == 0), _LANE)
    return fit(_CAUSAL_BQ), fit(_CAUSAL_BK)


def _lanes(d):
    """``d`` rounded up to whole lanes."""
    return -(-d // _LANE) * _LANE


def _pad_lanes(x):
    """The last axis zero-padded to whole lanes: a 192-wide contraction costs
    the MXU 256 either way, and the kernels run a tenth faster on operands
    that come padded than on ones Mosaic has to mask (PERF.md section 6,
    PR 36)."""
    d = x.shape[-1]
    return x if d % _LANE == 0 else jnp.pad(
        x, [(0, 0)] * (x.ndim - 1) + [(0, _lanes(d) - d)])


def causal_attn_vmem_bytes(S, d_qk, d_v, itemsize, tiles=None):
    """Estimated VMEM working set of the BACKWARD kernel, the larger: one
    (document, head)'s whole float32 ``dq`` and its two output buffers, the
    q / do / k / v / dk / dv blocks twice, the ``dk`` / ``dv`` accumulators
    and six (tile, block) float32 planes; lanes padded to 128."""
    bq, bk = tiles or causal_attn_tiles(S)
    dq, dv = _lanes(d_qk), _lanes(d_v)
    return (S * dq * (4 + 2 * itemsize)
            + 2 * itemsize * (bq * (dq + dv) + 2 * bk * (dq + dv))
            + 4 * bk * (dq + dv) + 6 * 4 * bq * bk + 2 * 8 * bq * 4)


def causal_attn_why_not(S, Hq, Hkv, d_qk, d_v, dtype):
    """Why the kernel pair does not take these shapes (they walk), or None:
    it takes float32 and bfloat16, values whose lanes are full, scores a
    multiple of 64 wide, query heads in whole groups a key head, sequences
    of whole 128-key tiles, and a working set within ``_VMEM_LIMIT``."""
    dtype = jnp.dtype(dtype)
    if dtype not in (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16)):
        return "compute type %s is neither float32 nor bfloat16" % dtype
    if d_v % _LANE:
        return "value heads of %d are not whole lanes of %d" % (d_v, _LANE)
    if d_qk % 64:
        return "score heads of %d are not a multiple of 64" % d_qk
    if Hq % Hkv:
        return "%d query heads do not group over %d key heads" % (Hq, Hkv)
    if S % _LANE:
        return "a sequence of %d is not whole tiles of %d keys" % (S, _LANE)
    need = causal_attn_vmem_bytes(S, d_qk, d_v, dtype.itemsize)
    if need > _VMEM_LIMIT:
        return ("a working set of %d MB is over the %d MB the kernels ask "
                "for" % (need >> 20, _VMEM_LIMIT >> 20))
    return None


def causal_attn_supported(S, Hq, Hkv, d_qk, d_v, dtype):
    return causal_attn_why_not(S, Hq, Hkv, d_qk, d_v, dtype) is None


def _causal_schedule(S, bq, bk, keys_outer):
    """The live (query block, key tile) pairs in the order a kernel walks
    them -> (blocks, tiles) int32 tables.  Live: the tile's first key is at
    or before the block's last query."""
    import numpy as np

    pairs = [(i, j) for i in range(S // bq) for j in range(S // bk)
             if j * bk <= i * bq + bq - 1]
    if keys_outer:
        pairs.sort(key=lambda p: (p[1], p[0]))
    qi, kj = np.asarray(pairs, np.int32).T
    return qi, kj


def causal_attn_walked(S, tiles=None):
    """(query, key) pairs one head of one document walks: whole live tiles."""
    bq, bk = tiles or causal_attn_tiles(S)
    return len(_causal_schedule(S, bq, bk, False)[0]) * bq * bk


@register_cost("causal_attn_pallas_fwd")
def cost_causal_attn_fwd(pairs, n, hq, hkv, s, d_qk, d_v, itemsize=2):
    """Two products a walked (query, key) pair of each of ``n hq`` heads:
    scores and values; q, k, v and o once, the rows' log-sums."""
    return {"flops": 2 * n * hq * pairs * (d_qk + d_v),
            "bytes_accessed": (n * s * itemsize * (hq * (d_qk + d_v)
                                                   + hkv * (d_qk + d_v))
                               + n * hq * s * 4)}


@register_cost("causal_attn_pallas_bwd")
def cost_causal_attn_bwd(pairs, n, hq, hkv, s, d_qk, d_v, itemsize=2):
    """Five products a pair: scores, ``do v``, ``dv``, ``dk``, ``dq``; q, k,
    v, do and the three gradients once, the rows' log-sums and ``do . o``."""
    return {"flops": 2 * n * hq * pairs * (3 * d_qk + 2 * d_v),
            "bytes_accessed": (n * s * itemsize * (hq * (2 * d_qk + d_v)
                                                   + 2 * hkv * (d_qk + d_v))
                               + 2 * n * hq * s * 4)}


def _causal_mask(x, i, j, bq, bk, keys_first):
    """Zero where the key's position is after the query's; ``x`` (bq, bk), or
    (bk, bq) with ``keys_first``.  Positions from the grid, formed in VMEM."""
    qd, kd = (1, 0) if keys_first else (0, 1)
    queries = i * bq + lax.broadcasted_iota(jnp.int32, x.shape, qd)
    keys = j * bk + lax.broadcasted_iota(jnp.int32, x.shape, kd)
    return jnp.where(keys <= queries, x, 0.0)


def _on_diagonal(step, i, j, bq, bk):
    """Run ``step(masked)``: masked only where the tile's last key is after
    the block's first query; the tiles below the diagonal form no mask."""
    from jax.experimental import pallas as pl

    diagonal = (j + 1) * bk - 1 > i * bq
    pl.when(diagonal)(lambda: step(True))
    pl.when(jnp.logical_not(diagonal))(lambda: step(False))


def _causal_fwd_kernel(scale, bq, bk):
    from jax.experimental import pallas as pl

    def kern(qi_ref, kj_ref, q_ref, kmax_ref, k_ref, v_ref, o_ref, lse_ref,
             acc_ref, z_ref, c_ref):
        t = pl.program_id(2)
        i, j = qi_ref[t], kj_ref[t]

        @pl.when(j == 0)
        def _():
            acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)
            z_ref[...] = jnp.zeros(z_ref.shape, jnp.float32)
            # the row's shift, scaled: |q| max|k| / sqrt(d), on every lane
            qf = q_ref[0, 0].astype(jnp.float32)
            c_ref[...] = jnp.broadcast_to(
                jnp.sqrt(jnp.sum(qf * qf, axis=-1, keepdims=True))
                * (kmax_ref[0, 0][:1, :1] * scale), c_ref.shape)

        def step(masked):
            s = _dot(q_ref[0, 0], k_ref[0, 0], ((1,), (1,)))    # (bq, bk)
            e = jnp.exp(s * scale - c_ref[:, :1])
            if masked:
                e = _causal_mask(e, i, j, bq, bk, False)
            z_ref[...] += _lane_chunks(e)
            acc_ref[...] += _dot(e.astype(v_ref.dtype), v_ref[0, 0],
                                 ((1,), (0,)))

        _on_diagonal(step, i, j, bq, bk)

        @pl.when(j == (i * bq + bq - 1) // bk)
        def _():
            z = jnp.sum(z_ref[...], axis=-1, keepdims=True)     # (bq, 1)
            o_ref[0, 0] = (acc_ref[...] / z).astype(o_ref.dtype)
            # a row's log-sum leaves as a ROW: the backward's planes have
            # the queries on the lanes
            lse_ref[0, 0] = (c_ref[...] + jnp.log(z)).T[:1]

    return kern


def _causal_bwd_kernel(scale, bq, bk):
    from jax.experimental import pallas as pl

    def kern(qi_ref, kj_ref, q_ref, do_ref, st_ref, k_ref, v_ref,
             dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc):
        t = pl.program_id(2)
        i, j = qi_ref[t], kj_ref[t]
        cdt = q_ref.dtype

        @pl.when(t == 0)
        def _():
            dq_acc[...] = jnp.zeros(dq_acc.shape, jnp.float32)

        @pl.when(i == j * bk // bq)
        def _():
            dk_acc[...] = jnp.zeros(dk_acc.shape, jnp.float32)
            dv_acc[...] = jnp.zeros(dv_acc.shape, jnp.float32)

        def step(masked):
            q, do, k, v = q_ref[0, 0], do_ref[0, 0], k_ref[0, 0], v_ref[0, 0]
            st = st_ref[0, 0]                       # rows: lse, do . o
            # the planes are (keys, queries): a query's statistics are a row
            # that broadcasts over the sublanes, and only dq's product has a
            # transposed operand
            s = _dot(k, q, ((1,), (1,)))                        # (bk, bq)
            p = jnp.exp(s * scale - st[0:1])
            if masked:
                p = _causal_mask(p, i, j, bq, bk, True)
            dv_acc[...] += _dot(p.astype(cdt), do, ((1,), (0,)))
            # o = p v:  dp = do . v,  ds = p (dp - do . o) / sqrt(d)
            dp = _dot(v, do, ((1,), (1,)))
            ds = (p * (dp - st[1:2]) * scale).astype(cdt)
            dk_acc[...] += _dot(ds, q, ((1,), (0,)))
            rows = pl.ds(pl.multiple_of(i * bq, bq), bq)
            dq_acc[rows, :] += _dot(ds, k, ((0,), (0,)))

        _on_diagonal(step, i, j, bq, bk)

        @pl.when(i == dq_acc.shape[0] // bq - 1)
        def _():
            dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
            dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)

        @pl.when(t == pl.num_programs(2) - 1)
        def _():
            dq_ref[0, 0] = dq_acc[...].astype(dq_ref.dtype)

    return kern


def _causal_shapes(q, k, v, tiles):
    N, Hq, S, d = q.shape
    Hkv, dv = k.shape[1], v.shape[3]
    bq, bk = tiles
    if (k.shape != (N, Hkv, S, d) or v.shape != (N, Hkv, S, dv) or Hq % Hkv
            or S % bq or S % bk or bq % _LANE or bk % _LANE):
        raise ValueError("q %r, k %r, v %r and tiles %r do not fit together"
                         % (q.shape, k.shape, v.shape, tiles))
    return N, Hq, Hkv, S, d, dv


def _head_block(rows, width, g=1, of_keys=False):
    """A (rows, width) block of a heads-major (N, H, S, width) array: the
    step's query block, or with ``of_keys`` its key tile, of head ``h // g``
    (``g`` > 1: the key head a query head's group shares)."""
    from jax.experimental import pallas as pl

    return pl.BlockSpec(
        (1, 1, rows, width),
        lambda n, h, t, qi, kj: (n, h // g, (kj if of_keys else qi)[t], 0))


def _causal_params(interpret):
    from jax.experimental.pallas import tpu as pltpu

    if interpret:
        return {"interpret": True}
    return {"compiler_params": pltpu.CompilerParams(
        vmem_limit_bytes=_VMEM_LIMIT,
        dimension_semantics=("parallel", "parallel", "arbitrary"))}


# jitted like the selected-key pair's calls: layers of one shape share one
# trace of the kernel (and one record of its cost) and one Mosaic lowering
@functools.partial(jax.jit, static_argnames=("tiles", "interpret"))
def causal_attn_fwd(q, k, v, kmax, *, tiles=None, interpret=False):
    """Dense causal attention, heads major: ``q`` (N, Hq, S, d), ``k`` (N,
    Hkv, S, d), ``v`` (N, Hkv, S, dv), ``kmax`` (N, Hkv) float32 ``max_s
    |k_s|`` of each document's key head.  Query t reads its own document's
    keys ``s <= t`` of key head ``h // (Hq / Hkv)``.
    -> o (N, Hq, S, dv) in q's type; lse (N, Hq, S) float32, each row's
    shift plus the log of its weights' sum."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    tiles = tiles or causal_attn_tiles(q.shape[2])
    N, Hq, Hkv, S, d, dv = _causal_shapes(q, k, v, tiles)
    _record_cost("causal_attn_pallas_fwd", cost_causal_attn_fwd(
        causal_attn_walked(S, tiles), N, Hq, Hkv, S, d, dv,
        q.dtype.itemsize), q.shape)
    g, (bq, bk) = Hq // Hkv, tiles
    scale = d ** -0.5
    q, k, d = _pad_lanes(q), _pad_lanes(k), _lanes(d)
    qi, kj = _causal_schedule(S, bq, bk, False)
    kmax_b = jnp.broadcast_to(kmax.astype(jnp.float32)[:, :, None, None],
                              (N, Hkv, 8, _LANE))
    o, lse = pl.pallas_call(
        _causal_fwd_kernel(scale, bq, bk),
        out_shape=(jax.ShapeDtypeStruct((N, Hq, S, dv), q.dtype),
                   jax.ShapeDtypeStruct((N, Hq, 1, S), jnp.float32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(N, Hq, len(qi)),
            in_specs=[_head_block(bq, d),
                      pl.BlockSpec((1, 1, 8, _LANE),
                                   lambda n, h, t, qi, kj: (n, h // g, 0, 0)),
                      _head_block(bk, d, g, True),
                      _head_block(bk, dv, g, True)],
            out_specs=(_head_block(bq, dv),
                       pl.BlockSpec((1, 1, 1, bq),
                                    lambda n, h, t, qi, kj: (n, h, 0, qi[t]))),
            scratch_shapes=[pltpu.VMEM((bq, dv), jnp.float32),
                            pltpu.VMEM((bq, _LANE), jnp.float32),
                            pltpu.VMEM((bq, _LANE), jnp.float32)]),
        name="causal_attn_pallas_fwd",
        **_causal_params(interpret),
    )(jnp.asarray(qi), jnp.asarray(kj), q, kmax_b, k, v)
    return o, lse[:, :, 0]


@functools.partial(jax.jit, static_argnames=("tiles", "interpret"))
def causal_attn_bwd(q, k, v, lse, delta, do, *, tiles=None, interpret=False):
    """The gradients, every block's probabilities recomputed from ``lse``
    (:func:`causal_attn_fwd`'s).  ``do`` (N, Hq, S, dv) the output's
    cotangent, ``delta`` (N, Hq, S) float32 ``sum_d do o``.  A key tile is
    resident while the query blocks under it pass: ``dk`` / ``dv`` are summed
    in float32 in VMEM and rounded once; ``dq`` of one (document, head) is
    whole in VMEM, float32, until its last tile.
    -> dq (N, Hq, S, d), dk (N, Hkv, S, d), dv (N, Hkv, S, dv) in q's type."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    tiles = tiles or causal_attn_tiles(q.shape[2])
    N, Hq, Hkv, S, d, dv = _causal_shapes(q, k, v, tiles)
    _record_cost("causal_attn_pallas_bwd", cost_causal_attn_bwd(
        causal_attn_walked(S, tiles), N, Hq, Hkv, S, d, dv,
        q.dtype.itemsize), q.shape)
    do = do.astype(q.dtype)
    g, (bq, bk) = Hq // Hkv, tiles
    d_qk, scale = d, d ** -0.5
    q, k, d = _pad_lanes(q), _pad_lanes(k), _lanes(d)
    qi, kj = _causal_schedule(S, bq, bk, True)
    stats = jnp.stack([lse, delta], axis=2)                  # (N, Hq, 2, S)
    # a key head's gradients gather its group's query heads: each head
    # writes its own float32 share, summed and rounded once outside
    kv_type = q.dtype if g == 1 else jnp.float32
    dq, dk, dv_ = pl.pallas_call(
        _causal_bwd_kernel(scale, bq, bk),
        out_shape=(jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((N, Hq, S, d), kv_type),
                   jax.ShapeDtypeStruct((N, Hq, S, dv), kv_type)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(N, Hq, len(qi)),
            in_specs=[_head_block(bq, d), _head_block(bq, dv),
                      pl.BlockSpec((1, 1, 2, bq),
                                   lambda n, h, t, qi, kj: (n, h, 0, qi[t])),
                      _head_block(bk, d, g, True),
                      _head_block(bk, dv, g, True)],
            out_specs=(pl.BlockSpec((1, 1, S, d),
                                    lambda n, h, t, qi, kj: (n, h, 0, 0)),
                       _head_block(bk, d, of_keys=True),
                       _head_block(bk, dv, of_keys=True)),
            scratch_shapes=[pltpu.VMEM((S, d), jnp.float32),
                            pltpu.VMEM((bk, d), jnp.float32),
                            pltpu.VMEM((bk, dv), jnp.float32)]),
        name="causal_attn_pallas_bwd",
        **_causal_params(interpret),
    )(jnp.asarray(qi), jnp.asarray(kj), q, do, stats, k, v)
    if g > 1:
        dk, dv_ = (x.reshape((N, Hkv, g) + x.shape[2:]).sum(2).astype(q.dtype)
                   for x in (dk, dv_))
    return dq[..., :d_qk], dk[..., :d_qk], dv_
