"""Pallas TPU kernels for the quantization hot path.

The int8/uint8 (de)quantize ops (ops/quantization.py, reference
``src/operator/quantization/quantize-inl.h``) are pure HBM-bandwidth ops:
read fp32, write int8 + two scalars. The jnp formulation lowers to several
XLA ops (abs, max-reduce, scale, clip, round, cast) that XLA usually fuses —
these Pallas versions make the single-pass structure explicit (one VMEM tile
in, one tile out, scalar range in SMEM) and serve as the template for
further kernels (pallas_guide.md "Quantization Kernels" pattern).

Used automatically by the quantize/dequantize ops on TPU for tile-aligned
inputs; the jnp path remains the fallback (CPU tests run it via
``interpret=True`` coverage here).
"""
from __future__ import annotations

import functools
import threading

import jax
import jax.numpy as jnp

__all__ = ["quantize_int8_pallas", "dequantize_int8_pallas", "supported",
           "nms_alive_pallas", "dconv_col_pallas",
           "register_cost", "cost_fns", "registered_custom_calls",
           "traced_costs", "reset_traced_costs"]

_LANE = 128
# minimum sublane count per dtype (pallas_guide.md tiling constraints)
_MIN_SUBLANES = {jnp.dtype(jnp.float32): 8, jnp.dtype(jnp.bfloat16): 16,
                 jnp.dtype(jnp.int8): 32}


def _vmem_limit():
    """The shared per-grid-step VMEM budget every ``*_fits_vmem`` guard
    judges against: ``MXNET_DCONV_VMEM_MB`` when set positive, else the
    calibrated ``_DCONV_VMEM_LIMIT`` (defined with its calibration notes
    at the dconv section below)."""
    import os

    try:
        limit = int(float(os.environ.get("MXNET_DCONV_VMEM_MB", 0))
                    * (1 << 20))
    except ValueError:
        limit = 0
    return limit if limit > 0 else _DCONV_VMEM_LIMIT


# ---------------------------------------------------------------------------
# Custom-call cost registry (ISSUE 1 observability)
# ---------------------------------------------------------------------------
#
# XLA cost analysis sees a pallas_call as a zero-FLOP black box, which is
# what broke the roofline certification in VERDICT round 5.  Each kernel
# here DECLARES its per-invocation FLOPs and HBM bytes as a function of the
# concrete shapes (flops: useful arithmetic, not MXU-padded; bytes: HBM
# traffic only — VMEM-resident intermediates, the whole point of these
# kernels, are excluded).  The impl functions record the evaluated cost at
# TRACE time (shapes are concrete inside jit tracing; zero runtime
# overhead), profiler dumps embed the table as a "custom_call_costs"
# metadata event, and tools/trace_summary.py merges it with per-op device
# times into the roofline table.

_cost_mu = threading.Lock()
_COST_FNS = {}    # name -> {"fn": shape-cost fn, "aliases": (substr, ...)}
_TRACED = {}      # name -> {"flops", "bytes_accessed", "calls", "shape"}


def register_cost(name, aliases=()):
    """Decorator: register ``fn(**shape kwargs) -> {"flops", "bytes_accessed"}``
    as the declared cost model for custom-call ``name``.  ``aliases`` are
    extra substrings trace_summary may see in device-trace op names."""
    def deco(fn):
        with _cost_mu:
            _COST_FNS[name] = {"fn": fn, "aliases": tuple(aliases)}
        return fn
    return deco


def cost_fns():
    """name -> cost fn for every registered custom call."""
    with _cost_mu:
        return {k: v["fn"] for k, v in _COST_FNS.items()}


def registered_custom_calls():
    """→ {name: (alias, ...)} for trace_summary's matcher."""
    with _cost_mu:
        return {k: v["aliases"] for k, v in _COST_FNS.items()}


def traced_costs():
    """Costs recorded at trace time since import (or the last reset):
    name -> {"flops", "bytes_accessed", "calls", "shapes", "shape"}.

    flops/bytes are PER INVOCATION; when a kernel traced at several shapes
    ("shapes" > 1) they are the mean over the traced invocations — a device
    trace's events carry no shapes, so the mean is the unbiased price per
    call (last-shape-wins would misprice every other shape)."""
    with _cost_mu:
        out = {}
        for name, ent in _TRACED.items():
            calls = max(ent["calls"], 1)
            out[name] = {"flops": ent["flops_sum"] // calls,
                         "bytes_accessed": ent["bytes_sum"] // calls,
                         "calls": ent["calls"],
                         "shapes": len(ent["per_shape"]),
                         "shape": ent["shape"]}
        return out


def reset_traced_costs():
    with _cost_mu:
        _TRACED.clear()


def _record_cost(name, cost, shape):
    """Called from the kernel impls while tracing — accumulate the table and
    mirror it into the telemetry event stream when that is enabled."""
    with _cost_mu:
        ent = _TRACED.setdefault(
            name, {"flops_sum": 0, "bytes_sum": 0, "calls": 0,
                   "per_shape": {}, "shape": None})
        ent["flops_sum"] += int(cost["flops"])
        ent["bytes_sum"] += int(cost["bytes_accessed"])
        ent["shape"] = list(shape)
        ent["calls"] += 1
        ent["per_shape"][str(tuple(shape))] = ent["per_shape"].get(
            str(tuple(shape)), 0) + 1
    from .. import telemetry

    if telemetry.enabled():
        telemetry.event("custom_call_cost", name=name, shape=list(shape),
                        **{k: int(cost[k]) for k in ("flops", "bytes_accessed")})


def _prod(shape):
    n = 1
    for s in shape:
        n *= int(s)
    return n


@register_cost("quantize_int8_pallas", aliases=("quantize_int8", "_q_kernel"))
def cost_quantize_int8(shape):
    n = _prod(shape)
    # sign/abs/mul/add/min per element; fp32 in, int8 out, scalar scale
    return {"flops": 5 * n, "bytes_accessed": 4 * n + n + 4}


@register_cost("dequantize_int8_pallas",
               aliases=("dequantize_int8", "_dq_kernel"))
def cost_dequantize_int8(shape):
    n = _prod(shape)
    return {"flops": 2 * n, "bytes_accessed": n + 4 * n + 4}


@register_cost("nms_alive_pallas", aliases=("nms_alive", "_nms_kernel"))
def cost_nms_alive(batch, n_boxes):
    T = _NMS_TILE
    nb = max(1, -(-int(n_boxes) // T))
    np_ = nb * T
    # each (settle, sweep) tile pair: a TxT IoU build (~16 flop/pair) plus
    # one (1,T)x(T,T) suppression matmul (2 flop MAC); fixed-point repeats
    # of the settle matmul are data-dependent and not declared
    pair_tiles = int(batch) * nb * (nb + 1) // 2
    flops = pair_tiles * T * T * 18
    # cols (8, Np) + colst (Np, 8) fp32 in, alive (1, Np) fp32 out, per image
    bytes_accessed = int(batch) * (2 * 8 * np_ * 4 + np_ * 4)
    return {"flops": flops, "bytes_accessed": bytes_accessed}


@register_cost("dconv_col_pallas_fwd",
               aliases=("dconv_col", "dconv_fwd_kernel"))
def cost_dconv_col_fwd(bg, n, hw, c, ft_itemsize=4):
    """FLOPs of the kernel at a band of the whole map, an UPPER BOUND since
    PR 27: the kernel contracts each row block over the chunks its samples
    touch, a share ``dconv_band_share`` of these (0.15 at the R-FCN cell's
    offsets), and how many is data, not shape.  Bytes are the operator's own
    and do not depend on the band."""
    # A build (~10 elementwise flops per A element) + col = A @ ft; A stays
    # in VMEM so its HW*N footprint never counts as bytes_accessed
    flops = 2 * bg * n * hw * c + 10 * bg * n * hw
    bytes_accessed = (7 * bg * n * 4                 # y0..lf factor rows
                      + bg * hw * c * ft_itemsize    # ft in
                      + bg * n * c * ft_itemsize)    # col out
    return {"flops": flops, "bytes_accessed": bytes_accessed}


@register_cost("dconv_col_pallas_bwd", aliases=("dconv_bwd_kernel",))
def cost_dconv_col_bwd(bg, n, hw, c, ft_itemsize=4):
    """As ``cost_dconv_col_fwd``: FLOPs at a band of the whole map, the
    upper bound; bytes do not depend on the band."""
    # dA = g @ ft^T and dft += A^T @ g (2 MXU dots) + the four masked corner
    # sums over dA (~12 flops per A element); dA also VMEM-resident
    flops = 4 * bg * n * hw * c + 12 * bg * n * hw
    bytes_accessed = (7 * bg * n * 4
                      + bg * hw * c * ft_itemsize    # ft in
                      + bg * n * c * ft_itemsize     # g in
                      + 3 * bg * n * 4               # dly/dlx/dlf out
                      + bg * hw * c * 4)             # dft out (f32)
    return {"flops": flops, "bytes_accessed": bytes_accessed}


def supported(shape, dtype):
    """Tile-aligned 2D-reshapeable arrays of a pallas-kernel dtype on TPU."""
    sub = _MIN_SUBLANES.get(jnp.dtype(dtype))
    if sub is None:
        return False
    n = 1
    for s in shape:
        n *= int(s)
    return n >= sub * _LANE and n % (sub * _LANE) == 0


# Mosaic kernels cannot be partitioned by GSPMD: under a jit whose arrays are
# sharded over several chips the lowering raises "Mosaic kernels cannot be
# automatically partitioned. Please wrap the call in a shard_map" (chip run,
# PR 21: the detection phase of dryrun_multichip(4)).  The batched kernels
# below are independent per leading-axis row, so where the trace can see a
# mesh with the data-parallel axis (``with jax.set_mesh(mesh):`` around the
# jit) they run per shard of it.  Without a visible mesh the call stays
# plain: fine on one chip, and on several it fails with jax's message.
_BATCH_AXIS = "dp"


def _per_batch_shard(fn, *args):
    """``fn(*args)``; every array argument and result has the same leading
    batch axis."""
    from jax.sharding import PartitionSpec as P

    mesh = jax.sharding.get_abstract_mesh()
    n = mesh.shape.get(_BATCH_AXIS, 1)
    if n == 1 or _BATCH_AXIS in mesh.manual_axes or args[0].shape[0] % n:
        return fn(*args)
    spec = P(_BATCH_AXIS)
    return jax.shard_map(fn, in_specs=spec, out_specs=spec,
                         check_vma=False)(*args)


def _q_kernel(x_ref, scale_ref, out_ref):
    """Symmetric int8: q = sign(x) * min(|x|*127/range + 0.5, 127)
    (reference quantize-inl.h:70-80)."""
    scale = scale_ref[0]
    x = x_ref[:]
    q = jnp.sign(x) * jnp.minimum(jnp.abs(x) * scale + 0.5, 127.0)
    out_ref[:] = q.astype(jnp.int8)


def _dq_kernel(q_ref, scale_ref, out_ref):
    out_ref[:] = q_ref[:].astype(jnp.float32) * scale_ref[0]


def quant_vmem_bytes(block, in_itemsize, out_itemsize):
    """Estimated per-grid-step VMEM working set of one tiled elementwise
    int8 kernel: the (block, 128) input and output tiles (the SMEM scalar
    is noise).  Shares dconv's calibrated 24 MB budget."""
    return block * _LANE * (int(in_itemsize) + int(out_itemsize))


def quant_fits_vmem(block, in_itemsize, out_itemsize):
    """True when a candidate row block fits the shared VMEM budget —
    the autotuner's admission guard for the quantize/dequantize spaces
    (ISSUE 18), same idiom as ``dconv_fits_vmem``."""
    return quant_vmem_bytes(block, in_itemsize, out_itemsize) \
        <= _vmem_limit()


def _quant_block(kernel, rows, in_itemsize, out_itemsize):
    """Row-block size for one tiled-elementwise problem (trace time only,
    same adoption idiom as ``_dconv_grid``): the hand-tuned default is
    ``min(rows, 512)``; with ``MXNET_AUTOTUNE`` set a persisted winner for
    this (device kind, shape signature) overrides it, re-validated against
    the VMEM guard at adoption time.  Gate unset = one env read and the
    shipped constant, byte-identical (tested)."""
    block = min(rows, 512)
    from ..base import env_flag

    if kernel is not None and env_flag("MXNET_AUTOTUNE"):
        from .. import autotune

        cfg = autotune.config_for(
            kernel, autotune.quant_shape_sig(rows, in_itemsize))
        if cfg:
            try:
                adopted = int(cfg["block"])
            except (KeyError, TypeError, ValueError):
                adopted = None  # malformed winner: keep the default
            if adopted is not None and adopted > 0 and quant_fits_vmem(
                    min(adopted, rows), in_itemsize, out_itemsize):
                block = min(adopted, rows)
    return max(1, block)


def _tiled_elementwise(kernel, x, scale, out_dtype, interpret, name=None):
    """Shared scaffolding: flatten to (rows, 128) tiles, grid over row
    blocks, scalar in SMEM — the template for further elementwise kernels.
    ``name`` keys the autotuned row-block lookup (None = the constant) and
    names the kernel in the compiled module and the device trace."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    shape = x.shape
    flat = x.reshape(-1, _LANE)
    rows = flat.shape[0]
    block = _quant_block(name, rows, jnp.dtype(x.dtype).itemsize,
                         jnp.dtype(out_dtype).itemsize)
    # normalize any adopted value to a divisor of rows: the kernel is
    # elementwise, so halving only changes the grid, never the values
    while rows % block:
        block //= 2
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(flat.shape, out_dtype),
        grid=(rows // block,),
        in_specs=[
            pl.BlockSpec((block, _LANE), lambda i: (i, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((block, _LANE), lambda i: (i, 0)),
        interpret=interpret,
        name=name,
    )(flat, scale)
    return out.reshape(shape)


@functools.partial(jax.jit, static_argnames=("interpret",))
def quantize_int8_pallas(x, real_range, interpret=False):
    """x: fp32 (any tile-aligned shape); real_range: scalar max-abs.
    Returns int8 of the same shape."""
    _record_cost("quantize_int8_pallas", cost_quantize_int8(x.shape), x.shape)
    scale = (127.0 / real_range).reshape(1).astype(jnp.float32)
    return _tiled_elementwise(_q_kernel, x, scale, jnp.int8, interpret,
                              name="quantize_int8_pallas")


@functools.partial(jax.jit, static_argnames=("interpret",))
def dequantize_int8_pallas(q, real_range, interpret=False):
    """Inverse of quantize_int8_pallas."""
    _record_cost("dequantize_int8_pallas", cost_dequantize_int8(q.shape),
                 q.shape)
    scale = (real_range / 127.0).reshape(1).astype(jnp.float32)
    return _tiled_elementwise(_dq_kernel, q, scale, jnp.float32, interpret,
                              name="dequantize_int8_pallas")


# ---------------------------------------------------------------------------
# Blocked greedy NMS (north-star hot kernel, VERDICT r2 item 3)
# ---------------------------------------------------------------------------

_NMS_TILE = 256  # multiple of 128 so every lane-dim slice below is aligned


def nms_vmem_bytes(N, tile=_NMS_TILE):
    """Estimated per-grid-step VMEM working set of the blocked NMS kernel
    (all f32): the whole per-image cols block (8, Np) + alive row (Np),
    the transposed tile block whose lane dim pads 8→128, and ~3 (T, T)
    IoU/suppression planes live across the fixed-point iteration.
    Deliberately overcounts (Mosaic fuses several) — same calibration
    stance as ``dconv_bwd_vmem_bytes`` against the shared 24 MB budget."""
    tile = int(tile)
    np_ = max(1, -(-int(N) // tile)) * tile
    return 4 * (9 * np_ + tile * _LANE + 3 * tile * tile)


def nms_fits_vmem(N, tile=_NMS_TILE):
    """True when a candidate box-tile size fits the shared VMEM budget —
    the autotuner's admission guard for the ``nms_alive_pallas`` space
    (ISSUE 18) and the adoption-time re-check in :func:`_nms_tile`."""
    return nms_vmem_bytes(N, tile=tile) <= _vmem_limit()


def _nms_tile(B, N):
    """Box-tile size for one NMS problem (trace time only, the
    ``_dconv_grid`` adoption idiom): hand-tuned ``_NMS_TILE`` unless
    ``MXNET_AUTOTUNE`` is set and the store holds a winner for this
    (device kind, B×N signature) — which must still be lane-aligned and
    re-pass the VMEM guard under the CURRENT budget, else the default
    stays.  Gate unset = one env read, byte-identical (tested)."""
    tile = _NMS_TILE
    from ..base import env_flag

    if env_flag("MXNET_AUTOTUNE"):
        from .. import autotune

        cfg = autotune.config_for("nms_alive_pallas",
                                  autotune.nms_shape_sig(B, N))
        if cfg:
            try:
                adopted = int(cfg["tile"])
            except (KeyError, TypeError, ValueError):
                adopted = None  # malformed winner: keep the default
            if adopted is not None and adopted >= _LANE \
                    and adopted % _LANE == 0 \
                    and nms_fits_vmem(N, tile=adopted):
                tile = adopted
    return tile


def _nms_kernel_factory(nb, thresh, plus_one, use_ids, tile=_NMS_TILE):
    """Build the kernel body for ``nb`` tiles of ``tile`` boxes.

    Same greedy semantics as ops/detection.py ``_nms_alive_blocked``
    (reference multi_proposal.cc:221-273): grid step (b, k) settles image
    b's tile k's survivor set by fixed-point iteration over the intra-tile
    suppression map, then sweeps the settled survivors over every LATER
    tile.  The image's whole alive vector lives in VMEM across the
    sequential inner grid; the "does any earlier survivor hit me"
    reductions run as (1,T)x(T,T) matmuls on the MXU instead of
    broadcast+reduce chains on the VPU.
    """
    import jax.experimental.pallas as pl

    T = int(tile)

    def iou2d(cx1, cy1, cx2, cy2, car, rx1, ry1, rx2, ry2, rar):
        """(T,1) column boxes vs (1,S) row boxes -> (T,S) IoU."""
        w = jnp.maximum(jnp.minimum(cx2, rx2) - jnp.maximum(cx1, rx1)
                        + plus_one, 0.0)
        h = jnp.maximum(jnp.minimum(cy2, ry2) - jnp.maximum(cy1, ry1)
                        + plus_one, 0.0)
        inter = w * h
        union = car + rar - inter
        return jnp.where(union <= 0.0, 0.0, inter / jnp.maximum(union, 1e-12))

    def kernel(cols_ref, colst_ref, alive_ref):
        # blocks: cols (1, 8, Np) and alive (1, 1, Np) span one whole image;
        # colst (1, T, 8) is just the CURRENT tile in column layout — its
        # lane dim pads 8->128, so keeping all Np rows resident would cost
        # Np*128*4 bytes of VMEM (12 MB at SSD-512's 24.5k anchors)
        k = pl.program_id(1)

        @pl.when(k == 0)
        def _():
            alive_ref[0, 0:1, :] = cols_ref[0, 5:6, :]

        off = k * T
        # tile boxes, column layout (T,1) from the transposed tile block
        tc = [colst_ref[0, :, i:i + 1] for i in range(5)]
        # tile boxes, row layout (1,T)
        tr = [cols_ref[0, i:i + 1, pl.ds(off, T)] for i in range(5)]
        ta = alive_ref[0, 0:1, pl.ds(off, T)]  # incl. earlier tiles' kills

        sup = iou2d(*tc, *tr) > thresh
        if use_ids:
            tidc = colst_ref[0, :, 6:7]
            sup = sup & (tidc == cols_ref[0, 6:7, pl.ds(off, T)])
        lt = (jax.lax.broadcasted_iota(jnp.int32, (T, T), 0)
              < jax.lax.broadcasted_iota(jnp.int32, (T, T), 1))
        supf = jnp.where(sup & lt, 1.0, 0.0)  # sup[j,i]: j kills later i

        def killed(cur):  # (1,T) 0/1 -> (1,T) 0/1
            hits = jax.lax.dot_general(
                cur, supf, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return jnp.where(hits > 0.0, 1.0, 0.0)

        # fixed point of cur = ta & ~killed(cur); unique greedy survivor set
        first = ta * (1.0 - killed(ta))

        def w_cond(st):
            return jnp.any(st[0] != st[1])

        def w_body(st):
            _, cur = st
            return cur, ta * (1.0 - killed(cur))

        _, cur = jax.lax.while_loop(w_cond, w_body, (ta, first))
        alive_ref[0, 0:1, pl.ds(off, T)] = cur

        # settled survivors kill overlapping boxes in every later tile
        def sweep(c, carry):
            coff = c * T
            cr = [cols_ref[0, i:i + 1, pl.ds(coff, T)] for i in range(5)]
            m = iou2d(*tc, *cr) > thresh
            if use_ids:
                m = m & (tidc == cols_ref[0, 6:7, pl.ds(coff, T)])
            hit = jax.lax.dot_general(
                cur, jnp.where(m, 1.0, 0.0), (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            a = alive_ref[0, 0:1, pl.ds(coff, T)]
            alive_ref[0, 0:1, pl.ds(coff, T)] = a * jnp.where(
                hit > 0.0, 0.0, 1.0)
            return carry

        jax.lax.fori_loop(k + 1, nb, sweep, 0)

    return kernel


def _nms_pallas_batched(boxes, valid, idv, thresh, plus_one, use_ids,
                        interpret):
    """boxes (B,N,4) f32, valid (B,N) bool, idv (B,N) f32 -> alive (B,N)."""
    return _per_batch_shard(
        lambda b, v, i: _nms_call(b, v, i, thresh, plus_one, use_ids,
                                  interpret), boxes, valid, idv)


def _nms_call(boxes, valid, idv, thresh, plus_one, use_ids, interpret):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, N = boxes.shape[:2]
    _record_cost("nms_alive_pallas", cost_nms_alive(B, N), boxes.shape)
    T = _nms_tile(B, N)
    nb = max(1, -(-N // T))
    Np = nb * T
    f32 = jnp.float32
    b = boxes.astype(f32)
    x1, y1, x2, y2 = b[..., 0], b[..., 1], b[..., 2], b[..., 3]
    area = jnp.maximum(x2 - x1 + plus_one, 0.0) * jnp.maximum(
        y2 - y1 + plus_one, 0.0)
    cols = jnp.stack([x1, y1, x2, y2, area, valid.astype(f32),
                      idv.astype(f32), jnp.zeros((B, N), f32)], axis=1)
    cols = jnp.pad(cols, ((0, 0), (0, 0), (0, Np - N)))  # pads are dead
    colst = jnp.swapaxes(cols, 1, 2)                     # (B, Np, 8)

    alive = pl.pallas_call(
        _nms_kernel_factory(nb, float(thresh), float(plus_one), use_ids,
                            tile=T),
        out_shape=jax.ShapeDtypeStruct((B, 1, Np), f32),
        grid=(B, nb),
        in_specs=[
            pl.BlockSpec((1, 8, Np), lambda b, k: (b, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, T, 8), lambda b, k: (b, k, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, 1, Np), lambda b, k: (b, 0, 0),
                               memory_space=pltpu.VMEM),
        interpret=interpret,
        name="nms_alive_pallas",
    )(cols, colst)
    return alive[:, 0, :N] > 0.0


@functools.lru_cache(maxsize=64)  # keyed on per-call threshold: keep bounded
def _nms_single(thresh, plus_one, use_ids, interpret):
    """Single-image entry with a custom vmap rule: a vmapped call lands on
    the natively-batched (B, nb) grid instead of pallas' generic batching
    (which would prepend a grid axis and silently shift ``program_id``)."""

    @jax.custom_batching.custom_vmap
    def f(boxes, valid, idv):
        return _nms_pallas_batched(boxes[None], valid[None], idv[None],
                                   thresh, plus_one, use_ids, interpret)[0]

    @f.def_vmap
    def _rule(axis_size, in_batched, boxes, valid, idv):
        def bc(x, batched):
            return x if batched else jnp.broadcast_to(
                x[None], (axis_size,) + x.shape)

        out = _nms_pallas_batched(
            bc(boxes, in_batched[0]), bc(valid, in_batched[1]),
            bc(idv, in_batched[2]), thresh, plus_one, use_ids, interpret)
        return out, True

    # custom_vmap has no JVP rule; the survivor mask is piecewise-constant
    # in the boxes (zero derivative a.e. — the XLA path's bool output is
    # equally non-differentiable), so declare a symbolic-zero tangent.
    @jax.custom_jvp
    def g(boxes, valid, idv):
        return f(boxes, valid, idv)

    @g.defjvp
    def _jvp(primals, tangents):
        import numpy as _np

        out = f(*primals)
        return out, _np.zeros(out.shape, jax.dtypes.float0)

    return g


def nms_alive_pallas(boxes, valid, ids, *, thresh, plus_one=1.0,
                     force_suppress=True, interpret=False):
    """Greedy-NMS survivor mask over score-ordered (N,4) boxes — Pallas.

    Drop-in for ops/detection.py ``_nms_alive_blocked`` (same fixed-point
    blocked restructuring of reference multi_proposal.cc:221-273; see the
    measured head-to-head in docs/PERF_NOTES.md).  ``valid`` is a bool (N,)
    mask of initially-live rows (pass all-ones for none); ``ids`` with
    ``force_suppress=False`` restricts suppression to equal-id pairs
    (box_nms / MultiBoxDetection per-class mode).  vmap lands on a
    natively-batched (B, tiles) grid.  Returns bool (N,).
    """
    N = boxes.shape[0]
    use_ids = (ids is not None) and (not force_suppress)
    idv = ids.astype(jnp.float32) if use_ids else jnp.zeros((N,), jnp.float32)
    f = _nms_single(float(thresh), float(plus_one), use_ids, bool(interpret))
    return f(jax.lax.stop_gradient(boxes.astype(jnp.float32)),
             valid, idv)


# ---------------------------------------------------------------------------
# Fused deformable-conv sampling matmul (round-5 north-star kernel)
# ---------------------------------------------------------------------------
#
# The deformable conv's one-hot path materializes, per (image, group), a
# sample matrix A[n, p] over the flat feature axis p = h*W + w (bf16, ~106 MB
# at north-star shapes) and feeds it to ``col = A @ feat``; AD then
# materializes dA in f32 (~213 MB).  This kernel pair keeps A (and dA, in
# the backward) in VMEM: the one-hot entries are rebuilt per block from the
# integer/lerp inputs with iota compares (no gather, no reshape) and the
# contraction runs on the MXU.
#
# Each row of A has four non-zero entries, the bilinear corners, and the
# rows arrive tap-major: one block of ``nblk`` consecutive rows is a few
# output rows of one tap, so all its corners fall in a few feature rows.
# The contraction is therefore BAND-LIMITED (PR 27): the flat axis is cut
# into chunks of ``_DCONV_CHUNK`` positions, XLA computes per (bg, row
# block) the first and last chunk any corner of the block falls in
# (``_dconv_band``, from y0/y1), the two small int32 arrays reach the kernel
# by scalar prefetch (SMEM), and the kernel contracts over ``lo..hi`` only,
# ``_DCONV_STEP`` chunks a loop step.  The chunks left out hold exact zeros
# of A, so nothing changes in the sums.  A block whose band is more than
# half the map (large offsets, a block that straddles two taps) contracts
# over all of it in one step, which is the dense form this replaced and
# costs what that did (chip run, PR 27: PERF.md section 6).
#
# The block of A is built TRANSPOSED, positions on sublanes and samples on
# lanes: the per-sample inputs are stored lane-dense, so comparing a sublane
# iota against them needs no relayout, and the masked reductions of the
# backward run down the sublanes (plain vector adds).
#
#   A_T[p, n] = sum over the corners k of  w_k[n] * (p == f_k[n])
#     f_k = y*W + x of corner k,  w_k = its lerp weight times lf
#     (corners that coincide at the last row / column add their weights)
# Forward:   col^T = sum over the band's steps of  ft^T[:, step] @ A_T[step]
# Backward:  dA_T[step] = ft[step] @ g^T stays in VMEM; the four corner
#   values of dA are masked column sums of it, and d_ly / d_lx / d_lf are
#   their lerp combinations; d_ft[step] += A_T[step] @ g for the band's
#   steps only (the accumulator is zeroed once per bg).
#
# The columns cross the kernel boundary CHANNELS-MAJOR (PR 32): the forward
# writes its ``(C, nblk)`` accumulator as it is into ``col^T (BG, C, n_pad)``
# and the backward reads ``g^T`` in ``(C, nblk)`` blocks, samples on the
# lanes on both sides, so the operator's ``(B, C, K2, Ho, Wo)`` columns are a
# reshape of what the kernels write and read and no columns-sized array is
# transposed anywhere (on the chip that reshape is still a copy: the taps
# move from the lanes to the sublanes of a tiled array, PERF.md section 7
# row 18).  The features come in as ``ft^T (BG, C, HW)``, the data's own
# view: the forward reads that; the backward contracts over ``ft (HW, C)``
# and accumulates ``d_ft (HW, C)``, which XLA transposes on the way in and
# out (features-sized: a ninth of the columns under a 3x3 kernel).

_DCONV_NBLK = 128
# the band's unit: positions of the flat feature axis per chunk (the 128
# lanes), and the chunks one step of the kernels' loop contracts over.  The
# loop starts at the band's first chunk, wherever that is, so a band of up
# to ``_DCONV_STEP`` chunks is one step; the map is padded inside the
# wrapper so that the last step of any band stays inside it.  3 is the
# band of two output rows of a 64-wide map under offsets below one cell;
# the chip's sweep of both constants is in PERF.md section 6 (PR 27)
_DCONV_CHUNK = 128
_DCONV_STEP = 3

# Mosaic hard-fails when one grid step's working set exceeds its scoped VMEM
# limit.  The estimate below intentionally OVERCOUNTS (it sums seven planes
# as if simultaneously resident; Mosaic fuses several), and the limit is a
# number that worked, not one the compiler is given: no pallas_call here
# passes ``vmem_limit_bytes``.  On jaxlib 0.9.0 / libtpu 0.0.34, TPU v5 lite
# (chip run, PR 21), north-star res5 (HW=2432, cpg=128: 10.2 MB bf16) and
# cpg=512 (15.8 MB) compile and run — and so did a conv4-scale map (HW=9728,
# cpg=64) scoring 36.9 MB.  So 24 MB is conservative here: shapes between
# it and the real limit take the XLA scan though the kernel would build
# (ROADMAP A2c ties guard and compiler to one number).
_DCONV_VMEM_LIMIT = 24 << 20


def _dconv_chunks(HW):
    return -(-HW // _DCONV_CHUNK)


def _dconv_hw_pad(HW):
    return (_dconv_chunks(HW) + _DCONV_STEP - 1) * _DCONV_CHUNK


def dconv_bwd_vmem_bytes(HW, C, itemsize, nblk=_DCONV_NBLK):
    """Estimated per-grid-step VMEM working set of the dconv BACKWARD kernel
    (the larger of the two passes) at its widest, a band of the whole
    (padded) map in one step: dA_T, A_T and the four corner masks with
    their selects (seven f32 ``(HW, nblk)`` planes), the ft block and the
    f32 dft accumulator (``(HW, C)``), and the g^T block (``(C, nblk)``).  A
    narrow band needs ``_DCONV_STEP`` chunks of the planes only, but which
    a block gets is data.  Drives the auto-branch guard in ``detection.py
    deformable_convolution`` — above ``_DCONV_VMEM_LIMIT`` (override:
    MXNET_DCONV_VMEM_MB) large feature maps take the XLA scan instead of
    risking a hard Mosaic failure (ADVICE round 5)."""
    HW = _dconv_hw_pad(HW)
    return (7 * 4 * nblk * HW          # dA_T + A_T + masks and selects, f32
            + HW * C * (itemsize + 4)  # ft block + f32 dft accumulator
            + nblk * C * (itemsize + 4))  # g^T block + col^T block


def dconv_fits_vmem(HW, C, itemsize, nblk=_DCONV_NBLK):
    """True when the fused dconv kernel's estimated footprint fits VMEM.
    ``nblk`` lets the autotuner (ISSUE 9) constrain CANDIDATE block sizes
    with the same budget the auto branch enforces for the default."""
    return dconv_bwd_vmem_bytes(HW, C, itemsize, nblk=nblk) <= _vmem_limit()


def _dconv_band(y0, y1, W, HW, nblk):
    """First and last chunk of the flat feature axis that any corner of
    each row block falls in: two int32 ``(BG, n_pad // nblk)`` arrays.
    y0 / y1: ``(BG, n_pad)``, clipped to the map, padded rows repeating a
    live row of their block (``_dconv_pad(..., "edge")``)."""
    BG = y0.shape[0]
    lo = y0.reshape(BG, -1, nblk).min(-1) * W // _DCONV_CHUNK
    hi = (y1.reshape(BG, -1, nblk).max(-1) * W + W - 1) // _DCONV_CHUNK
    # the kernels slice VMEM by these and nothing checks a slice there: a
    # row outside the map must not move the band outside it
    last = _dconv_chunks(HW) - 1
    lo = jnp.clip(lo, 0, last)
    return lo, jnp.clip(hi, lo, last)


def dconv_band_share(y0, y1, hw, nblk=_DCONV_NBLK):
    """Mean share of the flat feature axis that a row block of ``nblk``
    samples contracts over, ``(hi - lo + 1) / n_chunks`` of ``_dconv_band``:
    1.0 means the band saved nothing.  A device value; read it outside the
    step (``chip_smoke.py``), never from inside it."""
    H, W = hw
    N = y0.shape[1]
    nblk = min(nblk, N)
    n_pad = -(-N // nblk) * nblk
    lo, hi = _dconv_band(_dconv_pad(y0, n_pad, "edge")[:, 0],
                         _dconv_pad(y1, n_pad, "edge")[:, 0], W, H * W, nblk)
    return jnp.mean((hi - lo + 1) / _dconv_chunks(H * W))


def _dconv_prec(dot_dtype):
    # f32 kernels must not silently drop to the MXU's default bf16
    # multiplies — the XLA formulation pins HIGHEST for f32 (detection.py);
    # bf16 stays single-pass
    return (jax.lax.Precision.HIGHEST
            if jnp.dtype(dot_dtype) == jnp.float32 else None)


def _dconv_corners(y0, y1, x0, x1, ly, lx, lf, W):
    """Flat index and weight of the four bilinear corners, in the inputs'
    own (lane-dense) layout; weights in the order 00, 01, 10, 11."""
    f = (y0 * W + x0, y0 * W + x1, y1 * W + x0, y1 * W + x1)
    w = ((1.0 - ly) * (1.0 - lx) * lf, (1.0 - ly) * lx * lf,
         ly * (1.0 - lx) * lf, ly * lx * lf)
    return f, w


def _dconv_band_loop(lo_ref, hi_ref, nblk, n_chunks, f, w, step_fn, init):
    """What both kernels do with this grid step's band:
    ``step_fn(rows, hit, a_t, carry) -> carry`` once per step of it, with
    ``rows`` the slice of the flat axis, ``hit`` the four corner masks and
    ``a_t`` the f32 ``(len(rows), nblk)`` block of A transposed.  A band of
    more than half the map is one step over all of it, the dense form: a
    step of the loop costs a fixed time beside its positions'."""
    import jax.experimental.pallas as pl

    bg, i = pl.program_id(0), pl.program_id(1)
    lo, hi = lo_ref[bg, i], hi_ref[bg, i]

    def step(rows, carry):
        pos = jax.lax.broadcasted_iota(jnp.int32, (rows.size, nblk), 0)
        hit = [pos == fk - rows.start for fk in f]
        a_t = sum(jnp.where(h, wk, 0.0) for h, wk in zip(hit, w))
        return step_fn(rows, hit, a_t, carry)

    def banded():
        win = _DCONV_STEP * _DCONV_CHUNK
        return jax.lax.fori_loop(
            0, (hi - lo + _DCONV_STEP) // _DCONV_STEP,
            lambda j, carry: step(pl.ds(pl.multiple_of(
                (lo + j * _DCONV_STEP) * _DCONV_CHUNK, _DCONV_CHUNK), win),
                carry), init)

    return jax.lax.cond(
        2 * (hi - lo + 1) > n_chunks,
        lambda: step(pl.ds(0, n_chunks * _DCONV_CHUNK), init), banded)


def _dconv_fwd_kernel_factory(W, nblk, dot_dtype):
    def kern(lo_ref, hi_ref, y0_ref, y1_ref, x0_ref, x1_ref, ly_ref, lx_ref,
             lf_ref, ftt_ref, col_ref):
        import jax.experimental.pallas as pl

        # factor blocks hold the WHOLE (padded) row per bg (N*4 bytes =
        # ~87 KB at north-star shapes — Mosaic requires lane-dim blocks be
        # full or 128-multiples; slicing the current block in-kernel keeps
        # the spec legal and the row resident across the i-grid)
        off = pl.program_id(1) * nblk
        sl = lambda ref: ref[0, :, pl.ds(off, nblk)]          # (1, nblk)
        f, w = _dconv_corners(*(sl(r) for r in (
            y0_ref, y1_ref, x0_ref, x1_ref, ly_ref, lx_ref, lf_ref)), W)

        # col^T += ft^T[:, step] @ A_T[step]: with the samples on the lanes
        # nothing is transposed, in the loop or after it
        def step(rows, hit, a_t, acc):
            return acc + jnp.dot(
                ftt_ref[0, :, rows], a_t.astype(dot_dtype),
                precision=_dconv_prec(dot_dtype),
                preferred_element_type=jnp.float32)

        acc = _dconv_band_loop(
            lo_ref, hi_ref, nblk, ftt_ref.shape[2] // _DCONV_CHUNK, f, w,
            step, jnp.zeros(col_ref.shape[1:], jnp.float32))
        col_ref[0] = acc.astype(col_ref.dtype)
    return kern


def _dconv_bwd_kernel_factory(W, nblk, dot_dtype):
    def kern(lo_ref, hi_ref, y0_ref, y1_ref, x0_ref, x1_ref, ly_ref, lx_ref,
             lf_ref, ft_ref, gt_ref, dly_ref, dlx_ref, dlf_ref, dft_ref):
        import jax.experimental.pallas as pl

        i = pl.program_id(1)
        out = pl.ds(i * nblk, nblk)
        sl = lambda ref: ref[0, :, out]                        # (1, nblk)
        ly, lx, lf = sl(ly_ref), sl(lx_ref), sl(lf_ref)
        f, w = _dconv_corners(sl(y0_ref), sl(y1_ref), sl(x0_ref),
                              sl(x1_ref), ly, lx, lf, W)
        g_t = gt_ref[0].astype(dot_dtype)                      # (C, nblk)

        @pl.when(i == 0)
        def _init():
            dft_ref[0] = jnp.zeros_like(dft_ref[0])

        def step(rows, hit, a_t, corner_sums):
            # dA_T = ft[step] @ g^T — contraction over channels, in VMEM
            da_t = jnp.dot(
                ft_ref[0, rows, :], g_t, precision=_dconv_prec(dot_dtype),
                preferred_element_type=jnp.float32)
            # d_ft[step] += A_T[step] @ g, the band's rows only: g^T's
            # samples lie on the lanes as A_T's do, so the two lane axes
            # contract (the q @ k^T form)
            dft_ref[0, rows, :] += jax.lax.dot_general(
                a_t.astype(dot_dtype), g_t, (((1,), (1,)), ((), ())),
                precision=_dconv_prec(dot_dtype),
                preferred_element_type=jnp.float32)
            return tuple(
                s + jnp.where(h, da_t, 0.0).sum(axis=0, keepdims=True)
                for s, h in zip(corner_sums, hit))

        zero = jnp.zeros((1, nblk), jnp.float32)
        d00, d01, d10, d11 = _dconv_band_loop(
            lo_ref, hi_ref, nblk, ft_ref.shape[1] // _DCONV_CHUNK, f, w,
            step, (zero,) * 4)
        dly_ref[0, :, out] = lf * ((1.0 - lx) * (d10 - d00)
                                   + lx * (d11 - d01))
        dlx_ref[0, :, out] = lf * ((1.0 - ly) * (d01 - d00)
                                   + ly * (d11 - d10))
        dlf_ref[0, :, out] = ((1.0 - ly) * ((1.0 - lx) * d00 + lx * d01)
                              + ly * ((1.0 - lx) * d10 + lx * d11))
    return kern


def _dconv_pad(a, n_pad, mode="constant"):
    a = jnp.pad(a, ((0, 0), (0, n_pad - a.shape[1])), mode=mode)
    # (BG, 1, n_pad): Mosaic block shapes need the last two dims full or
    # (8, 128)-divisible; a singleton sublane dim satisfies "full"
    return a[:, None, :]


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9))
def dconv_col_pallas(y0, y1, x0, x1, ly, lx, lf, ftt, hw, interpret=False):
    """col^T[bg] = ft^T[bg] @ A^T[bg] with A built in VMEM (above):
    ``col^T[bg, c, n] = sum_p A[bg, n, p] * ft^T[bg, c, p]``, channels-major
    on both sides.

    y0..x1: (BG, N) int32, inside the map; ly/lx/lf: (BG, N) f32;
    ftt: (BG, C, H*W), the NCHW data's own view; ``hw`` = (H, W) static.
    Returns (BG, C, N) in ftt's dtype with f32 accumulation (== the XLA
    path's ft^T @ a.astype(ft.dtype)^T contract); its cotangent comes back
    in the same layout, and d_ftt is (BG, C, H*W).
    """
    return _dconv_impl(y0, y1, x0, x1, ly, lx, lf, ftt, hw, interpret)


def _dconv_grid(N, HW=None, C=None, itemsize=4):
    """Row-block size + padded row count for one dconv problem.

    The hand-tuned default is ``_DCONV_NBLK``; with ``MXNET_AUTOTUNE`` set
    a persisted winner for this (device kind, shape signature) — searched
    by ``tools/autotune.py`` over the declared space under the same VMEM
    guard — overrides it.  Runs at TRACE time only (shapes are concrete
    inside jit tracing), so the lookup costs nothing per dispatch; with
    the gate unset this is one env read and behavior is byte-identical
    to the constant (tested in tests/test_autotune.py)."""
    nblk = _DCONV_NBLK
    from ..base import env_flag

    if env_flag("MXNET_AUTOTUNE") and HW is not None and C is not None:
        from .. import autotune

        cfg = autotune.config_for(
            "dconv_col_pallas",
            autotune.dconv_shape_sig(N, HW, C, itemsize))
        if cfg:
            try:
                adopted = max(8, int(cfg["nblk"]))
            except (KeyError, TypeError, ValueError):
                adopted = None  # malformed winner: keep the default
            # re-validate against the CURRENT VMEM budget: a winner searched
            # under a larger MXNET_DCONV_VMEM_MB must not hard-fail Mosaic
            # here — the guard that admitted it at search time re-decides at
            # adoption time, and the hand-tuned default stays otherwise
            if adopted is not None and dconv_fits_vmem(
                    HW, C, itemsize, nblk=min(adopted, N)):
                nblk = adopted
    nblk = min(nblk, N)
    return nblk, -(-N // nblk) * nblk


def _dconv_operands(y0, y1, x0, x1, ly, lx, lf, ftt, W):
    """What both kernels take: the grid, then the band (scalar prefetch)
    and the seven per-sample rows padded to the grid, then ft^T padded to
    whole steps, and the specs of a per-sample row and of a ``(C, nblk)``
    block of columns."""
    from jax.experimental import pallas as pl

    N = y0.shape[1]
    C, HW = ftt.shape[1], ftt.shape[2]
    nblk, n_pad = _dconv_grid(N, HW, C, jnp.dtype(ftt.dtype).itemsize)
    # padded rows carry lf=0 => A row = 0 => no effect anywhere; their
    # corners repeat a live row of the block, so they never widen its band
    ints = [_dconv_pad(a, n_pad, "edge") for a in (y0, y1, x0, x1)]
    flts = [_dconv_pad(a, n_pad) for a in (ly, lx, lf)]
    band = _dconv_band(ints[0][:, 0], ints[1][:, 0], W, HW, nblk)
    ftt = jnp.pad(ftt, ((0, 0), (0, 0), (0, _dconv_hw_pad(HW) - HW)))
    row_spec = pl.BlockSpec((1, 1, n_pad), lambda bg, i, lo, hi: (bg, 0, 0))
    col_spec = pl.BlockSpec((1, C, nblk), lambda bg, i, lo, hi: (bg, 0, i))
    return nblk, n_pad, (*band, *ints, *flts), ftt, row_spec, col_spec


def _dconv_impl(y0, y1, x0, x1, ly, lx, lf, ftt, hw, interpret):
    return _per_batch_shard(
        lambda *a: _dconv_fwd_call(*a, hw, interpret),
        y0, y1, x0, x1, ly, lx, lf, ftt)


def _dconv_fwd_call(y0, y1, x0, x1, ly, lx, lf, ftt, hw, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    W = hw[1]
    BG, N = y0.shape
    C, HW = ftt.shape[1], ftt.shape[2]
    _record_cost(
        "dconv_col_pallas_fwd",
        cost_dconv_col_fwd(BG, N, HW, C, jnp.dtype(ftt.dtype).itemsize),
        ftt.shape)
    nblk, n_pad, rows, ftt, row_spec, col_spec = _dconv_operands(
        y0, y1, x0, x1, ly, lx, lf, ftt, W)
    out = pl.pallas_call(
        _dconv_fwd_kernel_factory(W, nblk, ftt.dtype),
        out_shape=jax.ShapeDtypeStruct((BG, C, n_pad), ftt.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(BG, n_pad // nblk),
            in_specs=[row_spec] * 7 + [pl.BlockSpec(
                (1, C, ftt.shape[2]), lambda bg, i, lo, hi: (bg, 0, 0))],
            out_specs=col_spec),
        interpret=interpret,
        name="dconv_col_pallas_fwd",
    )(*rows, ftt)
    return out[:, :, :N]


def _dconv_fwd(y0, y1, x0, x1, ly, lx, lf, ftt, hw, interpret):
    out = _dconv_impl(y0, y1, x0, x1, ly, lx, lf, ftt, hw, interpret)
    return out, (y0, y1, x0, x1, ly, lx, lf, ftt)


def _dconv_bwd(hw, interpret, res, g):
    import numpy as _np

    dly, dlx, dlf, dftt = _per_batch_shard(
        lambda *a: _dconv_bwd_call(*a, hw, interpret), *res, g)
    f0 = lambda a: _np.zeros(a.shape, jax.dtypes.float0)
    return (*(f0(a) for a in res[:4]), dly, dlx, dlf, dftt)


def _dconv_bwd_call(y0, y1, x0, x1, ly, lx, lf, ftt, g_t, hw, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    W = hw[1]
    BG, N = y0.shape
    C, HW = ftt.shape[1], ftt.shape[2]
    _record_cost(
        "dconv_col_pallas_bwd",
        cost_dconv_col_bwd(BG, N, HW, C, jnp.dtype(ftt.dtype).itemsize),
        ftt.shape)
    nblk, n_pad, rows, ftt, row_spec, col_spec = _dconv_operands(
        y0, y1, x0, x1, ly, lx, lf, ftt, W)
    # the backward contracts over and accumulates (HW, C): the two
    # features-sized transposes left to XLA
    ft = ftt.transpose(0, 2, 1)
    # padded columns of g^T meet rows of A that are zero (lf = 0)
    gp = jnp.pad(g_t, ((0, 0), (0, 0), (0, n_pad - N)))
    row_out = jax.ShapeDtypeStruct((BG, 1, n_pad), jnp.float32)
    map_spec = pl.BlockSpec((1, ft.shape[1], C),
                            lambda bg, i, lo, hi: (bg, 0, 0))
    dly, dlx, dlf, dft = pl.pallas_call(
        _dconv_bwd_kernel_factory(W, nblk, ft.dtype),
        out_shape=(row_out, row_out, row_out,
                   jax.ShapeDtypeStruct(ft.shape, jnp.float32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(BG, n_pad // nblk),
            in_specs=[row_spec] * 7 + [map_spec, col_spec],
            out_specs=(row_spec, row_spec, row_spec, map_spec)),
        interpret=interpret,
        name="dconv_col_pallas_bwd",
    )(*rows, ft, gp)
    return (dly[:, 0, :N], dlx[:, 0, :N], dlf[:, 0, :N],
            dft[:, :HW].astype(ft.dtype).transpose(0, 2, 1))


dconv_col_pallas.defvjp(_dconv_fwd, _dconv_bwd)
