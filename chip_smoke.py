"""chip_smoke.py — does the system still start on the chip?

Run from the repo root on a machine with a TPU, one process per chip::

    python chip_smoke.py

It drives the main path once at full width, in this one process:

1. Deformable R-FCN, ResNet-101, 608x1024, 80 classes, batch 8, bf16 — the
   step ``bench.py`` measures (``examples/deformable_rfcn/train_fused.py``:
   ``build_net`` -> ``make_rfcn_train_step`` -> ``jax.jit(step,
   donate_argnums=(0,))``): compiled once, >= 3 chained steps on donated
   state, loss finite and different on every step, and the COMPILED module
   holds the Mosaic custom calls for dconv forward, dconv backward and NMS.
2. Every Pallas kernel that is default-on for TPU, non-interpreted, against
   the repo's XLA / jnp formulation of the same operator (``sparse_attn_leg``:
   one layer of the text model's selected-key attention, forward + backward,
   the kernel pair against the XLA walk, with milliseconds a call).
   Beside them ``psroi_leg``: deformable PS-ROI pooling alone (no kernel:
   XLA's one-hot path) at the step's three shapes, against its gather path,
   with milliseconds a call.
3. ``Module.fit`` on the symbolic ResNet-50 (224x224, 1000 classes): Symbol
   -> Executor -> ``FusedStepper`` -> optimizer, with the recipe's
   ``context=mx.current_context()``; the fused step must engage and the
   parameters must live on the TPU afterwards.
4. With >= 4 chips visible: ``dryrun_multichip(4)`` and leg 1 data-parallel
   over a dp=4 mesh at global batch 32.  Never on virtual devices.

Weights are random from a seed; nothing is read outside the tracked tree.
Any failed check raises: there is no skipped phase that still exits 0.  With
no TPU it exits non-zero and prints no result line.  The last line of stdout
is ``{"ok": true, "device": {"platform", "kind", "count"}}``.

The legs are importable functions with their sizes as arguments so
``tests/test_chip_smoke.py`` can drive them at toy size on the CPU with
interpret-mode kernels; ``__main__`` is always full width and always
requires the chip.
"""
from __future__ import annotations

import gc
import json
import os
import re
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

# longest first: "quantize_int8_pallas" is a substring of the dequantize name
KERNELS = ("dequantize_int8_pallas", "quantize_int8_pallas",
           "dconv_col_pallas_fwd", "dconv_col_pallas_bwd", "nms_alive_pallas")
STEP_KERNELS = ("dconv_col_pallas_fwd", "dconv_col_pallas_bwd",
                "nms_alive_pallas")


def say(msg):
    print("[chip_smoke] " + msg, flush=True)


def require_tpu():
    """-> {"platform", "kind", "count"} as JAX reports it, or exit non-zero
    naming what was found.  Prints the installation."""
    import jax
    import jaxlib

    try:
        import libtpu
        libtpu_version = getattr(libtpu, "__version__", "unknown")
    except ImportError:
        libtpu_version = "not installed"
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    say("jax %s jaxlib %s libtpu %s python %s"
        % (jax.__version__, jaxlib.__version__, libtpu_version,
           sys.version.split()[0]))
    say("device %s" % json.dumps(device))
    if device["platform"] != "tpu":
        raise SystemExit(
            "chip_smoke.py: needs a TPU, found platform=%r device_kind=%r "
            "count=%d (JAX_PLATFORMS=%r)"
            % (device["platform"], device["kind"], device["count"],
               os.environ.get("JAX_PLATFORMS")))
    return device


def native_lib_status():
    """Build-or-degrade of the native data plane is silent; say which."""
    from mxnet_tpu import _native

    prebuilt = os.path.exists(_native._SO_PATH)
    if _native.lib() is None:
        return "degraded to pure Python"
    return "prebuilt .so loaded" if prebuilt else "built from src/ and loaded"


def mosaic_calls(hlo_text):
    """One ``{"kernel", "count", "result"}`` per distinct Mosaic custom call
    in a COMPILED module's text.  The kernel is recognised by the ``name=``
    its pallas_call carries into the instruction's ``op_name`` metadata; the
    result type is the per-device shape after SPMD partitioning (layouts
    dropped; compiled text prints no operand types)."""
    found = {}
    for line in hlo_text.splitlines():
        call = re.search(r"= (.*?) custom-call\(.*"
                         r'custom_call_target="tpu_custom_call"', line)
        if not call:
            continue
        op_name = re.search(r'op_name="([^"]*)"', line)
        op_name = op_name.group(1) if op_name else ""
        kernel = next((k for k in KERNELS if k in op_name),
                      "unnamed:" + op_name[-80:])
        key = (kernel, re.sub(r"\{[^}]*\}", "", call.group(1)))
        found[key] = found.get(key, 0) + 1
    return [{"kernel": k, "count": n, "result": r}
            for (k, r), n in found.items()]


def _load_rfcn_recipe():
    from mxnet_tpu.test_utils import load_module_by_path

    return load_module_by_path(
        os.path.join(REPO, "examples", "deformable_rfcn", "train_fused.py"),
        "_chip_smoke_rfcn_train_fused")


def rfcn_leg(resnet101=True, batch=8, image_shape=None, steps=3,
             dtype="bfloat16", dp=1):
    """The north-star train step; ``batch`` is per device, ``dp`` > 1 runs it
    data-parallel over a ``{"dp": dp}`` mesh of the first ``dp`` devices
    (params replicated, batch sharded).  -> facts dict."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    import mxnet_tpu as mx
    from mxnet_tpu import parallel

    tf = _load_rfcn_recipe()
    mx.random.seed(0)
    rng = np.random.RandomState(0)
    net, shape, classes = tf.build_net(resnet101, image_shape)
    gbatch = batch * dp
    data, im_info, gt = tf.synthetic_coco(rng, gbatch, shape, classes,
                                          net.max_gts)
    step, state = tf.make_rfcn_train_step(
        net, gbatch, learning_rate=5e-4, momentum=0.9, compute_dtype=dtype)
    devs = jax.devices()[:dp]
    mesh = parallel.make_mesh({"dp": dp}, devices=devs)
    if dp > 1:
        repl = NamedSharding(mesh, P())
        state = jax.tree_util.tree_map(
            lambda v: jax.device_put(v, repl), state)
        sharded = NamedSharding(mesh, P("dp"))
        batch_arrays = [jax.device_put(a, sharded)
                        for a in (data, im_info, gt)]
    else:
        batch_arrays = [jax.device_put(a) for a in (data, im_info, gt)]
    key = jax.random.PRNGKey(0)

    jstep = jax.jit(step, donate_argnums=(0,))
    t0 = time.perf_counter()
    # traced under set_mesh so the Pallas calls can see the dp axis and run
    # per shard: GSPMD cannot partition a Mosaic kernel
    with jax.set_mesh(mesh):
        lowered = jstep.lower(state, *batch_arrays, key)
    t1 = time.perf_counter()
    compiled = lowered.compile()
    t2 = time.perf_counter()
    hlo = compiled.as_text()
    calls = mosaic_calls(hlo)

    losses = []
    for s in range(steps):
        state, loss, _parts = compiled(state, *batch_arrays,
                                       jax.random.fold_in(key, s))
        losses.append(loss)
    jax.block_until_ready(state)
    t3 = time.perf_counter()
    losses = [float(l) for l in losses]
    if not all(np.isfinite(losses)):
        raise AssertionError("non-finite loss: %r" % (losses,))
    if len(set(losses)) != len(losses):
        raise AssertionError("loss did not change between steps: %r"
                             % (losses,))

    state_devices = sorted({d.id for leaf in jax.tree_util.tree_leaves(state)
                            for d in leaf.devices()})
    batch_devices = [sh.device.id
                     for sh in batch_arrays[0].addressable_shards]
    if state_devices != [d.id for d in devs] \
            or sorted(batch_devices) != [d.id for d in devs]:
        raise AssertionError(
            "placement: state on devices %r, batch shards on %r, wanted %r"
            % (state_devices, batch_devices, [d.id for d in devs]))
    facts = {
        "model": "rfcn_%s" % ("r101" if resnet101 else "toy"),
        "image_shape": list(shape), "batch_per_device": batch, "dp": dp,
        "dtype": str(dtype), "params_m": round(
            sum(int(np.prod(v.shape)) for v in state[0]) / 1e6, 1),
        "trace_lower_s": round(t1 - t0, 1), "compile_s": round(t2 - t1, 1),
        "steps": steps, "steps_wall_s": round(t3 - t2, 2), "losses": losses,
        "mosaic_calls": calls,
        "collectives": {op: len(re.findall(
            r"= [^=\n]* %s(?:-start)?\(" % op, hlo))
            for op in ("all-reduce", "all-gather", "all-to-all",
                       "collective-permute")},
        # memory_stats()'s peak did not move with the step's activations on
        # the v5e runtime (PR 21 run), so the executable's own figure too
        "temp_bytes": compiled.memory_analysis().temp_size_in_bytes,
        "bytes_in_use": [(d.memory_stats() or {}).get("bytes_in_use")
                         for d in devs],
    }
    del state, compiled, lowered, batch_arrays, net
    gc.collect()
    return facts


def check_step_kernels(facts):
    """The compiled step must hold every STEP_KERNELS Mosaic call — neither
    ``dconv_fits_vmem`` nor the NMS ``N >= 1024`` gate may have quietly
    taken the XLA formulation at these shapes."""
    names = [c["kernel"] for c in facts["mosaic_calls"]]
    missing = [k for k in STEP_KERNELS if k not in names]
    if missing:
        raise AssertionError("compiled step lacks Mosaic calls %r; found %r"
                             % (missing, facts["mosaic_calls"]))


def check_dp_facts(facts, single):
    """Four-chip checks that need no judgement: a gradient all-reduce
    exists, every chip holds about the same bytes (not everything on chip
    0), and each chip runs the Pallas kernels at the single-chip shapes
    (``single``: the dp=1 facts at the same per-device batch) — its own
    shard, not the gathered global batch."""
    if facts["collectives"]["all-reduce"] < 1:
        raise AssertionError("dp step compiled without an all-reduce")
    used = facts["bytes_in_use"]
    if None in used or max(used) > 1.5 * min(used):
        raise AssertionError("uneven per-device memory: %r" % (used,))
    if facts["mosaic_calls"] != single["mosaic_calls"]:
        raise AssertionError(
            "per-device Pallas calls differ from the single-chip step: %r "
            "vs %r" % (facts["mosaic_calls"], single["mosaic_calls"]))


def quant_leg(shape=(256, 1024), interpret=False):
    """``quantize_int8_pallas`` / ``dequantize_int8_pallas`` at one
    tile-aligned shape against the jnp formula of ops/quantization.py."""
    import jax.numpy as jnp

    from mxnet_tpu.ops import pallas_kernels as pk

    x = jnp.asarray(np.random.RandomState(0).randn(*shape).astype(np.float32))
    if not pk.supported(x.shape, x.dtype):
        raise AssertionError("shape %r is not tile-aligned" % (shape,))
    r = jnp.max(jnp.abs(x))
    q = pk.quantize_int8_pallas(x, r, interpret=interpret)
    q_ref = (jnp.sign(x) * jnp.minimum(jnp.abs(x) * (127.0 / r) + 0.5, 127.0)
             ).astype(jnp.int8)
    dq = pk.dequantize_int8_pallas(q, r, interpret=interpret)
    dq_ref = q.astype(jnp.float32) * (r / 127.0)
    facts = {"quantize_mismatches": int(jnp.sum(q != q_ref)),
             "dequantize_max_err": float(jnp.max(jnp.abs(dq - dq_ref)))}
    if facts["quantize_mismatches"] or facts["dequantize_max_err"] > 1e-6:
        raise AssertionError("int8 kernels disagree with jnp: %r" % facts)
    return facts


def nms_leg(boxes=6000, batch=2, interpret=False):
    """``nms_alive_pallas`` (vmapped onto its batched grid, as MultiProposal
    calls it) against ``_nms_alive_blocked_xla``: identical survivors."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import detection, pallas_kernels as pk

    rng = np.random.RandomState(0)
    ctr = rng.rand(batch, boxes, 2) * np.array([1024.0, 608.0])
    wh = rng.rand(batch, boxes, 2) * 200.0 + 8.0
    b = jnp.asarray(np.concatenate([ctr - wh / 2, ctr + wh / 2], -1)
                    .astype(np.float32))
    valid = jnp.ones(b.shape[:2], bool)
    alive = jax.jit(jax.vmap(lambda b, v: pk.nms_alive_pallas(
        b, v, None, thresh=0.7, interpret=interpret)))(b, valid)
    alive_ref = jax.jit(jax.vmap(lambda b, v: detection._nms_alive_blocked_xla(
        b, 0.7, 256, 1.0, v, None, True)))(b, valid)
    facts = {"nms_survivors": [int(n) for n in np.asarray(alive).sum(1)],
             "nms_mismatches": int(jnp.sum(alive != alive_ref))}
    if facts["nms_mismatches"]:
        raise AssertionError("NMS kernel disagrees with XLA: %r" % facts)
    return facts


def per_call_ms(calls, fn, *args):
    """Milliseconds a call of a jitted ``fn`` over ``calls`` chained calls,
    after one that compiles it."""
    import jax

    jax.block_until_ready(fn(*args))
    t = time.perf_counter()
    for _ in range(calls):
        out = fn(*args)
    jax.block_until_ready(out)
    return round((time.perf_counter() - t) / calls * 1e3, 3)


# the leg's three draws of deformable samples: offsets below one cell (the
# benchmark's seeded offset branches, and how every fine-tune starts), up to
# three cells (trained res5 offsets), and uniform over the map (no detector
# sends it: every row block's band is the whole map, the kernels' worst case)
DCONV_REGIMES = (("offsets<1", 1.0), ("offsets<=3", 3.0), ("uniform", None))


def dconv_leg(bg=32, channels=128, hw=(38, 64), interpret=False, calls=20):
    """``dconv_col_pallas`` at north-star res5's shape, bf16 features, in
    the three regimes of ``DCONV_REGIMES``: forward and all four gradients
    against the dense one-hot matmul it replaces (on the first (image,
    group): the dense A of all ``bg`` would not fit), then
    ``dconv_band_share`` and the forward and the backward kernel's time for
    one call over ``bg`` (image, group)s, 8 images of 4 groups by default
    as in the benchmark's step."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import pallas_kernels as pk
    from mxnet_tpu.test_utils import (dconv_dense_reference,
                                      dconv_sample_inputs)

    H, W = hw
    N = 9 * H * W

    def dense(*args):
        return dconv_dense_reference(*args, hw)

    def first(arrays):
        return [a[:1] for a in arrays]

    def fused(y0, y1, x0, x1, ly, lx, lf, ft):
        return pk.dconv_col_pallas(y0, y1, x0, x1, ly, lx, lf, ft, (H, W),
                                   interpret)

    def values(fn, ints, flts, cot):
        def loss(*a):
            return jnp.sum(fn(*ints, *a).astype(jnp.float32) * cot)
        out = jax.jit(fn)(*ints, *flts)
        grads = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3)))(*flts)
        return [np.asarray(v.astype(jnp.float32)) for v in (out,) + grads]

    # the backward kernel alone: the forward's output is dead code here
    backward = jax.jit(lambda ints, flts, g: jax.vjp(
        lambda *a: fused(*ints, *a), *flts)[1](g))
    forward = jax.jit(fused)

    facts = {}
    for name, offset in DCONV_REGIMES:
        rng = np.random.RandomState(0)
        rows = [jnp.asarray(a) for a in
                dconv_sample_inputs(rng, bg, hw, offset)]
        # channels-major on both sides of the kernel: ft^T in, col^T out
        ft = jnp.asarray(rng.randn(bg, channels, H * W).astype(np.float32)
                         ).astype(jnp.bfloat16)
        ints, flts = rows[:4], rows[4:] + [ft]
        cot = jnp.cos(jnp.arange(N * channels, dtype=jnp.float32)
                      ).reshape(1, channels, N)
        errs = {}
        for out, got, want in zip(
                ("col", "d_ly", "d_lx", "d_lf", "d_ft"),
                values(fused, first(ints), first(flts), cot),
                values(dense, first(ints), first(flts), cot)):
            scale = max(float(np.abs(want).max()), 1e-6)
            errs[out] = float("%.3g" % (float(np.abs(got - want).max())
                                        / scale))
        # bf16 operands, f32 accumulation on both sides, but the dense
        # path's AD rounds dA to bf16 where the kernel keeps it f32: allow 4
        # bf16 ulps (2^-6) of the largest element
        if max(errs.values()) > 2.0 ** -6:
            raise AssertionError("dconv kernel disagrees with the dense "
                                 "formulation (%s): %r" % (name, errs))
        g = jnp.broadcast_to(cot, (bg, channels, N)).astype(jnp.bfloat16)
        facts[name] = {
            "rel_err": errs,
            "band_share": round(float(pk.dconv_band_share(
                ints[0], ints[1], hw)), 4),
            "fwd_ms": per_call_ms(calls, forward, *ints, *flts),
            "bwd_ms": per_call_ms(calls, backward, ints, flts, g)}
    return {"bg": bg, "dconv": facts}


def sparse_attn_leg(seq=16384, heads=32, kv_heads=4, head_dim=128,
                    index_heads=16, index_dim=64, topk=2048, block=256,
                    span=2048, interpret=False, calls=3):
    """One layer of ``IndexerSparseAttention`` at the text cell's shapes
    (bf16, per-head normalised queries and keys), forward and forward +
    backward: the Pallas kernel pair (``ops/pallas_attention.py``) against
    the XLA walk it replaces on a TPU: milliseconds a call of each, and the
    largest difference of the output, the KL term and the six gradients over
    the walk's largest element.  Indexer and selection are in both."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import transformer as tr

    ks = jax.random.split(jax.random.PRNGKey(0), 7)
    unit = lambda x: x * jax.lax.rsqrt(  # noqa: E731
        jnp.mean(x * x, axis=-1, keepdims=True))
    args = [unit(jax.random.normal(ks[0], (seq, heads, head_dim))),
            unit(jax.random.normal(ks[1], (seq, kv_heads, head_dim))),
            jax.random.normal(ks[2], (seq, kv_heads, head_dim)),
            jax.random.normal(ks[3], (seq, index_heads, index_dim)),
            jax.random.normal(ks[4], (seq, index_dim)),
            jax.random.normal(ks[5], (seq, index_heads))]
    args = [a.astype(jnp.bfloat16) for a in args]
    cot = jax.random.normal(ks[6], (seq, heads, head_dim))

    def layer(mode):
        def loss(*a):
            out = tr._sparse_attention(*a, topk, block, span, False, mode)
            return (jnp.sum(out[0].astype(jnp.float32) * cot)
                    + out[1]), out[:3]
        return (jax.jit(lambda *a: loss(*a)[1]),
                jax.jit(jax.value_and_grad(loss, argnums=tuple(range(6)),
                                           has_aux=True)))

    facts, values = {"seq": seq}, {}
    for name, mode in (("kernel", "interpret" if interpret else "auto"),
                       ("walk", "xla")):
        forward, both = layer(mode)
        (_, out), grads = both(*args)
        values[name] = [np.asarray(v, np.float32)
                        for v in tuple(out[:2]) + tuple(grads)]
        facts[name] = {"selected": int(out[2]),
                       "fwd_ms": per_call_ms(calls, forward, *args),
                       "fwd_bwd_ms": per_call_ms(calls, both, *args)}
    errs = {}
    for n, got, want in zip(("o", "kl", "d_q", "d_k", "d_v", "d_iq", "d_ik",
                             "d_iw"), values["kernel"], values["walk"]):
        errs[n] = float("%.3g" % (float(np.abs(got - want).max())
                                  / max(float(np.abs(want).max()), 1e-6)))
    facts["rel_err"] = errs
    # both round the weights to bf16 as MXU operands, the kernel after
    # normalising them; the keys' gradients are bf16 sums over 64 blocks.
    # The selection is XLA's in both, but in two programs: a score on its
    # row's threshold may fall either way (1 of 31 458 308 on the chip)
    if (max(errs.values()) > 2.0 ** -5
            or abs(facts["kernel"]["selected"] - facts["walk"]["selected"])
            > 1e-6 * facts["walk"]["selected"]):
        raise AssertionError("the attention kernels disagree with the XLA "
                             "walk: %r" % facts)
    return {"sparse_attn": facts}


# the R-FCN head's three poolings (model_zoo/detection.py ``_head``): name,
# output_dim, no_trans
PSROI_POOLINGS = (("offsets", 2, True), ("classes", 81, False),
                  ("boxes", 8, False))


def psroi_leg(batch=8, rois=128, hw=(38, 64), poolings=PSROI_POOLINGS, k=7,
              calls=20):
    """``deformable_psroi_pooling`` alone at the benchmark head's three
    shapes (``rois`` grouped rois an image over a res5 map, bf16): the
    one-hot path's first roi against the gather path's (one roi alone is
    under the threshold), then milliseconds a call forward, and forward +
    backward to the data and the offsets.  The operator by itself: the
    step's time is the benchmark's to say."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops.detection import deformable_psroi_pooling

    H, W = hw
    R = batch * rois
    rng = np.random.RandomState(0)
    box = np.zeros((R, 5), np.float32)
    box[:, 0] = np.repeat(np.arange(batch), rois)
    ctr = rng.rand(R, 2) * np.array([W, H]) * 16.0
    wh = rng.rand(R, 2) * np.array([W, H]) * 8.0 + 16.0
    box[:, 1:3], box[:, 3:5] = ctr - wh / 2, ctr + wh / 2  # some overhang
    box = jnp.asarray(box)
    trans = jnp.asarray(0.5 * rng.randn(R, 2, k, k).astype(np.float32)
                        ).astype(jnp.bfloat16)
    facts = {}
    for name, od, no_trans in poolings:
        kw = dict(spatial_scale=1.0 / 16, output_dim=od, group_size=k,
                  pooled_size=k, part_size=k, trans_std=0.1,
                  no_trans=no_trans)
        data = jnp.asarray(rng.randn(batch, od * k * k, H, W)
                           .astype(np.float32)).astype(jnp.bfloat16)
        cot = jnp.cos(jnp.arange(R * od * k * k, dtype=jnp.float32)
                      ).reshape(R, od, k, k)

        def pool(d, t, kw=kw):
            return deformable_psroi_pooling(d, box, t, rois_per_image=rois,
                                            **kw)

        forward = jax.jit(pool)
        both = jax.jit(jax.value_and_grad(
            lambda d, t: jnp.sum(pool(d, t).astype(jnp.float32) * cot),
            argnums=(0, 1)))
        got = np.asarray(forward(data, trans)[:1].astype(jnp.float32))
        want = np.asarray(deformable_psroi_pooling(
            data, box[:1], trans[:1], **kw).astype(jnp.float32))
        if not np.isfinite(got).all():
            raise AssertionError("PS-ROI pooling (%s) is not finite" % name)
        err = float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-6))
        # bf16 data on both sides, f32 accumulation: A's entries round to
        # bf16 on the one-hot path only; allow 4 bf16 ulps of the largest
        if err > 2.0 ** -6:
            raise AssertionError("PS-ROI one-hot path disagrees with the "
                                 "gather path (%s): %.3g" % (name, err))
        facts[name] = {
            "rel_err": float("%.3g" % err),
            "fwd_ms": per_call_ms(calls, forward, data, trans),
            "fwd_bwd_ms": per_call_ms(calls, both, data, trans)}
    return {"rois": R, "psroi": facts}


def module_fit_leg(num_layers=50, image=224, classes=1000, batch=32,
                   batches=4):
    """``Module.fit`` on the symbolic ResNet through the image-classification
    recipe's pieces (``symbols/resnet.py``, ``SyntheticDataIter``, and
    ``common/fit.py``'s ``context=mx.current_context()``)."""
    import mxnet_tpu as mx
    from mxnet_tpu.test_utils import load_module_by_path

    exdir = os.path.join(REPO, "examples", "image-classification")
    resnet = load_module_by_path(
        os.path.join(exdir, "symbols", "resnet.py"), "_chip_smoke_resnet_sym")
    data = load_module_by_path(
        os.path.join(exdir, "common", "data.py"), "_chip_smoke_ic_data")
    mx.random.seed(0)
    sym = resnet.get_symbol(num_classes=classes, num_layers=num_layers,
                            image_shape="3,%d,%d" % (image, image))
    train = data.SyntheticDataIter(classes, (batch, 3, image, image), batches,
                                   "float32")
    ctx = mx.current_context()
    mod = mx.mod.Module(symbol=sym, context=ctx)
    ce = []
    t0 = time.perf_counter()
    mod.fit(train, num_epoch=1, optimizer="sgd",
            optimizer_params={"learning_rate": 0.05, "momentum": 0.9,
                              "wd": 1e-4},
            initializer=mx.init.Xavier(rnd_type="gaussian",
                                       factor_type="in", magnitude=2),
            eval_metric="ce",
            batch_end_callback=lambda p: ce.append(
                float(p.eval_metric.get()[1])))
    wall = time.perf_counter() - t0
    if mod._fused is None:
        raise AssertionError("Module.fit did not engage the fused step")
    if len(ce) != batches or not all(np.isfinite(ce)):
        raise AssertionError("cross-entropy per batch: %r" % (ce,))
    arg_params, aux_params = mod.get_params()
    platforms = sorted({d.platform
                        for v in list(mod._exec.arg_dict.values())
                        + list(mod._exec.aux_dict.values())
                        for d in v._data.devices()})
    facts = {"model": "resnet%d_symbolic" % num_layers, "image": image,
             "batch": batch, "batches": batches,
             "default_context": str(ctx), "fused": True,
             "param_platforms": platforms,
             "params_m": round(sum(v.size for v in arg_params.values())
                               / 1e6, 1),
             "fit_wall_s": round(wall, 1), "running_cross_entropy": ce}
    del mod, train, arg_params, aux_params
    gc.collect()
    return facts


def cache_facts():
    import jax

    from mxnet_tpu import compile_cache

    d = jax.config.jax_compilation_cache_dir
    st = compile_cache.stats()
    return {"dir": d, "from_env": bool(os.environ.get(
                "JAX_COMPILATION_CACHE_DIR", "").strip()),
            "entries": len(os.listdir(d)) if d and os.path.isdir(d) else 0,
            "hits": st["xla_hits"], "misses": st["xla_misses"]}


def main():
    t_start = time.perf_counter()
    import mxnet_tpu  # noqa: F401 — places the compile cache at import

    device = require_tpu()
    say("compile cache at start: %s" % json.dumps(cache_facts()))
    say("native data plane: %s" % native_lib_status())

    single = rfcn_leg()
    say("rfcn 1 chip: %s" % json.dumps(single))
    check_step_kernels(single)

    for leg in (quant_leg, nms_leg, dconv_leg, sparse_attn_leg, psroi_leg):
        say("%s vs XLA/jnp: %s" % (leg.__name__, json.dumps(leg())))

    facts = module_fit_leg()
    say("Module.fit: %s" % json.dumps(facts))
    if facts["param_platforms"] != ["tpu"]:
        raise AssertionError("Module.fit parameters live on %r, not the TPU"
                             % (facts["param_platforms"],))

    if device["count"] >= 4:
        from __graft_entry__ import dryrun_multichip

        dryrun_multichip(4)
        facts = rfcn_leg(dp=4)
        say("rfcn dp=4: %s" % json.dumps(facts))
        check_step_kernels(facts)
        check_dp_facts(facts, single)
    else:
        say("dp=4 leg: not run, %d chip(s) visible" % device["count"])

    say("compile cache at end: %s" % json.dumps(cache_facts()))
    say("total %.0f s" % (time.perf_counter() - t_start))
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
