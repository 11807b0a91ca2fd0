#!/usr/bin/env python
"""Autotuning CLI — search / show / clear the winner store (ISSUE 9).

Searches a kernel's declared tuning space (``mxnet_tpu/autotune/space.py``)
with on-device measurement, or proposes a serving bucket ladder from a
recorded ``tools/loadgen.py --save-trace`` traffic trace, and persists the
winner per (device kind, kernel, shape signature) in the
``MXNET_AUTOTUNE_CACHE`` store.  A warm store short-circuits: a second
``search`` for the same key performs ZERO new measurements (pass
``--force`` to re-search).  Every run prints one machine-readable
``AUTOTUNE {json}`` line (``ci/check_autotune.py`` parses it).

Examples::

    # search dconv_col_pallas block shapes at a concrete problem shape,
    # on the samples a deformable layer sends (offsets below one cell)
    python tools/autotune.py search --kernel dconv_col_pallas \\
        --bg 32 --n 21888 --h 38 --w 64 --c 128 --dtype bfloat16 --offset 1

    # propose ladder rungs from recorded traffic, adopted by any Engine
    # started with MXNET_AUTOTUNE=1 for the same sample shapes
    python tools/loadgen.py --mode open --duration 5 --save-trace t.jsonl
    python tools/autotune.py search --trace t.jsonl

    python tools/autotune.py show
    python tools/autotune.py clear --kernel dconv_col_pallas

The CLI itself is the opt-in: it sets ``MXNET_AUTOTUNE=1`` for its own
process so the store and the dispatch-site overrides are live regardless
of the ambient environment.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..")))


def _emit(payload):
    print("AUTOTUNE " + json.dumps(payload, sort_keys=True))


def _warm_hit(kernel, sig, kind, args):
    """Warm-store short-circuit shared by every kernel runner: a persisted
    winner for this key means ZERO new measurements (--force re-searches)."""
    from mxnet_tpu import autotune

    if args.force:
        return False
    winner = autotune.lookup(kernel, sig)
    if winner is None:
        return False
    _emit({"kind": kind, "kernel": kernel, "sig": sig, "cached": True,
           "measurements": 0, "config": winner})
    print("autotune: warm store hit for %s — zero measurements "
          "(--force to re-search)" % sig)
    return True


def _resolve_strategy(kernel, args):
    """--strategy resolution: ``auto`` uses the learned cost model when it
    is enabled AND the store holds enough training rows, else grid.  An
    explicit ``predict`` that cannot be honored degrades to grid with a
    message (never an error: the model is advisory, ISSUE 18)."""
    from mxnet_tpu.autotune import costmodel

    want = getattr(args, "strategy", "auto")
    if want == "grid":
        return "grid", None
    if not costmodel.model_enabled():
        if want == "predict":
            print("autotune: MXNET_AUTOTUNE_MODEL=0 — grid search")
        return "grid", None
    model = costmodel.model_for(kernel)
    if model is None:
        if want == "predict":
            print("autotune: no usable cost model for %s yet (fewer than "
                  "%d stored trial rows) — grid search"
                  % (kernel, costmodel.MIN_ROWS))
        return "grid", None
    return "predict", model


def _run_and_finish(kernel, sig, kind, space_obj, ctx, measure, args,
                    meta_extra=None, emit_extra=None):
    """Shared search tail for every kernel runner: resolve the strategy,
    run grid search or predict-then-measure, persist the winner with its
    trial_costs training rows (finite trials only — a failed candidate's
    +inf sentinel must never teach the model a latency), print the trial
    table, emit the machine-readable AUTOTUNE line."""
    import math

    from mxnet_tpu import autotune
    from mxnet_tpu.autotune import costmodel
    from mxnet_tpu.autotune.store import _device_kind

    strategy, model = _resolve_strategy(kernel, args)
    grid = space_obj.configs(**ctx)
    if strategy == "predict":
        top_k = args.top_k if args.top_k > 0 \
            else costmodel.default_top_k(len(grid))
        dev = _device_kind()
        best, results, report = autotune.predict_then_measure(
            space_obj, measure,
            lambda cfg: model.predict_one(sig, cfg, device_kind=dev),
            ctx=ctx, top_k=top_k)
        saved = report["saved"]
    else:
        best, results = autotune.run_search(space_obj, measure, ctx=ctx,
                                            max_trials=args.max_trials)
        saved = 0
    finite = [r for r in results
              if isinstance(r["seconds"], (int, float))
              and math.isfinite(r["seconds"])]
    failed = len(results) - len(finite)
    if not finite:
        print("autotune: every candidate for %s failed — nothing recorded"
              % kernel, file=sys.stderr)
        _emit({"kind": kind, "kernel": kernel, "sig": sig, "cached": False,
               "measurements": len(results), "failed": failed,
               "strategy": strategy, "config": None})
        return 1
    default_s = results[0]["seconds"]
    default_ok = isinstance(default_s, (int, float)) \
        and math.isfinite(default_s)
    best_s = min(r["seconds"] for r in finite)
    meta = {"default_s": round(default_s, 6) if default_ok else None,
            "best_s": round(best_s, 6), "trials": len(results),
            "strategy": strategy, "grid": len(grid)}
    if failed:
        meta["failed"] = failed
    meta.update(meta_extra or {})
    # compile plane (ISSUE 13): under MXNET_COSTPLANE every successful
    # trial carried measured XLA cost features — persist them with the
    # winner (the learned cost model's training rows).  Gate off ⇒
    # features_for returns None and the meta stays byte-identical.
    trial_costs = []
    for r in finite:
        feats = autotune.measure.features_for(kernel, r["config"])
        if feats is not None:
            trial_costs.append(dict(config=r["config"],
                                    seconds=round(r["seconds"], 6),
                                    cost=feats))
    if trial_costs:
        meta["cost"] = autotune.measure.features_for(kernel, best)
        meta["trial_costs"] = trial_costs
    autotune.record(kernel, sig, best, score=best_s, meta=meta)
    for r in results:
        ok = isinstance(r["seconds"], (int, float)) \
            and math.isfinite(r["seconds"])
        print("  %-28s %s%s" % (
            r["config"],
            "%.6f s" % r["seconds"] if ok else "FAILED",
            "  (default)" if r is results[0] else ""))
    payload = {"kind": kind, "kernel": kernel, "sig": sig, "cached": False,
               "measurements": len(results), "config": best,
               "default_s": round(default_s, 6) if default_ok else None,
               "best_s": round(best_s, 6), "strategy": strategy,
               "grid": len(grid), "trials_saved": saved}
    if failed:
        payload["failed"] = failed
    payload.update(emit_extra or {})
    _emit(payload)
    return 0


def _search_dconv(args):
    """Measured grid search over the dconv_col_pallas block-shape space at
    one concrete problem shape (fwd + bwd, the kernel's real usage)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mxnet_tpu import autotune
    from mxnet_tpu.ops.pallas_kernels import dconv_col_pallas

    H, W, C, BG, N = args.h, args.w, args.c, args.bg, args.n
    HW = H * W
    dtype = jnp.dtype(args.dtype)
    itemsize = dtype.itemsize
    sig = autotune.dconv_shape_sig(N, HW, C, itemsize)
    kernel = "dconv_col_pallas"
    if _warm_hit(kernel, sig, "dconv", args):
        return 0

    rng = np.random.RandomState(args.seed)
    if args.offset is None:
        # every sample anywhere on the map: the band's worst case (each row
        # block contracts over the whole map), traffic no detector sends
        y0 = rng.randint(0, max(1, H - 1), (BG, N)).astype(np.int32)
        x0 = rng.randint(0, max(1, W - 1), (BG, N)).astype(np.int32)
        y1, x1 = np.minimum(y0 + 1, H - 1), np.minimum(x0 + 1, W - 1)
        ly = rng.rand(BG, N).astype(np.float32)
        lx = rng.rand(BG, N).astype(np.float32)
        lf = (rng.rand(BG, N) > 0.2).astype(np.float32)
    else:
        # what a deformable layer sends: the first N rows (tap-major) of a
        # dilated 3x3 grid plus offsets below --offset cells
        from mxnet_tpu.test_utils import dconv_sample_inputs

        if N > 9 * HW:
            raise SystemExit("autotune: --offset draws 9*h*w = %d rows, "
                             "--n asks for %d" % (9 * HW, N))
        y0, y1, x0, x1, ly, lx, lf = (
            a[:, :N] for a in dconv_sample_inputs(rng, BG, (H, W),
                                                  args.offset))
    y0, y1, x0, x1, ly, lx, lf = map(jnp.asarray,
                                     (y0, y1, x0, x1, ly, lx, lf))
    # channels-major on both sides of the kernel: ft^T in, col^T out
    ft = jnp.asarray(rng.randn(BG, C, HW)).astype(dtype)
    g = jnp.asarray(rng.randn(BG, C, N).astype(np.float32))
    # the compiled kernel exists only on TPU; elsewhere measure the
    # interpreter (relative ordering only — label the numbers honestly)
    interpret = jax.default_backend() != "tpu"

    def build():
        # a FRESH jit per candidate: the override pins the config for THIS
        # trace, and no signature cache can hand back another candidate
        @jax.jit
        def step(ly, lx, lf, ft):
            def loss(ly, lx, lf, ft):
                out = dconv_col_pallas(y0, y1, x0, x1, ly, lx, lf, ft,
                                       (H, W), interpret)
                return jnp.sum(out.astype(jnp.float32) * g)

            return jax.grad(loss, argnums=(0, 1, 2, 3))(ly, lx, lf, ft)

        return step

    space = autotune.get_space(kernel)
    ctx = {"N": N, "HW": HW, "C": C, "itemsize": itemsize}
    # dedupe by EFFECTIVE block size (nblk caps at N): measuring the same
    # realized grid twice wastes trials and can only add timer noise
    configs, seen = [], set()
    for cfg in space.configs(**ctx):
        eff = min(int(cfg["nblk"]), N)
        if eff not in seen:
            seen.add(eff)
            configs.append(cfg)
    eff_space = autotune.TuningSpace(
        kernel, {"nblk": tuple(c["nblk"] for c in configs)},
        space.default, space.constraint)

    def measure(cfg):
        return autotune.measure_candidate(
            kernel, cfg, build, (ly, lx, lf, ft),
            warmup=args.warmup, repeat=args.repeat)

    return _run_and_finish(kernel, sig, "dconv", eff_space, ctx, measure,
                           args,
                           meta_extra={"backend": jax.default_backend(),
                                       "interpret": interpret, "bg": BG},
                           emit_extra={"interpret": interpret})


def _search_ladder(args):
    """Pure-host ladder proposal from a recorded request trace."""
    from mxnet_tpu import autotune

    recs = autotune.ladder.load_trace(args.trace)
    if args.sample_shape:
        # store under the ENGINE's declared sample shapes: on a
        # variable-size stream the trace's elementwise-max shapes can
        # differ from what Engine(sample_shapes=...) will look up
        shapes = {}
        for spec in args.sample_shape:
            name, _, dims = spec.partition(":")
            shapes[name] = tuple(int(d) for d in dims.split(",") if d)
    else:
        shapes = autotune.ladder.trace_sample_shapes(recs)
    sig = autotune.ladder_sig(shapes)
    print("autotune: ladder signature %r" % sig)
    kernel = autotune.LADDER_KERNEL
    if not args.force:
        winner = autotune.lookup(kernel, sig)
        if winner is not None:
            _emit({"kind": "ladder", "kernel": kernel, "sig": sig,
                   "cached": True, "measurements": 0, "config": winner})
            print("autotune: warm store hit for %s — zero measurements "
                  "(--force to re-search)" % sig)
            return 0
    try:
        default = tuple(sorted({int(x) for x in
                                str(args.default_ladder).split(",")
                                if x.strip()}))
    except ValueError:
        default = ()
    if not default or default[0] < 1:
        print("autotune: --default-ladder must be comma-separated positive "
              "ints, got %r" % args.default_ladder, file=sys.stderr)
        return 2
    tuned, rep = autotune.propose(
        recs, default=default, max_rungs=args.max_rungs,
        max_wait_s=args.max_wait_ms / 1000.0)
    autotune.record(kernel, sig, {"batch_sizes": list(tuned)},
                    score=rep["objective_tuned"],
                    meta={"trace": os.path.basename(args.trace),
                          "requests": rep["requests"],
                          "objective_default": rep["objective_default"],
                          "default": list(default)})
    print("autotune: %d requests  default %s obj %.4f  ->  tuned %s obj %.4f"
          % (rep["requests"], default, rep["objective_default"],
             tuned, rep["objective_tuned"]))
    _emit({"kind": "ladder", "kernel": kernel, "sig": sig, "cached": False,
           "measurements": 0, "config": {"batch_sizes": list(tuned)},
           "objective_default": round(rep["objective_default"], 6),
           "objective_tuned": round(rep["objective_tuned"], 6),
           "requests": rep["requests"]})
    return 0


def _search_nms(args):
    """Measured search over the blocked-NMS box-tile space at one N."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mxnet_tpu import autotune
    from mxnet_tpu.ops.pallas_kernels import nms_alive_pallas

    kernel = "nms_alive_pallas"
    N = args.nms_boxes
    sig = autotune.nms_shape_sig(1, N)
    if _warm_hit(kernel, sig, "nms", args):
        return 0
    rng = np.random.RandomState(args.seed)
    # clustered unit-square corner boxes: enough overlap that suppression
    # actually iterates (an all-disjoint set would measure the no-op path)
    wh = rng.rand(N, 2).astype(np.float32) * 0.2 + 0.05
    xy = rng.rand(N, 2).astype(np.float32) * 0.8
    boxes = jnp.asarray(np.concatenate([xy, xy + wh], axis=1))
    valid = jnp.ones((N,), bool)
    interpret = jax.default_backend() != "tpu"

    def build():
        # fresh jit per candidate; _nms_single's cached custom_vmap fn is
        # NOT jitted, so each outer trace re-reads the pinned tile
        @jax.jit
        def run(b, v):
            return nms_alive_pallas(b, v, None, thresh=0.5,
                                    interpret=interpret)

        return run

    def measure(cfg):
        return autotune.measure_candidate(kernel, cfg, build, (boxes, valid),
                                          warmup=args.warmup,
                                          repeat=args.repeat)

    return _run_and_finish(kernel, sig, "nms", autotune.get_space(kernel),
                           {"N": N}, measure, args,
                           meta_extra={"backend": jax.default_backend(),
                                       "interpret": interpret},
                           emit_extra={"interpret": interpret})


def _search_quant(args, kernel):
    """Measured search over one tiled-elementwise int8 row-block space."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mxnet_tpu import autotune
    from mxnet_tpu.ops import pallas_kernels as pk

    rows = args.q_rows
    quantize = kernel == "quantize_int8_pallas"
    in_itemsize = 4 if quantize else 1
    out_itemsize = 1 if quantize else 4
    sig = autotune.quant_shape_sig(rows, in_itemsize)
    if _warm_hit(kernel, sig, "quant", args):
        return 0
    rng = np.random.RandomState(args.seed)
    if quantize:
        x = jnp.asarray(rng.randn(rows, pk._LANE).astype(np.float32))
        fn = pk.quantize_int8_pallas
    else:
        x = jnp.asarray(rng.randint(-127, 128,
                                    (rows, pk._LANE)).astype(np.int8))
        fn = pk.dequantize_int8_pallas
    interpret = jax.default_backend() != "tpu"

    def build():
        # the kernel entry is itself module-level @jax.jit: drop its trace
        # cache so THIS candidate's pinned block shapes the inner jaxpr (a
        # same-shape hit would silently reuse the previous candidate's grid)
        try:
            fn.clear_cache()
        except Exception:
            pass

        @jax.jit
        def run(x):
            return fn(x, 4.0, interpret=interpret)

        return run

    def measure(cfg):
        return autotune.measure_candidate(kernel, cfg, build, (x,),
                                          warmup=args.warmup,
                                          repeat=args.repeat)

    ctx = {"rows": rows, "in_itemsize": in_itemsize,
           "out_itemsize": out_itemsize}
    return _run_and_finish(kernel, sig, "quant", autotune.get_space(kernel),
                           ctx, measure, args,
                           meta_extra={"backend": jax.default_backend(),
                                       "interpret": interpret},
                           emit_extra={"interpret": interpret})


def _search_quantize(args):
    return _search_quant(args, "quantize_int8_pallas")


def _search_dequantize(args):
    return _search_quant(args, "dequantize_int8_pallas")


def _search_fused_step(args):
    """Measured search over the NON-kernel fused-step layout space (ISSUE
    18): ZeRO-1 on/off × input prefetch depth, timed end-to-end as a short
    training epoch of a tiny MLP Module.  The winner is adopted by
    operators (set ``MXNET_FUSED_ZERO`` / ``PrefetchingIter(
    prefetch_depth=...)`` from ``show``), not by a trace-time site."""
    import jax
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import autotune
    from mxnet_tpu import module as mod_mod
    from mxnet_tpu import parallel
    from mxnet_tpu.io import NDArrayIter, PrefetchingIter

    kernel = "fused_step_layout"
    batch, dim = args.fs_batch, args.fs_dim
    ndev = jax.device_count()
    use_mesh = ndev >= 2 and batch % ndev == 0
    sig = autotune.fused_step_sig(batch, dim, ndev if use_mesh else 1)
    if _warm_hit(kernel, sig, "fused_step", args):
        return 0
    os.environ["MXNET_MODULE_FUSED_STEP"] = "1"
    mesh = parallel.make_mesh({"dp": ndev}) if use_mesh else None
    mx.random.seed(args.seed)
    rng = np.random.RandomState(args.seed)
    rows = batch * args.fs_steps
    data = rng.randn(rows, dim).astype(np.float32)
    label = rng.randint(0, 4, (rows,)).astype(np.float32)

    def measure(cfg):
        # the layout knobs are env/wrapper state, not a trace-time store
        # lookup: pin them around a fresh Module per candidate (the fused
        # stepper's stale() check rebuilds on a MXNET_FUSED_ZERO flip)
        prev = os.environ.get("MXNET_FUSED_ZERO")
        os.environ["MXNET_FUSED_ZERO"] = str(int(cfg.get("zero", 0)))
        depth = int(cfg.get("prefetch", 0))
        holder = {}

        def build():
            d = mx.sym.var("data")
            h = mx.sym.FullyConnected(d, name="fc1", num_hidden=16)
            h = mx.sym.Activation(h, name="relu1", act_type="relu")
            sym = mx.sym.SoftmaxOutput(
                mx.sym.FullyConnected(h, name="fc2", num_hidden=4),
                name="softmax")
            mod = mod_mod.Module(sym, mesh=mesh)
            mod.bind(data_shapes=[("data", (batch, dim))],
                     label_shapes=[("softmax_label", (batch,))])
            mod.init_params()
            mod.init_optimizer(optimizer="sgd",
                               optimizer_params={"learning_rate": 0.1,
                                                 "momentum": 0.9})
            base = NDArrayIter(data, label, batch_size=batch)
            # prefetch=0 means NO wrapper: PrefetchingIter's depth-0 queue
            # would be UNBOUNDED, the opposite of "prefetch disabled"
            it = PrefetchingIter(base, prefetch_depth=depth) if depth \
                else base
            holder["it"] = it

            def epoch():
                it.reset()
                out = None
                for b in it:
                    mod.forward_backward(b)
                    mod.update()
                    out = mod.get_outputs()[0]
                return out.asnumpy()

            return epoch

        try:
            return autotune.measure_candidate(
                kernel, cfg, build, (), warmup=args.warmup,
                repeat=args.repeat)
        finally:
            stop = getattr(holder.get("it"), "_stop", None)
            if stop is not None:
                stop.set()  # don't leak a prefetch worker between trials
            if prev is None:
                os.environ.pop("MXNET_FUSED_ZERO", None)
            else:
                os.environ["MXNET_FUSED_ZERO"] = prev

    return _run_and_finish(kernel, sig, "fused_step",
                           autotune.get_space(kernel), {"mesh": use_mesh},
                           measure, args,
                           meta_extra={"backend": jax.default_backend(),
                                       "ndev": ndev,
                                       "steps": args.fs_steps})


# kernel name -> measured-search runner; a space registered in
# autotune.space without an entry here is a clean CLI error, not a crash
_KERNEL_RUNNERS = {
    "dconv_col_pallas": _search_dconv,
    "nms_alive_pallas": _search_nms,
    "quantize_int8_pallas": _search_quantize,
    "dequantize_int8_pallas": _search_dequantize,
    "fused_step_layout": _search_fused_step,
}


def _show(args):
    from mxnet_tpu import autotune

    ent = autotune.entries()
    if not ent:
        print("autotune: store %s is empty" % autotune.store_path())
        return 0
    print("autotune: %d entr%s in %s"
          % (len(ent), "y" if len(ent) == 1 else "ies",
             autotune.store_path()))
    for key in sorted(ent):
        e = ent[key]
        score = e.get("score")
        print("  %-60s %s%s" % (key, e.get("config"),
                                "" if score is None
                                else "  score=%.6g" % score))
        if getattr(args, "features", False):
            meta = e.get("meta") if isinstance(e.get("meta"), dict) else {}
            cost = meta.get("cost")
            if cost:
                print("      cost: %s" % json.dumps(cost, sort_keys=True))
            tcs = meta.get("trial_costs")
            if tcs:
                print("      trial rows: %d (strategy=%s, grid=%s)"
                      % (len(tcs), meta.get("strategy", "grid"),
                         meta.get("grid")))
    return 0


def _clear(args):
    from mxnet_tpu import autotune

    n = autotune.clear(kernel=args.kernel)
    print("autotune: removed %d entr%s%s" % (
        n, "y" if n == 1 else "ies",
        " for kernel %s" % args.kernel if args.kernel else ""))
    return 0


def _search_cmd(args):
    """search dispatch: ladder trace, one kernel, or --all-kernels; ends
    with one ``AUTOTUNE {"kind": "telemetry", ...}`` line (the bench
    telemetry block, trials_saved included) when telemetry is on."""
    if args.trace:
        rc = _search_ladder(args)
    elif args.all_kernels:
        rc = 0
        for name in sorted(_KERNEL_RUNNERS):
            print("autotune: === %s ===" % name)
            rc = max(rc, _KERNEL_RUNNERS[name](args))
    else:
        rc = _KERNEL_RUNNERS[args.kernel](args)
    from mxnet_tpu.telemetry import instrument as tin

    if tin.enabled():
        _emit({"kind": "telemetry", "telemetry": tin.summary()})
    return rc


def main(argv=None):
    # the CLI is the explicit opt-in: its own process always runs tuned
    os.environ["MXNET_AUTOTUNE"] = "1"
    p = argparse.ArgumentParser(prog="autotune",
                                description=__doc__.split("\n")[0])
    sub = p.add_subparsers(dest="cmd", required=True)

    s = sub.add_parser("search", help="search a kernel space or propose a "
                                      "ladder from a traffic trace")
    s.add_argument("--kernel", default=None,
                   help="registered tuning space to search (e.g. "
                        "dconv_col_pallas); omit with --trace")
    s.add_argument("--trace", default=None,
                   help="loadgen --save-trace JSONL: propose bucket-ladder "
                        "rungs instead of searching a kernel space")
    s.add_argument("--all-kernels", action="store_true",
                   help="search every runnable kernel space in turn "
                        "(shapes from the per-kernel flags below)")
    s.add_argument("--force", action="store_true",
                   help="re-search even on a warm store hit")
    s.add_argument("--strategy", choices=("auto", "grid", "predict"),
                   default="auto",
                   help="auto (default): predict-then-measure when the "
                        "learned cost model has enough stored rows, else "
                        "exhaustive grid; grid/predict force one (predict "
                        "degrades to grid with a message if unusable)")
    s.add_argument("--top-k", type=int, default=0,
                   help="candidates measured under predict (beyond the "
                        "always-measured default); 0 = MXNET_AUTOTUNE_TOPK "
                        "or a quarter of the grid")
    # dconv problem shape (defaults: a CPU-sized smoke problem; use the
    # north-star res5 shape on the chip: --bg 32 --n 21888 --h 38 --w 64
    # --c 128 --dtype bfloat16 --offset 1)
    s.add_argument("--bg", type=int, default=1, help="batch x groups")
    s.add_argument("--n", type=int, default=128, help="sample rows")
    s.add_argument("--h", type=int, default=4)
    s.add_argument("--w", type=int, default=8)
    s.add_argument("--c", type=int, default=16, help="channels per group")
    s.add_argument("--dtype", default="float32")
    s.add_argument("--offset", type=float, default=None,
                   help="dconv samples as a deformable layer sends them: a "
                        "dilated 3x3 grid plus offsets below this many "
                        "cells (--n <= 9*h*w rows of it); unset = uniform "
                        "over the map, the band's worst case")
    s.add_argument("--warmup", type=int, default=2)
    s.add_argument("--repeat", type=int, default=5)
    s.add_argument("--max-trials", type=int, default=64)
    s.add_argument("--seed", type=int, default=0)
    # nms_alive_pallas problem shape
    s.add_argument("--nms-boxes", type=int, default=512,
                   help="boxes per image for the NMS tile search")
    # quantize/dequantize_int8_pallas problem shape
    s.add_argument("--q-rows", type=int, default=1024,
                   help="(rows, 128) flattened tiles for the int8 kernels")
    # fused_step_layout problem shape
    s.add_argument("--fs-batch", type=int, default=16)
    s.add_argument("--fs-dim", type=int, default=8)
    s.add_argument("--fs-steps", type=int, default=4,
                   help="train steps per timed epoch")
    # ladder proposal knobs
    s.add_argument("--default-ladder", default="1,2,4,8",
                   help="the hand-configured ladder the proposal must "
                        "strictly beat (else it is kept)")
    s.add_argument("--max-rungs", type=int, default=4)
    s.add_argument("--max-wait-ms", type=float, default=5.0,
                   help="partial-batch flush deadline assumed by the "
                        "replay (match the Engine's MXNET_SERVE_MAX_WAIT_MS)")
    s.add_argument("--sample-shape", action="append", metavar="NAME:D1,D2",
                   help="store the ladder winner under these declared "
                        "per-sample shapes (repeatable; loadgen --shapes "
                        "syntax) instead of the trace's elementwise-max "
                        "shapes — required when the serving Engine "
                        "declares larger sample_shapes than the recorded "
                        "traffic ever reached, or its lookup would miss")
    s.set_defaults(fn=_search_cmd)

    sh = sub.add_parser("show", help="list persisted winners")
    sh.add_argument("--features", action="store_true",
                    help="also print each winner's persisted cost features "
                         "and trial-row counts (the model's training set)")
    sh.set_defaults(fn=_show)

    c = sub.add_parser("clear", help="drop persisted winners")
    c.add_argument("--kernel", default=None,
                   help="only this kernel's entries (default: everything)")
    c.set_defaults(fn=_clear)

    args = p.parse_args(argv)
    if args.cmd == "search" and not args.trace and not args.kernel \
            and not args.all_kernels:
        p.error("search needs --kernel <space>, --all-kernels, or "
                "--trace <jsonl>")
    if args.cmd == "search" and args.all_kernels and (args.kernel
                                                     or args.trace):
        p.error("--all-kernels replaces --kernel/--trace")
    if args.cmd == "search" and args.kernel is not None:
        # validate against the live registry, not a frozen list: a newly
        # registered space is rejected only until it gains a measurement
        # runner below
        from mxnet_tpu import autotune

        registered = sorted(autotune.spaces())
        if args.kernel not in registered:
            p.error("unknown kernel %r (registered: %s)"
                    % (args.kernel, ", ".join(registered)))
        if args.kernel not in _KERNEL_RUNNERS:
            p.error("no measurement runner for kernel %r yet (runnable: %s)"
                    % (args.kernel, ", ".join(sorted(_KERNEL_RUNNERS))))
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
