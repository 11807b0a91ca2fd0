"""Chip perf for the two perf-motivated non-detection op families
(VERDICT r4 item 3): both had correctness evidence but no chip numbers,
while the reference treats both as *performance* features.

(a) **Fused RNN** — the reference justifies its fused RNN op by kernel
    fusion (``src/operator/rnn-inl.h``, cuDNN ``cudnn_rnn-inl.h``): one
    call instead of per-step ops.  Here the fused op is ``ops/rnn.py``'s
    single ``lax.scan`` per layer with the input projection hoisted into
    one big MXU matmul; the baseline is the same cell math traced
    UNROLLED with a per-step input projection — the shape a user gets
    from ``rnn_cell.LSTMCell().unroll`` (the reference's non-fused path).
    Measured: LSTM LM train-step tokens/s (embed 512 → 2×LSTM(512) →
    vocab-10k softmax, batch 32, seq 64).

(b) **INT8 quantization** — the whole point of
    ``example/quantization`` in the reference is measured speedup.
    Measured: ResNet-50 (symbol zoo) batch-32 scoring img/s — fp32 vs
    bf16 vs the int8 graph produced by ``contrib.quantization
    .quantize_model`` (naive calibration) — plus the accuracy-delta
    protocol of ``examples/quantization/quantize_model.py`` for the
    quality side.

Timing rules (docs/PERF_NOTES.md): chained executions, one scalar fetch
at the end bounds the serial device queue; best-of-windows.

Run (chip): python examples/quality/perf_rnn_int8.py [--which rnn|int8]
"""
from __future__ import annotations

import argparse
import os
import sys
import time

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(_HERE, "..", ".."))

import numpy as np

import mxnet_tpu as mx


# ---------------------------------------------------------------------------
# (a) fused vs unrolled LSTM LM
# ---------------------------------------------------------------------------


def bench_rnn(batch=32, seq=64, vocab=10000, embed=512, hidden=512,
              layers=2, iters=20, windows=3, dtype="float32"):
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops.rnn import rnn as fused_rnn
    from mxnet_tpu.ops.rnn import _step_fn, _unpack_params, rnn_param_size

    dt = jnp.dtype(dtype)
    rng = np.random.RandomState(0)
    psize = rnn_param_size("lstm", embed, hidden, layers, False)
    params = dict(
        emb=jnp.asarray(rng.randn(vocab, embed).astype(np.float32) * 0.02, dt),
        rnn=jnp.asarray(rng.randn(psize).astype(np.float32) * 0.05, dt),
        wo=jnp.asarray(rng.randn(hidden, vocab).astype(np.float32) * 0.02, dt),
        bo=jnp.zeros((vocab,), dt),
    )
    tokens = jnp.asarray(rng.randint(0, vocab, (batch, seq + 1)))

    def unrolled_rnn(x, rnn_p):
        """Same cell math, traced unrolled with per-step projection — the
        op-per-step shape of the reference's non-fused cell path."""
        lp = _unpack_params(rnn_p, "lstm", embed, hidden, layers, 1)
        step = _step_fn("lstm", hidden)
        for layer in range(layers):
            wi, wh, bi, bh = lp[layer]
            carry = (jnp.zeros((batch, hidden), x.dtype),
                     jnp.zeros((batch, hidden), x.dtype))
            ys = []
            for t in range(x.shape[0]):
                xg = x[t] @ wi.T + bi
                carry, y = step(carry, xg, wh, bh)
                ys.append(y)
            x = jnp.stack(ys)
        return x

    def make_step(fused):
        def loss_fn(p, tokens):
            x = p["emb"][tokens[:, :-1]]          # (B, T, E)
            xs = x.transpose(1, 0, 2)             # (T, B, E) sequence-major
            if fused:
                z = jnp.zeros((layers, batch, hidden), xs.dtype)
                out, _h, _c = fused_rnn(xs, p["rnn"], z, z,
                                        state_size=hidden, num_layers=layers)
            else:
                out = unrolled_rnn(xs, p["rnn"])
            logits = out.reshape(seq * batch, hidden) @ p["wo"] + p["bo"]
            labels = tokens[:, 1:].T.reshape(-1)
            lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
            ll = jnp.take_along_axis(
                logits.astype(jnp.float32), labels[:, None], axis=1)[:, 0]
            return jnp.mean(lse - ll)

        def step(p, tokens):
            loss, g = jax.value_and_grad(loss_fn)(p, tokens)
            return {k: v - 1e-3 * g[k].astype(v.dtype) for k, v in p.items()}, loss

        return step

    results = {}
    for name, fused in (("fused(scan)", True), ("unrolled", False)):
        step = jax.jit(make_step(fused), donate_argnums=(0,))
        t0 = time.time()
        p = jax.tree_util.tree_map(jnp.copy, params)
        p, loss = step(p, tokens)
        jax.block_until_ready(loss)
        compile_s = time.time() - t0
        best = None
        for w in range(windows):
            t0 = time.perf_counter()
            for _ in range(iters):
                p, loss = step(p, tokens)
            float(loss)
            dt_s = (time.perf_counter() - t0) / iters
            best = dt_s if best is None else min(best, dt_s)
        toks = batch * seq / best
        results[name] = toks
        print("rnn %-13s compile %5.1fs  %7.2f ms/step  %9.0f tokens/s  "
              "loss %.3f" % (name, compile_s, best * 1e3, toks, float(loss)),
              flush=True)
    print("rnn fused/unrolled speedup: %.2fx"
          % (results["fused(scan)"] / results["unrolled"]), flush=True)
    return results


# ---------------------------------------------------------------------------
# (b) int8 vs bf16/fp32 ResNet-50 scoring
# ---------------------------------------------------------------------------


def _score_executor(exe, batch, iters, windows):
    """N serial forwards + ONE scalar fetch: executions serialize on the
    core, so the final fetch bounds the whole queue (timing rules)."""
    best = None
    for w in range(windows):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = exe.forward(is_train=False)
        float(out[0].sum().asnumpy())
        dt = (time.perf_counter() - t0) / iters
        best = dt if best is None else min(best, dt)
    return batch / best, best


def bench_int8(batch=32, iters=20, windows=3):
    sys.path.insert(0, os.path.join(_HERE, "..", "image-classification"))
    from importlib import import_module

    from mxnet_tpu import nd
    from mxnet_tpu.contrib.quantization import quantize_model
    from mxnet_tpu.io import NDArrayIter

    resnet = import_module("symbols.resnet")
    sym = resnet.get_symbol(num_classes=1000, num_layers=50,
                            image_shape="3,224,224")
    shape = (batch, 3, 224, 224)
    rng = np.random.RandomState(0)
    x = rng.rand(*shape).astype(np.float32)

    results = {}
    for dtype in ("float32", "bfloat16"):
        exe = sym.simple_bind(grad_req="null", data=shape,
                              type_dict={n: dtype for n in sym.list_arguments()})
        for k, v in exe.arg_dict.items():
            if k == "data":
                v[:] = x
            elif k.endswith("weight") or k.endswith("gamma"):
                v[:] = rng.randn(*v.shape).astype(np.float32) * 0.05
        t0 = time.time()
        exe.forward(is_train=False)
        compile_s = time.time() - t0
        ips, ms = _score_executor(exe, batch, iters, windows)
        results[dtype] = ips
        print("resnet50 score %-9s compile %5.1fs  %6.1f ms/batch  %8.1f img/s"
              % (dtype, compile_s, ms * 1e3, ips), flush=True)

    # int8 graph (naive calibration over one batch)
    args_p = {k: nd.array(rng.randn(*v.shape).astype(np.float32) * 0.05)
              for k, v in exe.arg_dict.items() if k != "data"}
    aux_p = {k: nd.array(np.abs(rng.randn(*v.shape)).astype(np.float32) * 0.01 + 1)
             for k, v in exe.aux_dict.items()}
    t0 = time.time()
    qsym, qargs, qaux = quantize_model(
        sym, args_p, aux_p, calib_mode="naive",
        calib_data=NDArrayIter(x, np.zeros(batch, np.float32), batch),
        num_calib_examples=batch)
    print("quantize_model (naive calib): %.1fs" % (time.time() - t0), flush=True)
    qexe = qsym.simple_bind(grad_req="null", data=shape)
    for k, v in qargs.items():
        if k in qexe.arg_dict:
            qexe.arg_dict[k][:] = v.asnumpy()
    for k, v in qaux.items():
        if k in qexe.aux_dict:
            qexe.aux_dict[k][:] = v.asnumpy()
    qexe.arg_dict["data"][:] = x
    t0 = time.time()
    qexe.forward(is_train=False)
    compile_s = time.time() - t0
    ips, ms = _score_executor(qexe, batch, iters, windows)
    results["int8"] = ips
    print("resnet50 score %-9s compile %5.1fs  %6.1f ms/batch  %8.1f img/s"
          % ("int8", compile_s, ms * 1e3, ips), flush=True)
    print("int8 vs bf16: %.2fx, vs fp32: %.2fx"
          % (results["int8"] / results["bfloat16"],
             results["int8"] / results["float32"]), flush=True)
    return results


def main():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--which", choices=("rnn", "int8", "both"), default="both")
    p.add_argument("--iters", type=int, default=20)
    p.add_argument("--batch", type=int, default=32)
    p.add_argument("--seq", type=int, default=64,
                   help="RNN sequence length (PERF_NOTES reports 64 and 256)")
    args = p.parse_args()
    if args.which in ("rnn", "both"):
        bench_rnn(batch=args.batch, seq=args.seq, iters=args.iters)
    if args.which in ("int8", "both"):
        bench_int8(batch=args.batch, iters=args.iters)


if __name__ == "__main__":
    main()
