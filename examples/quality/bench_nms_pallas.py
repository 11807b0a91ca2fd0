"""Head-to-head: XLA blocked NMS vs the Pallas NMS kernel, on the chip.

VERDICT r2 item 3 — the "Pallas where profiling justifies it" claim needs
profiling that includes the Pallas side.  This benches the north-star NMS
shapes (rpn_pre_nms_top_n=6000 single-class, reference
multi_proposal.cc:221-273 / rcnn config) and the SSD-512 decode shape
(24,564 anchors x 20-class per-class NMS, multibox_detection.cc:83-190)
for both implementations, checks they agree on-chip, and prints a table
for docs/PERF_NOTES.md.

Run:  python examples/quality/bench_nms_pallas.py
"""
from __future__ import annotations

import os
import sys
import time

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(_HERE, "..", ".."))

import numpy as np
import jax
import jax.numpy as jnp

from mxnet_tpu.ops.detection import _nms_alive_blocked
from mxnet_tpu.ops.pallas_kernels import nms_alive_pallas


def make_boxes(n, seed, extent=1000.0):
    rng = np.random.RandomState(seed)
    ctr = rng.uniform(0, extent, (n, 2))
    wh = rng.uniform(8, 300, (n, 2))
    return np.concatenate([ctr - wh / 2, ctr + wh / 2], 1).astype(np.float32)


def bench(step, boxes, valid, ids, iters=256):
    """Chained on-device timing that cancels the host round trip.

    Run K data-dependent NMS steps inside ONE jitted fori_loop (each
    step's boxes are nudged by the previous survivor count, forcing
    sequential execution), fetch the final scalar to host, and report
    (T(K) - T(1)) / (K - 1) so dispatch and fetch latency drop out.
    """
    import functools

    @functools.partial(jax.jit, static_argnames=("k",))
    def chain(b, v, i, k):
        def body(_, carry):
            bx, acc = carry
            alive = step(bx, v, i)
            s = alive.sum().astype(jnp.float32)
            return bx + 1e-30 * s, acc + s

        _, acc = jax.lax.fori_loop(0, k, body, (b, jnp.float32(0)))
        return acc

    def timed(k):
        float(chain(boxes, valid, ids, k))  # compile
        best = None
        for _ in range(5):
            t0 = time.perf_counter()
            float(chain(boxes, valid, ids, k))
            dt = time.perf_counter() - t0
            best = dt if best is None else min(best, dt)
        return best

    t1, tk = timed(1), timed(iters)
    ms = (tk - t1) / (iters - 1) * 1e3
    return ms, step(boxes, valid, ids)


def main():
    print(f"backend: {jax.default_backend()}  device: {jax.devices()[0]}")
    rows = []
    for name, n, ids_n, iters in [
            ("proposal 6000 (north star)", 6000, 0, 2048),
            ("proposal 12000", 12000, 0, 512),
            ("ssd-512 decode 24564 x 20cls", 24564, 20, 256)]:
        boxes = jnp.asarray(make_boxes(n, 7))
        valid = jnp.ones((n,), bool)
        if ids_n:
            ids = jnp.asarray(np.random.RandomState(1).randint(0, ids_n, n))
            fs, po = False, 0.0
        else:
            ids, fs, po = None, True, 1.0

        # _nms_alive_blocked auto-dispatches to pallas on TPU now; pin the
        # XLA side explicitly so this stays a real head-to-head
        os.environ["MXNET_NMS_IMPL"] = "xla"
        xla = lambda b, v, i: _nms_alive_blocked(
            b, 0.7, valid=v, ids=i, force_suppress=fs, plus_one=po)
        pal = lambda b, v, i: nms_alive_pallas(
            b, v, i, thresh=0.7, plus_one=po, force_suppress=fs)

        t_x, r_x = bench(xla, boxes, valid, ids, iters=iters)
        t_p, r_p = bench(pal, boxes, valid, ids, iters=iters)
        os.environ.pop("MXNET_NMS_IMPL", None)
        agree = bool((np.asarray(r_x) == np.asarray(r_p)).all())
        rows.append((name, n, t_x, t_p, int(np.asarray(r_x).sum()), agree))
        print(f"{name:32s} N={n:6d}  xla {t_x:7.2f} ms  pallas {t_p:7.2f} ms"
              f"  speedup {t_x / t_p:5.2f}x  survivors={rows[-1][4]}"
              f"  agree={agree}")

    print("\n| shape | N | XLA blocked | Pallas | speedup |")
    print("|---|---|---|---|---|")
    for name, n, t_x, t_p, _, agree in rows:
        assert agree, f"MISMATCH on {name}"
        print(f"| {name} | {n} | {t_x:.2f} ms | {t_p:.2f} ms "
              f"| {t_x / t_p:.2f}x |")


if __name__ == "__main__":
    main()
