"""Input-pipeline throughput: ImageRecordIter → device → jitted train step.

The reference keeps its GPUs fed with a multithreaded C++ decode+augment
pipeline (``src/io/iter_image_recordio_2.cc:50,663``).  This script
measures each stage of the equivalent path here — native RecordIO/JPEG
batch loader, host→device transfer, double-buffered prefetch into the
jitted ResNet-50 train step — and reports the end-to-end steady state
next to the synthetic-batch number.

Environment honesty (documented in docs/PERF_NOTES.md): the numbers on
record were taken on an earlier installation with ONE CPU core and a
~47 MB/s H2D / ~13 MB/s D2H host link, where neither the decode (reference
used 72-vcore hosts) nor the H2D leg could keep a 2,300 img/s step fed;
the measurement proves the machinery (overlap, prefetch, native decode)
and quantifies each stage's ceiling on whatever host runs it.

Run (chip): python examples/quality/bench_input_pipeline.py
CPU smoke:  ./dev.sh python examples/quality/bench_input_pipeline.py --images 64 --batch 16 --steps 2
"""
from __future__ import annotations

import argparse
import os
import sys
import tempfile
import time
import threading

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", ".."))

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import recordio


def write_rec(path, n, hw=224, seed=0):
    rng = np.random.RandomState(seed)
    rec = recordio.MXIndexedRecordIO(path + ".idx", path, "w")
    for i in range(n):
        img = (rng.rand(hw, hw, 3) * 255).astype(np.uint8)
        rec.write_idx(i, recordio.pack_img(
            recordio.IRHeader(0, float(i % 10), i, 0), img, quality=85))
    rec.close()


def main():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--images", type=int, default=512)
    p.add_argument("--batch", type=int, default=64)
    p.add_argument("--steps", type=int, default=8)
    p.add_argument("--image-size", type=int, default=224)
    args = p.parse_args()

    import jax

    on_tpu = any(d.platform == "tpu" for d in jax.devices())
    tmp = tempfile.mkdtemp()
    rec_path = os.path.join(tmp, "bench.rec")
    t0 = time.perf_counter()
    write_rec(rec_path, args.images, args.image_size)
    print("wrote %d jpegs in %.1fs" % (args.images, time.perf_counter() - t0))

    # -- stage 1: host pipeline throughput (native decode+augment+batch) --
    it = mx.io.ImageRecordIter(
        path_imgrec=rec_path, data_shape=(3, args.image_size, args.image_size),
        batch_size=args.batch, rand_mirror=True, preprocess_threads=2)
    n = 0
    t0 = time.perf_counter()
    for batch in it:
        n += batch.data[0].shape[0] - batch.pad
    host_dt = time.perf_counter() - t0
    host_ips = n / host_dt
    print("host pipeline (native decode+augment): %.1f img/s" % host_ips)

    # -- stage 2: H2D transfer bandwidth for one batch --------------------
    it.reset()
    first = next(iter(it))
    arr = first.data[0].asnumpy()
    mb = arr.nbytes / 1e6
    t0 = time.perf_counter()
    d = jax.device_put(arr)
    jax.block_until_ready(d)
    h2d_dt = time.perf_counter() - t0
    print("H2D: %.1f MB batch in %.2fs (%.1f MB/s)" % (mb, h2d_dt, mb / h2d_dt))

    from mxnet_tpu.gluon import loss as loss_mod
    from mxnet_tpu.gluon.functional import make_train_step
    from __graft_entry__ import _build_resnet

    net = _build_resnet(classes=10, version=50, image_size=args.image_size)
    step, state, _ = make_train_step(
        net, loss_mod.SoftmaxCrossEntropyLoss(), learning_rate=0.05,
        momentum=0.9, compute_dtype="bfloat16" if on_tpu else None)
    jstep = jax.jit(step, donate_argnums=(0,))
    key = jax.random.PRNGKey(0)

    # -- stage 3: synthetic-batch reference (also compiles the step) ------
    rng = np.random.RandomState(0)
    xs = jax.device_put(rng.randn(args.batch, 3, args.image_size,
                                  args.image_size).astype(np.float32))
    ys = jax.device_put(rng.randint(0, 10, (args.batch,)).astype(np.float32))
    state, loss = jstep(state, xs, ys, key)
    jax.block_until_ready(loss)
    # keys precomputed outside the timed window (eager fold_in costs
    # several host dispatches per step)
    kpre = [jax.random.fold_in(key, 100 + s) for s in range(args.steps)]
    jax.block_until_ready(kpre[-1])
    t0 = time.perf_counter()
    for s in range(args.steps):
        state, loss = jstep(state, xs, ys, kpre[s])
    jax.block_until_ready(loss)
    syn_dt = time.perf_counter() - t0
    syn_ips = args.steps * args.batch / syn_dt
    print("synthetic-batch step:                  %.1f img/s" % syn_ips)
    # -- stage 4: pipeline-fed train step, double-buffered ----------------
    # double-buffer: a loader thread decodes + device_puts the NEXT batch
    # while the current step runs (jax dispatch is async, so device_put and
    # compute overlap naturally; the thread hides the host decode)
    it.reset()
    it_iter = [iter(it)]
    slot = {}

    def stage(i):
        try:
            b = next(it_iter[0])
        except StopIteration:  # epoch boundary: wrap like a training loop
            it.reset()
            it_iter[0] = iter(it)
            b = next(it_iter[0])
        slot[i] = (jax.device_put(b.data[0].asnumpy()),
                   jax.device_put(b.label[0].asnumpy()))

    stage(0)
    kfeed = [jax.random.fold_in(key, s) for s in range(args.steps)]
    jax.block_until_ready(kfeed[-1])
    t0 = time.perf_counter()
    loader = None
    done = 0
    for s in range(args.steps):
        if loader is not None:
            loader.join()
        x, y = slot.pop(s)
        if s + 1 < args.steps:
            loader = threading.Thread(target=stage, args=(s + 1,))
            loader.start()
        state, loss = jstep(state, x, y, kfeed[s])
        done += args.batch
    jax.block_until_ready(loss)
    fed_dt = time.perf_counter() - t0
    fed_ips = done / fed_dt
    print("pipeline-fed train step (double-buffered): %.1f img/s "
          "over %d steps" % (fed_ips, args.steps))

    print("SUMMARY input_pipeline: host=%.1f h2d=%.1fMB/s fed=%.1f "
          "synthetic=%.1f img/s (batch %d)"
          % (host_ips, mb / h2d_dt, fed_ips, syn_ips, args.batch))


if __name__ == "__main__":
    main()
