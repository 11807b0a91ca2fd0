"""Companion script for docs/tutorials/performance.md — the performance
prescriptions from docs/PERF_NOTES.md as runnable code (reference
``docs/faq/perf.md``): one fused train step, bf16 mixed precision, state
donation, remat, and reading the compiled module's cost analysis."""
from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", ".."))

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import gluon, nd
from mxnet_tpu.gluon.functional import make_train_step

import jax


def build():
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Conv2D(32, 3, padding=1, activation="relu"),
            gluon.nn.BatchNorm(),
            gluon.nn.Conv2D(64, 3, padding=1, strides=2, activation="relu"),
            gluon.nn.BatchNorm(),
            gluon.nn.GlobalAvgPool2D(),
            gluon.nn.Dense(10))
    net.initialize()
    net(nd.zeros((2, 3, 32, 32)))
    return net


rng = np.random.RandomState(0)
X = rng.rand(64, 3, 32, 32).astype(np.float32)
y = (rng.rand(64) * 10).astype(np.float32)
loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()

# --- prescription 1: ONE jitted train step -------------------------------
# fwd + bwd + BN stats + optimizer in a single XLA module — no per-op
# dispatch, full fusion (the reference needed engine bulking for less).
mx.random.seed(0)
step, state, _ = make_train_step(build(), loss_fn, learning_rate=0.1,
                                 momentum=0.9)
# --- prescription 2: donate the state so buffers update in place ---------
jstep = jax.jit(step, donate_argnums=(0,))
key = jax.random.PRNGKey(0)
state, loss = jstep(state, X, y, key)          # compile
jax.block_until_ready(loss)

# --- prescription 3: read the compiled module's cost analysis ------------
# flops vs bytes tells you which roofline you are on; detection/CNN steps
# here are HBM-bound (PERF_NOTES: ResNet-50 at 152 GB/step vs 10 TF)
comp = jax.jit(step, donate_argnums=(0,)).lower(state, X, y, key).compile()
ca = comp.cost_analysis()
ca = ca[0] if isinstance(ca, (list, tuple)) else (ca or {})
flops, gbytes = ca.get("flops", 0) / 1e9, ca.get("bytes accessed", 0) / 1e9
print("cost analysis: %.2f GFLOP, %.3f GB accessed per step" % (flops, gbytes))
assert gbytes > 0

# --- prescription 4: bf16 compute, fp32 master params --------------------
# halves HBM traffic on the bound that matters; loss/BN stats stay fp32
mx.random.seed(0)
step16, state16, _ = make_train_step(build(), loss_fn, learning_rate=0.1,
                                     momentum=0.9, compute_dtype="bfloat16")
jstep16 = jax.jit(step16, donate_argnums=(0,))
state16, loss16 = jstep16(state16, X, y, key)
jax.block_until_ready(loss16)
print("bf16 step loss %.4f (fp32 %.4f) — master params stay fp32: %s"
      % (float(loss16), float(loss), state16[0][0].dtype))
assert state16[0][0].dtype == np.float32

# --- prescription 5: remat when activations crowd HBM --------------------
# ≡ the reference's MXNET_BACKWARD_DO_MIRROR, but ~free on memory-bound
# models (PERF_NOTES measured ~2% vs the reference's ~30%)
net_r = build()
net_r.set_remat(True)
mx.random.seed(0)
step_r, state_r, _ = make_train_step(net_r, loss_fn, learning_rate=0.1)
state_r, loss_r = jax.jit(step_r, donate_argnums=(0,))(state_r, X, y, key)
print("remat step runs: loss %.4f" % float(loss_r))

# --- prescription 6: measure honestly ------------------------------------
# chain steps with donated state and fetch ONE scalar; timing each step
# with a device sync measures dispatch latency, not the chip
# (docs/PERF_NOTES.md "Measurement note")
for _ in range(3):
    state, loss = jstep(state, X, y, key)
t0 = time.perf_counter()
K = 10
for _ in range(K):
    state, loss = jstep(state, X, y, key)
float(loss)
print("chained measurement: %.2f ms/step over %d steps"
      % ((time.perf_counter() - t0) / K * 1e3, K))

print("PERF-TUNING TUTORIAL OK")
