"""Test harness — TPU-native port of reference ``python/mxnet/test_utils.py``.

Same testing philosophy as the reference (SURVEY §4): numpy oracles,
dtype-aware tolerance tables (test_utils.py:470), finite-difference gradient
checks (:792), symbolic fwd/bwd checks (:925, :999), and cross-backend
``check_consistency`` (:1207) — here CPU-vs-TPU instead of CPU-vs-GPU.
"""
from __future__ import annotations

import numpy as np

from .context import Context, cpu, current_context
from .ndarray.ndarray import NDArray, array

_rng = np.random.RandomState(1234)

# dtype-aware default tolerances (reference test_utils.py:470 table)
_DEFAULT_RTOL = {
    np.dtype(np.float16): 1e-2,
    np.dtype(np.float32): 1e-4,
    np.dtype(np.float64): 1e-5,
    np.dtype(np.bool_): 0,
    np.dtype(np.int8): 0,
    np.dtype(np.uint8): 0,
    np.dtype(np.int32): 0,
    np.dtype(np.int64): 0,
}
_DEFAULT_ATOL = {
    np.dtype(np.float16): 1e-1,
    np.dtype(np.float32): 1e-3,
    np.dtype(np.float64): 1e-20,
    np.dtype(np.bool_): 0,
    np.dtype(np.int8): 0,
    np.dtype(np.uint8): 0,
    np.dtype(np.int32): 0,
    np.dtype(np.int64): 0,
}


def default_context():
    """Context under test; switched by env like the reference (test_utils.py:53)."""
    import os

    dev = os.environ.get("MXNET_TEST_DEVICE", "")
    if dev.startswith("tpu") or dev.startswith("gpu"):
        from .context import tpu

        return tpu(0)
    return current_context()


def default_dtype():
    return np.float32


def get_atol(atol=None, dtype=np.dtype(np.float64)):
    return _DEFAULT_ATOL[np.dtype(dtype)] if atol is None else atol


def get_rtol(rtol=None, dtype=np.dtype(np.float64)):
    return _DEFAULT_RTOL[np.dtype(dtype)] if rtol is None else rtol


def _as_np(a):
    if isinstance(a, NDArray):
        return a.asnumpy()
    return np.asarray(a)


def same(a, b):
    return np.array_equal(_as_np(a), _as_np(b))


def almost_equal(a, b, rtol=None, atol=None, equal_nan=False):
    a, b = _as_np(a), _as_np(b)
    ct = np.promote_types(a.dtype, b.dtype)
    return np.allclose(a, b, get_rtol(rtol, ct), get_atol(atol, ct), equal_nan)


def assert_almost_equal(a, b, rtol=None, atol=None, names=("a", "b"), equal_nan=False):
    """Elementwise closeness with the reference's relative-error report
    (reference test_utils.py:470)."""
    a, b = _as_np(a), _as_np(b)
    ct = np.promote_types(a.dtype, b.dtype)
    rtol, atol = get_rtol(rtol, ct), get_atol(atol, ct)
    if np.allclose(a, b, rtol, atol, equal_nan):
        return
    denom = np.abs(a) + np.abs(b) + atol
    rel = np.abs(a - b) / denom
    idx = np.unravel_index(np.argmax(rel), rel.shape)
    raise AssertionError(
        "Error %f exceeds tolerance rtol=%e, atol=%e (max at %s: %s=%s, %s=%s)\n%s vs %s"
        % (rel[idx], rtol, atol, idx, names[0], a[idx], names[1], b[idx], a.flatten()[:10], b.flatten()[:10])
    )


def rand_shape_nd(ndim, dim=10):
    return tuple(_rng.randint(1, dim + 1, size=ndim))


def rand_shape_2d(dim0=10, dim1=10):
    return _rng.randint(1, dim0 + 1), _rng.randint(1, dim1 + 1)


def rand_shape_3d(dim0=10, dim1=10, dim2=10):
    return _rng.randint(1, dim0 + 1), _rng.randint(1, dim1 + 1), _rng.randint(1, dim2 + 1)


def rand_ndarray(shape, stype="default", density=None, dtype=None, ctx=None):
    """Random NDArray (reference test_utils.py:339).  Sparse stypes return the
    BCOO-backed sparse types when requested."""
    dtype = dtype or np.float32
    data = _rng.uniform(-1.0, 1.0, size=shape).astype(dtype)
    if stype == "default":
        return array(data, ctx=ctx)
    from .ndarray import sparse

    if density is not None:
        mask = _rng.uniform(0, 1, size=shape) < density
        data = data * mask
    return sparse.cast_storage(array(data, ctx=ctx), stype=stype)


def random_arrays(*shapes):
    arrays = [np.array(_rng.randn(), dtype=np.float64) if len(s) == 0 else _rng.randn(*s).astype(np.float64) for s in shapes]
    if len(arrays) == 1:
        return arrays[0]
    return arrays


def check_numeric_gradient(
    f,
    locations,
    grads=None,
    rtol=1e-2,
    atol=None,
    eps=1e-4,
    dtype=np.float64,
):
    """Finite-difference check of an NDArray function's autograd gradients
    (reference test_utils.py:792 — here against the autograd tape instead of
    executor backward).

    f: callable taking NDArrays and returning one NDArray (scalar-reduced
    internally if not already scalar).
    locations: list of numpy arrays (the differentiable inputs).
    """
    from . import autograd
    from .ndarray import ones as nd_ones

    nd_inputs = [array(loc.astype(np.float32)) for loc in locations]
    for x in nd_inputs:
        x.attach_grad()
    with autograd.record():
        out = f(*nd_inputs)
        loss = out.sum() if out.size != 1 else out
    loss.backward()
    sym_grads = [x.grad.asnumpy().astype(np.float64) for x in nd_inputs]

    # numeric gradients via central differences on numpy copies
    for gi, loc in enumerate(locations):
        if grads is not None and gi not in grads:
            continue
        num_grad = np.zeros_like(loc, dtype=np.float64)
        flat = loc.reshape(-1)
        for i in range(flat.size):
            orig = flat[i]
            flat[i] = orig + eps
            plus = float(
                f(*[array(l.astype(np.float32)) for l in locations]).sum().asscalar()
            )
            flat[i] = orig - eps
            minus = float(
                f(*[array(l.astype(np.float32)) for l in locations]).sum().asscalar()
            )
            flat[i] = orig
            num_grad.reshape(-1)[i] = (plus - minus) / (2 * eps)
        assert_almost_equal(
            num_grad,
            sym_grads[gi],
            rtol=rtol,
            atol=atol if atol is not None else 1e-3,
            names=("numeric_grad_%d" % gi, "autograd_%d" % gi),
        )


def check_symbolic_forward(sym, locations, expected, rtol=1e-4, atol=1e-5, aux_states=None, ctx=None):
    """Bind a Symbol, run forward, compare to expected numpy (reference :925)."""
    from .executor import _simple_bind_for_test

    exe = _simple_bind_for_test(sym, locations, aux_states=aux_states, ctx=ctx)
    outs = exe.forward(is_train=False)
    for o, e in zip(outs, expected):
        assert_almost_equal(o.asnumpy(), e, rtol=rtol, atol=atol)
    return [o.asnumpy() for o in outs]


def check_symbolic_backward(sym, locations, out_grads, expected, rtol=1e-4, atol=1e-5, aux_states=None, ctx=None):
    """Run backward, compare input grads to expected numpy (reference :999)."""
    from .executor import _simple_bind_for_test

    exe = _simple_bind_for_test(sym, locations, aux_states=aux_states, ctx=ctx, grad_req="write")
    exe.forward(is_train=True)
    exe.backward(out_grads=[array(g) for g in out_grads])
    grads = {k: v.asnumpy() for k, v in zip(sym.list_arguments(), exe.grad_arrays) if v is not None}
    if isinstance(expected, dict):
        for name, e in expected.items():
            assert_almost_equal(grads[name], e, rtol=rtol, atol=atol, names=("grad_" + name, "expected"))
    else:
        for (name, g), e in zip(sorted(grads.items()), expected):
            assert_almost_equal(g, e, rtol=rtol, atol=atol)
    return grads


def check_consistency(f, inputs, ctx_list=None, rtol=None, atol=None):
    """Run the same computation on each context and cross-compare
    (reference test_utils.py:1207 — CPU vs TPU instead of CPU vs GPU)."""
    ctx_list = ctx_list or [cpu(0), default_context()]
    results = []
    for ctx in ctx_list:
        nd_in = [array(x, ctx=ctx) for x in inputs]
        out = f(*nd_in)
        results.append(out.asnumpy() if isinstance(out, NDArray) else [o.asnumpy() for o in out])
    base = results[0]
    for r in results[1:]:
        if isinstance(base, list):
            for a, b in zip(base, r):
                assert_almost_equal(a, b, rtol=rtol, atol=atol)
        else:
            assert_almost_equal(base, r, rtol=rtol, atol=atol)
    return results


def simple_forward(sym, ctx=None, is_train=False, **inputs):
    from .executor import _simple_bind_for_test

    exe = _simple_bind_for_test(sym, inputs, ctx=ctx)
    outputs = exe.forward(is_train=is_train)
    if len(outputs) == 1:
        return outputs[0].asnumpy()
    return [o.asnumpy() for o in outputs]


def discard_stderr(fn):
    return fn


def load_module_by_path(path, name=None):
    """Import a python file by explicit path, bypassing sys.path.

    Several example families reuse file names (two ``train_fused.py``, two
    ``metric.py``), so ``sys.path``-based imports silently grab whichever
    directory was prepended last — tests and cross-example imports load by
    path instead.
    """
    import importlib.util
    import os
    import sys

    if name is None:
        name = "_bypath_" + os.path.abspath(path).strip(os.sep).replace(
            os.sep, "_").replace("-", "_").replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    try:
        spec.loader.exec_module(mod)
    except BaseException:
        sys.modules.pop(name, None)  # never leave a half-initialized entry
        raise
    return mod


def tiny_mlp_checkpoint(in_dim=8, num_hidden=16, num_classes=4, seed=0):
    """(symbol, params) for the canonical tiny softmax MLP used by the
    serving tests and ``tools/loadgen.py`` — ONE definition so the Engine
    fixture and the load generator cannot drift apart.  Params are seeded
    random NDArrays; no files involved."""
    import mxnet_tpu as mx
    from mxnet_tpu import nd

    data = mx.sym.Variable("data")
    fc1 = mx.sym.FullyConnected(data, num_hidden=num_hidden, name="fc1")
    act = mx.sym.Activation(fc1, act_type="relu", name="relu1")
    fc2 = mx.sym.FullyConnected(act, num_hidden=num_classes, name="fc2")
    sym = mx.sym.SoftmaxOutput(fc2, name="softmax")
    exe = sym.simple_bind(grad_req="null", data=(2, in_dim))
    rng = np.random.RandomState(seed)
    params = {n: nd.array(rng.randn(*a.shape).astype(np.float32))
              for n, a in exe.arg_dict.items()
              if n not in ("data", "softmax_label")}
    return sym, params


def deploy_twin_checkpoint(batch=16, image=32, seed=0):
    """(symbol, params, input_shapes) for the two-head deploy-twin graph —
    the ``MXNET_BENCH=predictor`` benchmark topology (conv+BN trunk, then a
    classifier head AND an embedding head, each re-deriving the pooled
    trunk features through a shared helper, so the captured graph carries
    the duplicated subexpressions CSE merges and the eval-dead dropout the
    inference rewrite drops).  ONE definition shared by ``bench.py``,
    ``ci/check_numerics.py`` and the numerics tests, so the acceptance
    surface and the benchmark can never drift apart (ISSUE 11)."""
    import mxnet_tpu as mx
    from mxnet_tpu import nd

    data = mx.sym.var("data")
    h = data
    for i, nf in enumerate((16, 32)):
        h = mx.sym.Convolution(h, name="conv%d" % i, kernel=(3, 3),
                               num_filter=nf, pad=(1, 1))
        h = mx.sym.BatchNorm(h, name="bn%d" % i, fix_gamma=False)
        h = mx.sym.Activation(h, name="act%d" % i, act_type="relu")
        h = mx.sym.Pooling(h, name="pool%d" % i, kernel=(2, 2),
                           stride=(2, 2), pool_type="max")

    def pooled_features(trunk):
        # per-head feature derivation (auto-named: each call captures a
        # fresh chain — exactly the duplication CSE exists to merge)
        p = mx.sym.Pooling(trunk, kernel=(1, 1), global_pool=True,
                           pool_type="avg")
        return mx.sym.L2Normalization(mx.sym.Flatten(p))

    emb = pooled_features(h)  # embedding head (served for similarity)
    cls = mx.sym.Dropout(pooled_features(h), p=0.5)
    prob = mx.sym.softmax(
        mx.sym.FullyConnected(cls, name="fc2", num_hidden=10), name="prob")
    sym = mx.sym.Group([prob, emb])

    rng = np.random.RandomState(seed)
    input_shapes = {"data": (batch, 3, image, image)}
    arg_shapes, _, aux_shapes = sym.infer_shape(**input_shapes)
    params = {}
    for n, s in zip(sym.list_arguments(), arg_shapes):
        if n != "data":
            params["arg:" + n] = nd.array(
                rng.randn(*s).astype(np.float32) * 0.05)
    for n, s in zip(sym.list_auxiliary_states(), aux_shapes):
        params["aux:" + n] = nd.array(
            np.ones(s, np.float32) if n.endswith("_var")
            else np.zeros(s, np.float32))
    return sym, params, input_shapes


def dconv_sample_inputs(rng, bg, hw, offset=None, kernel=3, dilate=2,
                        dead=0.1):
    """The seven per-sample inputs of ``dconv_col_pallas`` as numpy arrays
    ``(y0, y1, x0, x1, ly, lx, lf)``, each ``(bg, kernel**2 * H * W)``, rows
    tap-major as ``deformable_convolution`` orders them.

    ``offset=None`` draws every sample uniformly over the map and kills a
    share ``dead`` of them: the band's worst case, traffic no detector
    sends.  ``offset=m`` is what a deformable layer sends: the stride-1
    'same' grid of a dilated ``kernel`` x ``kernel`` convolution plus
    offsets uniform in ``(-m, m)``, with the operator's own clipping and
    ``lf`` (samples outside the map are dead and sit on its edge)."""
    H, W = hw
    n = kernel * kernel * H * W
    if offset is None:
        sy = rng.uniform(0, H - 1, (bg, n))
        sx = rng.uniform(0, W - 1, (bg, n))
        live = rng.rand(bg, n) > dead
    else:
        pad = dilate * (kernel - 1) // 2
        tap = np.arange(kernel) * dilate - pad
        sy = (tap[:, None, None, None] + np.arange(H)[None, None, :, None]
              + np.zeros((1, kernel, 1, W))).reshape(1, n)
        sx = (tap[None, :, None, None] + np.arange(W)[None, None, None, :]
              + np.zeros((kernel, 1, H, 1))).reshape(1, n)
        sy = sy + rng.uniform(-offset, offset, (bg, n))
        sx = sx + rng.uniform(-offset, offset, (bg, n))
        live = (sy >= 0) & (sy < H) & (sx >= 0) & (sx < W)
    sy = np.clip(sy, 0, H - 1).astype(np.float32)
    sx = np.clip(sx, 0, W - 1).astype(np.float32)
    y0, x0 = np.floor(sy).astype(np.int32), np.floor(sx).astype(np.int32)
    y1, x1 = np.minimum(y0 + 1, H - 1), np.minimum(x0 + 1, W - 1)
    return y0, y1, x0, x1, sy - y0, sx - x0, live.astype(np.float32)


def dconv_dense_reference(y0, y1, x0, x1, ly, lx, lf, ftt, hw):
    """The dense one-hot formulation of ``dconv_col_pallas``, what
    ``deformable_convolution``'s XLA scan computes, in the kernel's
    channels-major layout: ``ftt (BG, C, H*W)`` times the whole sample
    matrix A transposed, rounded to ftt's dtype, with f32 accumulation:
    ``(BG, C, N)``.  A is ``(BG, N, H*W)`` f32: for toy sizes, or one
    (image, group)."""
    import jax
    import jax.numpy as jnp

    H, W = hw
    iy, ix = jnp.arange(H), jnp.arange(W)
    yv = ((1 - ly)[..., None] * (y0[..., None] == iy)
          + ly[..., None] * (y1[..., None] == iy))
    xv = lf[..., None] * ((1 - lx)[..., None] * (x0[..., None] == ix)
                          + lx[..., None] * (x1[..., None] == ix))
    a = (yv[..., :, None] * xv[..., None, :]).reshape(*y0.shape, H * W)
    return jnp.einsum("bcp,bnp->bcn", ftt, a.astype(ftt.dtype),
                      precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32).astype(ftt.dtype)
