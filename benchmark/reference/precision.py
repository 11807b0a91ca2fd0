"""Arithmetic of the plain references, by precision.

``float32`` is the reference itself: every convolution and matrix product
at ``Precision.HIGHEST`` (a TPU multiplies float32 in bfloat16 passes
unless told otherwise).  The two lower settings exist for the controls of
``correct`` (README, "How correct is decided"): the same reference with
the operands of every convolution rounded to the next precision down, which
has to come out as not correct.

* ``bfloat16``: operands and results of every convolution rounded to
  bfloat16 (the control of a float32 configuration).
* ``float8``: operands rounded to float8_e4m3fn under one scale per tensor
  (max |x| -> 448), results to bfloat16 (the control of a bfloat16
  configuration).

Rounding passes gradients straight through (``x + stop_gradient(q(x) - x)``):
the backward pass of a plain ``astype`` would round the cotangent to the low
type with no scale, and float8 would flush most of it to zero.

Rounding is arithmetic on float32 (``lax.reduce_precision``; scale, round,
clamp), never ``astype`` there and back: the TPU compiler removes a
float32 -> bfloat16 -> float32 pair of converts (excess precision is allowed
by default), and a control rounded that way read the same as the reference on
the chip (PERF.md section 6, PR 25).
"""
import jax
import jax.numpy as jnp
from jax import lax

PRECISIONS = ("float32", "bfloat16", "float8")


def _straight_through(q, x):
    return x + lax.stop_gradient(q(x) - x)


def _round_bf16(x):
    return _straight_through(
        lambda v: lax.reduce_precision(v, exponent_bits=8, mantissa_bits=7), x)


def _e4m3(v):
    """Nearest float8_e4m3fn value (3 mantissa bits, least normal 2**-6,
    subnormals in steps of 2**-9, largest 448), ties to even."""
    a = jnp.abs(v)
    _, e = jnp.frexp(jnp.maximum(a, 2.0 ** -6))      # a = m * 2**e, m in [.5, 1)
    step = jnp.exp2((e - 4).astype(jnp.float32))
    return jnp.sign(v) * jnp.minimum(jnp.round(a / step) * step, 448.0)


def _round_fp8(x):
    def q(v):
        scale = jnp.maximum(jnp.max(jnp.abs(v)), 1e-30) / 448.0
        return _e4m3(v / scale) * scale

    return _straight_through(q, x)


def round_in(x, prec):
    """An operand of a convolution or matrix product, as ``prec`` holds it."""
    if prec == "float32":
        return x
    if prec == "bfloat16":
        return _round_bf16(x)
    if prec == "float8":
        return _round_fp8(x)
    raise ValueError("unknown precision %r, one of %r" % (prec, PRECISIONS))


def round_out(x, prec):
    """A result, as ``prec`` stores it."""
    return x if prec == "float32" else _round_bf16(x)


def conv(x, w, prec, stride=1, pad=0, dilation=1):
    """NHWC activations, OIHW weights, square stride / pad / dilation."""
    y = lax.conv_general_dilated(
        round_in(x, prec), round_in(w, prec), (stride, stride),
        [(pad, pad), (pad, pad)], rhs_dilation=(dilation, dilation),
        dimension_numbers=("NHWC", "OIHW", "NHWC"),
        precision=lax.Precision.HIGHEST)
    return round_out(y, prec)


def einsum(spec, a, b, prec):
    y = jnp.einsum(spec, round_in(a, prec), round_in(b, prec),
                   precision=lax.Precision.HIGHEST)
    return round_out(y, prec)


def max_pool_3x3_s2(x):
    """3x3 max pooling, stride 2, pad 1, floor ("valid") output size; NHWC."""
    return lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                             [(0, 0), (1, 1), (1, 1), (0, 0)])


def tree_l2(leaves):
    return [jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for v in leaves]


tree_l2_jit = jax.jit(tree_l2)
