"""Everything a run draws from ``--seed``: weights and input batches.

Made on the device, each in one jitted call, in float32 (the master type
both configurations train in).  The same seed gives the same arrays; the
program and the plain reference are handed the same ones.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

# kind -> (distribution, parameter).  "he": normal, std = sqrt(2 / fan_in).
# Frozen BatchNorm (R-FCN) does not normalise, so the gamma of each block's
# last BatchNorm is small: 33 residual blocks then grow the activations by
# a small factor, not 2^33.  The offset branches of the deformable operators
# are zero in the published recipe; here they are small and non-zero so that
# every sample really is a four-corner bilinear one.
KINDS = {
    "conv": ("he", None),
    "conv_res": ("he", 0.25),
    "head": ("normal", 0.02),
    "dense": ("normal", 0.01),
    "bias": ("normal", 0.01),
    "gamma": ("uniform", (0.8, 1.2)),
    "gamma_res": ("uniform", (0.15, 0.25)),
    "beta": ("normal", 0.05),
    "mean": ("normal", 0.05),
    "var": ("uniform", (0.8, 1.2)),
    "offset_w": ("normal", 0.01),
    "offset_b": ("normal", 0.1),
    "trans_w": ("normal", 0.05),
}


def root_key(seed):
    """A PRNG key from any whole number up to 2**63 (the driver's seeds do
    not fit 32 signed bits)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError("--seed must not be negative, got %d" % seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed >> 31),
                              seed & 0x7FFFFFFF)


def make_weights(spec, seed):
    """``spec``: [(name, shape, kind)] -> {name: float32 array}."""
    spec = tuple((n, tuple(s), k) for n, s, k in spec)
    return _make_weights(spec, jax.random.fold_in(root_key(seed), 1))


@functools.partial(jax.jit, static_argnums=(0,))
def _make_weights(spec, key):
    """Two draws (one normal, one uniform vector) cut into the leaves: one
    small program however many leaves there are."""
    sizes = [int(np.prod(s)) for _, s, _ in spec]
    kn, ku = jax.random.split(key)
    pools = {"normal": jax.random.normal(kn, (sum(
                 z for z, (_, _, k) in zip(sizes, spec)
                 if KINDS[k][0] != "uniform"),), jnp.float32),
             "uniform": jax.random.uniform(ku, (sum(
                 z for z, (_, _, k) in zip(sizes, spec)
                 if KINDS[k][0] == "uniform"),), jnp.float32)}
    at = {"normal": 0, "uniform": 0}
    out = {}
    for (name, shape, kind), size in zip(spec, sizes):
        dist, arg = KINDS[kind]
        pool = "uniform" if dist == "uniform" else "normal"
        raw = pools[pool][at[pool]:at[pool] + size].reshape(shape)
        at[pool] += size
        if dist == "he":
            out[name] = raw * (np.sqrt(2.0 / int(np.prod(shape[1:])))
                               * (arg or 1.0))
        elif dist == "normal":
            out[name] = raw * arg
        else:
            out[name] = arg[0] + raw * (arg[1] - arg[0])
    return out


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _detection_batch(key, batch, image_shape, classes, max_gts):
    h, w = image_shape
    kn, kg, kc, kw, kh, kx, ky = jax.random.split(key, 7)
    data = jax.random.uniform(kn, (batch, 3, h, w), jnp.float32) * 0.2
    n_boxes = jax.random.randint(kg, (batch,), 1, min(max_gts, 8) + 1)
    cls = jax.random.randint(kc, (batch, max_gts), 0, classes)
    bw = (jax.random.uniform(kw, (batch, max_gts)) * 0.42 + 0.08) * w
    bh = (jax.random.uniform(kh, (batch, max_gts)) * 0.42 + 0.08) * h
    x1 = jax.random.uniform(kx, (batch, max_gts)) * (w - bw)
    y1 = jax.random.uniform(ky, (batch, max_gts)) * (h - bh)
    valid = jnp.arange(max_gts)[None, :] < n_boxes[:, None]
    gt = jnp.where(valid[..., None], jnp.stack(
        [cls.astype(jnp.float32), x1, y1, x1 + bw, y1 + bh], -1), -1.0)
    yy = jnp.arange(h, dtype=jnp.float32)[:, None]
    xx = jnp.arange(w, dtype=jnp.float32)[None, :]
    chan = jax.nn.one_hot(cls % 3, 3)

    def paint(g, img):
        m = ((yy >= y1[:, g, None, None]) & (yy < (y1 + bh)[:, g, None, None])
             & (xx >= x1[:, g, None, None]) & (xx < (x1 + bw)[:, g, None, None])
             & valid[:, g, None, None])
        return img + 0.8 * m[:, None] * chan[:, g, :, None, None]

    data = jax.lax.fori_loop(0, max_gts, paint, data)
    im_info = jnp.tile(jnp.array([h, w, 1.0], jnp.float32), (batch, 1))
    return data, im_info, gt


def detection_batch(seed, batch, image_shape, classes, max_gts):
    """COCO-shaped synthetic detection batch: a noise canvas with 1..8 bright
    rectangles of 8-50 % of each side painted onto channel ``class % 3``.
    -> data (B,3,H,W), im_info (B,3) [h, w, 1], gt (B,G,5) [class, x1, y1,
    x2, y2], unused rows -1.  Every row differs."""
    return _detection_batch(jax.random.fold_in(root_key(seed), 2), batch,
                            tuple(image_shape), classes, max_gts)


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _classification_batch(key, batch, image_shape, classes):
    kd, kl = jax.random.split(key)
    data = jax.random.uniform(kd, (batch,) + image_shape, jnp.float32, -1, 1)
    label = jax.random.randint(kl, (batch,), 0, classes).astype(jnp.float32)
    return data, label


def classification_batch(seed, batch, image_shape, classes):
    """What upstream's SyntheticDataIter holds: uniform(-1, 1) images and
    uniform integer labels (as float32, MXNet's label type)."""
    return _classification_batch(jax.random.fold_in(root_key(seed), 2), batch,
                                 tuple(image_shape), classes)


def step_keys(seed, n):
    """One PRNG key per training step, made before the window."""
    base = jax.random.fold_in(root_key(seed), 3)
    return [jax.random.fold_in(base, i) for i in range(n)]
