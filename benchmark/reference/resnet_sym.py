"""Plain reference of the symbolic pre-activation ResNet train step
(float32, jax.numpy).  Imports nothing of the program.

From He et al., "Identity Mappings in Deep Residual Networks", as upstream
MXNet's ``example/image-classification/symbols/resnet.py`` builds it for
ImageNet: BatchNorm (fixed gamma) on the data, 7x7/2 convolution, BN, ReLU,
3x3/2 max pooling, four stages of bottleneck units (BN-ReLU-conv 1x1,
BN-ReLU-conv 3x3 carrying the stride, BN-ReLU-conv 1x1, shortcut from the
first ReLU where shapes change), BN, ReLU, global average pooling, a dense
layer and softmax cross-entropy.  BatchNorm uses the batch's statistics
(biased variance, eps 2e-5) and moves its running statistics by 0.9.

The optimizer is upstream's SGD: ``rescale_grad = 1/batch`` on the summed
softmax gradient (so the loss is the batch mean), weight decay on the
``_weight`` and ``_gamma`` leaves only, ``mom = m*mom - lr*(g + wd*w)``,
``w += mom``.
"""
import functools
import json

import jax
import jax.numpy as jnp

from . import precision as P

BN_EPS = 2e-5
BN_MOMENTUM = 0.9


def param_spec(cfg):
    """-> [(name, shape, kind)]: the symbol's argument and auxiliary names."""
    spec = []
    f = cfg["filter_list"]
    cin_img = cfg["image_shape"][0]

    def bn(name, c, res=False):
        spec.append((name + "_gamma", (c,), "gamma_res" if res else "gamma"))
        spec.append((name + "_beta", (c,), "beta"))
        spec.append((name + "_moving_mean", (c,), "mean"))
        spec.append((name + "_moving_var", (c,), "var"))

    bn("bn_data", cin_img)
    spec.append(("conv0_weight", (f[0], cin_img, 7, 7), "conv"))
    bn("bn0", f[0])
    cin = f[0]
    for i, units in enumerate(cfg["units"]):
        c = f[i + 1]
        mid = c // 4
        for u in range(1, units + 1):
            n = "stage%d_unit%d" % (i + 1, u)
            bn(n + "_bn1", cin)
            spec.append((n + "_conv1_weight", (mid, cin, 1, 1), "conv"))
            bn(n + "_bn2", mid)
            spec.append((n + "_conv2_weight", (mid, mid, 3, 3), "conv"))
            bn(n + "_bn3", mid)
            spec.append((n + "_conv3_weight", (c, mid, 1, 1), "conv_res"))
            if u == 1:
                spec.append((n + "_sc_weight", (c, cin, 1, 1), "conv"))
            cin = c
    bn("bn1", cin)
    spec.append(("fc1_weight", (cfg["classes"], cin), "dense"))
    spec.append(("fc1_bias", (cfg["classes"],), "bias"))
    return spec


def is_aux(name):
    return name.endswith("_moving_mean") or name.endswith("_moving_var")


def decays(name):
    return name.endswith("_weight") or name.endswith("_gamma")


def batch_norm(x, p, name, stats, prec, fix_gamma=False):
    """NHWC, batch statistics; records them in ``stats``."""
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    stats[name] = (mean, var)
    g = 1.0 if fix_gamma else p[name + "_gamma"]
    y = (x - mean) * (g / jnp.sqrt(var + BN_EPS)) + p[name + "_beta"]
    return P.round_out(y, prec)


def forward(p, data, label, cfg, prec):
    """-> (mean cross-entropy, {bn name: (batch mean, batch var)})."""
    stats = {}
    x = jnp.transpose(data, (0, 2, 3, 1))
    x = batch_norm(x, p, "bn_data", stats, prec, fix_gamma=True)
    x = P.conv(x, p["conv0_weight"], prec, stride=2, pad=3)
    x = jax.nn.relu(batch_norm(x, p, "bn0", stats, prec))
    x = P.max_pool_3x3_s2(x)
    for i, units in enumerate(cfg["units"]):
        for u in range(1, units + 1):
            n = "stage%d_unit%d" % (i + 1, u)
            st = 2 if (u == 1 and i > 0) else 1
            a1 = jax.nn.relu(batch_norm(x, p, n + "_bn1", stats, prec))
            y = P.conv(a1, p[n + "_conv1_weight"], prec)
            y = jax.nn.relu(batch_norm(y, p, n + "_bn2", stats, prec))
            y = P.conv(y, p[n + "_conv2_weight"], prec, stride=st, pad=1)
            y = jax.nn.relu(batch_norm(y, p, n + "_bn3", stats, prec))
            y = P.conv(y, p[n + "_conv3_weight"], prec)
            sc = x if u > 1 else P.conv(a1, p[n + "_sc_weight"], prec, stride=st)
            x = y + sc
    x = jax.nn.relu(batch_norm(x, p, "bn1", stats, prec))
    x = jnp.mean(x, axis=(1, 2))
    logits = P.einsum("bc,kc->bk", x, p["fc1_weight"], prec) + p["fc1_bias"]
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    ce = -jnp.take_along_axis(logp, label.astype(jnp.int32)[:, None], 1)
    return jnp.mean(ce), stats


class Reference:
    def __init__(self, cfg, params, prec="float32"):
        self.cfg = cfg
        names = [n for n, _, _ in param_spec(cfg)]
        self.learn_names = [n for n in names if not is_aux(n)]
        self.p = dict(params)
        self.mom = {n: jnp.zeros_like(self.p[n]) for n in self.learn_names}

        self._step = _program(json.dumps(cfg, sort_keys=True), prec)

    def step(self, data, label, images=None):
        """One step at the present parameters -> (loss, grads).  ``images``:
        the rows a planted fault keeps (the mean is taken over them)."""
        if images is not None:
            idx = jnp.asarray(list(images))
            data, label = data[idx], label[idx]
        learn = {k: self.p[k] for k in self.learn_names}
        aux = {k: v for k, v in self.p.items() if is_aux(k)}
        loss, g, learn, aux, self.mom = self._step(learn, aux, self.mom,
                                                   data, label)
        self.p = {**learn, **aux}
        return loss, g


@functools.lru_cache(maxsize=None)
def _program(cfg_json, prec):
    """The jitted train step of one configuration and precision."""
    cfg = json.loads(cfg_json)

    def step(learn, aux, mom, data, label):
        def f(learn):
            return forward({**learn, **aux}, data, label, cfg, prec)
        (loss, stats), g = jax.value_and_grad(f, has_aux=True)(learn)
        lr, m, wd = cfg["learning_rate"], cfg["momentum"], cfg["wd"]
        new_mom, new_learn = {}, {}
        for k in learn:
            gk = g[k] + (wd * learn[k] if decays(k) else 0.0)
            new_mom[k] = m * mom[k] - lr * gk
            new_learn[k] = learn[k] + new_mom[k]
        new_aux = dict(aux)
        for name, (mean, var) in stats.items():
            for leaf, val in (("_moving_mean", mean), ("_moving_var", var)):
                new_aux[name + leaf] = BN_MOMENTUM * aux[name + leaf] \
                    + (1 - BN_MOMENTUM) * val
        return loss, g, new_learn, new_aux, new_mom

    return jax.jit(step)
