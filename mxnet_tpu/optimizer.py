"""Optimizers — reference ``python/mxnet/optimizer.py`` (registry at :35) and
the fused update kernels of ``src/operator/optimizer_op.cc``.

Design: every rule is a *pure* function ``(weight, grad, *state, lr, wd, ...)
→ (new_weight, *new_state)`` so the same rule runs eagerly (Updater path) or
fused inside a jitted/pjit'ed train step (the TPU-performance path — the
reference fused these as C++ kernels for the same reason).  Optimizer classes
wrap the rules with MXNet's lr/wd multiplier & scheduling semantics.
"""
from __future__ import annotations

import math
import pickle

import numpy as np

from .base import MXNetError
from .ndarray.ndarray import NDArray, _wrap, array

__all__ = [
    "Optimizer",
    "SGD",
    "NAG",
    "Signum",
    "SGLD",
    "Adam",
    "AdaGrad",
    "AdaDelta",
    "Adamax",
    "Nadam",
    "RMSProp",
    "Ftrl",
    "Ftml",
    "DCASGD",
    "LBSGD",
    "Updater",
    "get_updater",
    "create",
    "register",
]

_OPT_REGISTRY = {}


def register(klass):
    """Register an Optimizer subclass under its lowercase name (reference
    Optimizer.register)."""
    _OPT_REGISTRY[klass.__name__.lower()] = klass
    return klass


def create(name, **kwargs):
    if name.lower() not in _OPT_REGISTRY:
        raise MXNetError("Optimizer %s not registered (have %s)" % (name, sorted(_OPT_REGISTRY)))
    return _OPT_REGISTRY[name.lower()](**kwargs)


class Optimizer:
    """Base optimizer (reference optimizer.py:35).

    Tracks per-parameter lr/wd multipliers, update counts, and optional
    multi-precision (bf16 weights with f32 master copy — the TPU analog of
    the reference's fp16/fp32 multi-precision path).
    """

    def __init__(
        self,
        rescale_grad=1.0,
        param_idx2name=None,
        wd=0.0,
        clip_gradient=None,
        learning_rate=0.01,
        lr_scheduler=None,
        sym=None,
        begin_num_update=0,
        multi_precision=False,
        param_dict=None,
    ):
        self.rescale_grad = rescale_grad
        self.lr = learning_rate
        self.lr_scheduler = lr_scheduler
        if lr_scheduler is not None:
            self.lr_scheduler.base_lr = learning_rate
        self.wd = wd
        self.lr_mult = {}
        self.wd_mult = {}
        self.begin_num_update = begin_num_update
        self.num_update = begin_num_update
        self._index_update_count = {}
        self.clip_gradient = clip_gradient
        self.multi_precision = multi_precision
        self.idx2name = dict(param_idx2name or {})
        self.param_dict = param_dict or {}
        self.sym_info = None
        if sym is not None:
            self.sym_info = (sym.attr_dict(), sym.list_arguments())

    # -- multipliers (reference optimizer.py set_lr_mult/set_wd_mult) ------
    def set_lr_mult(self, args_lr_mult):
        self.lr_mult = {}
        if self.sym_info:
            attr, arg_names = self.sym_info
            for name in arg_names:
                if name in attr and "__lr_mult__" in attr[name]:
                    self.lr_mult[name] = float(attr[name]["__lr_mult__"])
        self.lr_mult.update(args_lr_mult)

    def set_wd_mult(self, args_wd_mult):
        self.wd_mult = {}
        for n in self.idx2name.values():
            # bias/gamma/beta traditionally exempt from wd (reference :309)
            if not (n.endswith("_weight") or n.endswith("_gamma")):
                self.wd_mult[n] = 0.0
        if self.sym_info:
            attr, arg_names = self.sym_info
            for name in arg_names:
                if name in attr and "__wd_mult__" in attr[name]:
                    self.wd_mult[name] = float(attr[name]["__wd_mult__"])
        self.wd_mult.update(args_wd_mult)

    def _update_count(self, index):
        if index not in self._index_update_count:
            self._index_update_count[index] = self.begin_num_update
        self._index_update_count[index] += 1
        self.num_update = max(self._index_update_count[index], self.num_update)

    def _get_lr(self, index):
        lr = self.lr_scheduler(self.num_update) if self.lr_scheduler is not None else self.lr
        if index in self.param_dict:
            lr *= self.param_dict[index].lr_mult
        elif index in self.lr_mult:
            lr *= self.lr_mult[index]
        elif index in self.idx2name:
            lr *= self.lr_mult.get(self.idx2name[index], 1.0)
        return lr

    def _get_wd(self, index):
        wd = self.wd
        if index in self.param_dict:
            wd *= self.param_dict[index].wd_mult
        elif index in self.wd_mult:
            wd *= self.wd_mult[index]
        elif index in self.idx2name:
            wd *= self.wd_mult.get(self.idx2name[index], 1.0)
        return wd

    # -- to be provided by subclasses ---------------------------------------
    def create_state(self, index, weight):
        raise NotImplementedError

    def update(self, index, weight, grad, state):
        raise NotImplementedError

    def fused_step_kind(self):
        """Kind tag consumed by the Module fused train step
        (``module/fused_step.py`` + ``ops.optimizer_ops.fused_update``), or
        None when this optimizer's update cannot be folded into the jitted
        step graph (stateful host logic, sparse-only rules, multi-precision
        master-weight tuples) — the Module then routes through the legacy
        per-parameter Updater path."""
        return None

    def create_state_multi_precision(self, index, weight):
        """f32 master weights for low-precision params (reference :201-249)."""
        import jax.numpy as jnp

        if self.multi_precision and weight.dtype in (np.float16, jnp.bfloat16):
            master = weight.astype("float32")
            return (master, self.create_state(index, master))
        return self.create_state(index, weight)

    def update_multi_precision(self, index, weight, grad, state):
        import jax.numpy as jnp

        from .ndarray.sparse import BaseSparseNDArray, RowSparseNDArray

        if isinstance(grad, BaseSparseNDArray) and not (
            isinstance(grad, RowSparseNDArray) and getattr(self, "_handles_sparse", False)
        ):
            # only row_sparse has dedicated rules; everything else densifies
            grad = grad.todense()
        if self.multi_precision and isinstance(state, tuple) and len(state) == 2 and isinstance(state[0], NDArray):
            master, base_state = state
            if isinstance(grad, BaseSparseNDArray):
                grad = grad.todense()
            self.update(index, master, grad.astype("float32"), base_state)
            weight._rebind(master._data.astype(weight._data.dtype))
        else:
            self.update(index, weight, grad, state)

    # -- shared grad preprocessing ------------------------------------------
    def _preprocess(self, grad):
        import jax.numpy as jnp

        g = grad._data * self.rescale_grad
        if self.clip_gradient is not None:
            g = jnp.clip(g, -self.clip_gradient, self.clip_gradient)
        return g

    def serialize(self):
        return pickle.dumps(self)

    @staticmethod
    def deserialize(buf):
        return pickle.loads(buf)


# ---------------------------------------------------------------------------
# pure update rules (usable inside jit; see parallel.trainer for fused use)
# ---------------------------------------------------------------------------


def sgd_rule(w, g, mom, *, lr, wd, momentum=0.0):
    """w -= lr*(g + wd*w) with momentum (reference sgd_mom_update)."""
    g = g + wd * w
    if mom is None:
        return w - lr * g, None
    new_mom = momentum * mom - lr * g
    return w + new_mom, new_mom


def nag_rule(w, g, mom, *, lr, wd, momentum=0.0):
    """Nesterov momentum (reference NAG optimizer)."""
    g = g + wd * w
    new_mom = momentum * mom + g
    return w - lr * (g + momentum * new_mom), new_mom


def adam_rule(w, g, m, v, t, *, lr, wd, beta1=0.9, beta2=0.999, epsilon=1e-8):
    import jax.numpy as jnp

    g = g + wd * w
    m = beta1 * m + (1 - beta1) * g
    v = beta2 * v + (1 - beta2) * jnp.square(g)
    coef1 = 1.0 - beta1**t
    coef2 = 1.0 - beta2**t
    lr_t = lr * (coef2**0.5) / coef1
    return w - lr_t * m / (jnp.sqrt(v) + epsilon), m, v


def rmsprop_rule(w, g, n, *, lr, wd, gamma1=0.9, epsilon=1e-8):
    import jax.numpy as jnp

    g = g + wd * w
    n = (1 - gamma1) * jnp.square(g) + gamma1 * n
    return w - lr * g / jnp.sqrt(n + epsilon), n


def rmspropalex_rule(w, g, n, gavg, delta, *, lr, wd, gamma1=0.9, gamma2=0.9, epsilon=1e-8):
    import jax.numpy as jnp

    g = g + wd * w
    n = (1 - gamma1) * jnp.square(g) + gamma1 * n
    gavg = (1 - gamma1) * g + gamma1 * gavg
    delta = gamma2 * delta - lr * g / jnp.sqrt(n - jnp.square(gavg) + epsilon)
    return w + delta, n, gavg, delta


def adagrad_rule(w, g, hist, *, lr, wd, epsilon=1e-7):
    import jax.numpy as jnp

    g = g + wd * w
    hist = hist + jnp.square(g)
    return w - lr * g / (jnp.sqrt(hist) + epsilon), hist


def adadelta_rule(w, g, acc_g, acc_delta, *, lr, wd, rho=0.90, epsilon=1e-5):
    import jax.numpy as jnp

    g = g + wd * w
    acc_g = rho * acc_g + (1 - rho) * jnp.square(g)
    delta = jnp.sqrt(acc_delta + epsilon) / jnp.sqrt(acc_g + epsilon) * g
    acc_delta = rho * acc_delta + (1 - rho) * jnp.square(delta)
    return w - delta, acc_g, acc_delta


def adamax_rule(w, g, m, u, t, *, lr, wd, beta1=0.9, beta2=0.999):
    import jax.numpy as jnp

    g = g + wd * w
    m = beta1 * m + (1 - beta1) * g
    u = jnp.maximum(beta2 * u, jnp.abs(g))
    lr_t = lr / (1.0 - beta1**t)
    return w - lr_t * m / (u + 1e-8), m, u


def nadam_rule(w, g, m, v, t, *, lr, wd, beta1=0.9, beta2=0.999, epsilon=1e-8, schedule_decay=0.004):
    import jax.numpy as jnp

    g = g + wd * w
    mom_t = beta1 * (1.0 - 0.5 * 0.96 ** (t * schedule_decay))
    mom_t1 = beta1 * (1.0 - 0.5 * 0.96 ** ((t + 1) * schedule_decay))
    m = beta1 * m + (1 - beta1) * g
    v = beta2 * v + (1 - beta2) * jnp.square(g)
    g_prime = g / (1.0 - mom_t)
    m_prime = m / (1.0 - beta1 ** (t + 1))
    v_prime = v / (1.0 - beta2**t)
    m_bar = (1.0 - mom_t) * g_prime + mom_t1 * m_prime
    return w - lr * m_bar / (jnp.sqrt(v_prime) + epsilon), m, v


def ftrl_rule(w, g, z, n, *, lr, wd, lamda1=0.01, beta=1.0):
    import jax.numpy as jnp

    g = g  # wd enters via the prox term
    new_n = n + jnp.square(g)
    sigma = (jnp.sqrt(new_n) - jnp.sqrt(n)) / lr
    z = z + g - sigma * w
    new_w = jnp.where(
        jnp.abs(z) > lamda1,
        -(z - jnp.sign(z) * lamda1) / ((beta + jnp.sqrt(new_n)) / lr + wd),
        jnp.zeros_like(w),
    )
    return new_w, z, new_n


def signum_rule(w, g, mom, *, lr, wd, momentum=0.0, wd_lh=0.0):
    import jax.numpy as jnp

    if mom is None:
        return (1 - lr * wd_lh) * w - lr * jnp.sign(g + wd * w), None
    new_mom = momentum * mom - (1 - momentum) * (g + wd * w)
    return (1 - lr * wd_lh) * w + lr * jnp.sign(new_mom), new_mom


def ftml_rule(w, g, d, v, z, t, *, lr, wd, beta1=0.6, beta2=0.999, epsilon=1e-8):
    import jax.numpy as jnp

    g = g + wd * w
    v = beta2 * v + (1 - beta2) * jnp.square(g)
    d_t = (1 - beta1**t) / lr * (jnp.sqrt(v / (1 - beta2**t)) + epsilon)
    sigma = d_t - beta1 * d
    z = beta1 * z + (1 - beta1) * g - sigma * w
    new_w = -z / d_t
    return new_w, d_t, v, z


RULES = {
    "sgd": sgd_rule,
    "nag": nag_rule,
    "adam": adam_rule,
    "rmsprop": rmsprop_rule,
    "adagrad": adagrad_rule,
    "adadelta": adadelta_rule,
    "adamax": adamax_rule,
    "nadam": nadam_rule,
    "ftrl": ftrl_rule,
    "signum": signum_rule,
    "ftml": ftml_rule,
}


# ---------------------------------------------------------------------------
# optimizer classes
# ---------------------------------------------------------------------------


def _zeros_like_nd(w):
    import jax.numpy as jnp

    return _wrap(jnp.zeros_like(w._data))


@register
class SGD(Optimizer):
    """SGD with momentum & multi-precision (reference optimizer.py SGD).

    Row-sparse gradients take the lazy path: only rows present in the
    gradient are updated (reference sgd_update/sgd_mom_update sparse kernels,
    src/operator/optimizer_op.cc) — on TPU this is a gather/scatter over the
    touched rows, the embedding-training fast path.
    """

    _handles_sparse = True

    def __init__(self, momentum=0.0, lazy_update=True, **kwargs):
        super().__init__(**kwargs)
        self.momentum = momentum
        self.lazy_update = lazy_update

    def fused_step_kind(self):
        # subclasses (LBSGD) override update() with host-side logic the
        # fused graph can't reproduce — only plain SGD folds in.  One kind
        # for both momentum modes: like sgd_rule, the fused kernel picks
        # plain-vs-momentum per parameter from the presence of a state slot
        if type(self) is not SGD or self.multi_precision:
            return None
        return "sgd"

    def create_state(self, index, weight):
        if self.momentum == 0.0:
            return None
        return _zeros_like_nd(weight)

    def _sparse_update(self, index, weight, grad, state):
        import jax.numpy as jnp

        lr, wd = self._get_lr(index), self._get_wd(index)
        rows = grad._aux["indices"]
        g = grad._aux["data"] * self.rescale_grad
        if self.clip_gradient is not None:
            g = jnp.clip(g, -self.clip_gradient, self.clip_gradient)
        w = weight._data
        w_rows = w[rows]
        g = g + wd * w_rows
        if state is None:
            weight._rebind(w.at[rows].add(-lr * g))
        else:
            mom_rows = self.momentum * state._data[rows] - lr * g
            state._rebind(state._data.at[rows].set(mom_rows))
            weight._rebind(w.at[rows].add(mom_rows))

    def update(self, index, weight, grad, state):
        from .ndarray.sparse import RowSparseNDArray

        self._update_count(index)
        if isinstance(grad, RowSparseNDArray):
            if self.lazy_update:
                return self._sparse_update(index, weight, grad, state)
            grad = grad.todense()
        lr, wd = self._get_lr(index), self._get_wd(index)
        g = self._preprocess(grad)
        mom = state._data if state is not None else None
        new_w, new_mom = sgd_rule(weight._data, g, mom, lr=lr, wd=wd, momentum=self.momentum)
        weight._rebind(new_w)
        if state is not None:
            state._rebind(new_mom)


@register
class NAG(Optimizer):
    def __init__(self, momentum=0.0, **kwargs):
        super().__init__(**kwargs)
        self.momentum = momentum

    def create_state(self, index, weight):
        return _zeros_like_nd(weight)

    def update(self, index, weight, grad, state):
        self._update_count(index)
        g = self._preprocess(grad)
        new_w, new_mom = nag_rule(
            weight._data, g, state._data, lr=self._get_lr(index), wd=self._get_wd(index), momentum=self.momentum
        )
        weight._rebind(new_w)
        state._rebind(new_mom)


@register
class Signum(Optimizer):
    def __init__(self, learning_rate=0.01, momentum=0.9, wd_lh=0.0, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.momentum = momentum
        self.wd_lh = wd_lh

    def create_state(self, index, weight):
        return _zeros_like_nd(weight) if self.momentum != 0.0 else None

    def update(self, index, weight, grad, state):
        self._update_count(index)
        g = self._preprocess(grad)
        new_w, new_mom = signum_rule(
            weight._data,
            g,
            state._data if state is not None else None,
            lr=self._get_lr(index),
            wd=self._get_wd(index),
            momentum=self.momentum,
            wd_lh=self.wd_lh,
        )
        weight._rebind(new_w)
        if state is not None:
            state._rebind(new_mom)


@register
class SGLD(Optimizer):
    """Stochastic gradient Langevin dynamics (reference optimizer.py SGLD)."""

    def create_state(self, index, weight):
        return None

    def update(self, index, weight, grad, state):
        import jax

        from . import random as _rnd

        self._update_count(index)
        lr, wd = self._get_lr(index), self._get_wd(index)
        g = self._preprocess(grad) + wd * weight._data
        noise = jax.random.normal(_rnd.next_key(), weight.shape, dtype=weight._data.dtype) * math.sqrt(lr)
        weight._rebind(weight._data - lr / 2 * g + noise)


@register
class DCASGD(Optimizer):
    """Delay-compensated async SGD (reference optimizer.py DCASGD)."""

    def __init__(self, momentum=0.0, lamda=0.04, **kwargs):
        super().__init__(**kwargs)
        self.momentum = momentum
        self.weight_previous = {}
        self.lamda = lamda

    def create_state(self, index, weight):
        mom = _zeros_like_nd(weight) if self.momentum != 0.0 else None
        return (mom, weight.copy())

    def update(self, index, weight, grad, state):
        import jax.numpy as jnp

        self._update_count(index)
        lr, wd = self._get_lr(index), self._get_wd(index)
        g = self._preprocess(grad)
        mom, prev = state
        comp = g + wd * weight._data + self.lamda * g * g * (weight._data - prev._data)
        if mom is not None:
            new_mom = self.momentum * mom._data - lr * comp
            mom._rebind(new_mom)
            upd = new_mom
        else:
            upd = -lr * comp
        prev._rebind(weight._data)
        weight._rebind(weight._data + upd)


@register
class Adam(Optimizer):
    _handles_sparse = True

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999, epsilon=1e-8, lazy_update=True, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1, self.beta2, self.epsilon = beta1, beta2, epsilon
        self.lazy_update = lazy_update

    def fused_step_kind(self):
        if type(self) is not Adam or self.multi_precision:
            return None
        return "adam"

    def create_state(self, index, weight):
        return (_zeros_like_nd(weight), _zeros_like_nd(weight))

    def _sparse_update(self, index, weight, grad, state, t):
        """Lazy row-sparse adam (reference adam_update row_sparse kernel)."""
        import jax.numpy as jnp

        lr, wd = self._get_lr(index), self._get_wd(index)
        rows = grad._aux["indices"]
        g = grad._aux["data"] * self.rescale_grad
        if self.clip_gradient is not None:
            g = jnp.clip(g, -self.clip_gradient, self.clip_gradient)
        m, v = state
        w_rows = weight._data[rows]
        g = g + wd * w_rows
        m_rows = self.beta1 * m._data[rows] + (1 - self.beta1) * g
        v_rows = self.beta2 * v._data[rows] + (1 - self.beta2) * g * g
        lr_t = lr * np.sqrt(1 - self.beta2**t) / (1 - self.beta1**t)
        upd = -lr_t * m_rows / (jnp.sqrt(v_rows) + self.epsilon)
        m._rebind(m._data.at[rows].set(m_rows))
        v._rebind(v._data.at[rows].set(v_rows))
        weight._rebind(weight._data.at[rows].add(upd))

    def update(self, index, weight, grad, state):
        from .ndarray.sparse import RowSparseNDArray

        self._update_count(index)
        t = self._index_update_count[index]
        if isinstance(grad, RowSparseNDArray):
            if self.lazy_update:
                return self._sparse_update(index, weight, grad, state, t)
            grad = grad.todense()
        g = self._preprocess(grad)
        m, v = state
        new_w, new_m, new_v = adam_rule(
            weight._data, g, m._data, v._data, t,
            lr=self._get_lr(index), wd=self._get_wd(index),
            beta1=self.beta1, beta2=self.beta2, epsilon=self.epsilon,
        )
        weight._rebind(new_w)
        m._rebind(new_m)
        v._rebind(new_v)


@register
class AdaGrad(Optimizer):
    def __init__(self, eps=1e-7, **kwargs):
        super().__init__(**kwargs)
        self.float_stable_eps = eps

    def create_state(self, index, weight):
        return _zeros_like_nd(weight)

    def update(self, index, weight, grad, state):
        self._update_count(index)
        g = self._preprocess(grad)
        new_w, new_h = adagrad_rule(
            weight._data, g, state._data, lr=self._get_lr(index), wd=self._get_wd(index), epsilon=self.float_stable_eps
        )
        weight._rebind(new_w)
        state._rebind(new_h)


@register
class AdaDelta(Optimizer):
    def __init__(self, rho=0.90, epsilon=1e-5, **kwargs):
        super().__init__(**kwargs)
        self.rho, self.epsilon = rho, epsilon

    def create_state(self, index, weight):
        return (_zeros_like_nd(weight), _zeros_like_nd(weight))

    def update(self, index, weight, grad, state):
        self._update_count(index)
        g = self._preprocess(grad)
        acc_g, acc_d = state
        new_w, ng, ndl = adadelta_rule(
            weight._data, g, acc_g._data, acc_d._data,
            lr=self._get_lr(index), wd=self._get_wd(index), rho=self.rho, epsilon=self.epsilon,
        )
        weight._rebind(new_w)
        acc_g._rebind(ng)
        acc_d._rebind(ndl)


@register
class Adamax(Optimizer):
    def __init__(self, learning_rate=0.002, beta1=0.9, beta2=0.999, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1, self.beta2 = beta1, beta2

    def create_state(self, index, weight):
        return (_zeros_like_nd(weight), _zeros_like_nd(weight))

    def update(self, index, weight, grad, state):
        self._update_count(index)
        t = self._index_update_count[index]
        g = self._preprocess(grad)
        m, u = state
        new_w, nm, nu = adamax_rule(
            weight._data, g, m._data, u._data, t,
            lr=self._get_lr(index), wd=self._get_wd(index), beta1=self.beta1, beta2=self.beta2,
        )
        weight._rebind(new_w)
        m._rebind(nm)
        u._rebind(nu)


@register
class Nadam(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999, epsilon=1e-8, schedule_decay=0.004, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1, self.beta2, self.epsilon = beta1, beta2, epsilon
        self.schedule_decay = schedule_decay

    def create_state(self, index, weight):
        return (_zeros_like_nd(weight), _zeros_like_nd(weight))

    def update(self, index, weight, grad, state):
        self._update_count(index)
        t = self._index_update_count[index]
        g = self._preprocess(grad)
        m, v = state
        new_w, nm, nv = nadam_rule(
            weight._data, g, m._data, v._data, t,
            lr=self._get_lr(index), wd=self._get_wd(index),
            beta1=self.beta1, beta2=self.beta2, epsilon=self.epsilon, schedule_decay=self.schedule_decay,
        )
        weight._rebind(new_w)
        m._rebind(nm)
        v._rebind(nv)


@register
class RMSProp(Optimizer):
    def __init__(self, learning_rate=0.001, gamma1=0.9, gamma2=0.9, epsilon=1e-8, centered=False, clip_weights=None, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.gamma1, self.gamma2, self.epsilon = gamma1, gamma2, epsilon
        self.centered = centered
        self.clip_weights = clip_weights

    def create_state(self, index, weight):
        if self.centered:
            return (_zeros_like_nd(weight), _zeros_like_nd(weight), _zeros_like_nd(weight))
        return _zeros_like_nd(weight)

    def update(self, index, weight, grad, state):
        self._update_count(index)
        g = self._preprocess(grad)
        lr, wd = self._get_lr(index), self._get_wd(index)
        if self.centered:
            n, gavg, delta = state
            new_w, nn, ng, nd_ = rmspropalex_rule(
                weight._data, g, n._data, gavg._data, delta._data,
                lr=lr, wd=wd, gamma1=self.gamma1, gamma2=self.gamma2, epsilon=self.epsilon,
            )
            n._rebind(nn)
            gavg._rebind(ng)
            delta._rebind(nd_)
        else:
            new_w, nn = rmsprop_rule(weight._data, g, state._data, lr=lr, wd=wd, gamma1=self.gamma1, epsilon=self.epsilon)
            state._rebind(nn)
        if self.clip_weights:
            import jax.numpy as jnp

            new_w = jnp.clip(new_w, -self.clip_weights, self.clip_weights)
        weight._rebind(new_w)


@register
class Ftrl(Optimizer):
    def __init__(self, lamda1=0.01, learning_rate=0.1, beta=1.0, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.lamda1, self.beta = lamda1, beta

    def create_state(self, index, weight):
        return (_zeros_like_nd(weight), _zeros_like_nd(weight))

    def update(self, index, weight, grad, state):
        self._update_count(index)
        g = self._preprocess(grad)
        z, n = state
        new_w, nz, nn = ftrl_rule(
            weight._data, g, z._data, n._data,
            lr=self._get_lr(index), wd=self._get_wd(index), lamda1=self.lamda1, beta=self.beta,
        )
        weight._rebind(new_w)
        z._rebind(nz)
        n._rebind(nn)


@register
class Ftml(Optimizer):
    def __init__(self, beta1=0.6, beta2=0.999, epsilon=1e-8, **kwargs):
        super().__init__(**kwargs)
        self.beta1, self.beta2, self.epsilon = beta1, beta2, epsilon

    def create_state(self, index, weight):
        return (_zeros_like_nd(weight), _zeros_like_nd(weight), _zeros_like_nd(weight))

    def update(self, index, weight, grad, state):
        self._update_count(index)
        t = self._index_update_count[index]
        g = self._preprocess(grad)
        d, v, z = state
        new_w, ndt, nv, nz = ftml_rule(
            weight._data, g, d._data, v._data, z._data, t,
            lr=self._get_lr(index), wd=self._get_wd(index),
            beta1=self.beta1, beta2=self.beta2, epsilon=self.epsilon,
        )
        weight._rebind(new_w)
        d._rebind(ndt)
        v._rebind(nv)
        z._rebind(nz)


@register
class LBSGD(SGD):
    """Large-batch SGD with LARS-style layer-wise adaptive rate
    (reference optimizer.py LBSGD, simplified to the LARS core)."""

    def __init__(self, momentum=0.0, eta=0.001, **kwargs):
        super().__init__(momentum=momentum, **kwargs)
        self.eta = eta

    def update(self, index, weight, grad, state):
        import jax.numpy as jnp

        self._update_count(index)
        lr, wd = self._get_lr(index), self._get_wd(index)
        g = self._preprocess(grad)
        wnorm = jnp.linalg.norm(weight._data)
        gnorm = jnp.linalg.norm(g)
        lars = jnp.where(
            (wnorm > 0) & (gnorm > 0), self.eta * wnorm / (gnorm + wd * wnorm + 1e-9), 1.0
        )
        mom = state._data if state is not None else None
        new_w, new_mom = sgd_rule(weight._data, g, mom, lr=lr * lars, wd=wd, momentum=self.momentum)
        weight._rebind(new_w)
        if state is not None:
            state._rebind(new_mom)


# 'Test' optimizer used by reference unit tests
@register
class Test(Optimizer):
    def create_state(self, index, weight):
        return _zeros_like_nd(weight)

    def update(self, index, weight, grad, state):
        weight._rebind(weight._data - self.lr * self._preprocess(grad))


class Updater:
    """Applies an optimizer locally, managing per-key states (reference
    optimizer.py Updater; the kvstore 'local update' path)."""

    def __init__(self, optimizer):
        self.optimizer = optimizer
        self._states = {}
        self.states_synced = {}
        # a FusedStepper that holds newer state values than the slots do
        # (executor.py's ``_owner``): reading ``states`` writes them back
        self._owner = None

    @property
    def states(self):
        if self._owner is not None:
            self._owner.materialize()
        return self._states

    def __call__(self, index, grad, weight):
        if index not in self.states:
            self.states[index] = self.optimizer.create_state_multi_precision(index, weight)
            self.states_synced[index] = True
        self.optimizer.update_multi_precision(index, weight, grad, self.states[index])

    def get_states(self, dump_optimizer=False):
        states = {
            k: (v.asnumpy() if isinstance(v, NDArray) else _state_np(v)) for k, v in self.states.items()
        }
        payload = (states, self.optimizer) if dump_optimizer else states
        return pickle.dumps(payload)

    def set_states(self, states_bytes):
        data = pickle.loads(states_bytes)
        if isinstance(data, tuple):
            states, self.optimizer = data
        else:
            states = data
        for k, v in states.items():
            self.states[k] = _state_nd(v)
            self.states_synced[k] = True


def _state_np(state):
    if state is None:
        return None
    if isinstance(state, NDArray):
        return state.asnumpy()
    return tuple(_state_np(s) for s in state)


def _state_nd(state):
    if state is None:
        return None
    if isinstance(state, np.ndarray):
        return array(state)
    return tuple(_state_nd(s) for s in state)


def get_updater(optimizer):
    return Updater(optimizer)
