"""Shared model helpers + legacy FeedForward API.

Reference ``python/mxnet/model.py``: kvstore selection (`_create_kvstore:77`),
kvstore-driven update loops (`:116-157`), checkpoint save/load (`:384,414`).
"""
from __future__ import annotations

import logging

from . import ndarray as nd
from . import symbol as sym_mod
from .base import MXNetError

__all__ = [
    "save_checkpoint",
    "load_checkpoint",
    "BatchEndParam",
    "FeedForward",
]

import collections

BatchEndParam = collections.namedtuple(
    "BatchEndParams", ["epoch", "nbatch", "eval_metric", "locals"]
)


def _create_kvstore(kvstore, num_device, arg_params, mesh=None):
    """Resolve a kvstore spec → (kvstore, update_on_kvstore).

    Reference ``model.py:77``.  On TPU a single process drives all local
    devices and gradient reduction happens in-step via psum, so a store is
    only created for explicit instances or dist types.  ``mesh`` is the
    Module's device mesh: a local-family *string* spec (``'local'`` /
    ``'device'`` / ``'nccl'``) under a dp mesh resolves to no store at all —
    where the reference built a CommDevice reduction tree per key
    (``comm.h:451``), the sharded fused step's in-step psum (ISSUE 5,
    ``module/fused_step.py``) already sums gradients over the dp axis inside
    the compiled step, so an eager push/pull loop would only re-serialize
    it.  Dist specs still create real stores (cross-process aggregation has
    no in-step equivalent); explicit ``KVStore`` instances are honored and
    folded later via ``KVStore.folds_into_fused_step`` when possible.
    """
    from . import kvstore as kv_mod

    update_on_kvstore = True
    if kvstore is None:
        kv = None
    elif isinstance(kvstore, kv_mod.KVStore):
        kv = kvstore
        if mesh is not None and kv.folds_into_fused_step(mesh):
            # explicit local-family store under a dp mesh: keep the store as
            # the (identity) grad-aggregation layer but let the local
            # updater own the optimizer, so the fused step can absorb the
            # whole update (stores running their own updater/optimizer or
            # compression keep update_on_kvstore=True and the legacy path)
            update_on_kvstore = False
    elif isinstance(kvstore, str):
        if "dist" not in kvstore and (num_device == 1 or mesh is not None):
            # single device, or single-process dp mesh: the local updater
            # plus the in-step psum is cheaper than a store round-trip
            kv = None
        else:
            kv = kv_mod.create(kvstore)
            if kvstore == "local":
                max_size = max(int(__import__("numpy").prod(p.shape)) for p in arg_params.values())
                update_on_kvstore = max_size < 1024 * 1024 * 16
            elif mesh is not None and kv.folds_into_fused_step(mesh):
                # dist spec under a PROCESS-SPANNING mesh (ISSUE 20): the
                # fused step's GSPMD psum over the host-crossing dp axis IS
                # the cross-process aggregation, so the local updater owns
                # the optimizer and the store stays an (idle) identity
                # layer — same contract as the explicit-instance fold above
                update_on_kvstore = False
    else:
        raise TypeError("kvstore must be KVStore, str or None")
    if kv is None:
        update_on_kvstore = False
    return kv, update_on_kvstore


def _initialize_kvstore(kvstore, param_arrays, arg_params, param_names, update_on_kvstore):
    """Reference ``model.py:116`` — push initial weights."""
    for idx, param_on_devs in enumerate(param_arrays):
        name = param_names[idx]
        kvstore.init(name, arg_params[name])
        if update_on_kvstore:
            kvstore.pull(name, param_on_devs, priority=-idx)


def _update_params_on_kvstore(param_arrays, grad_arrays, kvstore, param_names):
    """Reference ``model.py:145`` — push grads, pull updated weights."""
    from .telemetry import tracing

    with tracing.span("optimizer_update", path="kvstore",
                      params=len(param_arrays)):
        for index, pair in enumerate(zip(param_arrays, grad_arrays)):
            arg_list, grad_list = pair
            if grad_list is None or (isinstance(grad_list, list) and grad_list[0] is None):
                continue
            name = param_names[index]
            kvstore.push(name, grad_list, priority=-index)
            kvstore.pull(name, arg_list, priority=-index)
            # the per-parameter dispatch storm the fused Module step removes
            # (ISSUE 3) — counted so bench/telemetry expose dispatches_per_step
            tracing.count("dispatch", path="legacy")


def _update_params(param_arrays, grad_arrays, updater, num_device, kvstore=None, param_names=None):
    """Reference ``model.py:157+`` — kvstore aggregation + local updater."""
    from .telemetry import tracing

    with tracing.span("optimizer_update", path="local",
                      params=len(param_arrays)):
        for i, pair in enumerate(zip(param_arrays, grad_arrays)):
            arg_list, grad_list = pair
            if grad_list is None or (isinstance(grad_list, list) and grad_list[0] is None):
                continue
            index = i
            if kvstore:
                name = param_names[index]
                kvstore.push(name, grad_list, priority=-index)
                kvstore.pull(name, grad_list, priority=-index)
            if not isinstance(arg_list, (list, tuple)):
                arg_list, grad_list = [arg_list], [grad_list]
            for k, (w, g) in enumerate(zip(arg_list, grad_list)):
                # one updater state per device copy (reference uses index*num_device+k)
                updater(index * num_device + k, g, w)
                tracing.count("dispatch", path="legacy")


def save_checkpoint(prefix, epoch, symbol, arg_params, aux_params):
    """``prefix-symbol.json`` + ``prefix-%04d.params`` (reference model.py:384)."""
    if symbol is not None:
        symbol.save("%s-symbol.json" % prefix)
    save_dict = {("arg:%s" % k): v for k, v in arg_params.items()}
    save_dict.update({("aux:%s" % k): v for k, v in aux_params.items()})
    param_name = "%s-%04d.params" % (prefix, epoch)
    nd.save(param_name, save_dict)
    logging.info("Saved checkpoint to \"%s\"", param_name)


def load_checkpoint(prefix, epoch):
    """→ (symbol, arg_params, aux_params) (reference model.py:414)."""
    import os

    symbol = None
    if os.path.exists("%s-symbol.json" % prefix):
        symbol = sym_mod.load("%s-symbol.json" % prefix)
    save_dict = nd.load("%s-%04d.params" % (prefix, epoch))
    arg_params, aux_params = {}, {}
    for k, v in save_dict.items():
        tp, name = k.split(":", 1)
        if tp == "arg":
            arg_params[name] = v
        elif tp == "aux":
            aux_params[name] = v
    return symbol, arg_params, aux_params


class FeedForward:
    """Legacy training API (reference model.py FeedForward) — a thin veneer
    over Module kept for API completeness; new code should use mx.mod.Module
    or gluon."""

    def __init__(self, symbol, ctx=None, num_epoch=None, optimizer="sgd",
                 initializer=None, arg_params=None, aux_params=None, **kwargs):
        self.symbol = symbol
        self.ctx = ctx
        self.num_epoch = num_epoch
        self.optimizer = optimizer
        self.initializer = initializer
        self.arg_params = arg_params
        self.aux_params = aux_params
        self.kwargs = kwargs
        self._module = None

    def fit(self, X, y=None, eval_data=None, eval_metric="acc", epoch_end_callback=None,
            batch_end_callback=None, kvstore="local", logger=None, work_load_list=None):
        from .module import Module
        from .io import NDArrayIter

        if not hasattr(X, "provide_data"):
            X = NDArrayIter(X, y, batch_size=min(128, len(X)))
        label_names = [d[0] for d in X.provide_label] if X.provide_label else None
        mod = Module(self.symbol, data_names=[d[0] for d in X.provide_data],
                     label_names=label_names, context=self.ctx, logger=logger or logging)
        mod.fit(
            X,
            eval_data=eval_data,
            eval_metric=eval_metric,
            epoch_end_callback=epoch_end_callback,
            batch_end_callback=batch_end_callback,
            kvstore=kvstore,
            optimizer=self.optimizer,
            optimizer_params=self.kwargs or {"learning_rate": 0.01},
            initializer=self.initializer,
            arg_params=self.arg_params,
            aux_params=self.aux_params,
            num_epoch=self.num_epoch,
        )
        self._module = mod
        self.arg_params, self.aux_params = mod.get_params()
        return self

    def predict(self, X, num_batch=None):
        assert self._module is not None, "call fit first"
        return self._module.predict(X, num_batch=num_batch).asnumpy()

    def trainer_stats(self):
        """The process's last trainhealth row (ISSUE 12; the plane is
        process-global — see ``Module.trainer_stats``); None before fit,
        or with MXNET_TRAINHEALTH off."""
        return self._module.trainer_stats() if self._module is not None \
            else None

    def save(self, prefix, epoch=None):
        save_checkpoint(prefix, epoch if epoch is not None else (self.num_epoch or 0),
                        self.symbol, self.arg_params or {}, self.aux_params or {})

    @staticmethod
    def load(prefix, epoch, ctx=None, **kwargs):
        symbol, arg_params, aux_params = load_checkpoint(prefix, epoch)
        return FeedForward(symbol, ctx=ctx, arg_params=arg_params, aux_params=aux_params,
                           num_epoch=epoch, **kwargs)
