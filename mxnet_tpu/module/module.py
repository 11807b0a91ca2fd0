"""Module — the concrete symbolic training module.

Reference ``python/mxnet/module/module.py`` (bind ``:364``, init_optimizer
``:473``, forward ``:572``, update ``:643``, save_checkpoint ``:165``).

One jit Executor replaces the reference's per-device executor group; shape
changes re-bind (re-jit) exactly like the reference's MutableModule.  Data
parallelism: pass ``mesh=`` (a ``jax.sharding.Mesh`` with a ``dp`` axis) and
every batch is sharded over it while params stay replicated — the XLA
equivalent of DataParallelExecutorGroup + kvstore 'device'
(``executor_group.py:143``, ``comm.h:451``).  An eligible mesh-fed train
step runs as ONE donated sharding-annotated jit dispatch (vjp + in-step dp
psum + optimizer, module/fused_step.py ISSUE 5; ``MXNET_FUSED_ZERO=1`` adds
ZeRO-1 optimizer-state sharding), with the legacy sharded forward kept as
the fallback for the cases the fused graph cannot express.
"""
from __future__ import annotations

import logging

from .. import ndarray as nd
from .. import optimizer as opt_mod
from .. import telemetry
from ..base import MXNetError, env_flag
from ..io import DataDesc
from ..telemetry import tracing
from ..model import (
    _create_kvstore,
    _initialize_kvstore,
    _update_params,
    _update_params_on_kvstore,
    load_checkpoint,
    save_checkpoint,
)
from .base_module import BaseModule, _check_input_names

__all__ = ["Module"]


def _as_descs(shapes):
    if shapes is None:
        return None
    out = []
    for s in shapes:
        if isinstance(s, DataDesc):
            out.append(s)
        else:
            name, shape = s[0], s[1]
            out.append(DataDesc(name, shape))
    return out


class Module(BaseModule):
    def __init__(self, symbol, data_names=("data",), label_names=("softmax_label",),
                 logger=logging, context=None, work_load_list=None,
                 fixed_param_names=None, state_names=None, mesh=None):
        super().__init__(logger=logger)
        self._symbol = symbol
        self._data_names = list(data_names or [])
        self._label_names = list(label_names or [])
        self._state_names = list(state_names or [])
        self._fixed_param_names = list(fixed_param_names or [])
        self._context = context
        self._mesh = mesh
        _check_input_names(symbol, self._data_names, "data", True)
        _check_input_names(symbol, self._label_names, "label", False)
        _check_input_names(symbol, self._state_names, "state", True)
        _check_input_names(symbol, self._fixed_param_names, "fixed_param", True)

        arg_names = symbol.list_arguments()
        input_names = set(self._data_names + self._label_names + self._state_names)
        self._param_names = [n for n in arg_names if n not in input_names]
        self._aux_names = symbol.list_auxiliary_states()
        self._output_names = symbol.list_outputs()

        self._arg_params = None
        self._aux_params = None
        self._exec = None
        self._data_shapes = None
        self._label_shapes = None
        self._optimizer = None
        self._kvstore = None
        self._update_on_kvstore = None
        self._updater = None
        self._preload_opt_states = None
        self._grad_req = "write"
        # fused train-step state (ISSUE 3, module/fused_step.py): the cached
        # stepper and the staged-batch flag forward_backward hands update()
        self._fused = None
        self._fused_pending = False
        # another Module's executor reads this Module's arrays (bind with
        # shared_module, either side): its fused step then keeps one array
        # per leaf, since the other executor would read them stale
        self._params_shared = False
        # in-graph monitor (ISSUE 12): a pattern-filtered Monitor routed
        # onto the fused step's trainhealth stats instead of the un-jitted
        # executor callback (install_monitor decides the route)
        self._stat_monitor = None
        self._nan_step = 0  # MXNET_NANCHECK legacy-path step counter
        # prefetch state (ISSUE 5): (batch_obj, feed) pre-staged by
        # prepare() so the next batch's (sharded) device_put overlaps the
        # in-flight step instead of serializing behind it
        self._prestaged = None

    # -- properties ----------------------------------------------------------
    @property
    def data_names(self):
        return self._data_names

    @property
    def label_names(self):
        return self._label_names

    @property
    def output_names(self):
        return self._output_names

    @property
    def data_shapes(self):
        assert self.binded
        return self._data_shapes

    @property
    def label_shapes(self):
        assert self.binded
        return self._label_shapes

    @property
    def output_shapes(self):
        assert self.binded
        return [(n, o.shape) for n, o in zip(self._output_names, self._exec.outputs)] if self._exec.outputs else None

    # -- params ---------------------------------------------------------------
    def get_params(self):
        assert self.binded and self.params_initialized
        if self._fused is not None:
            # MXNET_NANCHECK reads the fused flag one step late; the natural
            # sync points (fit's epoch-end get_params, checkpointing) drain
            # the pending flag so the LAST step of a run is still checked
            self._fused.check_nonfinite()
        self._sync_params_from_exec()
        return dict(self._arg_params), dict(self._aux_params)

    def _sync_params_from_exec(self):
        if self._exec is None:
            return
        for n in self._param_names:
            self._arg_params[n] = self._exec.arg_dict[n]
        for n in self._aux_names:
            self._aux_params[n] = self._exec.aux_dict[n]

    def init_params(self, initializer=None, arg_params=None, aux_params=None,
                    allow_missing=False, force_init=False, allow_extra=False):
        """Reference module.py init_params — initializer fills anything not
        supplied by arg_params/aux_params."""
        assert self.binded, "call bind before initializing the parameters"
        if self.params_initialized and not force_init:
            return
        from ..initializer import Uniform, InitDesc

        initializer = initializer if initializer is not None else Uniform(0.01)

        def _impl(name, arr, cache):
            if cache is not None and name in cache:
                cached = cache[name]
                if cached is not arr:
                    if cached.shape != arr.shape:
                        raise ValueError(
                            "shape mismatch for %s: loaded %s vs expected %s"
                            % (name, cached.shape, arr.shape)
                        )
                    arr._rebind(cached._data)
            else:
                if cache is not None and not allow_missing:
                    raise RuntimeError("%s is not presented" % name)
                if initializer is not None:
                    initializer(InitDesc(name), arr)

        # Module.load pre-populates _arg_params; use them as the cache
        if arg_params is None and self._arg_params:
            arg_params = self._arg_params
            allow_missing = True
        if aux_params is None and self._aux_params:
            aux_params = self._aux_params
            allow_missing = True
        for name in self._param_names:
            arr = self._exec.arg_dict[name]
            _impl(name, arr, arg_params)
        for name in self._aux_names:
            arr = self._exec.aux_dict[name]
            _impl(name, arr, aux_params)

        if arg_params is not None and not allow_extra:
            for name in arg_params:
                if name not in self._param_names and name not in self._data_names + self._label_names:
                    raise ValueError("provided arg_params %s not found in symbol" % name)

        self._arg_params = {n: self._exec.arg_dict[n] for n in self._param_names}
        self._aux_params = {n: self._exec.aux_dict[n] for n in self._aux_names}
        self.params_initialized = True

    # -- bind -----------------------------------------------------------------
    def bind(self, data_shapes, label_shapes=None, for_training=True,
             inputs_need_grad=False, force_rebind=False, shared_module=None, grad_req="write"):
        if force_rebind:
            if self._fused is not None:
                self._fused.release()  # the executor it owns goes
            self._exec = None
            self.binded = False
        if self.binded:
            self.logger.warning("Already bound, ignoring bind()")
            return
        if shared_module is not None:
            if getattr(shared_module, "_fused", None) is not None:
                shared_module._fused.release()
            shared_module._params_shared = True
            self._params_shared = True
        self.for_training = for_training
        self.inputs_need_grad = inputs_need_grad
        self._grad_req = grad_req
        assert not (not for_training and inputs_need_grad)

        # Under a process-spanning mesh the caller (fit's iterator contract)
        # binds with HOST-LOCAL shapes; the jitted program must see global
        # ones — every rank traces the same global computation and feeds its
        # per-host shard (parallel.global_batch_array).  Single-host meshes
        # scale by 1, keeping the descs byte-identical.
        self._data_shapes = self._global_descs(_as_descs(data_shapes))
        self._label_shapes = self._global_descs(_as_descs(label_shapes))

        shape_dict = {d.name: d.shape for d in self._data_shapes}
        if self._label_shapes:
            shape_dict.update({d.name: d.shape for d in self._label_shapes})

        arg_names = self._symbol.list_arguments()
        arg_shapes, _, aux_shapes = self._symbol.infer_shape(**shape_dict)
        shape_of = dict(zip(arg_names, arg_shapes))

        args = {}
        for n in arg_names:
            if shared_module is not None and n in getattr(shared_module, "_param_names", []):
                args[n] = shared_module._exec.arg_dict[n]
            elif self._arg_params is not None and n in self._arg_params and self._arg_params[n].shape == shape_of[n]:
                args[n] = self._arg_params[n]  # survive re-bind (MutableModule)
            else:
                args[n] = nd.zeros(shape_of[n], ctx=self._context if not isinstance(self._context, list) else None)
        aux = {}
        aux_of = dict(zip(self._aux_names, aux_shapes))
        for n in self._aux_names:
            if shared_module is not None and n in getattr(shared_module, "_aux_names", []):
                aux[n] = shared_module._exec.aux_dict[n]
            elif self._aux_params is not None and n in self._aux_params and self._aux_params[n].shape == aux_of[n]:
                aux[n] = self._aux_params[n]
            else:
                aux[n] = nd.zeros(aux_of[n])

        grads = None
        req = {}
        if for_training and grad_req != "null":
            grads = {}
            for n in self._param_names:
                if n in self._fixed_param_names:
                    req[n] = "null"
                    continue
                req[n] = grad_req if isinstance(grad_req, str) else grad_req.get(n, "write")
                grads[n] = nd.zeros(shape_of[n])
            for n in self._data_names:
                if inputs_need_grad:
                    req[n] = "write"
                    grads[n] = nd.zeros(shape_of[n])
                else:
                    req[n] = "null"
            for n in self._label_names + self._state_names:
                req[n] = "null"
        else:
            req = "null"

        self._exec = self._symbol.bind(
            ctx=self._context if not isinstance(self._context, list) else None,
            args=args, args_grad=grads, grad_req=req, aux_states=aux,
        )
        self.binded = True
        self._prestaged = None  # pre-staged feed targeted the old executor

        if shared_module is not None and shared_module.params_initialized:
            self._arg_params = {n: self._exec.arg_dict[n] for n in self._param_names}
            self._aux_params = {n: self._exec.aux_dict[n] for n in self._aux_names}
            self.params_initialized = True

    def reshape(self, data_shapes, label_shapes=None):
        """Re-bind for new shapes, keeping params (reference module.py:452).
        The fused stepper survives re-binds of the same symbol — jax.jit
        re-traces once per new shape signature and caches it."""
        assert self.binded
        self._flush_pending()
        params_were_init = self.params_initialized
        self._sync_params_from_exec() if params_were_init else None
        self.bind(data_shapes, label_shapes, self.for_training, self.inputs_need_grad,
                  force_rebind=True, grad_req=self._grad_req)
        self.params_initialized = params_were_init

    # -- optimizer -------------------------------------------------------------
    def init_optimizer(self, kvstore="local", optimizer="sgd", optimizer_params=None, force_init=False):
        """Reference module.py:473 — chooses kvstore-vs-local updater."""
        assert self.binded and self.params_initialized
        if self.optimizer_initialized and not force_init:
            self.logger.warning("optimizer already initialized, ignoring...")
            return
        if self._fused is not None:  # drain any unread nancheck flag first
            self._fused.check_nonfinite()
            self._fused.release()
        self._fused = None  # stepper folds optimizer hyperparams: rebuild

        kv, update_on_kvstore = _create_kvstore(
            kvstore, 1, {n: self._exec.arg_dict[n] for n in self._param_names},
            mesh=self._mesh,
        )
        # loss-op backwards emit per-sample gradients; normalize by the
        # global batch like the reference (module.py:497 rescale_grad)
        batch_size = self._data_shapes[0].shape[0]
        if kv and "dist" in kv.type:
            from ..parallel.mesh import mesh_spans_processes

            # a process-spanning mesh already bound GLOBAL shapes (bind
            # scaled the iterator-local descs), so the num_workers multiply
            # would double-count the pod's batch
            if not mesh_spans_processes(self._mesh):
                batch_size *= kv.num_workers
        if isinstance(optimizer, str):
            optimizer_params = dict(optimizer_params or {})
            optimizer_params.setdefault("rescale_grad", 1.0 / batch_size)
            optimizer = opt_mod.create(optimizer, **optimizer_params)
        elif optimizer.rescale_grad != 1.0 / batch_size:
            # reference module.py:523-528: a manually-built optimizer keeps
            # its own rescale_grad, but a mismatch silently mis-scales
            # gradients by the batch size — warn exactly like the reference
            import warnings

            warnings.warn(
                "Optimizer created manually outside Module but rescale_grad "
                "is %g rather than 1.0/batch_size (%g). Is this intended?"
                % (optimizer.rescale_grad, 1.0 / batch_size))
        optimizer.idx2name = {i: n for i, n in enumerate(self._param_names)}
        if hasattr(self._symbol, "attr_dict"):
            optimizer.sym_info = (self._symbol.attr_dict(), self._symbol.list_arguments())
        # repopulate name-keyed multipliers now that idx2name is known
        # (wd exemption for bias/gamma, __lr_mult__/__wd_mult__ attrs)
        optimizer.set_lr_mult(getattr(optimizer, "lr_mult", {}) or {})
        optimizer.set_wd_mult(getattr(optimizer, "wd_mult", {}) or {})

        self._optimizer = optimizer
        self._kvstore = kv
        self._update_on_kvstore = update_on_kvstore
        self._updater = None
        if kv:
            if update_on_kvstore:
                kv.set_optimizer(self._optimizer)
            _initialize_kvstore(kv, [self._exec.arg_dict[n] for n in self._param_names],
                                {n: self._exec.arg_dict[n] for n in self._param_names},
                                self._param_names, update_on_kvstore)
        if not update_on_kvstore:
            self._updater = opt_mod.get_updater(optimizer)
        self.optimizer_initialized = True
        if self._preload_opt_states is not None:
            self.load_optimizer_states(self._preload_opt_states)
            self._preload_opt_states = None

    # -- compute ---------------------------------------------------------------
    def _batch_descs(self, data_batch):
        """DataDescs the batch would feed (shape-change detection)."""
        provide = getattr(data_batch, "provide_data", None)
        return _as_descs(provide) if provide else [
            DataDesc(n, a.shape) for n, a in zip(self._data_names, data_batch.data)
        ]

    def _global_descs(self, descs):
        """Scale iterator-local leading dims to the GLOBAL shapes the bound
        program uses.  Identity (factor 1) everywhere except a mesh whose dp
        axis spans processes, where each host feeds ``1/factor`` of the
        batch."""
        if not descs or self._mesh is None:
            return descs
        from ..parallel.mesh import mesh_batch_factor

        factor = mesh_batch_factor(self._mesh)
        if factor == 1:
            return descs
        return [DataDesc(d.name, (d.shape[0] * factor,) + tuple(d.shape[1:]))
                for d in descs]

    def _build_feed(self, data_batch):
        """{arg name: device-ready NDArray} for a shape-matching batch —
        under a mesh every array is committed dp-sharded here (the
        ``device_put`` the prefetch path issues early, ISSUE 5)."""
        feed = {}
        for name, arr in zip(self._data_names, data_batch.data):
            feed[name] = arr
        if self._label_shapes and getattr(data_batch, "label", None) is not None:
            for name, arr in zip(self._label_names, data_batch.label):
                feed[name] = arr
        elif self._label_shapes:
            # predict-mode batch without labels: keep stale label buffers
            pass
        if self._mesh is not None:
            from ..parallel import shard
            from ..parallel.mesh import global_batch_array, mesh_spans_processes

            if mesh_spans_processes(self._mesh):
                import numpy as np

                # pod mesh: this host holds only its shard of the batch —
                # assemble the global jax.Array from per-device local
                # buffers (no host gathering, tentpole contract)
                out = {}
                for k, v in feed.items():
                    arr = v.asnumpy() if isinstance(v, nd.NDArray) else np.asarray(v)
                    spec = ("dp",) + (None,) * (arr.ndim - 1)
                    out[k] = nd.NDArray(
                        global_batch_array(arr, self._mesh, spec))
                return out
            return {
                k: shard(v if isinstance(v, nd.NDArray) else nd.array(v),
                         ("dp",) + (None,) * (len(v.shape) - 1), mesh=self._mesh)
                for k, v in feed.items()
            }
        return {k: v if isinstance(v, nd.NDArray) else nd.array(v)
                for k, v in feed.items()}

    def _stage_batch(self, data_batch):
        """Reshape-on-new-batch-shape (MutableModule semantics) + write the
        batch feed into the executor's arg buffers.  Shared by ``forward``
        and the fused ``forward_backward`` staging (module/fused_step.py).
        A feed already pre-staged for this very batch by ``prepare`` is
        consumed as-is — its device_put was issued while the previous step
        was still in flight.

        Any object with a ``.data`` list is a valid batch (reference
        module.py duck-types the same way —
        example/python-howto/debug_conv.py SimpleData).
        """
        new_descs = self._batch_descs(data_batch)
        if ([d.shape for d in self._global_descs(new_descs)]
                != [d.shape for d in self._data_shapes]):
            if getattr(data_batch, "provide_label", None):
                new_labels = _as_descs(data_batch.provide_label)
            elif getattr(data_batch, "label", None) is not None and self._label_shapes:
                new_labels = [DataDesc(n, a.shape) for n, a in zip(self._label_names, data_batch.label)]
            elif self._label_shapes:
                # label-less batch (predict): rescale label batch dims to match
                new_batch = new_descs[0].shape[0]
                new_labels = [DataDesc(d.name, (new_batch,) + tuple(d.shape[1:]))
                              for d in self._label_shapes]
            else:
                new_labels = None
            self.reshape(new_descs, new_labels)

        staged = self._prestaged
        self._prestaged = None
        if staged is not None and staged[0] is data_batch:
            feed = staged[1]
        else:
            feed = self._build_feed(data_batch)
        # the raw dict: staging the batch must not write the fused step's
        # packed state back into the parameters (module/fused_step.py)
        for k, v in feed.items():
            self._exec._arg_dict[k] = v

    def prepare(self, data_batch):
        """Pre-stage the UPCOMING batch (ISSUE 5): issue its (sharded)
        host→device transfer now, while the in-flight step still occupies
        the device, so the copy overlaps compute instead of serializing at
        the next ``forward_backward``.  The fit loop calls this inside its
        ``data_wait`` accounting, keeping ``data_wait_frac`` honest about
        the hidden staging cost.  Batches whose shapes would trigger a
        reshape are left to ``_stage_batch`` (a mid-flight re-bind would
        tear down buffers the pending step output reads still need)."""
        if not (self.binded and self.params_initialized):
            return
        descs = self._batch_descs(data_batch)
        if ([d.shape for d in self._global_descs(descs)]
                != [d.shape for d in self._data_shapes]):
            self._prestaged = None
            return
        self._prestaged = (data_batch, self._build_feed(data_batch))

    def _flush_pending(self):
        """Materialize a staged fused step through the legacy path — a
        consumer asked for outputs/grads (or issued another forward) before
        ``update()`` could dispatch the fused step."""
        if not self._fused_pending:
            return
        self._fused_pending = False
        telemetry.note_fused_fallback("interleaved")
        self._exec.forward(is_train=True)
        self._exec.backward()

    def forward(self, data_batch, is_train=None):
        assert self.binded and self.params_initialized
        self._flush_pending()
        if is_train is None:
            is_train = self.for_training
        self._stage_batch(data_batch)
        self._exec.forward(is_train=is_train)

    def forward_backward(self, data_batch):
        """Reference base_module.py:192 — plus the ISSUE 3 fused fast path:
        when eligible the batch is only STAGED here, and forward + backward
        + optimizer update execute as ONE donated jit dispatch inside
        ``update()`` (module/fused_step.py; escape hatch
        ``MXNET_MODULE_FUSED_STEP=0``, fallback conditions in
        docs/PERF_NOTES.md "Fused Module train step")."""
        assert self.binded and self.params_initialized
        self._flush_pending()
        from .fused_step import fused_ineligible_reason

        reason = fused_ineligible_reason(self)
        if reason is None:
            path = "fused_mesh" if self._mesh is not None else "fused"
            with tracing.span("forward_backward", path=path):
                self._stage_batch(data_batch)
            self._fused_pending = True
            return
        if self._stat_monitor is not None and self._exec._monitor is None:
            # the fused path can't take this Module's steps, so the
            # in-graph monitor route would observe NOTHING — fall back to
            # the pre-ISSUE-12 un-jitted executor callback (full node
            # observation at legacy speed; sticky, like a monitor always
            # was before the in-graph route existed)
            mon, self._stat_monitor = self._stat_monitor, None
            mon.install(self._exec)
        # the legacy step's own forward/backward dispatches are counted at
        # the Executor dispatch sites, the optimizer storm in model.py
        telemetry.note_fused_fallback(reason)
        with tracing.span("forward_backward", path="legacy", reason=reason):
            super().forward_backward(data_batch)

    def backward(self, out_grads=None):
        assert self.binded and self.params_initialized
        self._flush_pending()
        self._exec.backward(out_grads=out_grads)

    def update(self):
        """Apply one optimizer step (reference module.py:643).

        With a fused step staged by ``forward_backward`` this is the single
        compiled dispatch of the whole training step; otherwise the legacy
        kvstore/Updater per-parameter loop runs."""
        assert self.binded and self.params_initialized and self.optimizer_initialized
        if self._fused_pending:
            self._fused_pending = False
            from .fused_step import FusedStepper, fused_zero_enabled

            if self._mesh is not None:
                span_kw = {"path": "fused_mesh",
                           "zero": int(fused_zero_enabled())}
            else:
                span_kw = {"path": "fused"}
            with tracing.span("update", **span_kw):
                if self._fused is not None and self._fused.stale(self):
                    # don't let a rebuild discard an unread nancheck flag
                    self._fused.check_nonfinite()
                    self._fused.release()
                    self._fused = None
                if self._fused is None:
                    self._fused = FusedStepper(self)
                self._fused.run(self)
                tracing.count("dispatch", path=span_kw["path"])
            if self._stat_monitor is not None \
                    and getattr(self._stat_monitor, "activated", False):
                # in-graph monitor route (install_monitor): feed this
                # step's stats rows, pattern-filtered by the monitor
                self._fused.feed_monitor(self._stat_monitor)
            telemetry.note_train_step(span_kw["path"])
            return
        telemetry.note_train_step("legacy")
        if env_flag("MXNET_NANCHECK"):
            self._nancheck_legacy()
        with tracing.span("update", path="legacy"):
            param_arrays = [self._exec.arg_dict[n] for n in self._param_names]
            grad_arrays = [self._exec.grad_dict.get(n)
                           for n in self._param_names]
            if self._kvstore and self._update_on_kvstore:
                _update_params_on_kvstore(param_arrays, grad_arrays,
                                          self._kvstore, self._param_names)
            else:
                _update_params(param_arrays, grad_arrays, self._updater, 1,
                               kvstore=self._kvstore,
                               param_names=self._param_names)

    def _nancheck_legacy(self):
        """Opt-in ``MXNET_NANCHECK`` guard for the legacy step: verify the
        loss heads and parameter gradients are finite BEFORE the optimizer
        writes them into the weights.  The legacy path already syncs per
        dispatch, so the device readbacks here cost noise; the fused path
        folds the same check into its one dispatch (module/fused_step.py)."""
        import jax.numpy as jnp

        self._nan_step += 1
        bad = []
        for name, o in zip(self._output_names, self._exec.outputs):
            if not bool(jnp.all(jnp.isfinite(o._data))):
                bad.append("output:%s" % name)
        for n in self._param_names:
            g = self._exec.grad_dict.get(n)
            if g is not None and not bool(jnp.all(jnp.isfinite(g._data))):
                bad.append("grad:%s" % n)
        if bad:
            telemetry.note_nonfinite("legacy")
            telemetry.trainhealth.note_nonfinite_trip(
                "legacy", self._nan_step, detail=", ".join(bad[:8]))
            raise MXNetError(
                "MXNET_NANCHECK: non-finite values at train step %d: %s"
                % (self._nan_step, ", ".join(bad[:8])))

    def get_outputs(self, merge_multi_context=True):
        assert self.binded
        self._flush_pending()
        return list(self._exec.outputs)

    def get_input_grads(self, merge_multi_context=True):
        assert self.binded and self.inputs_need_grad
        self._flush_pending()
        return [self._exec.grad_dict[n] for n in self._data_names]

    def update_metric(self, eval_metric, labels):
        outputs = self.get_outputs()
        if self._mesh is not None:
            from ..parallel.mesh import host_local_rows, mesh_spans_processes

            if mesh_spans_processes(self._mesh):
                # pod mesh: outputs are global arrays whose rows span other
                # hosts — score THIS host's block against its local labels
                # (per-worker metrics, the reference dist_sync semantics)
                outputs = [nd.array(host_local_rows(o._data))
                           for o in outputs]
        eval_metric.update(labels, outputs)

    def trainer_stats(self):
        """The PROCESS's last drained trainhealth row (host floats:
        global/per-group grad norms, update ratios, non-finite census) or
        None — ``MXNET_TRAINHEALTH`` off, or nothing drained yet.  The
        health plane is one per process, like the flight recorder: with
        several Modules training in one process this returns whichever
        drained last.  The same block is mirrored on the ops server's
        ``/statusz`` (docs/OBSERVABILITY.md "Training health")."""
        from ..telemetry import trainhealth

        return trainhealth.trainer_stats()

    def get_states(self, merge_multi_context=True):
        assert self.binded
        return [self._exec.arg_dict[n] for n in self._state_names]

    def set_states(self, states=None, value=None):
        assert self.binded
        if states is not None:
            for n, v in zip(self._state_names, states):
                self._exec.arg_dict[n] = v if isinstance(v, nd.NDArray) else nd.array(v)
        else:
            for n in self._state_names:
                self._exec.arg_dict[n][:] = value

    def install_monitor(self, mon):
        """Attach a :class:`~mxnet_tpu.monitor.Monitor` (ISSUE 12 routing).

        ``monitor_all=False`` (default) rides the **fused step**: the
        monitor observes the in-graph trainhealth stats — per-group
        grad/param norms and update ratios, pattern-filtered by its regex
        — and training keeps its one-donated-dispatch step.
        ``monitor_all=True`` is the escape hatch: the executor's un-jitted
        per-node callback (every node output + inputs), which forces the
        legacy path — full observability at legacy speed (the reference
        semantics, and the only route that sees intermediate tensors).
        A monitor is never silently blind: one whose pattern matches NO
        in-graph stat row (it targets tensor names like ``fc1_weight``)
        takes the un-jitted route directly, and a Module whose steps turn
        out fused-INELIGIBLE for another reason (optimizer, grad_req,
        kvstore, ...) re-routes at its first legacy ``forward_backward``."""
        assert self.binded
        from ..telemetry import trainhealth
        from .fused_step import fused_enabled

        matcher = getattr(mon, "re_prog", None)
        matches_stats = matcher is None or any(
            matcher.match(n)
            for n in trainhealth.monitor_row_names(self._param_names))
        if getattr(mon, "monitor_all", False) or not fused_enabled() \
                or not matches_stats:
            self._flush_pending()  # a monitor makes future steps legacy
            self._stat_monitor = None
            mon.install(self._exec)
            return
        # in-graph route: the stepper rebuilds with health stats on its
        # next update() (stale() keys on monitor attachment)
        self._stat_monitor = mon

    # -- checkpointing ----------------------------------------------------------
    def save_checkpoint(self, prefix, epoch, save_optimizer_states=False):
        """symbol json + params + optional optimizer states (reference
        module.py:165)."""
        arg_params, aux_params = self.get_params()
        save_checkpoint(prefix, epoch, self._symbol, arg_params, aux_params)
        if save_optimizer_states:
            self.save_optimizer_states("%s-%04d.states" % (prefix, epoch))

    @staticmethod
    def load(prefix, epoch, load_optimizer_states=False, **kwargs):
        sym, args, auxs = load_checkpoint(prefix, epoch)
        mod = Module(symbol=sym, **kwargs)
        mod._arg_params = args
        mod._aux_params = auxs
        if load_optimizer_states:
            mod._preload_opt_states = "%s-%04d.states" % (prefix, epoch)
        return mod

    def save_optimizer_states(self, fname):
        assert self.optimizer_initialized
        if self._update_on_kvstore:
            self._kvstore.save_optimizer_states(fname)
        else:
            with open(fname, "wb") as f:
                f.write(self._updater.get_states())

    def load_optimizer_states(self, fname):
        assert self.optimizer_initialized
        if self._update_on_kvstore:
            self._kvstore.load_optimizer_states(fname)
        else:
            with open(fname, "rb") as f:
                self._updater.set_states(f.read())
