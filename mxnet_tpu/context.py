"""Device context — TPU-native equivalent of reference ``python/mxnet/context.py``.

In the reference a ``Context(dev_type, dev_id)`` names a CPU/GPU device and a
thread-local default-context stack scopes imperative ops onto it.  Here a
Context maps onto a concrete ``jax.Device``.  ``gpu(i)`` is kept as an alias
for the i-th accelerator so reference scripts run unchanged on TPU.

Two placement rules, stated because both decide where arrays live:

* The thread-local DEFAULT context is the JAX default device — ``tpu(0)``
  on a host with a chip, ``cpu(0)`` without one.  Reference MXNet defaults
  to ``cpu(0)`` because its accelerator is opt-in.  Here arrays made
  without a ``ctx`` already land on the JAX default device, so a default of
  ``cpu(0)`` on a TPU host named a device nothing else used: ``Module``
  parameters ended up on the chip regardless (the initializer rebinds them;
  chip run, PR 21), while an array made with an explicit
  ``ctx=mx.current_context()`` was committed to the HOST device.
* In a process with NO accelerator (the CPU test tier), ``tpu(i)`` /
  ``gpu(i)`` alias the host devices so reference scripts and the tests run
  unchanged.  That alias is deliberate and CPU-only; whatever must not
  mistake a CPU for the chip (``chip_smoke.py``, ``bench.py``) asserts
  ``jax.devices()[0].platform`` and array placement itself.  With an
  accelerator present, a ``device_id`` past the last chip raises.
"""
from __future__ import annotations

import threading

__all__ = ["Context", "cpu", "gpu", "tpu", "current_context", "num_gpus", "num_tpus"]


class Context:
    """A device context.

    Parameters mirror the reference (``python/mxnet/context.py:23``):
    ``Context('tpu', 0)``, ``Context('cpu')``.  ``device_type`` of ``'gpu'``
    resolves to the platform's accelerators (TPU here) so that reference
    training scripts written with ``mx.gpu(i)`` work verbatim.
    """

    _default_ctx = threading.local()

    devtype2str = {1: "cpu", 2: "gpu", 3: "cpu_pinned", 5: "cpu_shared", 6: "tpu"}
    devstr2type = {"cpu": 1, "gpu": 2, "cpu_pinned": 3, "cpu_shared": 5, "tpu": 6}

    def __init__(self, device_type, device_id=0):
        if isinstance(device_type, Context):
            self.device_typeid = device_type.device_typeid
            self.device_id = device_type.device_id
        else:
            self.device_typeid = Context.devstr2type[device_type]
            self.device_id = device_id
        self._old_ctx = None

    @property
    def device_type(self):
        return Context.devtype2str[self.device_typeid]

    def __hash__(self):
        return hash((self.device_typeid, self.device_id))

    def __eq__(self, other):
        return (
            isinstance(other, Context)
            and self.device_typeid == other.device_typeid
            and self.device_id == other.device_id
        )

    def __str__(self):
        return "%s(%d)" % (self.device_type, self.device_id)

    def __repr__(self):
        return self.__str__()

    def __enter__(self):
        self._old_ctx = current_context()
        Context._default_ctx.value = self
        return self

    def __exit__(self, ptype, value, trace):
        Context._default_ctx.value = self._old_ctx

    # -- JAX mapping --------------------------------------------------------
    @property
    def jax_device(self):
        """The concrete ``jax.Device`` this context denotes."""
        import jax

        if self.device_type in ("cpu", "cpu_pinned", "cpu_shared"):
            return jax.devices("cpu")[self.device_id]
        # 'gpu' and 'tpu' both mean "the platform accelerator".
        accel = [d for d in jax.devices() if d.platform != "cpu"]
        if not accel:
            # CPU-only process: the documented alias (module docstring)
            devs = jax.devices()
            return devs[self.device_id % len(devs)]
        if self.device_id >= len(accel):
            from .base import MXNetError

            raise MXNetError("%s: this process sees %d accelerator device(s)"
                             % (self, len(accel)))
        return accel[self.device_id]

    def empty_cache(self):
        """Release pooled device memory (reference ctx.empty_cache)."""
        # XLA owns the allocator; live buffers are freed by GC.  Nothing to do
        # beyond encouraging a collection.
        import gc

        gc.collect()


def cpu(device_id=0):
    """Return a CPU context."""
    return Context("cpu", device_id)


def gpu(device_id=0):
    """Return the i-th accelerator context (alias of :func:`tpu` on TPU hosts)."""
    return Context("gpu", device_id)


def tpu(device_id=0):
    """Return a TPU context."""
    return Context("tpu", device_id)


def num_gpus():
    """Number of accelerator devices visible (reference mx.context.num_gpus)."""
    import jax

    try:
        return len([d for d in jax.devices() if d.platform != "cpu"])
    except RuntimeError:
        return 0


num_tpus = num_gpus


def current_context():
    """The thread-local default context (reference context.py
    current_context); outside any ``with ctx:`` scope, the context of the JAX
    default device (module docstring)."""
    if not hasattr(Context._default_ctx, "value"):
        import jax

        on_cpu = jax.local_devices()[0].platform == "cpu"
        Context._default_ctx.value = Context("cpu" if on_cpu else "tpu", 0)
    return Context._default_ctx.value
