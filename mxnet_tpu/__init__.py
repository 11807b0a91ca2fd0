"""mxnet_tpu — a TPU-native deep-learning framework with MXNet's capabilities.

Brand-new design on JAX/XLA/Pallas: the reference's threaded dependency engine
becomes XLA async dispatch; NNVM graph passes become jit tracing; CUDA kernels
become XLA ops + Pallas kernels; ps-lite KVStore becomes XLA collectives over
a device mesh.  See SURVEY.md at the repo root for the full blueprint.

Import surface mirrors ``import mxnet as mx``: mx.nd, mx.sym, mx.gluon,
mx.autograd, mx.init, mx.io, mx.kv, mx.metric, mx.mod, ...
"""
__version__ = "0.1.0"

from .base import MXNetError
from .context import Context, cpu, gpu, tpu, current_context, num_gpus
from . import base
from . import ndarray
from . import ndarray as nd
from . import autograd
from . import random

seed = random.seed

# jax's persistent compilation cache latches its directory at the FIRST XLA
# compile in the process, so where it lives (compile_cache.place_jax_cache:
# JAX_COMPILATION_CACHE_DIR if set, else <checkout>/.jax_cache on an
# accelerator, else off) and the MXNET_AOT_CACHE tiers must be applied at
# import, before anything can compile.
from . import compile_cache as _compile_cache

_compile_cache.place_jax_cache()
_compile_cache.activate()


def __getattr__(name):
    """Lazy submodule loading keeps `import mxnet_tpu` fast."""
    import importlib

    lazy = {
        "sym": ".symbol",
        "symbol": ".symbol",
        "gluon": ".gluon",
        "init": ".initializer",
        "initializer": ".initializer",
        "optimizer": ".optimizer",
        "metric": ".metric",
        "io": ".io",
        "kv": ".kvstore",
        "kvstore": ".kvstore",
        "mod": ".module",
        "module": ".module",
        "callback": ".callback",
        "lr_scheduler": ".lr_scheduler",
        "model": ".model",
        "name": ".name",
        "attribute": ".attribute",
        "autotune": ".autotune",
        "operator": ".operator",
        "rnn": ".rnn",
        "executor_manager": ".executor_manager",
        "viz": ".visualization",
        "profiler": ".profiler",
        "telemetry": ".telemetry",
        "recordio": ".recordio",
        "image": ".image",
        "test_utils": ".test_utils",
        "parallel": ".parallel",
        "executor": ".executor",
        "compile_cache": ".compile_cache",
        "monitor": ".monitor",
        "visualization": ".visualization",
        "contrib": ".contrib",
        "engine": ".engine",
        "rtc": ".rtc",
        "predictor": ".predictor",
        "serving": ".serving",
        "th": ".torch_bridge",
        "torch_bridge": ".torch_bridge",
    }
    if name in lazy:
        try:
            mod = importlib.import_module(lazy[name], __name__)
        except ImportError as e:
            # a missing OR broken optional dependency (torch absent, torch's
            # native extension failing to load, …) reads as "feature absent"
            # for hasattr()-style probes; an import failure originating in
            # one of our OWN submodules must surface loudly, not masquerade
            # as an absent feature
            if (getattr(e, "name", None) or "").split(".")[0] == __name__.split(".")[0]:
                raise
            raise AttributeError(
                "module %r has no attribute %r (%s)" % (__name__, name, e)
            ) from e
        globals()[name] = mod
        return mod
    raise AttributeError("module %r has no attribute %r" % (__name__, name))
