"""Seconds of set-up spent tracing programs to jaxprs and lowering them to
MLIR, summed over every program of the process: ``compile_cache.stats()``
``trace_s + lower_s`` at the end of set-up, which are JAX's own
``jaxpr_trace_duration`` and ``jaxpr_to_mlir_module_duration`` events.  Paid
on every run, cache hit or not.  Source: program counter."""


def read(run):
    stats = run.cache_stats
    if "trace_s" not in stats:
        return None
    return stats["trace_s"] + stats["lower_s"]
