"""Seconds of set-up in which some program was being lowered to MLIR:
``compile_cache.stats()`` ``lower_union_s`` at the end of set-up, the union
of JAX's ``jaxpr_to_mlir_module_duration`` spans.  Paid on every run, cache
hit or not.  None for a program that does not keep the union.  Source:
program counter."""


def read(run):
    return run.cache_stats.get("lower_union_s")
