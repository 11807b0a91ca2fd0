"""Seconds of set-up in which some program was being traced to a jaxpr:
``compile_cache.stats()`` ``trace_union_s`` at the end of set-up, the union
of JAX's ``jaxpr_trace_duration`` spans, so a jit traced inside another
program's trace counts once (``compile.trace_lower_s`` sums them and counts
it twice).  Paid on every run, cache hit or not.  None for a program that
does not keep the union.  Source: program counter."""


def read(run):
    return run.cache_stats.get("trace_union_s")
