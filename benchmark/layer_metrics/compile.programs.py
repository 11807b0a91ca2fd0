"""Programs set-up compiled or loaded from the persistent cache:
``compile_cache.stats()`` ``programs`` at the end of set-up, the count of
JAX's ``backend_compile_duration`` events, an eager operator's
one-primitive program among them.  The same on every run of a cell, warm or
cold.  None for a program that does not count them.  Source: program
counter."""


def read(run):
    return run.cache_stats.get("programs")
