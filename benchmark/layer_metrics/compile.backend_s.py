"""Seconds of set-up spent in the backend compile of every program of the
process, which on a hit of the persistent cache is reading and loading the
executable: ``compile_cache.stats()`` ``backend_s`` at the end of set-up,
JAX's own ``backend_compile_duration`` events.  Source: program counter."""


def read(run):
    return run.cache_stats.get("backend_s")
