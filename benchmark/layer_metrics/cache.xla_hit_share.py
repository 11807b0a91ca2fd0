"""Share of the programs asked of XLA's persistent cache during set-up that
it held, in %: ``compile_cache.stats()`` xla_hits / (hits + misses) at the
end of set-up.  Source: program counter."""


def read(run):
    hits = run.cache_stats.get("xla_hits", 0)
    asked = hits + run.cache_stats.get("xla_misses", 0)
    return hits / asked * 100.0 if asked else None
