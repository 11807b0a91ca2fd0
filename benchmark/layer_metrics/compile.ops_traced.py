"""Registered-operator calls set-up made while tracing a program (the Gluon
path's ``nd._invoke_raw`` under jit, the Symbol path's ``run_node``):
``compile_cache.stats()`` ``ops_traced`` at the end of set-up.  The same on
every run of a cell and on every host, so it says whether a change traced
less work where the trace's seconds move with the host.  None for a program
that does not count them.  Source: program counter."""


def read(run):
    return run.cache_stats.get("ops_traced")
