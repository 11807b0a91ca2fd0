"""Per step, the time a chip spends in collective operations while nothing
else runs on it, in ms, on the worst chip.  Nothing to read on one chip.
Source: device trace."""
from benchmark import trace_reduce as tr


def read(run):
    if not run.steps or not any(
            tr.is_collective(o[3]) for d in run.trace.devices for o in d.ops):
        return None
    worst = max(d.exposed_collective_s() for d in run.trace.devices)
    return worst / run.steps * 1e3
