"""Device time per step in copies, transposes and layout changes (alone or
as fusions), in ms, on the fullest chip.  Source: device trace."""
from benchmark import trace_reduce as tr


def read(run):
    dev = run.trace.fullest()
    t = dev.time_where(lambda o: tr.is_formatting(o[2], o[3]))
    return t / run.steps * 1e3 if t > 0 and run.steps else None
