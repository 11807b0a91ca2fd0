"""Device time per step in convolutions and convolution fusions (matrix
products included: the TPU compiler lowers them to convolutions), in ms, on
the fullest chip.  Source: device trace."""
from benchmark import trace_reduce as tr


def read(run):
    dev = run.trace.fullest()
    t = dev.time_where(lambda o: tr.is_convolution(o[2], o[3], o[4]))
    return t / run.steps * 1e3 if t > 0 and run.steps else None
