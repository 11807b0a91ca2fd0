"""Device idle between one training step's program and the next, mean per
step, in ms: what the front end (FusedStepper, the functional step's
dispatch) leaves the chip waiting for.  Source: device trace."""
from benchmark import trace_reduce as tr


def read(run):
    dev = run.trace.fullest()
    steps = dev.steps()
    if len(steps) < 2:
        return None
    between = [(steps[i][1], steps[i + 1][0]) for i in range(len(steps) - 1)]
    idle = tr.subtract_length(between, [(o[0], o[1]) for o in dev.ops])
    return idle / len(between) * 1e3
