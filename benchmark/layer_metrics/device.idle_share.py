"""Share of the traced window in which no operation ran on the chip, in %,
on the worst chip.  Source: device trace."""


def read(run):
    busy = min(d.busy_s() for d in run.trace.devices)
    return (1.0 - busy / run.window_s) * 100.0
