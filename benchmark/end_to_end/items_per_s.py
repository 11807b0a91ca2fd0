"""Images trained in the window over the window's wall time: every step
completed x the global batch / (end of the last step - start of the first),
host clock, the last step closed by a host read.  All the work over all the
time: no median of chunks, no trimmed mean; a stall shows."""


def read(window):
    return window.steps * window.items_per_step / (window.ends[-1] - window.t_start)
