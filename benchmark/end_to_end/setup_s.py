"""Process start to the first timed step: imports, weights, trace and lower,
compile or cache load, the first (checked) steps and the warm-up."""


def read(window):
    return window.t_start - window.t_process
