"""Device time per step in the greedy-NMS Pallas kernel
(``nms_alive_pallas``), in ms, on the fullest chip.  Nothing to read where
it did not run.  Source: device trace."""


def read(run):
    dev = run.trace.fullest()
    t = dev.time_where(lambda o: o[3] == "custom-call"
                       and "nms_alive_pallas" in o[2])
    return t / run.steps * 1e3 if t > 0 and run.steps else None
