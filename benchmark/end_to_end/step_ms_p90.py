"""90th percentile of the wall time of every step of the window, each step
closed on the host, in ms."""
import numpy as np


def read(window):
    return float(np.percentile(window.durations, 90)) * 1e3
