"""Share of the traced window in which no operation ran on the device:
1 - (union of the op intervals) / window, from the profiler trace."""
from bench import trace as T


def read(run):
    w0, w1 = T.window(run.trace)
    return 100.0 * (1.0 - T.busy_ns(run.trace) / (w1 - w0))
