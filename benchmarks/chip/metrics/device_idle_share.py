"""device_idle_share: 1 - (union of device op intervals) / window, in %,
from the profiler trace of the window."""


def read(run):
    if run.trace is None:
        return None
    return 100.0 * run.trace.idle_share
