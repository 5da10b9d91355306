"""facade_execute_ms: median host wall time of one ``StringIndex.execute``
call over the window, from the benchmark's wrapper around the index."""
import numpy as np


def read(run):
    if not len(run.flush_ms):
        return None
    return float(np.median(run.flush_ms))
