"""device_search_ms: device time of the search programs
(``_search_batch_jit``) in the trace of the window, per 1000 gets due in
the window (ms per 1000 ops)."""
from lits_bench import trace


def read(run):
    n = len(run.latency_ms)
    if run.trace is None or not n:
        return None
    s = trace.program_seconds(run.trace, "_search_batch_jit")
    return None if s is None else s * 1e3 / (n / 1000)
