"""gen_lag_p99_ms: 99th percentile of how late the load generator
submitted an op after it was due (host clock)."""
from lits_bench.stats import percentile


def read(run):
    return percentile(run.gen_lag_ms, 99)
