"""p50_ms: median client latency, from the time an op was due on the
open-loop schedule to the time the client holds its answer (host clock).
Every op of the window counts; a failed one counts as the grace limit."""
from lits_bench.harness import GRACE_S
from lits_bench.stats import percentile


def read(run):
    return percentile(run.latency_ms, 50, GRACE_S * 1e3)
