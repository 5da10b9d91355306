"""client_p99_ms: 99th percentile of client latency, due time to answer
(host clock), over every op of the window; a failed op counts as the grace
limit.  A pause of the whole machine (106-113 ms, seven in 204 s of
windows on a TPU v5e host) delays more than 1% of a 10 s window's ops, so
this tail swings from run to run; it is read per layer, beside the
end-to-end median."""
from lits_bench.harness import GRACE_S
from lits_bench.stats import percentile


def read(run):
    return percentile(run.latency_ms, 99, GRACE_S * 1e3)
