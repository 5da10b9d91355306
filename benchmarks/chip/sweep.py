"""Find a cell's knee: one set-up, then one open-loop window per rate.

    python benchmarks/chip/sweep.py --workload email-c --seed 11 --seconds 5 \
        --rates 2000,4000,8000,16000

For each rate it prints one JSON line: latency quartiles and tail, the
median latency of the window's first and last quarter (a backlog that grows
shows as a last quarter far above the first), the share of ops answered by
the close, ops per flush and programs made ready in the window.  Every
answer is checked against the reference at the end.  The knee is the
highest rate whose backlog does not grow; the cell's traffic file holds
0.8 of it.
"""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                   "src")]


def sweep(cell, seed: int, seconds: float, rates, **session_kw):
    """One JSON-ready row per rate, and the check of every answer."""
    import json

    import numpy as np

    from lits_bench.harness import GRACE_S, Session
    from lits_bench.stats import percentile

    s = Session(cell, seed, **session_kw)
    rows = []
    try:
        s.warm()
        for k, rate in enumerate(rates):
            ph = s.window(seconds, seed + k, rate=rate)
            win = ph.win
            lat = (win.done - (win.t0 + ph.stream.due)) * 1e3
            q = len(lat) // 4
            cap = GRACE_S * 1e3
            rows.append({
                "rate": rate, "ops": len(lat),
                "p50_ms": percentile(lat, 50, cap),
                "p99_ms": percentile(lat, 99, cap),
                "p50_first_quarter_ms": percentile(lat[:q], 50, cap),
                "p50_last_quarter_ms": percentile(lat[-q:], 50, cap),
                "answered_by_close": float(np.mean(
                    win.done <= win.t0 + seconds)),
                "ops_per_flush": ph.completed / max(ph.flushes, 1),
                "facade_execute_ms": float(np.median(ph.flush_ms))
                if len(ph.flush_ms) else None,
                "gen_lag_p99_ms": percentile(
                    (win.submitted - (win.t0 + ph.stream.due)) * 1e3, 99),
                "compiles_in_window": ph.compiles})
            print(json.dumps(rows[-1]), flush=True)
            s.check()
    finally:
        s.close()
    return rows, s.check()


def main(argv=None) -> int:
    import argparse
    import json

    from lits_bench.spec import load_cell

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    _rows, res = sweep(load_cell(args.workload), args.seed, args.seconds,
                       [float(r) for r in args.rates.split(",")])
    print(json.dumps({"correct": res.correct, "checks": res.as_checks(),
                      "first_fault": res.first_fault}), flush=True)
    return 0 if res.correct else 1


if __name__ == "__main__":
    sys.exit(main())
