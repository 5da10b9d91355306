"""Correctness on many seeds in one process, and the control.

    python benchmarks/chip/seeds.py --workload email-c --seconds 10 \
        --seeds 101,102,103 --control-seeds 201,202,203

One set-up; then, for each seed, one window of the cell's traffic at its
rate with every answer checked against the reference (the readings
``correct`` rests on).  Then the control: the reference itself, storing
values in 32 bits, put in the index's place under the same service, on
each control seed.  A sound program reads 0 wrong answers; the control
must read more.  One JSON line per seed.
"""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                   "src")]


def run_seeds(cell, seeds, seconds, label, wrap_index=None, **session_kw):
    """One row per seed: what the check counted in that seed's window."""
    import json

    from lits_bench.harness import Session

    s = Session(cell, seeds[0], wrap_index=wrap_index, **session_kw)
    out = []
    try:
        s.warm()
        s.check()
        for seed in seeds:
            before = dict(vars(s.result))
            ph = s.window(seconds, seed)
            res = s.check()
            row = {"run": label, "seed": seed, "ops": len(ph.answers),
                   "compiles_in_window": ph.compiles}
            for k in ("wrong", "unanswered", "misaligned", "refused"):
                row[k] = getattr(res, k) - before[k]
            row["first_fault"] = res.first_fault if row["wrong"] else None
            res.first_fault = None
            print(json.dumps(row), flush=True)
            out.append(row)
    finally:
        s.close()
    return out


def main(argv=None) -> int:
    import argparse
    import functools

    from lits_bench.spec import load_cell

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    seeds = [int(x) for x in args.seeds.split(",")]
    rows = run_seeds(cell, seeds, args.seconds, "program")
    ok = all(r["wrong"] == r["unanswered"] == r["misaligned"] == 0
             for r in rows)
    cseeds = [int(x) for x in args.control_seeds.split(",") if x]
    if cseeds:
        rows = run_seeds(cell, cseeds, args.seconds, "control_int32",
                         functools.partial(reference_in_place, value_bits=32))
        ok = ok and all(r["wrong"] > 0 for r in rows)
    return 0 if ok else 1


def reference_in_place(index, corpus, prefix, **kw):
    from lits_bench.control import ReferenceIndex

    return ReferenceIndex(index, corpus, prefix, **kw)


if __name__ == "__main__":
    sys.exit(main())
