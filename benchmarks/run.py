"""Benchmark driver: one module per paper table/figure.

``python -m benchmarks.run [--quick] [--only fig8,table2,...]``
prints ``name,us_per_call,derived`` CSV lines per the harness contract and
writes full row dumps to ``benchmarks/out/<bench>.csv``.
"""
from __future__ import annotations

import argparse
import csv
import os
import time


def _write_csv(rows, path):
    if not rows:
        return
    keys = sorted({k for r in rows for k in r})
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w", newline="") as f:
        w = csv.DictWriter(f, fieldnames=keys)
        w.writeheader()
        for r in rows:
            w.writerow(r)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="small sizes (CI)")
    ap.add_argument("--only", default=None, help="comma-separated bench names")
    ap.add_argument("--out", default=os.path.join(os.path.dirname(__file__), "out"))
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    from . import (fig1_prefix_skew, fig7_pmss, fig8_ycsb, fig9_ycsb_mixed,
                   fig11_space, fig13_unique_rate, fig14_models, fig15_cnode,
                   fig16_subtrie, table2_hardness, table3_height)

    n = 3000 if args.quick else 20000
    benches = {
        "fig1": lambda: fig1_prefix_skew.run(n),
        "table2": lambda: table2_hardness.run(min(n, 12000), 1000 if args.quick else 2000),
        "table3": lambda: table3_height.run(n),
        "fig7": lambda: fig7_pmss.run(quick=args.quick),
        "fig8": lambda: fig8_ycsb.run(n, 500 if args.quick else 2000),
        "fig9": lambda: fig9_ycsb_mixed.run(3000 if args.quick else 8000,
                                            800 if args.quick else 3000),
        "fig11": lambda: fig11_space.run(n),
        "fig13": lambda: fig13_unique_rate.run(n),
        "fig14": lambda: fig14_models.run(3000 if args.quick else 12000),
        "fig15": lambda: fig15_cnode.run(4000 if args.quick else 16000),
        "fig16": lambda: fig16_subtrie.run(n),
    }
    selected = args.only.split(",") if args.only else list(benches)
    print("name,us_per_call,derived")
    for name in selected:
        t0 = time.perf_counter()
        rows = benches[name]()
        dt = time.perf_counter() - t0
        _write_csv(rows, os.path.join(args.out, f"{name}.csv"))
        # one summary CSV line per bench module (harness contract)
        n_rows = len(rows)
        print(f"{name},{dt * 1e6 / max(n_rows, 1):.1f},rows={n_rows};wall_s={dt:.1f}")
        for r in rows[:4]:
            print(f"#   {r}")


if __name__ == "__main__":
    main()
