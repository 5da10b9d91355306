"""Quickstart: the `StringIndex` facade — bulk load, typed mixed batches,
auto-compaction, versioned snapshots (DESIGN.md §8).

    PYTHONPATH=src python examples/quickstart.py [--n 20000]
"""
import argparse
import os
import tempfile

import numpy as np

from repro.data.synthetic import load
from repro.index import (
    GetRequest, IndexConfig, PutRequest, ScanRequest, Status, StringIndex,
)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=20000)
    args = ap.parse_args()

    # 1. bulk load (paper Sec. 3.1): sample -> HPT -> collision-driven build.
    #    IndexConfig is the one policy object: delta sizing, merge threshold,
    #    scan window.
    keys = sorted(set(load("email", args.n, seed=0)))
    values = np.arange(len(keys), dtype=np.int64) * 10
    cfg = IndexConfig(delta_capacity=2048, auto_merge_threshold=0.75)
    index = StringIndex.bulk_load(keys, values, cfg)
    print(f"bulk loaded {index.n_entries} keys; width={index.width}, "
          f"device size {index.nbytes() / 2**20:.1f} MiB")

    # 2. one typed mixed batch: gets + a range scan + fresh puts.  execute()
    #    plans it into grouped fused dispatches (one insert_batch for all
    #    puts, one search_batch for all gets, one scan_batch per window).
    probe = keys[::97][:512]
    batch = (
        [GetRequest(k) for k in probe]
        + [ScanRequest(probe[0], window=5)]
        + [PutRequest(b"zz-new-key-%04d" % i, 100000 + i) for i in range(128)]
        + [GetRequest(b"zz-new-key-0007"), GetRequest(b"definitely-missing")]
    )
    res = index.execute(batch)
    gets = res.results[: len(probe)]
    got_ok = all(
        r.ok and r.value == values[keys.index(k)] for r, k in zip(gets, probe))
    print(f"mixed batch: {res.n_get} gets / {res.n_put} puts / "
          f"{res.n_scan} scans; values ok={got_ok}")
    scan_entries = res.results[len(probe)].entries
    print(f"scan from {probe[0]!r}: {[k for k, _ in scan_entries]}")
    fresh = res.results[len(probe) + 1 + 128]
    missing = res.results[-1]
    print(f"get-after-put in one batch: {fresh.status.name} value={fresh.value} "
          f"(puts apply first); miss status={missing.status.name}")

    # 3. auto-compaction: enough puts to cross the configured threshold —
    #    no delta_fill_fraction polling in application code.
    waves = [PutRequest(b"wave-%05d" % i, i) for i in range(1600)]
    r2 = index.execute(waves)
    print(f"after {len(waves)} more puts: auto-merged={r2.merged}, "
          f"delta fill={r2.delta_fill:.2f}, merges so far={index.merge_count}")
    # scans are read-your-writes (DESIGN.md §11): unmerged delta puts are
    # scannable immediately — the merge only changes the physical layout
    print(f"freshly-put keys scannable: "
          f"{[k for k, _ in index.scan(b'wave-', 3)]}")

    # 4. versioned snapshot roundtrip: save -> load -> identical answers
    path = os.path.join(tempfile.gettempdir(), "quickstart-lits.snap")
    index.save(path)
    restored = StringIndex.load(path, cfg)
    f, v = restored.get_batch(probe)
    same = bool(f.all()) and (v == np.asarray([values[keys.index(k)] for k in probe])).all()
    print(f"snapshot roundtrip ({os.path.getsize(path) / 2**20:.1f} MiB): "
          f"restored lookups identical={bool(same)}")
    assert got_ok and same and fresh.status == Status.OK


if __name__ == "__main__":
    main()
