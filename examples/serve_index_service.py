"""IndexService walkthrough: N concurrent clients on one request plane.

The service (DESIGN.md §9) fronts a StringIndex with an async, multi-tenant
API: clients submit typed ops and get futures; a micro-batch coalescer folds
everyone into shared fused dispatches; tenants are isolated key ranges;
large scans stream through opaque cursors; compaction runs on a maintenance
thread.  This example runs mixed GET/PUT/SCAN/DELETE traffic from
``--clients`` threads over two tenants and verifies the answers against a
host-side oracle.

    PYTHONPATH=src python examples/serve_index_service.py [--n 20000]
"""
import argparse
import threading

import numpy as np

from repro.data.synthetic import load
from repro.index import (
    DeleteRequest, GetRequest, IndexConfig, PutRequest, ScanRequest, Status,
)
from repro.serve.service import IndexService, ServiceConfig


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=20000)
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--ops", type=int, default=200, help="ops per client")
    ap.add_argument("--max-batch", type=int, default=256)
    ap.add_argument("--flush-ms", type=float, default=2.0)
    args = ap.parse_args()

    # 1. bulk load two tenant corpora behind ONE service: tenants share the
    #    device index but live in disjoint, contiguous key ranges.
    keys = sorted(set(load("email", args.n, seed=0)))
    vals = np.arange(len(keys), dtype=np.int64) * 10
    svc = IndexService.bulk_load(
        {"web": (keys, vals), "batch": (keys[: len(keys) // 2],
                                        vals[: len(keys) // 2] + 1)},
        IndexConfig(delta_capacity=max(4096, args.clients * args.ops)),
        ServiceConfig(max_batch=args.max_batch, max_delay_ms=args.flush_ms))
    print(f"service over {len(keys)} web + {len(keys) // 2} batch keys; "
          f"max_batch={args.max_batch} flush={args.flush_ms}ms")

    # 2. N logical clients hammer the plane concurrently: each submits mixed
    #    typed ops and awaits its futures — the coalescer does the batching.
    errors = []
    barrier = threading.Barrier(args.clients)

    def client(i: int) -> None:
        rng = np.random.default_rng(100 + i)
        tenant = "web" if i % 2 == 0 else "batch"
        tkeys = keys if tenant == "web" else keys[: len(keys) // 2]
        bias = 0 if tenant == "web" else 1
        mine = [bytes(k) for k in rng.choice(np.array(tkeys, object),
                                             args.ops // 2)]
        ops = [GetRequest(k) for k in mine]
        ops += [PutRequest(b"c%03d-%05d" % (i, j), i * 100000 + j)
                for j in range(args.ops // 4)]
        ops += [GetRequest(b"c%03d-%05d" % (i, j))
                for j in range(args.ops // 8)]
        # delete a DISJOINT slice of this client's fresh puts: within one
        # coalesced flush the plan order is puts -> deletes -> gets, so
        # deleting a key you also read back in the same batch reads absent
        ops += [DeleteRequest(b"c%03d-%05d" % (i, j))
                for j in range(args.ops // 8, args.ops // 4)]
        barrier.wait()
        res = svc.execute(ops, tenant=tenant)
        k = len(mine)
        oracle = {key: int(v) + bias for key, v in zip(tkeys, vals)}
        for q, r in zip(mine, res[:k]):
            if not r.ok or r.value != oracle[q]:
                errors.append((i, q, r))
        for j, r in enumerate(res[k: k + args.ops // 4]):
            if not r.ok:
                errors.append((i, "put", j, r))
        for j, r in enumerate(res[k + args.ops // 4:
                                  k + args.ops // 4 + args.ops // 8]):
            if r.value != i * 100000 + j:
                errors.append((i, "read-your-write", j, r))
        for j, r in enumerate(res[-args.ops // 8:]):
            if r.status != Status.OK:
                errors.append((i, "delete", j, r))

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(args.clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()

    # 3. tenant isolation: "batch" puts never leak into "web" and scans stay
    #    inside the tenant's range (keys come back tenant-local).
    leak = svc.execute([GetRequest(b"c001-00000")], tenant="web")[0]
    assert leak.status == Status.NOT_FOUND, "cross-tenant get must miss"
    scan = svc.execute([ScanRequest(keys[0], 8)], tenant="batch")[0]
    assert all(b"\x1f" not in k for k, _ in scan.entries)

    # 4. streaming scans: cursor pages concatenate to the one-shot answer.
    one = svc.execute([ScanRequest(b"", 40)], tenant="web")[0].entries
    paged, page = [], svc.scan_page(start=b"", page_size=9, tenant="web")
    while True:
        paged.extend(page.entries)
        if page.cursor is None or len(paged) >= 40:
            break
        # cursors are tenant-bound: the caller re-asserts its tenant and the
        # service checks it against the token (forged cursors -> FORBIDDEN)
        page = svc.scan_page(cursor=page.cursor, tenant="web")
    assert list(one) == paged[:40], "cursor pagination == one-shot scan"

    s = svc.stats()
    print(f"{args.clients} clients x {len(threads) and args.ops} ops: "
          f"completed={s.completed} flushes={s.flushes} "
          f"coalescing={s.coalescing_factor:.1f} ops/dispatch "
          f"max_flush={s.max_flush}")
    print(f"latency p50={s.p50_ms:.2f}ms p99={s.p99_ms:.2f}ms "
          f"(queue wait {s.mean_queue_wait_ms:.2f}ms + flush "
          f"{s.mean_flush_ms:.2f}ms, {s.syncs_per_flush:.2f} device syncs "
          f"per flush); shed={s.shed} maintenance_merges={s.merges} "
          f"delta_fill={s.delta_fill:.2f}")
    print(f"errors={len(errors)}")
    assert not errors, errors[:3]
    assert s.coalescing_factor > 1.0, "clients must share fused dispatches"
    svc.close()
    print("OK: coalesced, isolated, cursor-stable, bounded")


if __name__ == "__main__":
    main()
