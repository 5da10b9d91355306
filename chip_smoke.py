"""Smoke test of the main path on a TPU: bulk load -> StringIndex -> IndexService.

    python chip_smoke.py                        # one chip, 1M email keys
    python chip_smoke.py --chips 4 --n 200000   # 4 key-range shards, 4 chips

One chip (the default): generate ``--n`` unique ``email`` keys with random
int64 values from ``--seed``, bulk-load them into a ``StringIndex`` (jnp
traversal, 16384-entry delta buffer) and check that every pool lives on the
TPU.  Front it with an ``IndexService`` (one tenant) and send mixed flushes of
puts (new keys and updates), deletes, gets (hits and misses) and read-your-
writes scans, then ``scan_page`` pages.  Force an epoch compaction, and read
every acknowledged put and delete back.  Every result is checked against a
host dict / sorted-list oracle of the same semantics.

``--chips 4`` runs only the sharded phase: a ``DistributedStringIndex`` with
4 shards over a 4-chip mesh, served through an ``IndexService``, whose routed
gets and cross-shard scans are compared with a single-chip ``StringIndex``
over the same keys and with the oracle.

The script refuses to run (exit 1, no result line) when JAX finds no TPU.
Phase times and compile counts are printed as information.  Only if every
check held is the last line of stdout
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
The compile cache goes where ``JAX_COMPILATION_CACHE_DIR`` says, else to
``.jax_cache/`` in the checkout.
"""
from __future__ import annotations

import argparse
import bisect
import json
import os
import sys
import time

import jax
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

TENANT = "smoke"
DATASET = "email"
DELTA_CAPACITY = 16384
ROUNDS = 12            # mixed flushes before the compaction
NEW, UPD_BASE, UPD_FRESH = 512, 448, 64          # puts per flush
DEL_BASE, DEL_FRESH, DEL_ABSENT = 192, 48, 16    # deletes per flush
GETS = 2048            # gets per flush (and per read-back batch)
SCANS, WINDOW = 16, 64  # scans per flush, scan window
MISSES = 256           # keys that are never inserted
PAGES = 4              # scan_page pages per walk
MIN_N = 4000


def require_tpu():
    """The devices JAX sees; exits 1 unless they are TPUs."""
    devs = jax.devices()
    print(f"jax {jax.__version__}; devices: {devs}", flush=True)
    if devs[0].platform != "tpu":
        raise SystemExit(
            f"chip_smoke: JAX found no TPU (platform {devs[0].platform!r}); "
            "this script runs only on the chip")
    return devs


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def info(msg: str) -> None:
    print(msg, flush=True)


class CompileCounter:
    """Backend compiles (seconds include persistent-cache reads) and
    persistent-cache hits/misses, from JAX's monitoring events."""

    def __init__(self):
        import jax.monitoring as mon

        self.count, self.seconds, self.hits, self.misses = 0, 0.0, 0, 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def line(self, label: str) -> str:
        return (f"{label}: {self.count} backend compiles, {self.seconds:.1f} s; "
                f"persistent cache hits {self.hits}, misses {self.misses}")


class Oracle:
    """The semantics the index must match: a dict plus a sorted key list."""

    def __init__(self, keys, vals):
        self.kv = dict(zip(keys, vals))
        self.order = sorted(keys)

    def get(self, k):
        return self.kv.get(k)

    def put(self, k, v) -> bool:
        """Upsert; True when the key was live (an update)."""
        existed = k in self.kv
        if not existed:
            bisect.insort(self.order, k)
        self.kv[k] = v
        return existed

    def delete(self, k) -> bool:
        if k not in self.kv:
            return False
        del self.kv[k]
        del self.order[bisect.bisect_left(self.order, k)]
        return True

    def scan(self, start, window):
        i = bisect.bisect_left(self.order, start)
        return [(k, self.kv[k]) for k in self.order[i:i + window]]


def make_data(n: int, n_fresh: int, seed: int):
    """``n`` base keys and ``n_fresh`` keys not among them, with int64
    values; one deterministic stream from the seed."""
    from repro.data.synthetic import load

    keys = load(DATASET, n + n_fresh, seed=seed)
    rng = np.random.default_rng(seed)
    i64 = np.iinfo(np.int64)
    vals = rng.integers(i64.min, i64.max, size=n + n_fresh, dtype=np.int64)
    return keys[:n], vals[:n], keys[n:], vals[n:], rng


def check_on_device(tree, dev, what: str) -> None:
    for leaf in jax.tree_util.tree_leaves(tree):
        check(leaf.devices() == {dev},
              f"{what}: a pool lives on {leaf.devices()}, not {dev}")


def memory_in_use(dev):
    stats = dev.memory_stats()
    return None if stats is None else stats.get("bytes_in_use")


def check_results(batch, results, expected) -> None:
    """``expected[i]``: (status name, value / updated flag / entries)."""
    check(len(results) == len(batch), "result count != request count")
    for req, res, (status, want) in zip(batch, results, expected):
        got = res.status.name
        check(got == status, f"{req}: status {got}, expected {status}")
        if status != "OK":
            continue
        kind = type(req).__name__
        if kind == "GetRequest":
            check(res.value == want, f"{req}: value {res.value} != {want}")
        elif kind == "PutRequest":
            check(res.updated == want, f"{req}: updated {res.updated}")
        elif kind == "ScanRequest":
            check(list(res.entries) == want,
                  f"{req}: scan entries differ from the oracle")


# ---------------------------------------------------------------------------
# one chip: load, serve, compact
# ---------------------------------------------------------------------------

def run_one_chip(args, dev) -> None:
    from repro.index import (
        DeleteRequest, GetRequest, IndexConfig, PutRequest, ScanRequest,
        StringIndex,
    )
    from repro.serve.service import IndexService

    check(args.n >= MIN_N, f"--n must be at least {MIN_N}")
    t0 = time.perf_counter()
    base, base_vals, fresh, fresh_vals, rng = make_data(
        args.n, ROUNDS * NEW + MISSES, args.seed)
    t1 = time.perf_counter()
    info(f"data: {len(base)} {DATASET} keys + {len(fresh)} fresh, "
         f"{t1 - t0:.1f} s")

    enc = [IndexService.encode_key(TENANT, k) for k in base]
    index = StringIndex.bulk_load(
        enc, base_vals, IndexConfig(delta_capacity=DELTA_CAPACITY))
    t2 = time.perf_counter()
    check_on_device(index.ti, dev, "bulk load")
    info(f"load: {index.n_entries} keys, width {index.width}, "
         f"index.nbytes {index.nbytes()}, host build {t2 - t1:.1f} s, "
         f"device bytes in use {memory_in_use(dev)}")
    check(index.n_entries == len(base), "entry count != key count")

    oracle = Oracle(base, base_vals.tolist())
    live_fresh: list = []          # fresh keys put and not deleted
    acked_puts, acked_dels, scan_starts = [], [], []
    crossed_puts = crossed_dels = 0
    misses = fresh[ROUNDS * NEW:]
    svc = IndexService(index)
    try:
        def pick_live(pool, k, exclude):
            out = []
            for i in rng.permutation(len(pool)):
                key = pool[i]
                if key in oracle.kv and key not in exclude:
                    out.append(key)
                    exclude.add(key)
                    if len(out) == k:
                        break
            check(len(out) == k, "not enough live keys to pick from")
            return out

        flush_s = []
        for r in range(ROUNDS):
            new = fresh[r * NEW:(r + 1) * NEW]
            new_vals = fresh_vals[r * NEW:(r + 1) * NEW].tolist()
            used = set(new)
            upd = pick_live(base, UPD_BASE, used) + (
                pick_live(live_fresh, UPD_FRESH, used) if r else
                pick_live(base, UPD_FRESH, used))
            upd_vals = rng.integers(-2**63, 2**63 - 1, len(upd),
                                    dtype=np.int64).tolist()
            dels = pick_live(base, DEL_BASE, used) + (
                pick_live(live_fresh, DEL_FRESH, used) if r else
                pick_live(base, DEL_FRESH, used)) + list(
                misses[r * DEL_ABSENT % MISSES:][:DEL_ABSENT])
            earlier = [live_fresh[i] for i in rng.integers(
                0, len(live_fresh), 256)] if live_fresh else []
            gets = ([base[i] for i in rng.integers(0, len(base), GETS // 2)]
                    + new[:256] + dels[:256] + earlier + list(misses))
            gets += [base[i] for i in rng.integers(0, len(base),
                                                   GETS - len(gets))]
            starts = [k[:-1] for k in new[:SCANS // 2]] \
                + [k[:-2] for k in dels[:SCANS // 2]]
            scan_starts += starts

            # the oracle, in the order execute applies a batch: puts,
            # then deletes, then gets and scans see the result
            exp_put = [("OK", oracle.put(k, v)) for k, v in
                       zip(new + upd, new_vals + upd_vals)]
            exp_del = [("OK" if oracle.delete(k) else "NOT_FOUND", None)
                       for k in dels]
            live_fresh = [k for k in live_fresh + new if k in oracle.kv]
            exp_get = [("OK", oracle.get(k)) if k in oracle.kv
                       else ("NOT_FOUND", None) for k in gets]
            exp_scan = [("OK", oracle.scan(s, WINDOW)) for s in starts]
            newset, delset = set(new), set(dels)
            for s, (_, ent) in zip(starts, exp_scan):
                crossed_puts += any(k in newset for k, _ in ent)
                hi = ent[-1][0] if len(ent) == WINDOW else None
                crossed_dels += any(s <= d and (hi is None or d < hi)
                                    for d in delset)
            batch = ([PutRequest(k, v) for k, v in
                      zip(new + upd, new_vals + upd_vals)]
                     + [DeleteRequest(k) for k in dels]
                     + [GetRequest(k) for k in gets]
                     + [ScanRequest(s, WINDOW) for s in starts])
            expected = exp_put + exp_del + exp_get + exp_scan
            perm = rng.permutation(len(batch))
            batch = [batch[i] for i in perm]
            expected = [expected[i] for i in perm]

            ts = time.perf_counter()
            results = svc.execute(batch, tenant=TENANT, timeout=900)
            flush_s.append(time.perf_counter() - ts)
            check_results(batch, results, expected)
            acked_puts += new + upd
            acked_dels += [k for k, (st, _) in zip(dels, exp_del)
                           if st == "OK"]
        t3 = time.perf_counter()
        st = svc.stats()
        info(f"serve: {ROUNDS} flushes of {len(batch)} ops checked, first "
             f"{flush_s[0]:.2f} s (compiles), later median "
             f"{float(np.median(flush_s[1:])):.3f} s; delta fill "
             f"{st.delta_fill:.3f}; scans crossing unmerged puts "
             f"{crossed_puts}, crossing tombstones {crossed_dels}")
        check(crossed_puts > 0 and crossed_dels > 0,
              "no scan crossed an unmerged put and a tombstone")
        check(st.delta_fill > 0.25, f"delta fill {st.delta_fill} too low")

        def walk_pages(start):
            got, cursor = [], None
            for _ in range(PAGES):
                page = svc.scan_page(start, WINDOW, tenant=TENANT,
                                     cursor=cursor)
                check(page.status.name == "OK", f"scan_page {page.status}")
                got += list(page.entries)
                cursor = page.cursor
                if cursor is None:
                    break
            check(got == oracle.scan(start, len(got)) and
                  len(got) == min(PAGES * WINDOW,
                                  len(oracle.scan(start, PAGES * WINDOW))),
                  f"scan_page walk from {start!r} differs from the oracle")

        walk_pages(scan_starts[0])
        walk_pages(scan_starts[-1])

        # forced epoch compaction, then read every acknowledged write back
        epoch0 = st.epoch
        check(svc.compact(), "compact() ran no merge")
        t4 = time.perf_counter()
        st = svc.stats()
        check(st.epoch == epoch0 + 1, f"epoch {epoch0} -> {st.epoch}")
        check(st.maintenance_errors == 0,
              f"maintenance errors: {st.last_maintenance_error}")
        check(st.delta_fill == 0.0, "delta not empty after compaction")
        check_on_device(index.ti, dev, "after compaction")
        info(f"compact: epoch {epoch0} -> {st.epoch}, merge wall "
             f"{st.merge_wall_ms:.0f} ms, {t4 - t3:.1f} s")

        untouched = [base[i] for i in rng.integers(0, len(base), GETS)]
        keys = acked_puts + acked_dels + untouched
        keys += untouched[: -len(keys) % GETS]
        for lo in range(0, len(keys), GETS):
            chunk = keys[lo:lo + GETS]
            batch = [GetRequest(k) for k in chunk]
            exp = [("OK", oracle.get(k)) if k in oracle.kv
                   else ("NOT_FOUND", None) for k in chunk]
            check_results(batch, svc.execute(batch, tenant=TENANT,
                                              timeout=900), exp)
        for lo in range(0, len(scan_starts), SCANS):
            batch = [ScanRequest(s, WINDOW)
                     for s in scan_starts[lo:lo + SCANS]]
            exp = [("OK", oracle.scan(r.start, WINDOW)) for r in batch]
            check_results(batch, svc.execute(batch, tenant=TENANT,
                                              timeout=900), exp)
        walk_pages(scan_starts[0])
        st = svc.stats()
        check(st.maintenance_errors == 0,
              f"maintenance errors: {st.last_maintenance_error}")
        info(f"read-back: {len(acked_puts)} puts, {len(acked_dels)} "
             f"deletes, {len(scan_starts)} scans checked after compaction, "
             f"{time.perf_counter() - t4:.1f} s; device bytes in use "
             f"{memory_in_use(dev)}")
    finally:
        svc.close()


# ---------------------------------------------------------------------------
# four chips: routed gets and cross-shard scans
# ---------------------------------------------------------------------------

def run_sharded(args, devs) -> None:
    from repro.distributed.index_service import DistributedStringIndex
    from repro.index import GetRequest, ScanRequest, StringIndex
    from repro.launch.mesh import make_mesh
    from repro.serve.service import IndexService

    n_shards = 4
    check(len(devs) >= n_shards, f"--chips 4 needs 4 devices, saw {len(devs)}")
    check(args.n >= MIN_N, f"--n must be at least {MIN_N}")
    mesh = make_mesh((n_shards,), ("data",), devices=devs[:n_shards])
    t0 = time.perf_counter()
    base, base_vals, _fresh, _fv, rng = make_data(args.n, 0, args.seed)
    enc = [IndexService.encode_key(TENANT, k) for k in base]
    t1 = time.perf_counter()
    dsi = DistributedStringIndex.build(enc, base_vals, n_shards=n_shards,
                                       mesh=mesh,
                                       per_dest_capacity=GETS // n_shards)
    t2 = time.perf_counter()
    ref = StringIndex.bulk_load(enc, base_vals)
    t3 = time.perf_counter()
    check_on_device(ref.ti, devs[0], "single-chip reference")
    for leaf in jax.tree_util.tree_leaves(dsi.sidx.stacked):
        shards = leaf.addressable_shards
        check(sorted(s.device.id for s in shards)
              == sorted(d.id for d in devs[:n_shards])
              and all(s.data.shape[0] == 1 for s in shards),
              "a stacked pool is not split one shard per chip")
    info(f"load: {len(base)} keys in {n_shards} shards, width {dsi.width}, "
         f"data {t1 - t0:.1f} s, sharded build {t2 - t1:.1f} s, reference "
         f"build {t3 - t2:.1f} s, chip-0 bytes in use "
         f"{memory_in_use(devs[0])}")

    oracle = Oracle(base, base_vals.tolist())
    sorted_keys = oracle.order
    svc = IndexService(dsi)
    enc_key = lambda k: IndexService.encode_key(TENANT, k)   # noqa: E731
    plen = len(enc_key(b""))
    try:
        n_gets = n_scans = 0
        for _ in range(4):
            gets = [base[i] for i in rng.integers(0, len(base), GETS - 256)]
            gets += [k + b"~" for k in gets[:256]]          # misses
            cuts = [len(base) * s // n_shards for s in range(1, n_shards)]
            starts = [sorted_keys[max(c - int(j), 0)] for c in cuts
                      for j in rng.integers(1, WINDOW, SCANS // 4)]
            starts += [sorted_keys[i] for i in
                       rng.integers(0, len(base), SCANS - len(starts))]
            batch = ([GetRequest(k) for k in gets]
                     + [ScanRequest(s, WINDOW) for s in starts])
            exp = ([("OK", oracle.get(k)) if k in oracle.kv
                    else ("NOT_FOUND", None) for k in gets]
                   + [("OK", oracle.scan(s, WINDOW)) for s in starts])
            results = svc.execute(batch, tenant=TENANT, timeout=900)
            check_results(batch, results, exp)
            ref_res = ref.execute(
                [GetRequest(enc_key(k)) for k in gets]
                + [ScanRequest(enc_key(s), WINDOW) for s in starts]).results
            for r, a, b in zip(batch, results, ref_res):
                same = (a.status == b.status and a.value == b.value
                        and (a.entries is None or list(a.entries) ==
                             [(k[plen:], v) for k, v in b.entries]))
                check(same, f"{r}: sharded {a} and single-chip {b} results differ")
            n_gets += len(gets)
            n_scans += len(starts)
        st = svc.stats()
        check(st.maintenance_errors == 0, "maintenance errors")
        info(f"serve: {n_gets} routed gets and {n_scans} cross-shard scans "
             f"match the single-chip index and the oracle, "
             f"{time.perf_counter() - t3:.1f} s")
    finally:
        svc.close()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--n", type=int, default=1_000_000,
                    help="bulk-loaded keys (default 1M)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded phase on four chips")
    args = ap.parse_args(argv)

    devs = require_tpu()
    from repro.launch.compile_cache import enable_compile_cache

    info(f"compile cache: {enable_compile_cache()}")
    counter = CompileCounter()
    t0 = time.perf_counter()
    if args.chips == 4:
        run_sharded(args, devs)
    else:
        run_one_chip(args, devs[0])
    info(counter.line(f"total {time.perf_counter() - t0:.1f} s"))
    dev = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
