"""IndexService request plane: coalescing bit-identity, tenancy, cursors,
admission control, maintenance (DESIGN.md §9).

The acceptance contract (ISSUE 3):

* ops coalesced across >= 8 concurrent logical clients resolve bit-identical
  to a direct ``StringIndex.execute`` of the same ops, with the service on
  a built and on a loaded index, and on the distributed backend for its
  supported op set;
* tenants are isolated: cross-tenant gets miss, scans never leak another
  tenant's keys and return tenant-local (stripped) keys;
* cursor pagination concatenates to exactly the one-shot scan;
* past ``max_queue`` pending ops, submissions shed with
  ``Status.OVERLOADED`` as data (no exceptions), and the queued ops still
  complete;
* compaction runs from the maintenance step, not the request path.
"""
import threading

import numpy as np
import pytest

from repro.core.strings import random_strings
from repro.index import (
    DeleteRequest, GetRequest, IndexConfig, PutRequest, ScanRequest, Status,
    StringIndex,
)
from repro.serve.service import IndexService, ServiceConfig


def _corpus(rng, n=600):
    keys = sorted(set(random_strings(rng, n, 2, 24)))
    vals = np.arange(len(keys), dtype=np.int64) * 5 + 1
    return keys, vals


def _twins(keys, vals, tenant="t0", index_form=lambda index: index,
           **svc_kw):
    """(service, direct) over identical bulk loads of tenant-encoded keys;
    ``index_form`` maps the service's index (e.g. to a loaded copy)."""
    cfg = IndexConfig(delta_capacity=4096, auto_merge_threshold=None,
                      scan_window=6)
    enc = [IndexService.encode_key(tenant, k) for k in keys]
    direct = StringIndex.bulk_load(enc, vals, cfg)
    kw = dict(max_batch=4096, max_delay_ms=25.0, merge_threshold=None,
              default_tenant=tenant)
    kw.update(svc_kw)
    svc = IndexService(index_form(StringIndex.bulk_load(enc, vals, cfg)),
                       ServiceConfig(**kw))
    return svc, direct


def _strip(tenant, entries):
    p = IndexService.encode_key(tenant, b"")
    return tuple((k[len(p):], v) for k, v in entries)


def _same_result(got, want, tenant):
    assert got.status == want.status, (got, want)
    assert got.value == want.value and got.updated == want.updated
    if want.entries is not None:
        assert got.entries == _strip(tenant, want.entries)


@pytest.mark.parametrize("form", ["built", "loaded"])
def test_single_flush_bit_identical_to_direct_execute(rng, form, as_form):
    """One coalesced flush of a mixed GET/PUT/SCAN/DELETE batch == one direct
    facade ``execute`` of the same batch, op for op, bit for bit."""
    keys, vals = _corpus(rng)
    svc, direct = _twins(keys, vals,
                         index_form=lambda index: as_form(index, form))
    batch = (
        [GetRequest(k) for k in keys[:30]]
        + [GetRequest(k + b"~miss") for k in keys[:5]]
        + [PutRequest(b"np-%03d" % i, 9000 + i) for i in range(20)]
        + [PutRequest(keys[4], 4444)]                      # base value update
        + [DeleteRequest(keys[7]), DeleteRequest(b"absent-key")]
        + [GetRequest(b"np-003"), GetRequest(keys[7]), GetRequest(keys[4])]
        + [ScanRequest(keys[0]), ScanRequest(keys[50][:2], 11)]
    )
    got = svc.execute(batch)                      # one flush (max_batch=4096)
    want = direct.execute([svc._encode(r, None) for r in batch])
    assert len(got) == len(want.results)
    for g, w in zip(got, want.results):
        _same_result(g, w, "t0")
    # spot-check semantics rode through the coalescer
    assert got[35].ok and not got[35].updated     # fresh put
    assert got[55].ok and got[55].updated         # base value update
    assert got[56].status == Status.OK            # delete of a base key
    assert got[57].status == Status.NOT_FOUND     # delete of an absent key
    assert got[58].value == 9003                  # get-after-put, same flush
    assert got[59].status == Status.NOT_FOUND     # get-after-delete
    assert got[60].value == 4444                  # updated base value
    assert svc.stats().flushes == 1
    svc.close()


@pytest.mark.parametrize("form", ["built", "loaded"])
def test_concurrent_clients_coalesced_and_bit_identical(rng, form, as_form):
    """>= 8 logical clients with disjoint keyspaces submit concurrently; the
    coalescer folds them into shared dispatches (coalescing factor > 1) and
    every client's results match a direct facade run of its ops."""
    keys, vals = _corpus(rng, 800)
    svc, direct = _twins(keys, vals, max_batch=64,
                         index_form=lambda index: as_form(index, form))
    n_clients = 8

    def client_ops(i):
        mine = keys[i::n_clients]
        return (
            [GetRequest(k) for k in mine[:15]]
            + [PutRequest(b"c%d-%04d" % (i, j), i * 10000 + j)
               for j in range(10)]
            + [GetRequest(b"c%d-0007" % i)]            # read-your-write
            + [DeleteRequest(k) for k in mine[15:20]]
            + [GetRequest(mine[15])]                   # read-your-delete
            + [ScanRequest(mine[0], 9)]
        )

    results = {}
    barrier = threading.Barrier(n_clients)

    def run(i):
        ops = client_ops(i)
        barrier.wait()
        results[i] = svc.execute(ops)

    threads = [threading.Thread(target=run, args=(i,))
               for i in range(n_clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()

    s = svc.stats()
    assert s.completed == sum(len(client_ops(i)) for i in range(n_clients))
    assert s.coalescing_factor > 1.0, \
        f"clients must share dispatches, got {s.coalescing_factor}"
    # the direct twin replays each client's batch; keyspaces are disjoint and
    # puts are fresh keys, so per-op results are order-independent across
    # clients — any interleaving the coalescer chose must give these bits
    for i in range(n_clients):
        want = direct.execute([svc._encode(r, None) for r in client_ops(i)])
        for g, w in zip(results[i], want.results):
            _same_result(g, w, "t0")
    svc.close()


def test_tenant_isolation_gets_and_scans(rng):
    keys, vals = _corpus(rng, 300)
    svc = IndexService.bulk_load(
        {"alice": (keys, vals), "bob": (keys[:50], vals[:50] + 7)},
        IndexConfig(delta_capacity=512, auto_merge_threshold=None),
        ServiceConfig(max_batch=1024, merge_threshold=None))
    # same key, different tenants, different values
    ra = svc.execute([GetRequest(keys[3])], tenant="alice")[0]
    rb = svc.execute([GetRequest(keys[3])], tenant="bob")[0]
    assert ra.value == int(vals[3]) and rb.value == int(vals[3]) + 7
    # bob can't see alice-only keys
    assert svc.execute([GetRequest(keys[100])], tenant="bob")[0].status \
        == Status.NOT_FOUND
    # a put is invisible across the boundary
    svc.execute([PutRequest(b"secret", 42)], tenant="alice")
    assert svc.execute([GetRequest(b"secret")], tenant="bob")[0].status \
        == Status.NOT_FOUND
    # scans: bob's scan window would overrun into... nothing — the service
    # truncates at the tenant boundary and strips the prefix ("alice" < "bob"
    # so bob's range is chased by the end of the index; check alice -> bob)
    pa = svc.execute([ScanRequest(keys[48], 40)], tenant="bob")[0]
    assert [k for k, _ in pa.entries] == keys[48:50], \
        "scan must stop at the tenant's last key, never leak a neighbour"
    pb = svc.execute([ScanRequest(keys[len(keys) - 2], 40)], tenant="alice")[0]
    assert [k for k, _ in pb.entries] == keys[-2:], \
        "alice's scan must not leak bob's range"
    # stripped keys: nothing tenant-prefixed escapes the boundary
    for k, _ in pa.entries + pb.entries:
        assert b"\x1f" not in k
    # unknown-tenant ids are malformed requests -> exception (not data)
    with pytest.raises(ValueError):
        svc.execute([GetRequest(b"x")], tenant="no spaces allowed")
    svc.close()


def test_cursor_pagination_equals_one_shot_scan(rng):
    keys, vals = _corpus(rng, 250)
    svc = IndexService.bulk_load(
        {"t": (keys, vals)},
        IndexConfig(auto_merge_threshold=None),
        ServiceConfig(max_batch=1024, merge_threshold=None))
    one = svc.execute([ScanRequest(b"", 60)], tenant="t")[0].entries
    assert len(one) == 60
    pages, page = [], svc.scan_page(start=b"", page_size=7, tenant="t")
    hops = 0
    while True:
        pages.extend(page.entries)
        if page.cursor is None or len(pages) >= 60:
            break
        # token carries the position; the caller re-asserts its tenant and
        # the service verifies it against the token (forged-cursor defense)
        page = svc.scan_page(cursor=page.cursor, tenant="t")
        hops += 1
    assert pages[:60] == list(one), "pages must concatenate to the one-shot"
    assert hops >= 8
    # exhaustion: paginate off the end of the tenant -> cursor goes None
    tail = svc.scan_page(start=keys[-3], page_size=50, tenant="t")
    assert [k for k, _ in tail.entries] == keys[-3:]
    assert tail.cursor is None
    # garbled tokens are malformed requests
    with pytest.raises(ValueError):
        svc.scan_page(cursor="not-a-cursor")
    svc.close()


def test_forged_cursor_cannot_cross_tenants(rng):
    """Tenant-isolation regression: a scan cursor embeds the tenant it was
    issued for; presenting it as a DIFFERENT tenant (forged or replayed
    token) must be refused with Status.FORBIDDEN as data — never serve the
    embedded tenant's namespace."""
    keys, vals = _corpus(rng, 120)
    svc = IndexService.bulk_load(
        {"alice": (keys, vals), "bob": (keys[:30], vals[:30] + 9)},
        IndexConfig(auto_merge_threshold=None),
        ServiceConfig(max_batch=1024, merge_threshold=None))
    alice_page = svc.scan_page(start=b"", page_size=5, tenant="alice")
    assert alice_page.cursor is not None
    # bob replays alice's cursor — and gets a typed refusal, zero entries
    forged = svc.scan_page(cursor=alice_page.cursor, tenant="bob")
    assert forged.status == Status.FORBIDDEN
    assert forged.entries == () and forged.cursor is None
    # hand-forging a token for another tenant is equally refused
    from repro.serve.service import _make_cursor

    crafted = _make_cursor("alice", b"", 50)
    res = svc.scan_page(cursor=crafted, tenant="bob")
    assert res.status == Status.FORBIDDEN and res.entries == ()
    # omitting the tenant resolves to default_tenant — still mismatched
    res = svc.scan_page(cursor=crafted)
    assert res.status == Status.FORBIDDEN
    # the rightful owner's continuation still works
    cont = svc.scan_page(cursor=alice_page.cursor, tenant="alice")
    assert cont.status == Status.OK and len(cont.entries) > 0
    for k, _ in cont.entries:
        assert b"\x1f" not in k
    svc.close()


def test_maintenance_failures_surface_in_stats(rng, caplog):
    """A persistently failing compaction must be visible: counted in
    ServiceStats.maintenance_errors, last error string surfaced, logged once
    per distinct error — and the service must keep serving."""
    import logging

    import time

    keys, vals = _corpus(rng, 100)
    svc = IndexService.bulk_load(
        {"t": (keys, vals)},
        IndexConfig(delta_capacity=16, auto_merge_threshold=None),
        ServiceConfig(max_batch=1024, default_tenant="t", merge_threshold=0.5,
                      maintenance_interval_ms=60_000.0))
    boom = RuntimeError("injected merge failure")

    def failing_merge(*a, **kw):
        raise boom

    # inject BEFORE the delta crosses the threshold: every compaction the
    # flusher/maintenance attempts from here on fails at the epoch seam
    svc.index.begin_merge = failing_merge
    with caplog.at_level(logging.ERROR, logger="repro.serve.service"):
        svc.execute([PutRequest(b"f-%03d" % i, i) for i in range(10)])
        for want in (1, 2, 3):             # retries of the SAME error
            svc._maint_wake.set()
            deadline = time.monotonic() + 5.0
            while time.monotonic() < deadline:
                if svc.stats().maintenance_errors >= want:
                    break
                time.sleep(0.005)
    s = svc.stats()
    assert s.maintenance_errors >= 1
    assert "injected merge failure" in (s.last_maintenance_error or "")
    logged = [r for r in caplog.records
              if "injected merge failure" in r.getMessage()]
    assert len(logged) == 1, "one log line per DISTINCT error, not per retry"
    # the request path is unaffected by the failing maintenance loop
    assert svc.execute([GetRequest(keys[0])])[0].value == int(vals[0])
    svc.close()


def test_stats_polling_never_syncs_device(rng, monkeypatch):
    """ServiceStats reads host mirrors only: stats()/maintenance polling must
    never call the device-syncing delta_fill_fraction."""
    from repro.core import tensor_index as tix

    keys, vals = _corpus(rng, 80)
    svc = IndexService.bulk_load(
        {"t": (keys, vals)}, IndexConfig(auto_merge_threshold=None),
        ServiceConfig(max_batch=1024, default_tenant="t",
                      merge_threshold=0.9))
    svc.execute([PutRequest(b"s-%03d" % i, i) for i in range(10)])

    def forbidden(ti):
        raise AssertionError("stats polling must not sync the device")

    monkeypatch.setattr(tix, "delta_fill_fraction", forbidden)
    s = svc.stats()
    assert s.delta_fill > 0.0          # mirror, not device
    assert svc.maintenance_step() is False  # below threshold: mirror check only
    svc.close()


def test_admission_control_sheds_as_data(rng):
    keys, vals = _corpus(rng, 120)
    svc = IndexService.bulk_load(
        {"t": (keys, vals)}, None,
        ServiceConfig(max_batch=4096, max_delay_ms=10_000.0, max_queue=16,
                      default_tenant="t", merge_threshold=None))
    # stall the flusher with a huge deadline; fill the queue past the bound
    futs = svc.submit_many([GetRequest(keys[i % len(keys)])
                            for i in range(50)])
    shed = [f for f in futs if f.done()]
    assert len(shed) == 50 - 16
    assert all(f.result().status == Status.OVERLOADED for f in shed)
    svc.flush()                                   # release the queued 16
    head = [f.result(timeout=120) for f in futs[:16]]
    assert all(r.status == Status.OK for r in head), \
        "admitted ops must complete normally after the shed burst"
    s = svc.stats()
    assert s.shed == 34 and s.completed == 16
    assert s.p99_ms >= s.p50_ms >= 0.0
    svc.close()


def test_maintenance_owns_compaction_not_request_path(rng):
    import dataclasses

    keys, vals = _corpus(rng, 200)
    cfg = IndexConfig(delta_capacity=64, auto_merge_threshold=0.75)
    # threshold starts above the fill this test creates, so neither the
    # flusher's wake signal nor the interval timer compacts behind our back
    svc = IndexService.bulk_load(
        {"t": (keys, vals)}, cfg,
        ServiceConfig(max_batch=1024, default_tenant="t",
                      merge_threshold=0.99,
                      maintenance_interval_ms=10_000.0))
    # the service demotes the facade's in-band auto-merge...
    assert svc.index.config.auto_merge_threshold is None
    svc.execute([PutRequest(b"zz-%03d" % i, i) for i in range(40)])
    assert svc.index.merge_count == 0, "request path must NOT compact"
    assert svc.index.delta_fill >= 0.5
    # ...and the maintenance step does it out-of-band
    svc.config = dataclasses.replace(svc.config, merge_threshold=0.5)
    assert svc.maintenance_step() is True
    assert svc.index.merge_count == 1 and svc.stats().merges == 1
    assert svc.index.delta_fill == 0.0
    # merged keys visible (and now scannable) through the service
    res = svc.execute([GetRequest(b"zz-007"), ScanRequest(b"zz-", 5)])
    assert res[0].value == 7
    assert [k for k, _ in res[1].entries] == [b"zz-%03d" % i for i in range(5)]
    svc.close()


def test_close_restores_index_compaction_policy(rng):
    """The service demotes the facade's auto-merge while it owns the index;
    close() must hand the index back with its original policy (a caller
    using the index directly afterwards would otherwise never compact)."""
    keys, vals = _corpus(rng, 120)
    idx = StringIndex.bulk_load(keys, vals,
                                IndexConfig(auto_merge_threshold=0.5))
    svc = IndexService(idx, ServiceConfig(merge_threshold=None))
    assert idx.config.auto_merge_threshold is None
    svc.close()
    assert idx.config.auto_merge_threshold == 0.5


def test_maintenance_compacts_on_overflow_below_fill_threshold(rng):
    """The byte pool can reject (latched overflow) while the entry count is
    still far below merge_threshold; maintenance must compact anyway or
    every later put stays REJECTED_FULL forever."""
    keys, vals = _corpus(rng, 150)
    svc = IndexService.bulk_load(
        {"t": (keys, vals)},
        IndexConfig(delta_capacity=256, delta_bytes=64,  # tiny BYTE pool
                    auto_merge_threshold=None),
        ServiceConfig(max_batch=1024, default_tenant="t",
                      merge_threshold=0.6,
                      maintenance_interval_ms=60_000.0))
    res = svc.execute([PutRequest(b"k-%02d" % i, i) for i in range(40)])
    assert any(r.status == Status.REJECTED_FULL for r in res), \
        "the 64-byte pool must overflow long before 256 entries"
    assert svc.index.delta_fill < 0.6
    # the flusher signals maintenance on the latched overflow even though
    # the fill is below threshold; the background step (or this explicit
    # one, whoever wins the race) must compact
    import time

    deadline = time.monotonic() + 10.0
    while svc.index.merge_count == 0 and time.monotonic() < deadline:
        svc.maintenance_step()
        time.sleep(0.01)
    assert svc.index.merge_count >= 1, \
        "overflow must trigger compaction even below the fill threshold"
    assert not svc.index.delta_overflowed
    ok = svc.execute([PutRequest(b"post-merge", 1), GetRequest(b"post-merge")])
    assert ok[0].ok and ok[1].value == 1
    svc.close()


def test_compact_forces_merge_past_disabled_threshold(rng):
    """`compact()` is the escape hatch for callers whose next op needs
    delta space: it merges even when merge_threshold=None keeps the
    maintenance path inert."""
    keys, vals = _corpus(rng, 150)
    svc = IndexService.bulk_load(
        {"t": (keys, vals)}, IndexConfig(delta_capacity=64),
        ServiceConfig(max_batch=256, default_tenant="t",
                      merge_threshold=None))
    svc.execute([PutRequest(b"c-%03d" % i, i) for i in range(20)])
    assert svc.maintenance_step() is False     # threshold disabled: inert
    assert svc.index.merge_count == 0
    assert svc.compact() is True               # forced: merges anyway
    assert svc.index.merge_count == 1 and svc.index.delta_fill == 0.0
    assert svc.stats().merges == 1
    assert svc.compact() is False              # empty delta: nothing to do
    svc.close()


def test_service_over_distributed_backend(rng):
    """The same request plane fronts the mesh-distributed read-only index:
    coalesced gets are bit-identical to direct ``execute``; mutations come
    back UNSUPPORTED as data (facade contract riding through the service)."""
    from repro.distributed.index_service import DistributedStringIndex

    keys, vals = _corpus(rng, 400)
    enc = [IndexService.encode_key("t", k) for k in keys]
    dsi = DistributedStringIndex.build(enc, vals, n_shards=1)
    svc = IndexService(dsi, ServiceConfig(max_batch=64, default_tenant="t",
                                          merge_threshold=None))
    n_clients = 8
    results = {}
    barrier = threading.Barrier(n_clients)

    def run(i):
        ops = ([GetRequest(k) for k in keys[i::n_clients][:20]]
               + [GetRequest(b"miss-%d" % i), PutRequest(b"x-%d" % i, 1),
                  DeleteRequest(b"y-%d" % i)])
        barrier.wait()
        results[i] = (ops, svc.execute(ops))

    threads = [threading.Thread(target=run, args=(i,))
               for i in range(n_clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    direct = dsi.execute  # the same backend, uncoalesced
    for i in range(n_clients):
        ops, got = results[i]
        want = direct([svc._encode(r, None) for r in ops]).results
        for g, w in zip(got, want):
            assert g.status == w.status and g.value == w.value
        assert got[-2].status == Status.UNSUPPORTED   # put on read-only mesh
        assert got[-1].status == Status.UNSUPPORTED   # delete likewise
    assert svc.stats().coalescing_factor > 1.0
    svc.close()
