"""Scan-consistency edges for the delta-aware scan engine (ISSUE 5 / §11).

Deterministic regressions complementing the generative oracle suite
(tests/test_scan_oracle.py): tombstone shadowing mid-window,
put-resurrect-then-scan, windows straddling the base/delta seam,
delta-only indexes (empty base), ``scan_page`` resumption across a forced
``compact()`` mid-stream, and tenant-boundary truncation with delta keys
at the boundary.
"""
import numpy as np
import pytest

from repro.index import (
    DeleteRequest,
    GetRequest,
    IndexConfig,
    PutRequest,
    ScanRequest,
    Status,
    StringIndex,
)
from repro.serve.service import IndexService, ServiceConfig

BASE = [b"k-%03d" % i for i in range(0, 40, 2)]      # even keys frozen


def _index(keys=BASE):
    cfg = IndexConfig(width=16, delta_capacity=64, auto_merge_threshold=None)
    vals = np.arange(len(keys), dtype=np.int64) * 10 + 1
    return StringIndex.bulk_load(keys, vals, cfg)


@pytest.fixture(params=["built", "loaded"])
def make_index(request, as_form):
    """``make_index(keys=BASE)``: :func:`_index`, as built or as loaded
    from a snapshot."""
    return lambda keys=BASE: as_form(_index(keys), request.param)


def test_tombstone_shadowing_mid_window(make_index):
    index = make_index()
    index.execute([DeleteRequest(b"k-006"), DeleteRequest(b"k-010")])
    got = [k for k, _ in index.scan(b"k-004", 5)]
    # the window slides PAST the two tombstoned keys to later live keys
    assert got == [b"k-004", b"k-008", b"k-012", b"k-014", b"k-016"]
    # a window made entirely of tombstones at its head still fills
    index.execute([DeleteRequest(b"k-000"), DeleteRequest(b"k-002"),
                   DeleteRequest(b"k-004")])
    got = [k for k, _ in index.scan(b"", 3)]
    assert got == [b"k-008", b"k-012", b"k-014"]


def test_put_resurrect_then_scan(make_index):
    index = make_index()
    index.execute([DeleteRequest(b"k-008")])
    assert [k for k, _ in index.scan(b"k-006", 3)] == \
        [b"k-006", b"k-010", b"k-012"]
    # resurrect with a NEW value: scans must show the key exactly once,
    # carrying the delta value (the live delta entry shadows its stale
    # base twin)
    index.execute([PutRequest(b"k-008", 777)])
    got = index.scan(b"k-006", 3)
    assert [k for k, _ in got] == [b"k-006", b"k-008", b"k-010"]
    assert dict(got)[b"k-008"] == 777
    # and the same holds after the merge reconciles
    index.merge()
    got = index.scan(b"k-006", 3)
    assert [k for k, _ in got] == [b"k-006", b"k-008", b"k-010"]
    assert dict(got)[b"k-008"] == 777


def test_window_straddles_base_delta_seam(make_index):
    index = make_index()
    odd = [b"k-%03d" % i for i in range(1, 21, 2)]   # interleaves the base
    index.execute([PutRequest(k, 5000 + i) for i, k in enumerate(odd)])
    got = [k for k, _ in index.scan(b"k-003", 8)]
    assert got == [b"k-%03d" % i for i in range(3, 11)], \
        "window must interleave frozen and delta keys in sorted order"
    # seam at the window edge: start inside the delta run, end in base-only
    got = [k for k, _ in index.scan(b"k-018", 4)]
    assert got == [b"k-018", b"k-019", b"k-020", b"k-022"]
    # values resolve per-stream (base pools vs delta pools)
    got = dict(index.scan(b"k-003", 4))
    assert got[b"k-003"] == 5001 and got[b"k-004"] == 21


def test_delta_only_index_scans(make_index):
    """ISSUE 5 satellite: an EMPTY base with a live delta must scan — the
    old ``root_item != 0`` guard masked every window to nothing."""
    index = make_index(keys=[])
    assert index.n_entries <= 1  # only the freeze pad sentinel
    res = index.execute([ScanRequest(b"", 8)])
    assert res.results[0].entries == ()   # truly empty index: empty scan
    index.execute([PutRequest(b"x-2", 2), PutRequest(b"x-1", 1),
                   PutRequest(b"x-3", 3)])
    got = index.scan(b"", 8)
    assert got == [(b"x-1", 1), (b"x-2", 2), (b"x-3", 3)]
    # gets agree (read-your-writes holds on both op families)
    assert index.get(b"x-2") == 2
    # and tombstoning one hides it immediately
    index.execute([DeleteRequest(b"x-2")])
    assert [k for k, _ in index.scan(b"", 8)] == [b"x-1", b"x-3"]


def test_scan_after_emptying_delta_only_index(make_index):
    index = make_index(keys=[])
    index.execute([PutRequest(b"solo", 9)])
    index.execute([DeleteRequest(b"solo")])
    assert index.scan(b"", 4) == []


def test_scan_page_resumes_across_forced_compact(rng):
    """scan_page cursors embed a resume KEY, not a rank: a compaction
    between pages renames every entry id and bumps the epoch, yet the
    concatenated pages equal the one-shot scan."""
    keys = [b"t-%03d" % i for i in range(60)]
    vals = np.arange(len(keys), dtype=np.int64)
    svc = IndexService.bulk_load(
        {"t": (keys, vals)},
        IndexConfig(width=24, delta_capacity=128, auto_merge_threshold=None),
        ServiceConfig(max_batch=512, merge_threshold=None))
    try:
        # live delta on top of the frozen base: fresh keys + a tombstone
        svc.execute([PutRequest(b"t-%03da" % i, 900 + i) for i in range(20)]
                    + [DeleteRequest(b"t-007")], tenant="t")
        one = svc.execute([ScanRequest(b"", 100)], tenant="t")[0].entries
        assert len(one) == 79  # 60 + 20 - 1 tombstone
        epoch0 = svc.stats().epoch
        pages, page = [], svc.scan_page(start=b"", page_size=7, tenant="t")
        hops = 0
        while True:
            pages.extend(page.entries)
            if page.cursor is None:
                break
            if hops == 4:
                assert svc.compact(), "forced mid-stream compaction"
                assert svc.stats().epoch == epoch0 + 1
            page = svc.scan_page(cursor=page.cursor, tenant="t")
            hops += 1
        assert pages == list(one), \
            "pages must concatenate to the one-shot scan across the epoch bump"
    finally:
        svc.close()


def test_tenant_boundary_truncation_with_delta_keys():
    """Delta keys sorting at the very END of a tenant's range must be
    served to that tenant and must not bleed into (or pull in) the
    neighbouring tenant's range."""
    a_keys = [b"a-%02d" % i for i in range(10)]
    b_keys = [b"b-%02d" % i for i in range(10)]
    svc = IndexService.bulk_load(
        {"alice": (a_keys, np.arange(10, dtype=np.int64)),
         "bob": (b_keys, np.arange(10, dtype=np.int64) + 50)},
        IndexConfig(width=24, delta_capacity=64, auto_merge_threshold=None),
        ServiceConfig(max_batch=512, merge_threshold=None))
    try:
        # unmerged delta keys at alice's upper boundary (b"~..." sorts after
        # every bulk-loaded a-* key but still inside alice's 0x1f-prefixed
        # range) and at bob's lower boundary
        svc.execute([PutRequest(b"~end-1", 101), PutRequest(b"~end-2", 102)],
                    tenant="alice")
        svc.execute([PutRequest(b"-first", 200)], tenant="bob")
        got = svc.execute([ScanRequest(a_keys[7], 40)], tenant="alice")[0]
        assert [k for k, _ in got.entries] == \
            a_keys[7:] + [b"~end-1", b"~end-2"], \
            "alice's scan must include her boundary delta keys and stop"
        assert all(not k.startswith(b"b-") for k, _ in got.entries)
        # bob's range begins with HIS unmerged delta key, never alice's tail
        got = svc.execute([ScanRequest(b"", 5)], tenant="bob")[0]
        assert [k for k, _ in got.entries] == \
            [b"-first"] + b_keys[:4]
        assert dict(got.entries)[b"-first"] == 200
        # a scan claiming to start BELOW bob's range cannot reach backwards
        # (the tenant prefix pins the low edge)
        got = svc.execute([ScanRequest(b"\x00", 3)], tenant="bob")[0]
        assert [k for k, _ in got.entries] == [b"-first"] + b_keys[:2]
    finally:
        svc.close()


def test_pre_v4_snapshot_recomputes_sorted_delta_view(tmp_path):
    """A v3 snapshot carries no ``ds_order``: loading one with a live delta
    (inserts + a tombstone) must rebuild the sorted view so delta-aware
    scans see the snapshot's unmerged state."""
    import json

    index = _index()
    index.execute([PutRequest(b"k-007", 7), PutRequest(b"k-033", 3),
                   DeleteRequest(b"k-012")])
    want = index.scan(b"k-004", 8)
    p = tmp_path / "v3.snap"
    index.save(str(p))
    z = dict(np.load(str(p), allow_pickle=False))
    hdr = json.loads(bytes(z["__snapshot_meta__"]).decode())
    hdr["version"] = 3
    hdr["data_fields"] = [f for f in hdr["data_fields"] if f != "ds_order"]
    z.pop("ds_order")
    z["__snapshot_meta__"] = np.frombuffer(json.dumps(hdr).encode(), np.uint8)
    v3 = tmp_path / "v3-stripped.snap"
    with open(v3, "wb") as f:
        np.savez_compressed(f, **z)
    loaded = StringIndex.load(str(v3))
    assert loaded.scan(b"k-004", 8) == want
    assert dict(want)[b"k-007"] == 7 and b"k-012" not in dict(want)


def test_gets_and_scans_agree_every_epoch(make_index):
    """Read-your-writes coherence: at no point may a key be gettable but
    unscannable or vice versa (the exact gap this PR closes)."""
    index = make_index()
    index.execute([PutRequest(b"k-001", 1), DeleteRequest(b"k-004"),
                   PutRequest(b"k-033", 3), DeleteRequest(b"k-033")])
    for _ in range(2):
        scanned = {k for k, _ in index.scan(b"", 64)}
        for k in set(BASE) | {b"k-001", b"k-033"}:
            r = index.execute([GetRequest(k)]).results[0]
            assert (r.status == Status.OK) == (k in scanned), \
                (k, r.status, k in scanned)
        index.merge()   # second pass: the compacted epoch must agree too
