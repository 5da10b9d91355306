"""StringIndex facade: batch planning, per-op statuses, auto-merge, snapshots.

The acceptance contract (ISSUE 2 / DESIGN.md §8):

* ``execute`` on a mixed GET/PUT/SCAN batch is bit-identical to the
  equivalent sequence of legacy free-function calls, on a built index and
  on one loaded from a snapshot;
* failures (over-width keys, full delta pool) surface as per-op Status
  codes, never exceptions;
* puts past the delta threshold trigger ``merge_delta`` automatically and
  subsequent gets/scans see the merged keys;
* a ``save``/``load`` roundtrip reproduces bit-identical ``search_batch``
  results, and version mismatches raise typed errors;
* ``get_batch``'s one fused program gives what the search walk, then
  ``lookup_values``, then the host value join give, counters included,
  over email and url keys with a live delta, updates, tombstones,
  resurrected puts, zero bytes and over-width keys.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    insert_batch, lookup_values, pad_queries, rank_batch, scan_batch,
    search_batch,
)
from repro.core.strings import random_strings
from repro.core.tensor_index import search_impl
from repro.data.synthetic import load
from repro.index import (
    DeleteRequest, GetRequest, IndexConfig, PutRequest, ScanRequest,
    SnapshotFormatError, SnapshotVersionError, Status, StringIndex,
)
from repro.index.facade import _join_values


def _corpus(rng, n=600):
    keys = sorted(set(random_strings(rng, n, 2, 24)))
    vals = np.arange(len(keys), dtype=np.int64) * 5 + 1
    return keys, vals


def _legacy_plan(ti, batch, scan_window):
    """The equivalent sequence of legacy free-function calls (the plan
    ``execute`` promises: one insert_batch, one search_batch, one
    scan_batch — puts first)."""
    puts = [r for r in batch if isinstance(r, PutRequest)]
    gets = [r for r in batch if isinstance(r, GetRequest)]
    scans = [r for r in batch if isinstance(r, ScanRequest)]
    out = {}
    if puts:
        qb, ql = pad_queries([r.key for r in puts], ti.width)
        v = np.asarray([r.value for r in puts], np.int64)
        ti, ins, upd = insert_batch(
            ti, jnp.asarray(qb), jnp.asarray(ql),
            jnp.asarray((v & 0xFFFFFFFF).astype(np.uint32).view(np.int32)),
            jnp.asarray((v >> 32).astype(np.int32)))
        out["ins"], out["upd"] = np.asarray(ins), np.asarray(upd)
    if gets:
        qb, ql = pad_queries([r.key for r in gets], ti.width)
        found, eid, isd = search_batch(ti, jnp.asarray(qb), jnp.asarray(ql))
        lo, hi = lookup_values(ti, eid, isd)
        out["found"] = np.asarray(found)
        out["values"] = (np.asarray(hi).astype(np.int64) << 32) | \
            np.asarray(lo).view(np.uint32).astype(np.int64)
    if scans:
        qb, ql = pad_queries([r.start for r in scans], ti.width)
        eids, valid, isd = scan_batch(ti, jnp.asarray(qb), jnp.asarray(ql),
                                      scan_window)
        out["eids"], out["valid"] = np.asarray(eids), np.asarray(valid)
        out["isd"] = np.asarray(isd)
    return ti, out


def _any_key(ti, eid: int, is_delta: bool) -> bytes:
    """Key bytes for a scan result id — base entry pool or delta byte pool."""
    if not is_delta:
        off, ln = int(ti.ent_off[eid]), int(ti.ent_len[eid])
        return np.asarray(ti.key_bytes[off: off + ln]).tobytes()
    off, ln = int(ti.de_off[eid]), int(ti.de_len[eid])
    return np.asarray(ti.db_bytes[off: off + ln]).tobytes()


@pytest.mark.parametrize("form", ["built", "loaded"])
def test_execute_bit_identical_to_legacy(rng, form, as_form):
    keys, vals = _corpus(rng)
    cfg = IndexConfig(delta_capacity=512, auto_merge_threshold=None,
                      scan_window=9)
    index = as_form(StringIndex.bulk_load(keys, vals, cfg), form)
    legacy = StringIndex.bulk_load(keys, vals, cfg)  # identical twin lineage

    batch = (
        [GetRequest(k) for k in keys[:40]]
        + [GetRequest(k + b"~miss") for k in keys[:10]]
        + [PutRequest(b"pp-%03d" % i, 7000 + i) for i in range(30)]
        + [PutRequest(keys[5], 99991), PutRequest(keys[6], 99992)]  # updates
        + [GetRequest(b"pp-007"), GetRequest(keys[5])]
        + [ScanRequest(keys[0]), ScanRequest(keys[100][:3]), ScanRequest(b"~~~"),
           ScanRequest(b"pp-")]  # straddles the batch's own fresh delta keys
    )
    res = index.execute(batch)
    legacy_ti, want = _legacy_plan(legacy.ti, batch, cfg.scan_window)

    gets = [r for r, q in zip(res.results, batch) if isinstance(q, GetRequest)]
    assert [r.ok for r in gets] == want["found"].tolist()
    got_vals = [r.value if r.ok else 0 for r in gets]
    assert got_vals == np.where(want["found"], want["values"], 0).tolist()
    puts = [r for r, q in zip(res.results, batch) if isinstance(q, PutRequest)]
    assert [r.ok for r in puts] == (want["ins"] | want["upd"]).tolist()
    assert [r.updated for r in puts] == want["upd"].tolist()
    scans = [r for r, q in zip(res.results, batch) if isinstance(q, ScanRequest)]
    saw_delta = False
    for row, r in enumerate(scans):
        want_keys = [_any_key(legacy_ti, int(e), bool(d))
                     for e, ok, d in zip(want["eids"][row], want["valid"][row],
                                         want["isd"][row]) if ok]
        saw_delta = saw_delta or bool(want["isd"][row][want["valid"][row]].any())
        assert [k for k, _ in r.entries] == want_keys
    # the batch's own puts must be scannable (read-your-writes, §11): the
    # "pp-" scan start window is seeded to hit the fresh delta keys
    assert saw_delta, "scan windows should cover unmerged delta inserts"


def test_per_op_error_statuses_not_exceptions(rng):
    keys, vals = _corpus(rng, 200)
    cfg = IndexConfig(delta_capacity=8, delta_bytes=64,
                      auto_merge_threshold=None)
    index = StringIndex.bulk_load(keys, vals, cfg)
    wide = b"w" * (index.width + 1)
    batch = (
        [PutRequest(wide, 1), GetRequest(wide)]
        + [PutRequest(b"f-%04d" % i, i) for i in range(32)]  # overflows cap=8
        + [GetRequest(keys[0])]
    )
    res = index.execute(batch)  # must NOT raise
    assert res.results[0].status == Status.REJECTED_OVER_WIDTH
    assert res.results[1].status == Status.REJECTED_OVER_WIDTH
    statuses = {r.status for r in res.results[2:-1]}
    assert Status.REJECTED_FULL in statuses  # pool exhausted mid-batch
    assert res.results[-1].status == Status.OK  # healthy op unaffected
    assert res.results[-1].value == int(vals[0])
    # auto_merge_threshold=None pins the delta epoch: even overflow must
    # NOT trigger an implicit merge — callers invoke merge() themselves
    assert index.merge_count == 0 and not res.merged
    index.merge()
    assert index.merge_count == 1 and index.get(b"f-0000") == 0


def test_auto_merge_regression(rng):
    """Puts past the delta threshold must trigger merge_delta inside
    ``execute``; subsequent gets AND scans see the merged keys without any
    caller-side delta_fill_fraction polling."""
    keys, vals = _corpus(rng, 300)
    cfg = IndexConfig(delta_capacity=64, auto_merge_threshold=0.5)
    index = StringIndex.bulk_load(keys, vals, cfg)
    res1 = index.execute([PutRequest(b"zm-%03d" % i, 100 + i) for i in range(20)])
    assert not res1.merged and index.merge_count == 0
    res2 = index.execute([PutRequest(b"zm-%03d" % i, 100 + i) for i in range(20, 40)])
    assert res2.merged and index.merge_count == 1  # 40/64 >= 0.5
    assert int(index.ti.de_count) == 0 and res2.delta_fill == 0.0
    res3 = index.execute(
        [GetRequest(b"zm-%03d" % i) for i in range(40)]
        + [ScanRequest(b"zm-", 12)])
    for i, r in enumerate(res3.results[:40]):
        assert r.status == Status.OK and r.value == 100 + i
    # merged keys are in the frozen order now -> scannable
    assert [k for k, _ in res3.results[40].entries] == \
        [b"zm-%03d" % i for i in range(12)]


@pytest.mark.parametrize("form", ["built", "loaded"])
def test_save_load_roundtrip_bit_identical(rng, tmp_path, form, as_form):
    keys, vals = _corpus(rng, 400)
    index = as_form(StringIndex.bulk_load(keys, vals, IndexConfig(
        delta_capacity=128, auto_merge_threshold=None)), form)
    # live delta state rides into the snapshot too
    index.execute([PutRequest(b"dl-%03d" % i, i) for i in range(30)])
    path = tmp_path / "idx.snap"
    index.save(str(path))
    restored = StringIndex.load(str(path))

    probe = keys[::7] + [b"dl-%03d" % i for i in range(30)] + [b"nope-1", b"nope-2"]
    qb, ql = pad_queries(probe, index.ti.width)
    qb, ql = jnp.asarray(qb), jnp.asarray(ql)
    f0, e0, d0 = search_batch(index.ti, qb, ql)
    f1, e1, d1 = search_batch(restored.ti, qb, ql)
    assert (np.asarray(f0) == np.asarray(f1)).all()
    assert (np.asarray(e0) == np.asarray(e1)).all()
    assert (np.asarray(d0) == np.asarray(d1)).all()
    r0 = rank_batch(index.ti, qb, ql)
    r1 = rank_batch(restored.ti, qb, ql)
    assert (np.asarray(r0) == np.asarray(r1)).all()


def test_snapshot_version_and_format_errors(rng, tmp_path):
    keys, vals = _corpus(rng, 120)
    index = StringIndex.bulk_load(keys, vals)
    path = tmp_path / "idx.snap"
    index.save(str(path))

    z = dict(np.load(str(path), allow_pickle=False))
    hdr = json.loads(bytes(z["__snapshot_meta__"]).decode())
    hdr["version"] = 99
    z["__snapshot_meta__"] = np.frombuffer(json.dumps(hdr).encode(), np.uint8)
    bad_version = tmp_path / "v99.snap"
    with open(bad_version, "wb") as f:
        np.savez_compressed(f, **z)
    with pytest.raises(SnapshotVersionError):
        StringIndex.load(str(bad_version))

    hdr["version"] = 1
    hdr["magic"] = "not-lits"
    z["__snapshot_meta__"] = np.frombuffer(json.dumps(hdr).encode(), np.uint8)
    bad_magic = tmp_path / "magic.snap"
    with open(bad_magic, "wb") as f:
        np.savez_compressed(f, **z)
    with pytest.raises(SnapshotFormatError):
        StringIndex.load(str(bad_magic))

    not_snap = tmp_path / "random.npz"
    with open(not_snap, "wb") as f:
        np.savez_compressed(f, a=np.arange(3))
    with pytest.raises(SnapshotFormatError):
        StringIndex.load(str(not_snap))


def test_update_of_first_entry_survives_a_mixed_batch():
    """A base-value update of entry 0 is kept when other ops in the same
    batch update no base entry (they must not rewrite entry 0)."""
    keys = [b"a%03d" % i for i in range(100)]
    index = StringIndex.bulk_load(keys, np.arange(100, dtype=np.int64))
    res = index.execute([PutRequest(b"a000", 777), PutRequest(b"zzz", 5),
                         DeleteRequest(b"a007"), PutRequest(b"a050", 888)])
    assert [r.updated for r in res.results] == [True, False, False, True]
    found, vals = index.get_batch([b"a000", b"a050", b"zzz", b"a007"])
    assert found.tolist() == [True, True, True, False]
    assert vals[:3].tolist() == [777, 888, 5]


def test_config_beats_env(rng, monkeypatch):
    """The config is the one source of index policy (DESIGN.md §8): the env
    vars that once picked a traversal engine are not read, and the explicit
    fields reach the index."""
    monkeypatch.setenv("REPRO_SEARCH_BACKEND", "pallas")
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "interpret")
    keys, vals = _corpus(rng, 120)
    cfg = IndexConfig(delta_capacity=96, auto_merge_threshold=None,
                      scan_window=5, search_backend="jnp")
    index = StringIndex.bulk_load(keys, vals, cfg)
    assert index.config is cfg and index.ti.de_off.shape[0] == 96
    res = index.execute([ScanRequest(keys[0]), GetRequest(keys[1])])
    assert [k for k, _ in res.results[0].entries] == keys[:5]
    assert res.results[1].value == int(vals[1])
    with pytest.raises(ValueError):
        IndexConfig(search_backend="bogus")


def test_scan_window_grouping_and_default(rng):
    keys, vals = _corpus(rng, 250)
    index = StringIndex.bulk_load(keys, vals, IndexConfig(scan_window=4))
    res = index.execute([
        ScanRequest(keys[0]),            # default window (4)
        ScanRequest(keys[0], window=8),  # explicit window
        ScanRequest(keys[3], window=8),
    ])
    assert len(res.results[0].entries) == 4
    assert len(res.results[1].entries) == 8
    assert [k for k, _ in res.results[0].entries] == keys[:4]
    assert [k for k, _ in res.results[1].entries] == keys[:8]
    assert [k for k, _ in res.results[2].entries] == keys[3:11]


@pytest.mark.parametrize("form", ["built", "loaded"])
def test_delete_tombstone_semantics(rng, form, as_form):
    """DELETE completes the typed op family (DESIGN.md §9): delta-buffer
    tombstones shadow the frozen base immediately for gets, reconcile
    physically at merge_delta, and puts resurrect."""
    keys, vals = _corpus(rng, 300)
    cfg = IndexConfig(delta_capacity=256, auto_merge_threshold=None)
    index = as_form(StringIndex.bulk_load(keys, vals, cfg), form)
    res = index.execute([
        DeleteRequest(keys[3]),          # base-resident -> tombstone shadow
        DeleteRequest(b"never-existed"),  # absent -> NOT_FOUND
        GetRequest(keys[3]),             # delete visible in the same batch
        GetRequest(keys[4]),             # neighbour untouched
        ScanRequest(keys[2], 4),         # read-your-writes: already hidden
    ])
    assert res.results[0].status == Status.OK
    assert res.results[1].status == Status.NOT_FOUND
    assert res.results[2].status == Status.NOT_FOUND
    assert res.results[3].value == int(vals[4])
    # §11: the tombstone suppresses keys[3] in the SAME batch's scan — the
    # window slides past it to the next live key
    assert [k for k, _ in res.results[4].entries] == \
        [keys[2]] + keys[4:7]
    assert res.n_delete == 2
    # double delete: the key is already unpublished
    assert index.delete(keys[3]).status == Status.NOT_FOUND
    # delta-resident key: tombstone set in place, no second slot
    index.put(b"fresh", 11)
    before = int(index.ti.de_count)
    assert index.delete(b"fresh").status == Status.OK
    assert int(index.ti.de_count) == before and index.get(b"fresh") is None
    # resurrect: a put clears the tombstone and reports an insert
    r = index.put(keys[3], 777)
    assert r.ok and not r.updated
    assert index.get(keys[3]) == 777
    # over-width keys were never representable
    wide = b"w" * (index.width + 1)
    assert index.delete(wide).status == Status.REJECTED_OVER_WIDTH
    # merge reconciles: builder.delete removes tombstoned keys physically
    index.delete(keys[5])
    index.merge()
    assert index.get(keys[5]) is None and index.get(keys[3]) == 777
    assert [k for k, _ in index.scan(keys[4], 3)] == \
        [keys[4], keys[6], keys[7]], "post-merge scans skip the deleted key"


def test_delete_full_pool_rejected_as_data(rng):
    keys, vals = _corpus(rng, 150)
    index = StringIndex.bulk_load(keys, vals, IndexConfig(
        delta_capacity=8, auto_merge_threshold=None))
    index.execute([PutRequest(b"f-%02d" % i, i) for i in range(8)])
    res = index.execute([DeleteRequest(keys[0])])  # needs a slot: pool full
    assert res.results[0].status == Status.REJECTED_FULL
    assert index.get(keys[0]) == int(vals[0]), "rejected delete is a no-op"
    index.merge()                                  # compaction frees slots
    assert index.delete(keys[0]).status == Status.OK
    assert index.get(keys[0]) is None


def test_snapshot_carries_tombstones_and_reads_v1(rng, tmp_path):
    import json

    keys, vals = _corpus(rng, 150)
    index = StringIndex.bulk_load(keys, vals, IndexConfig(
        auto_merge_threshold=None))
    index.execute([DeleteRequest(keys[9]), PutRequest(b"dl-1", 5)])
    path = tmp_path / "v2.snap"
    index.save(str(path))
    restored = StringIndex.load(str(path))
    assert restored.get(keys[9]) is None, "tombstone must survive the snapshot"
    assert restored.get(b"dl-1") == 5 and restored.get(keys[10]) == int(vals[10])
    # a v1 snapshot (pre-tombstone format) still loads: all delta entries
    # live.  Synthesize one from a delete-free index — a real v1 file can
    # only ever hold live entries.
    live = StringIndex.bulk_load(keys, vals, IndexConfig(
        auto_merge_threshold=None))
    live.execute([PutRequest(b"dl-1", 5)])
    path_live = tmp_path / "live.snap"
    live.save(str(path_live))
    z = dict(np.load(str(path_live), allow_pickle=False))
    hdr = json.loads(bytes(z["__snapshot_meta__"]).decode())
    hdr["version"] = 1
    hdr["data_fields"] = [f for f in hdr["data_fields"] if f != "de_tomb"]
    z.pop("de_tomb")
    z["__snapshot_meta__"] = np.frombuffer(json.dumps(hdr).encode(), np.uint8)
    v1 = tmp_path / "v1.snap"
    with open(v1, "wb") as f:
        np.savez_compressed(f, **z)
    old = StringIndex.load(str(v1))
    assert old.get(keys[9]) == int(vals[9]), \
        "v1 had no deletes: every delta entry loads live"
    assert old.get(b"dl-1") == 5
    assert old.delete(b"dl-1").status == Status.OK, \
        "a v1-loaded index speaks the full op family"


def test_get_put_convenience_roundtrip(rng):
    keys, vals = _corpus(rng, 150)
    index = StringIndex.bulk_load(keys, vals)
    assert index.get(keys[3]) == int(vals[3])
    assert index.get(b"absent") is None
    r = index.put(b"fresh-key", 1234)
    assert r.ok and not r.updated
    assert index.get(b"fresh-key") == 1234
    r2 = index.put(b"fresh-key", 5678)
    assert r2.ok and r2.updated
    assert index.get(b"fresh-key") == 5678


def test_values_64bit_roundtrip(rng):
    keys, _ = _corpus(rng, 100)
    vals = (np.arange(len(keys), dtype=np.int64) << 33) + 12345
    index = StringIndex.bulk_load(keys, vals)
    found, got = index.get_batch(keys[:50])
    assert found.all() and (got == vals[:50]).all()


# index width per corpus: the url index is widened past 255 so that its
# lengths take two bytes of the packed query buffer
GET_CORPORA = {"email": None, "url": 264}


@pytest.fixture(scope="module", params=sorted(GET_CORPORA))
def mutated_index(request):
    """``(index, queries, truth)``: a bulk-loaded index after delta puts,
    base and delta updates, tombstones on base and delta keys and puts that
    resurrect some of them; queries over all of these, misses, keys with
    zero bytes and keys at and past the width; ``truth`` the host dict."""
    corpus = request.param
    keys = sorted(set(load(corpus, 3000, seed=3)))
    vals = np.arange(len(keys), dtype=np.int64) * 7 - (1 << 33)
    index = StringIndex.bulk_load(keys, vals, IndexConfig(
        width=GET_CORPORA[corpus], delta_capacity=512,
        auto_merge_threshold=None))
    w = index.width
    truth = dict(zip(keys, vals.tolist()))
    fresh = [b"fresh-%04d" % i for i in range(60)] + [
        b"z\x00", b"\x00lead", b"\x00", keys[0] + b"\x00", b"x" * w]

    def put(ks, base):
        vs = [base + i for i in range(len(ks))]
        ins, upd, _ = index.put_batch(ks, vs)
        assert (ins | upd).all()
        truth.update(zip(ks, vs))

    def delete(ks):
        deleted, _, _ = index.delete_batch(ks)
        assert deleted.all()
        for k in ks:
            del truth[k]

    put(fresh, 1 << 40)
    put(keys[10:30] + fresh[:10], -(1 << 50))        # base and delta updates
    delete(keys[30:60] + fresh[10:20])               # base and delta tombstones
    put(keys[30:40] + fresh[10:15], 1 << 45)         # resurrected
    queries = (keys[:80] + fresh + [
        k + b"~miss" for k in keys[80:100]] + [
        b"", b"\x00\x00", b"x" * (w - 1) + b"y",   # misses, one at the width
        b"x" * w + b"\x00",                          # past it, a stored prefix
        keys[0] + b"q" * (w + 1 - len(keys[0])),
        b"y" * (w + 300)])
    order = np.random.default_rng(7).permutation(len(queries))
    return index, [queries[i] for i in order], truth


_walk = jax.jit(search_impl)


def _search_then_lookup(ti, keys):
    """A get group as two programs and six copies: the search walk, then
    ``lookup_values``, then the host value join."""
    qb, ql = pad_queries(keys, ti.width)
    found, eid, isd, iters, model_iters, rounds = _walk(
        ti, jnp.asarray(qb), jnp.asarray(ql))
    lo, hi = lookup_values(ti, eid, isd)
    found = np.asarray(found)
    return (found, np.where(found, _join_values(lo, hi), 0),
            (int(iters), int(model_iters), int(rounds)))


@pytest.mark.parametrize("group", [1, 37, 128])
def test_get_batch_matches_search_then_lookup(mutated_index, group):
    index, queries, truth = mutated_index
    n = -(-len(queries) // group) * group
    queries = (queries * 2)[:n]
    for i in range(0, n, group):
        keys = queries[i: i + group]
        counters = (index.walk_iters, index.model_step_iters,
                    index.resolve_rounds)
        found, vals = index.get_batch(keys)
        want_found, want_vals, want_counts = _search_then_lookup(index.ti,
                                                                 keys)
        assert found.dtype == bool and vals.dtype == np.int64
        assert found.tolist() == want_found.tolist()
        assert vals.tolist() == want_vals.tolist()
        assert tuple(b - a for a, b in zip(counters, (
            index.walk_iters, index.model_step_iters,
            index.resolve_rounds))) == want_counts
        assert found.tolist() == [k in truth and len(k) <= index.width
                                  for k in keys]
        assert vals.tolist() == [truth[k] if f else 0
                                 for k, f in zip(keys, found.tolist())]
