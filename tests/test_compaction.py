"""Epoch-based concurrent compaction (DESIGN.md §10).

The acceptance contract (ISSUE 4):

* the vectorized ``merge_delta`` replay (bulk ``insert_many``/``delete_many``
  + partial refreeze off the builder's incremental caches) is bit-identical
  to a sequential oracle, on a built index and on one loaded from a
  snapshot (whose first merge rebuilds the host builder);
* device-side in-place base value updates survive the merge (they replay
  into the builder via the val-sync seam — previously they silently
  reverted);
* writer threads racing a forced ``compact()`` lose nothing: every write
  accepted during a merge epoch is journaled and re-drained at the commit
  swap, and the final state equals the sequential oracle;
* the epoch counter increments per merge and round-trips through snapshot
  format v3, with v2 (and v1) files still loading.
"""
import json
import threading

import numpy as np
import pytest

from repro.core.strings import random_strings
from repro.index import (
    DeleteRequest, GetRequest, IndexConfig, PutRequest, ScanRequest, Status,
    StringIndex,
)
from repro.serve.service import IndexService, ServiceConfig


def _corpus(rng, n=500):
    keys = sorted(set(random_strings(rng, n, 3, 24)))
    vals = np.arange(len(keys), dtype=np.int64) * 3 + 1
    return keys, vals


def _check_oracle(index: StringIndex, oracle: dict) -> None:
    """Index content == oracle: every live key's value, absent keys miss,
    and the full scan reproduces the oracle's sorted key order."""
    live = sorted(oracle)
    found, vals = index.get_batch(live)
    assert found.all(), "oracle keys missing after merge"
    np.testing.assert_array_equal(vals, np.array([oracle[k] for k in live]))
    scanned = index.scan(b"", len(live) + 16)
    assert [k for k, _ in scanned] == live, "scan order diverged from oracle"
    assert [v for _, v in scanned] == [oracle[k] for k in live]


@pytest.mark.parametrize("form", ["built", "loaded"])
def test_vectorized_merge_bit_identical_to_sequential_oracle(rng, form,
                                                            as_form):
    """Mixed fresh puts / base updates / deletes / resurrects across TWO merge
    cycles (the second exercises the warm incremental caches) match a plain
    sequential dict oracle, on a built and on a loaded index."""
    keys, vals = _corpus(rng, 400)
    cfg = IndexConfig(delta_capacity=1024, auto_merge_threshold=None)
    index = as_form(StringIndex.bulk_load(keys, vals, cfg), form)
    oracle = {k: int(v) for k, v in zip(keys, vals)}

    def apply(batch):
        index.execute(batch)
        for r in batch:
            if isinstance(r, PutRequest):
                oracle[r.key] = r.value
            elif isinstance(r, DeleteRequest):
                oracle.pop(r.key, None)

    apply([PutRequest(b"m1-%04d" % i, 7000 + i) for i in range(120)]
          + [PutRequest(keys[3], 3333), PutRequest(keys[9], 9999)]  # base updates
          + [DeleteRequest(keys[5]), DeleteRequest(keys[6])]
          + [DeleteRequest(b"m1-0000"), PutRequest(b"m1-0001", 70001)])
    index.merge()
    assert index.epoch == 1 and index.merge_count == 1
    _check_oracle(index, oracle)

    # second cycle: delete a merged key, resurrect a deleted one, more puts
    apply([PutRequest(keys[5], 5550)]                     # resurrect
          + [DeleteRequest(b"m1-0002"), DeleteRequest(keys[9])]
          + [PutRequest(b"m2-%04d" % i, 8000 + i) for i in range(60)])
    index.merge()
    assert index.epoch == 2
    _check_oracle(index, oracle)


def test_base_value_update_survives_merge(rng):
    """In-place device updates of base entries (PUT on a bulk-loaded key)
    must replay into the builder at merge — they used to silently revert."""
    keys, vals = _corpus(rng, 100)
    index = StringIndex.bulk_load(
        keys, vals, IndexConfig(auto_merge_threshold=None))
    index.execute([PutRequest(keys[7], 424242),
                   PutRequest(b"fresh-key", 1)])  # delta non-empty -> real merge
    assert index.get(keys[7]) == 424242
    index.merge()
    assert index.get(keys[7]) == 424242, \
        "base value update lost by the merge replay"
    index.execute([PutRequest(keys[8], 848484), PutRequest(b"fresh-2", 2)])
    index.merge()   # second cycle: lockstep val-sync path
    assert index.get(keys[8]) == 848484 and index.get(keys[7]) == 424242


@pytest.mark.parametrize("form", ["built", "loaded"])
def test_concurrent_writers_race_forced_compaction(rng, form, as_form):
    """Writer threads + forced ``compact()`` racing on the service: merges
    run off-lock mid-traffic (epoch swap + journal re-drain), and the final
    index is bit-identical to the sequential per-thread oracle.  Disjoint
    per-writer keyspaces make the oracle interleaving-independent."""
    keys, vals = _corpus(rng, 300)
    index = StringIndex.bulk_load(
        [IndexService.encode_key("t", k) for k in keys], vals,
        IndexConfig(delta_capacity=8192, auto_merge_threshold=None))
    svc = IndexService(as_form(index, form),
                       ServiceConfig(max_batch=64, max_delay_ms=0.5,
                                     default_tenant="t",
                                     merge_threshold=None))
    n_writers, rounds = 4, 6
    oracle = {k: int(v) for k, v in zip(keys, vals)}
    barrier = threading.Barrier(n_writers + 1)
    statuses = []

    def writer(i):
        barrier.wait()
        for r in range(rounds):
            batch = [PutRequest(b"w%d-%04d" % (i, r * 50 + j),
                                i * 100000 + r * 50 + j) for j in range(50)]
            batch.append(DeleteRequest(b"w%d-%04d" % (i, r * 50)))
            batch.append(PutRequest(b"w%d-%04d" % (i, r * 50 + 1), -(i + r)))
            statuses.append(all(res.status == Status.OK
                                for res in svc.execute(batch)))
            for req in batch:
                if isinstance(req, PutRequest):
                    oracle[req.key] = req.value
                else:
                    oracle.pop(req.key, None)

    threads = [threading.Thread(target=writer, args=(i,))
               for i in range(n_writers)]
    for t in threads:
        t.start()
    barrier.wait()
    import time

    merges = 0
    for _ in range(4):
        time.sleep(0.05)        # let some flushes land between merges
        merges += bool(svc.compact())
    for t in threads:
        t.join()
    assert all(statuses), "no write may fail at this capacity"
    assert merges >= 1, "at least one merge must have raced the writers"
    svc.compact()   # fold any re-drained tail so scans see everything
    s = svc.stats()
    assert s.epoch == s.merges >= 1
    # final state == oracle through the service surface (strip tenancy)
    live = sorted(oracle)
    res = svc.execute([GetRequest(k) for k in live])
    assert [r.value for r in res] == [oracle[k] for k in live]
    page, got = svc.scan_page(b"", 200, tenant="t"), []
    while True:
        got.extend(page.entries)
        if page.cursor is None:
            break
        page = svc.scan_page(cursor=page.cursor, tenant="t")
    assert [k for k, _ in got] == live
    svc.close()


def test_commit_pause_excludes_merge_work(rng):
    """The §10 split: the heavy replay runs OFF the index lock — the
    commit pause the request path can observe is a small fraction of the
    total merge wall time."""
    keys, vals = _corpus(rng, 400)
    svc = IndexService.bulk_load(
        {"t": (keys, vals)},
        IndexConfig(delta_capacity=4096, auto_merge_threshold=None),
        ServiceConfig(default_tenant="t", merge_threshold=None))
    svc.execute([PutRequest(b"p-%05d" % i, i) for i in range(1500)])
    assert svc.compact() is True
    s = svc.stats()
    assert s.merge_wall_ms > 0 and s.merge_pause_ms >= 0
    assert s.merge_pause_ms < s.merge_wall_ms / 2, \
        (s.merge_pause_ms, s.merge_wall_ms)
    svc.close()


def test_facade_merge_seams_redrain_midmerge_writes(rng):
    """begin/run/commit directly: writes landed between begin and commit are
    journaled and re-drained onto the swapped epoch (nothing lost, nothing
    resurrected)."""
    keys, vals = _corpus(rng, 150)
    index = StringIndex.bulk_load(
        keys, vals, IndexConfig(delta_capacity=1024,
                                auto_merge_threshold=None))
    index.put_batch([b"pre-%03d" % i for i in range(40)], list(range(40)))
    ticket = index.begin_merge()
    with pytest.raises(RuntimeError):
        index.begin_merge()          # single open epoch
    index.execute([PutRequest(b"mid-%03d" % i, 500 + i) for i in range(25)]
                  + [DeleteRequest(keys[2]), PutRequest(keys[4], 404)])
    new_ti = index.run_merge(ticket)
    redrained = index.commit_merge(ticket, new_ti)
    assert redrained == 27
    assert index.epoch == 1
    assert index.get(b"mid-007") == 507
    assert index.get(b"pre-007") == 7
    assert index.get(keys[2]) is None
    assert index.get(keys[4]) == 404
    # abort leaves the live index intact and reopens the seam
    t2 = index.begin_merge()
    index.abort_merge(t2)
    index.merge()                    # plain merge still works after abort
    assert index.epoch >= 2 and index.get(b"mid-007") == 507


def test_epoch_roundtrips_through_snapshot_v3(rng, tmp_path):
    keys, vals = _corpus(rng, 120)
    index = StringIndex.bulk_load(keys, vals,
                                  IndexConfig(auto_merge_threshold=None))
    index.execute([PutRequest(b"x-%03d" % i, i) for i in range(30)])
    index.merge()
    index.execute([PutRequest(b"y-%03d" % i, i) for i in range(10)])
    index.merge()
    assert index.epoch == 2
    p = str(tmp_path / "epoch.snap")
    index.save(p)
    with open(p, "rb") as f:
        import numpy as _np
        z = _np.load(f, allow_pickle=False)
        header = json.loads(bytes(z["__snapshot_meta__"]).decode())
        from repro.index.snapshot import SNAPSHOT_VERSION
        assert header["version"] == SNAPSHOT_VERSION >= 3
        assert int(z["epoch"]) == 2
    loaded = StringIndex.load(p)
    assert loaded.epoch == 2
    assert loaded.get(b"x-007") == 7
    loaded.execute([PutRequest(b"z-000", 99)])
    loaded.merge()
    assert loaded.epoch == 3   # lineage continues from the snapshot


def test_emptied_index_does_not_resurrect_dead_keys_after_load(rng, tmp_path):
    """freeze pads an all-dead ``ent_sorted`` with a [0] sentinel; the
    post-load builder reconstruction must not replay pool slot 0 (a deleted
    key) back to life."""
    keys, vals = _corpus(rng, 60)
    index = StringIndex.bulk_load(keys, vals,
                                  IndexConfig(auto_merge_threshold=None))
    index.execute([DeleteRequest(k) for k in keys])
    index.merge()                       # physically empty base
    assert index.scan(b"", 10) == []
    p = str(tmp_path / "empty.snap")
    index.save(p)
    loaded = StringIndex.load(p)
    loaded.execute([PutRequest(b"only-key", 7)])
    loaded.merge()                      # builder reconstructed from nothing
    assert loaded.get(keys[0]) is None, "deleted key resurrected by reload"
    assert loaded.get(b"only-key") == 7
    assert [k for k, _ in loaded.scan(b"", 10)] == [b"only-key"]


def test_bulk_op_failure_invalidates_caches(rng):
    """A mid-batch insert_many failure (over-width key) leaves the builder
    partially replayed: the incremental sorted/height caches must be
    invalidated so the next freeze re-walks exactly — and a retried merge
    converges instead of wedging."""
    from repro.core import LITSBuilder, StringSet
    from repro.core.tensor_index import freeze, search_batch, pad_queries
    import jax.numpy as jnp

    keys, vals = _corpus(rng, 80)
    b = LITSBuilder()
    b.bulkload(StringSet.from_list(keys), np.asarray(vals), width=32)
    # poison key sorts BETWEEN the good ones (bulk walks run in key order),
    # so the failure strikes mid-batch: ok1 already inserted, ok2 not yet
    ok1, ok2 = b"aa-new-1", b"aa-new-2"
    bad = b"aa-new-1" + b"x" * 40       # > width 32 -> ValueError mid-walk
    with pytest.raises(ValueError):
        b.insert_many([ok1, bad, ok2], np.array([1, 2, 3], np.int64))
    # the partial mutation is visible, and the recomputed order matches a
    # full ordered walk (stale-cache corruption would drop the new key)
    got = list(b.sorted_eids())
    assert got == list(b.iter_subtree(b.root_item))
    ti = freeze(b)
    qb, ql = pad_queries([ok1, keys[0]], ti.width)
    found, _, _ = search_batch(ti, jnp.asarray(qb), jnp.asarray(ql))
    assert bool(found[0]) and bool(found[1])
    # retrying the batch (sans poison) upserts cleanly — no duplicates
    ins = b.insert_many([ok1, ok2], np.array([10, 30], np.int64))
    assert list(ins) == [False, True]   # ok1 already landed -> value refresh
    assert sorted(b.sorted_eids()) == sorted(set(b.sorted_eids()))


def test_snapshot_v2_loads_with_epoch_zero(rng, tmp_path):
    """Back-compat: a v2 snapshot (no epoch array) loads at epoch 0 and is
    fully functional — the v2 -> v3 upgrade path."""
    keys, vals = _corpus(rng, 100)
    index = StringIndex.bulk_load(keys, vals,
                                  IndexConfig(auto_merge_threshold=None))
    index.execute([PutRequest(b"d-%03d" % i, 100 + i) for i in range(20)])
    index.merge()
    assert index.epoch == 1
    p3 = str(tmp_path / "v3.snap")
    p2 = str(tmp_path / "v2.snap")
    index.save(p3)
    # rewrite as a faithful v2 file: drop the epoch array, downgrade header
    with open(p3, "rb") as f:
        z = np.load(f, allow_pickle=False)
        arrays = {n: z[n] for n in z.files if n != "__snapshot_meta__"}
        header = json.loads(bytes(z["__snapshot_meta__"]).decode())
    arrays.pop("epoch")
    header["version"] = 2
    header["data_fields"] = sorted(arrays)
    meta = np.frombuffer(json.dumps(header).encode(), dtype=np.uint8)
    with open(p2, "wb") as f:
        np.savez_compressed(f, __snapshot_meta__=meta, **arrays)
    loaded = StringIndex.load(p2)
    assert loaded.epoch == 0, "v2 files carry no epoch: lineage restarts"
    assert loaded.get(b"d-007") == 107
    assert loaded.get(keys[3]) == int(vals[3])
    # the restarted lineage merges forward normally
    loaded.execute([PutRequest(b"post-v2", 5)])
    loaded.merge()
    assert loaded.epoch == 1 and loaded.get(b"post-v2") == 5
    # scans match the one-shot pre-snapshot order
    assert [k for k, _ in loaded.scan(b"d-", 5)] == \
        [b"d-%03d" % i for i in range(5)]
