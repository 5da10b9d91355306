"""Bulkload -> search/insert/delete/scan oracle tests (host + device paths)."""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (
    AlwaysLIT, AlwaysTrie, LITSBuilder, LITSConfig, StringSet, freeze,
    insert_batch, lookup_values, merge_delta, pad_queries, rank_batch,
    scan_batch, search_batch,
)
from repro.core.strings import random_strings

key_st = st.lists(st.integers(1, 127), min_size=1, max_size=20).map(bytes)


def _build(keys, vals=None, **kw):
    b = LITSBuilder(**kw)
    v = np.asarray(vals if vals is not None else np.arange(len(keys)), np.int64)
    b.bulkload(StringSet.from_list(list(keys)), v)
    return b


@given(st.sets(key_st, min_size=1, max_size=200))
@settings(max_examples=40, deadline=None)
def test_host_roundtrip_hypothesis(keys):
    keys = sorted(keys)
    vals = np.arange(len(keys), dtype=np.int64) * 3 + 1
    b = _build(keys, vals)
    for k, v in zip(keys, vals):
        assert b.get(k) == v
    for k in keys[:20]:
        assert b.get(k + b"~") is None


@given(st.sets(key_st, min_size=2, max_size=120))
@settings(max_examples=25, deadline=None)
def test_device_roundtrip_hypothesis(keys):
    keys = sorted(keys)
    vals = np.arange(len(keys), dtype=np.int64)
    b = _build(keys, vals)
    ti = freeze(b)
    qb, ql = pad_queries(keys, ti.width)
    found, eid, isd = search_batch(ti, jnp.asarray(qb), jnp.asarray(ql))
    assert bool(found.all())
    lo, _ = lookup_values(ti, eid, isd)
    assert (np.asarray(lo) == vals).all()
    misses = [k + b"~miss" for k in keys[:10]]
    qb2, ql2 = pad_queries(misses, ti.width)
    f2, _, _ = search_batch(ti, jnp.asarray(qb2), jnp.asarray(ql2))
    real_miss = np.array([m not in set(keys) for m in misses])
    assert not (np.asarray(f2) & real_miss).any()


@pytest.mark.parametrize("pmss_cls", [AlwaysLIT, AlwaysTrie, None])
def test_structural_variants(rng, pmss_cls):
    """LIT (no subtrie), pure trie, and PMSS hybrid all answer identically."""
    keys = sorted(set(random_strings(rng, 1500, 2, 28)))
    kw = {"pmss": pmss_cls()} if pmss_cls else {}
    b = _build(keys, **kw)
    ti = freeze(b)
    qb, ql = pad_queries(keys, ti.width)
    found, _, _ = search_batch(ti, jnp.asarray(qb), jnp.asarray(ql))
    assert bool(found.all())


def test_scan_matches_sorted_order(rng):
    keys = sorted(set(random_strings(rng, 800, 2, 20)))
    b = _build(keys)
    ti = freeze(b)
    starts = [keys[10], keys[100][:3], b"zzzz", b"a"]
    qb, ql = pad_queries(starts, ti.width)
    eids, valid, _isd = scan_batch(ti, jnp.asarray(qb), jnp.asarray(ql), window=12)
    for i, s in enumerate(starts):
        expect = [k for k in keys if k >= s][:12]
        got = [b.key_at(int(e)) for e, ok in zip(np.asarray(eids)[i], np.asarray(valid)[i]) if ok]
        assert got == expect


def test_host_scan(rng):
    keys = sorted(set(random_strings(rng, 500, 2, 16)))
    b = _build(keys)
    got = [k for k, v in b.scan(keys[50], 20)]
    assert got == keys[50:70]


def test_insert_delete_update_cycle(rng):
    keys = sorted(set(random_strings(rng, 1000, 2, 20)))
    half = keys[::2]
    rest = [k for k in keys if k not in set(half)]
    b = _build(half)
    for i, k in enumerate(rest):
        assert b.insert(k, 100000 + i)
        assert not b.insert(k, 0), "duplicate insert must fail"
    for i, k in enumerate(rest):
        assert b.get(k) == 100000 + i
    for k in half:
        assert b.get(k) is not None
    # updates
    assert b.update(rest[0], 42)
    assert b.get(rest[0]) == 42
    assert not b.update(b"\x7fnot-there", 1)
    # deletes
    for k in rest[: len(rest) // 2]:
        assert b.delete(k)
        assert b.get(k) is None
    assert not b.delete(rest[0])
    # survivors intact
    for k in rest[len(rest) // 2 :]:
        assert b.get(k) is not None
    assert b.n_keys == len(half) + len(rest) - len(rest) // 2


def test_resize_rule_triggers(rng):
    """Mass inserts into one node must trigger the 2x rebuild (Alg. 3)."""
    keys = [b"k%04d" % i for i in range(0, 4000, 4)]
    b = _build(keys)
    h0 = b.heights()
    inserted = [b"k%04d" % i for i in range(1, 4000, 4)]
    for i, k in enumerate(inserted):
        b.insert(k, i)
    for k in keys + inserted:
        assert b.get(k) is not None, k
    h1 = b.heights()
    assert h1["base"] <= h0["base"] + 3  # rebuilds keep the tree shallow


def test_delta_buffer_and_merge(rng):
    keys = sorted(set(random_strings(rng, 400, 4, 16)))
    b = _build(keys)
    ti = freeze(b, delta_capacity=128)
    new = [b"delta-%04d" % i for i in range(100)]
    qb, ql = pad_queries(new, ti.width)
    vals = np.arange(100, dtype=np.int64) + 7
    ti2, ins, upd = insert_batch(
        ti, jnp.asarray(qb), jnp.asarray(ql),
        jnp.asarray((vals & 0xFFFFFFFF).astype(np.uint32).view(np.int32)),
        jnp.asarray((vals >> 32).astype(np.int32)),
    )
    assert int(ins.sum()) == 100 and not bool(ti2.delta_overflow)
    f, e, d = search_batch(ti2, jnp.asarray(qb), jnp.asarray(ql))
    assert bool(f.all()) and int(d.sum()) == 100
    lo, _ = lookup_values(ti2, e, d)
    assert (np.asarray(lo) == vals).all()
    # base keys still found
    qb0, ql0 = pad_queries(keys[:50], ti.width)
    f0, _, _ = search_batch(ti2, jnp.asarray(qb0), jnp.asarray(ql0))
    assert bool(f0.all())
    # merge moves delta into the base
    ti3 = merge_delta(b, ti2)
    f3, e3, d3 = search_batch(ti3, jnp.asarray(qb), jnp.asarray(ql))
    assert bool(f3.all()) and int(d3.sum()) == 0
    lo3, _ = lookup_values(ti3, e3, d3)
    assert (np.asarray(lo3) == vals).all()


def test_delta_overflow_flag(rng):
    keys = sorted(set(random_strings(rng, 100, 4, 12)))
    b = _build(keys)
    ti = freeze(b, delta_capacity=16)
    new = [b"of-%05d" % i for i in range(64)]
    qb, ql = pad_queries(new, ti.width)
    z = jnp.zeros(64, jnp.int32)
    ti2, ins, _ = insert_batch(ti, jnp.asarray(qb), jnp.asarray(ql), z, z)
    assert bool(ti2.delta_overflow)
    assert int(ins.sum()) < 64


def test_values_update_in_base(rng):
    keys = sorted(set(random_strings(rng, 200, 4, 12)))
    b = _build(keys)
    ti = freeze(b)
    qb, ql = pad_queries(keys[:32], ti.width)
    nv = np.arange(32, dtype=np.int64) + 999
    ti2, ins, upd = insert_batch(
        ti, jnp.asarray(qb), jnp.asarray(ql),
        jnp.asarray((nv & 0xFFFFFFFF).astype(np.uint32).view(np.int32)),
        jnp.asarray((nv >> 32).astype(np.int32)),
    )
    assert int(ins.sum()) == 0 and int(upd.sum()) == 32
    f, e, d = search_batch(ti2, jnp.asarray(qb), jnp.asarray(ql))
    lo, _ = lookup_values(ti2, e, d)
    assert (np.asarray(lo) == nv).all()


def test_rank_batch(rng):
    keys = sorted(set(random_strings(rng, 300, 2, 14)))
    b = _build(keys)
    ti = freeze(b)
    queries = [keys[0], keys[37], keys[-1], b"a", b"~~~~", keys[5] + b"x"]
    qb, ql = pad_queries(queries, ti.width)
    r = np.asarray(rank_batch(ti, jnp.asarray(qb), jnp.asarray(ql)))
    import bisect

    for q, got in zip(queries, r):
        assert got == bisect.bisect_left(keys, q)


class _DippingModel:
    """Host model that is monotone in the key except for dips: every 7th
    key's value falls below its predecessor's, as float32 CDF rounding can
    make a key's slot fall below its predecessor's."""

    def values(self, ss, start):
        out = []
        for row, ln in zip(ss.bytes, ss.lens):
            i = int(row[1:ln].tobytes()[:4])
            out.append(i - (1.5 if i % 7 == 3 else 0.0))
        return np.asarray(out, np.float64)


def test_builder_non_monotone_positions_keep_every_key():
    """Keys whose model position dips below their predecessor's share an
    aliased block of slots: every key stays reachable at its own position,
    the ordered walk stays in key order, and both hold across bulk
    inserts and deletes."""
    from repro.core import LITSBuilder, StringSet
    from repro.core.pmss import AlwaysLIT

    keys = [b"k%04d" % i for i in range(0, 800, 2)]
    b = LITSBuilder(host_model=_DippingModel(), pmss=AlwaysLIT())
    b.bulkload(StringSet.from_list(keys), np.arange(len(keys)))

    def check(live):
        live = sorted(live)
        assert all(b.host_search(k)[0] for k in live)
        walk = [b.key_at(e) for e in b.iter_subtree(b.root_item)]
        assert walk == live
        assert [b.key_at(int(e)) for e in b.sorted_eids()] == live

    check(keys)
    added = [b"k%04d" % i for i in range(1, 800, 6)]
    b.insert_many(added, np.arange(len(added)))
    gone = keys[::5]
    b.delete_many(gone)
    check(set(keys) - set(gone) | set(added))
