"""Device ops against host truth.

* point lookup, ordered rank and range scan (``search_batch``,
  ``rank_batch``, ``scan_batch``) against a dict and the sorted key list,
  with and without a live delta;
* the walk's parts on their own: the in-program HPT CDF against the host
  float64 CDF, model-node slot positions against the slots the builder
  filled, and the terminal resolve against a dict of cnode contents;
* the traversal is not selectable by config or environment;
* device hashes against the host hashes, over-width keys, and the packed
  query buffer read back on the device.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    LITSBuilder, StringSet, build_hpt, freeze, get_cdf_jnp, get_cdf_np64,
    insert_batch, lookup_values, merge_delta, pad_queries, rank_batch,
    scan_batch, search_batch,
)
from repro.core.builder import (
    TAG_CNODE, TAG_EMPTY, TAG_ENTRY, TAG_MNODE, make_item,
)
from repro.core.hpt import positions_impl
from repro.core.strings import key_hash16, random_strings
from repro.core.strops import hash16, hash32
from repro.core.tensor_index import pack_queries, search_impl, unpack_queries
from repro.core.walk import resolve_terminal, walk_terminal
from repro.data.synthetic import load
from repro.index import IndexConfig, StringIndex


@pytest.fixture(scope="module")
def setup():
    rng = np.random.default_rng(11)
    keys = random_strings(rng, 700, 1, 40)
    ss = StringSet.from_list(keys, width=48)
    hpt = build_hpt(ss, rows=256, cols=128)
    return ss, hpt, rng


# ---------------------------------------------------------------------------
# the HPT CDF the walk computes, against the host float64 CDF
# ---------------------------------------------------------------------------

# float32 accumulation over at most 48 steps against float64: the largest
# difference seen on these corpora is 1.2e-7
CDF_ATOL = 1e-6


def _cdf(hpt, qb, ql, start):
    """The walk's CDF (``get_cdf_impl`` in a program, as ``positions_impl``
    runs it for ``walk_terminal``) -> numpy."""
    return np.asarray(get_cdf_jnp(
        jnp.asarray(hpt.cdf_tab), jnp.asarray(hpt.prob_tab),
        jnp.asarray(qb), jnp.asarray(ql), start))


@pytest.mark.parametrize("start", [0, 3])
@pytest.mark.parametrize("bsz,width", [(1, 8), (7, 16), (64, 48), (300, 33)])
def test_hpt_cdf_matches_host(setup, start, bsz, width):
    ss, hpt, _ = setup
    sub = ss.take(np.arange(bsz) % len(ss)).pad_to(max(width, ss.width))
    qb = np.ascontiguousarray(sub.bytes[:, :width])
    ql = np.minimum(sub.lens, width)
    want = get_cdf_np64(hpt, StringSet(qb, ql), start)
    np.testing.assert_allclose(_cdf(hpt, qb, ql, start), want,
                               rtol=0, atol=CDF_ATOL)
    assert want.max() > 0


@pytest.mark.parametrize("rows", [64, 1024])
def test_hpt_cdf_rows_sweep(setup, rows):
    _, _, rng = setup
    keys = random_strings(rng, 128, 1, 24)
    s2 = StringSet.from_list(keys, width=32)
    hpt = build_hpt(s2, rows=rows, cols=128)
    got = _cdf(hpt, s2.bytes, s2.lens, 0)
    np.testing.assert_allclose(got, get_cdf_np64(hpt, s2), rtol=0,
                               atol=CDF_ATOL)
    # the CDF orders keys: sorted keys get non-decreasing values, up to
    # the same rounding
    order = np.argsort(np.array(keys, dtype=object), kind="stable")
    assert (np.diff(got[order]) >= -CDF_ATOL).all()


def test_hpt_cdf_start_offsets(setup):
    """A start offset per row (the model nodes' prefix lengths)."""
    ss, hpt, rng = setup
    start = rng.integers(0, 6, size=len(ss)).astype(np.int32)
    got = _cdf(hpt, ss.bytes, ss.lens, jnp.asarray(start))
    for s in np.unique(start):
        rows = np.flatnonzero(start == s)
        np.testing.assert_allclose(
            got[rows], get_cdf_np64(hpt, ss.take(rows), int(s)), rtol=0,
            atol=CDF_ATOL)


# ---------------------------------------------------------------------------
# model-node slot positions, against the slots the builder filled
# ---------------------------------------------------------------------------

@jax.jit
def _walk_positions(ti, qb, ql, nid):
    """``positions_impl`` at model node ``nid`` per query, with the
    arguments ``walk_terminal``'s model step gives it."""
    return positions_impl(
        ti.cdf_tab, ti.prob_tab, qb, ql, jnp.take(ti.mn_prefix_len, nid),
        jnp.take(ti.mn_alpha, nid), jnp.take(ti.mn_beta, nid),
        jnp.take(ti.mn_slot_cnt, nid), max_steps=ti.cdf_steps)


@pytest.mark.parametrize("dataset", ["email", "url"])
def test_positions_land_in_the_builders_slots(dataset):
    """For every model node and every key the builder placed under it, the
    device position names a slot whose subtree holds that key."""
    keys = sorted(set(load(dataset, 10000, seed=2)))
    b = LITSBuilder()
    b.bulkload(StringSet.from_list(keys), np.arange(len(keys), dtype=np.int64))
    ti = freeze(b)
    pairs = [(nid, eid) for nid in range(b.mn_slot_base.n)
             for eid in b.iter_subtree(make_item(TAG_MNODE, nid))]
    # many model nodes, most below a shared prefix
    assert len({nid for nid, _ in pairs}) >= 20
    assert int(np.asarray(ti.mn_prefix_len).max()) > 0
    nids = np.array([nid for nid, _ in pairs], np.int32)
    qb, ql = pad_queries([b.key_at(eid) for _, eid in pairs], ti.width)
    pos = np.asarray(_walk_positions(ti, jnp.asarray(qb), jnp.asarray(ql),
                                     jnp.asarray(nids)))
    items = b.items.view()
    below = {}          # slot item -> the entry ids of its subtree
    for (nid, eid), p in zip(pairs, pos.tolist()):
        item = int(items[int(b.mn_slot_base.data[nid]) + p])
        if item not in below:
            below[item] = set(b.iter_subtree(item))
        assert eid in below[item], (nid, eid, p)


# ---------------------------------------------------------------------------
# the terminal resolve, against a dict of cnode contents
# ---------------------------------------------------------------------------

CNODE_CAP = 16
N_CNODES = 12
N_ENTRIES = 8          # terminal ENTRY items


@functools.partial(jax.jit, static_argnames=("cnode_cap",))
def _resolve(qb, ql, items, pools, cnode_cap):
    return resolve_terminal(qb, ql, items, *pools, cnode_cap=cnode_cap)


@pytest.mark.parametrize("fill", [1, CNODE_CAP // 2, CNODE_CAP],
                         ids=["one", "half", "full"])
@pytest.mark.parametrize("B", [1, 65, 512])
def test_resolve_terminal_matches_host(B, fill):
    """Hand-built pools: ``N_CNODES`` cnodes of ``fill`` keys each and
    ``N_ENTRIES`` ENTRY terminals.  Queries land on a cnode with one of its
    keys, with another cnode's key or with an absent key; on an ENTRY with
    its key or another; or on EMPTY."""
    rng = np.random.default_rng(B * 31 + fill)
    W = 24
    pool_keys = sorted(set(random_strings(rng, 4 * (N_CNODES * fill + 64),
                                          2, W)))
    rng.shuffle(pool_keys)
    n_stored = N_CNODES * fill + N_ENTRIES
    stored, absent = pool_keys[:n_stored], pool_keys[n_stored:]
    ent_len = np.array([len(k) for k in stored], np.int32)
    ent_off = np.concatenate([[0], np.cumsum(ent_len)[:-1]]).astype(np.int32)
    key_bytes = np.frombuffer(b"".join(stored) + bytes(W + 1), np.uint8)
    qb_all, ql_all = pad_queries(stored, W)
    ch_hash = key_hash16(qb_all, ql_all)[:N_CNODES * fill].astype(np.int32)
    ch_ent = np.arange(N_CNODES * fill, dtype=np.int32)
    cn_base = np.arange(N_CNODES, dtype=np.int32) * fill
    cn_cnt = np.full(N_CNODES, fill, np.int32)
    members = [dict((stored[e], e) for e in range(c * fill, (c + 1) * fill))
               for c in range(N_CNODES)]

    queries, items, want = [], [], []
    for _ in range(B):
        kind = int(rng.integers(0, 6))
        c = int(rng.integers(0, N_CNODES))
        e = N_CNODES * fill + int(rng.integers(0, N_ENTRIES))
        if kind == 0:       # a member of this cnode
            key = stored[c * fill + int(rng.integers(0, fill))]
        elif kind == 1:     # stored, but under another cnode
            key = stored[((c + 1) % N_CNODES) * fill]
        elif kind == 2:     # absent
            key = absent[int(rng.integers(0, len(absent)))]
        elif kind == 3:     # an ENTRY with its own key
            key = stored[e]
        else:               # an ENTRY with another key, or EMPTY
            key = absent[int(rng.integers(0, len(absent)))]
        if kind in (0, 1, 2):
            item, hit = make_item(TAG_CNODE, c), members[c].get(key, -1)
        elif kind in (3, 4):
            item, hit = make_item(TAG_ENTRY, e), (e if key == stored[e] else -1)
        else:
            item, hit = make_item(TAG_EMPTY), -1
        queries.append(key)
        items.append(item)
        want.append(hit)

    qb, ql = pad_queries(queries, W)
    pools = tuple(jnp.asarray(a) for a in (
        cn_base, cn_cnt, ch_hash, ch_ent, key_bytes, ent_off, ent_len))
    found, eid, rounds = _resolve(jnp.asarray(qb), jnp.asarray(ql),
                                  jnp.asarray(np.array(items, np.int32)),
                                  pools, cnode_cap=CNODE_CAP)
    want = np.array(want)
    assert np.asarray(found).tolist() == (want >= 0).tolist()
    assert np.asarray(eid).tolist() == want.tolist()
    # a lane has a candidate on an ENTRY, or on a cnode slot of equal hash;
    # with none in the batch the resolve runs no compare round
    qh = key_hash16(qb, ql).astype(np.int32)
    cand = [item >> 28 == TAG_ENTRY or (
        item >> 28 == TAG_CNODE
        and (ch_hash[cn_base[item & 0xFFFFFFF]:][:fill] == h).any())
        for item, h in zip(items, qh.tolist())]
    assert (int(rounds) == 0) == (not any(cand))
    assert int(rounds) <= CNODE_CAP


# ---------------------------------------------------------------------------
# search, rank and scan against the host's dict and sorted key list
# ---------------------------------------------------------------------------

def _build_index(keys, vals=None, **freeze_kw):
    b = LITSBuilder()
    v = np.asarray(vals if vals is not None else np.arange(len(keys)), np.int64)
    b.bulkload(StringSet.from_list(list(keys)), v)
    return b, freeze(b, **freeze_kw)


def _skewed_prefix_corpus(rng):
    """Heavy shared prefixes -> deep mnode+trie mix (the paper's hard case)."""
    keys = set()
    for grp in (b"app/events/", b"app/users/", b"zz", b"app/", b"a"):
        for _ in range(150):
            keys.add(grp + (b"%05d" % int(rng.integers(0, 4000))))
    keys |= set(random_strings(rng, 200, 2, 20))
    keys = sorted(keys)
    queries = keys + [k + b"!" for k in keys[:100]] + [b"app/", b"app", b"zzz"]
    return keys, queries


def _long_key_corpus(rng):
    """Keys at/near width plus queries LONGER than width (sentinel path)."""
    keys = sorted(set(random_strings(rng, 400, 2, 24)))
    b = LITSBuilder()
    b.bulkload(StringSet.from_list(keys), np.arange(len(keys), dtype=np.int64))
    W = b.width
    queries = keys[:200]
    queries += [k + b"x" * (W - len(k) + 3) for k in keys[:50]]   # > width
    queries += [(k + b"q" * W)[:W] for k in keys[:50]]            # == width
    return keys, queries


def _mixed_corpus(rng):
    keys = sorted(set(random_strings(rng, 600, 2, 18)))
    queries = [bytes(q) for q in rng.permutation(np.array(keys, object))]
    queries += [k[:-1] for k in keys[:80] if len(k) > 1]
    return keys, queries


CORPORA = {"skewed": _skewed_prefix_corpus, "longkey": _long_key_corpus,
           "mixed": _mixed_corpus}


def _values(ti, eid, is_delta):
    lo, hi = lookup_values(ti, eid, is_delta)
    return ((np.asarray(hi).astype(np.int64) << 32)
            | (np.asarray(lo).astype(np.int64) & 0xFFFFFFFF))


def _encode(vals):
    vals = np.asarray(vals, np.int64)
    return (jnp.asarray((vals & 0xFFFFFFFF).astype(np.uint32).view(np.int32)),
            jnp.asarray((vals >> 32).astype(np.int32)))


@jax.jit
def _walk_levels(ti, qb, ql):
    """Per-query levels and loop iterations of the search walk."""
    _item, levels, iters, _model = walk_terminal(
        qb, ql, ti.root_item, ti.items, ti.mn_slot_base, ti.mn_slot_cnt,
        ti.mn_prefix_off, ti.mn_prefix_len, ti.mn_alpha, ti.mn_beta,
        ti.tr_byte, ti.tr_mask, ti.tr_left, ti.tr_right, ti.key_bytes,
        ti.cdf_tab, ti.prob_tab, width=ti.width, max_iters=ti.max_iters,
        cdf_steps=ti.cdf_steps)
    return levels, iters


@pytest.mark.parametrize("corpus", sorted(CORPORA))
def test_search_matches_host(rng, corpus):
    keys, queries = CORPORA[corpus](rng)
    vals = np.arange(len(keys), dtype=np.int64) * 7 - (1 << 33)
    b, ti = _build_index(keys, vals)
    qb, ql = pad_queries(queries, ti.width)
    qb, ql = jnp.asarray(qb), jnp.asarray(ql)
    found, eid, isd, iters, model_iters, rounds = jax.jit(search_impl)(
        ti, qb, ql)
    # ground truth: found iff the query is a stored key, with its value
    want = dict(zip(keys, vals.tolist()))
    present = np.array([q in want for q in queries])
    assert (np.asarray(found) == present).all()
    assert not np.asarray(isd).any()
    got = _values(ti, eid, isd)
    assert all(v == want[q] for q, f, v in zip(queries, present, got.tolist())
               if f)
    # the walk runs the model-node step only on levels where some query
    # sits on a model node: the skewed corpus's deep critbit tails give
    # this batch trie-only levels
    assert 1 <= int(model_iters) <= int(iters) <= ti.max_iters
    # every batch holds a hit, and no lane passes more equal-hash slots
    # than a cnode has
    assert 1 <= int(rounds) <= ti.cnode_cap
    if corpus == "skewed":
        assert int(model_iters) < int(iters)
    # per-query levels: at least one each, and the loop ran as many
    # iterations as the deepest query's walk
    levels, walk_iters = _walk_levels(ti, qb, ql)
    levels = np.asarray(levels)
    assert int(walk_iters) == int(iters)
    assert (levels >= 1).all() and int(levels.max()) == int(iters)


def test_search_with_delta_hits_matches_host(rng):
    """Base keys, fresh delta keys and absent keys in one batch."""
    keys = sorted(set(random_strings(rng, 300, 4, 16)))
    b, ti = _build_index(keys, delta_capacity=128)
    fresh = [b"delta-%04d" % i for i in range(80)]
    qb, ql = pad_queries(fresh, ti.width)
    fresh_vals = np.arange(80, dtype=np.int64) + 11 - (1 << 40)
    ti, ins, _ = insert_batch(ti, jnp.asarray(qb), jnp.asarray(ql),
                              *_encode(fresh_vals))
    assert int(ins.sum()) == 80
    want = dict(zip(keys, range(len(keys))))
    want.update(zip(fresh, fresh_vals.tolist()))
    queries = keys[:100] + fresh + [b"nope-%03d" % i for i in range(30)]
    qb, ql = pad_queries(queries, ti.width)
    found, eid, isd = search_batch(ti, jnp.asarray(qb), jnp.asarray(ql))
    assert np.asarray(found).tolist() == [q in want for q in queries]
    assert np.asarray(isd).tolist() == [q in fresh for q in queries]
    got = _values(ti, eid, isd)
    assert [v for q, v in zip(queries, got.tolist()) if q in want] == \
        [want[q] for q in queries if q in want]


@pytest.mark.parametrize("corpus", sorted(CORPORA))
def test_rank_matches_bisect(rng, corpus):
    import bisect

    keys, queries = CORPORA[corpus](rng)
    b, ti = _build_index(keys)
    qb, ql = pad_queries(queries, ti.width)
    r = np.asarray(rank_batch(ti, jnp.asarray(qb), jnp.asarray(ql)))
    # ground truth for in-width queries (over-width rows carry the length
    # sentinel, whose tie-break intentionally differs from raw bisect)
    checked = 0
    for q, got in zip(queries, r):
        if len(q) <= ti.width:
            assert got == bisect.bisect_left(keys, q), q
            checked += 1
    assert checked >= len(queries) // 2


def test_scan_matches_sorted_keys(rng):
    """Empty delta: every window is the next keys of the sorted list."""
    keys = sorted(set(random_strings(rng, 700, 2, 20)))
    vals = np.arange(len(keys), dtype=np.int64) * 3
    b, ti = _build_index(keys, vals)
    starts = keys[::13] + [k[:2] for k in keys[:40]] + [b"~~~", b"a"]
    qb, ql = pad_queries(starts, ti.width)
    eids, valid, isd = scan_batch(ti, jnp.asarray(qb), jnp.asarray(ql), 11)
    assert not np.asarray(isd).any()  # empty delta: pure frozen stream
    eids, valid = np.asarray(eids), np.asarray(valid)
    for row, start in enumerate(starts):
        got = [b.key_at(int(e)) for e, ok in zip(eids[row], valid[row]) if ok]
        assert got == [k for k in keys if k >= start][:11], start
    want_vals = dict(zip(keys, vals.tolist()))
    got_vals = _values(ti, jnp.asarray(np.maximum(eids, 0)),
                       jnp.zeros(eids.shape, bool))
    assert all(got_vals[r, c] == want_vals[b.key_at(int(eids[r, c]))]
               for r, c in zip(*np.nonzero(valid)))


def test_scan_with_live_delta_matches_host(rng):
    """The scan merges the LIVE delta (inserts + tombstones) into the frozen
    order (DESIGN.md §11): each window is the next keys of the sorted
    merged key list, with their values."""
    from repro.core import delete_batch

    keys = sorted(set(random_strings(rng, 500, 2, 20)))
    vals = np.arange(len(keys), dtype=np.int64) * 5 + 1
    b, ti = _build_index(keys, vals, delta_capacity=256)
    fresh = [b"dd-%03d" % i for i in range(60)] + \
        [keys[7][:-1] + b"\x00", keys[11] + b"!"]
    fresh_vals = np.arange(len(fresh), dtype=np.int64) + 9000
    qb, ql = pad_queries(fresh, ti.width)
    ti, ins, _ = insert_batch(ti, jnp.asarray(qb), jnp.asarray(ql),
                              *_encode(fresh_vals))
    assert np.asarray(ins).all()
    dead = keys[::9][:20] + fresh[::7][:5]          # base + delta tombstones
    qb, ql = pad_queries(dead, ti.width)
    ti, deleted, rej = delete_batch(ti, jnp.asarray(qb), jnp.asarray(ql))
    assert np.asarray(deleted).all() and not np.asarray(rej).any()
    live = dict(zip(keys, vals.tolist()))
    live.update(zip(fresh, fresh_vals.tolist()))
    for k in dead:
        del live[k]
    merged = sorted(live)
    db = np.asarray(ti.db_bytes)
    de_off, de_len = np.asarray(ti.de_off), np.asarray(ti.de_len)

    def key_of(e, d):
        if d:
            return db[de_off[e]:de_off[e] + de_len[e]].tobytes()
        return b.key_at(e)

    starts = keys[::17] + fresh[::5] + dead[::3] + [b"", b"~~~", b"dd-"]
    qb, ql = pad_queries(starts, ti.width)
    qb, ql = jnp.asarray(qb), jnp.asarray(ql)
    saw_delta = False
    for w in (1, 7, 16):
        eids, valid, isd = scan_batch(ti, qb, ql, w)
        got_vals = _values(ti, jnp.maximum(eids, 0), isd)
        eids, valid, isd = (np.asarray(x) for x in (eids, valid, isd))
        saw_delta = saw_delta or bool(isd[valid].any())
        for row, start in enumerate(starts):
            cols = np.flatnonzero(valid[row])
            got = [key_of(int(eids[row, c]), bool(isd[row, c])) for c in cols]
            assert got == [k for k in merged if k >= start][:w], (w, start)
            assert got_vals[row, cols].tolist() == [live[k] for k in got]
    assert saw_delta, "delta entries must appear in the scan"


# ---------------------------------------------------------------------------
# one traversal: neither the config nor the environment selects another
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("how", ["config", "env"])
def test_traversal_is_not_selectable(rng, monkeypatch, how):
    keys = sorted(set(random_strings(rng, 200, 2, 12)))
    if how == "config":
        with pytest.raises(ValueError, match="search_backend"):
            IndexConfig(search_backend="pallas")
        return
    plain = StringIndex.bulk_load(keys, np.arange(len(keys)))
    qb, ql = pad_queries(keys + [b"absent"], plain.ti.width)
    qb, ql = jnp.asarray(qb), jnp.asarray(ql)
    want = [np.asarray(x) for x in search_batch(plain.ti, qb, ql)]
    monkeypatch.setenv("REPRO_SEARCH_BACKEND", "pallas")
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "interpret")
    index = StringIndex.bulk_load(keys, np.arange(len(keys)))
    got = [np.asarray(x) for x in search_batch(index.ti, qb, ql)]
    for g, w in zip(got, want):
        assert (g == w).all()
    assert got[0].tolist() == [True] * len(keys) + [False]
    found, vals = index.get_batch(keys)
    assert found.all() and vals.tolist() == list(range(len(keys)))


# ---------------------------------------------------------------------------
# hash alignment + over-width keys (regression: device/host divergence)
# ---------------------------------------------------------------------------

def test_hash_device_host_bit_identical(rng):
    """strops.hash16 == strings.key_hash16 over the same-width matrix,
    including rows whose true length exceeds the matrix width."""
    W = 20
    ss = StringSet.from_list(random_strings(rng, 256, 1, W), width=W)
    lens = ss.lens.copy()
    lens[::5] = W + 1  # over-width sentinel rows
    dev = np.asarray(hash16(jnp.asarray(ss.bytes), jnp.asarray(lens)))
    host = key_hash16(ss.bytes, lens).astype(np.int32)
    assert (dev == host).all()
    dev32 = np.asarray(hash32(jnp.asarray(ss.bytes), jnp.asarray(lens)))
    assert dev32.dtype == np.uint32 and (dev32 != 0).any()


@pytest.mark.parametrize("width", [8, 43, 300])
def test_packed_queries_round_trip(width):
    """``pack_queries`` -> ``unpack_queries`` in a program gives the key
    bytes zero-padded to ``width`` and each length clipped to the
    ``width + 1`` sentinel: lengths past 255 and zero bytes exactly."""
    rng = np.random.default_rng(width)
    lens = list(range(width + 3)) + [width + 300, 70000]
    keys = [rng.integers(0, 4, n).astype(np.uint8).tobytes() for n in lens]
    keys += [b"\x00" * width, b"a\x00"]
    qb, ql = jax.jit(unpack_queries, static_argnums=1)(
        jnp.asarray(pack_queries(keys, width)), width)
    want = np.zeros((len(keys), width), np.uint8)
    for i, k in enumerate(keys):
        want[i, : min(len(k), width)] = np.frombuffer(k[:width], np.uint8)
    assert np.asarray(qb).tolist() == want.tolist()
    want_lens = [min(len(k), width + 1) for k in keys]
    assert np.asarray(ql).tolist() == want_lens
    host_qb, host_ql = pad_queries(keys, width)
    assert host_qb.tolist() == want.tolist()
    assert host_ql.dtype == np.int32 and host_ql.tolist() == want_lens


def test_insert_rejects_overwidth_keys(rng):
    """Keys > width must be rejected, not stored truncated (regression:
    truncated aliases used to be insertable, made two distinct long keys
    'equal', and corrupted merge_delta's byte replay)."""
    keys = sorted(set(random_strings(rng, 200, 2, 12)))
    b, ti = _build_index(keys, delta_capacity=64)
    W = ti.width
    long_a = b"L" * (W + 4)
    long_b = b"L" * W + b"diff"  # same first W bytes, different key
    qb, ql = pad_queries([long_a, long_b], W)
    assert (ql == W + 1).all()  # over-width sentinel
    z = jnp.zeros(2, jnp.int32)
    ti2, ins, upd = insert_batch(ti, jnp.asarray(qb), jnp.asarray(ql), z, z)
    assert int(ins.sum()) == 0 and int(upd.sum()) == 0
    assert not bool(ti2.delta_overflow)  # rejection is not pool overflow
    f, _, _ = search_batch(ti2, jnp.asarray(qb), jnp.asarray(ql))
    assert not bool(f.any())
    # merge replay stays clean after the rejected attempts
    ti3 = merge_delta(b, ti2)
    qb0, ql0 = pad_queries(keys, W)
    f0, _, _ = search_batch(ti3, jnp.asarray(qb0), jnp.asarray(ql0))
    assert bool(f0.all())


def test_insert_near_full_pool(rng):
    """Byte-pool gate uses the true key length, not the padded width
    (regression: inserts that fit used to be rejected near a full pool)."""
    keys = [b"base-a", b"base-b", b"base-c"]
    b = LITSBuilder()
    b.bulkload(StringSet.from_list(keys), np.arange(3, dtype=np.int64), width=16)
    ti = freeze(b, delta_capacity=8, delta_bytes=20)
    new = [b"dk%02d" % i for i in range(5)]  # 5 x 4B == exactly dbcap
    qb, ql = pad_queries(new, ti.width)
    v = jnp.arange(5, dtype=jnp.int32)
    ti2, ins, _ = insert_batch(ti, jnp.asarray(qb), jnp.asarray(ql), v, v)
    assert int(ins.sum()) == 5, "all five 4-byte keys fit in the 20-byte pool"
    assert not bool(ti2.delta_overflow)
    f, e, d = search_batch(ti2, jnp.asarray(qb), jnp.asarray(ql))
    assert bool(f.all()) and int(d.sum()) == 5
    lo, _ = lookup_values(ti2, e, d)
    assert (np.asarray(lo) == np.arange(5)).all()
    # the 6th insert genuinely overflows
    qb6, ql6 = pad_queries([b"dk99"], ti.width)
    ti3, ins6, _ = insert_batch(ti2, jnp.asarray(qb6), jnp.asarray(ql6),
                                v[:1], v[:1])
    assert int(ins6.sum()) == 0 and bool(ti3.delta_overflow)
    # earlier entries survive the full pool intact (scatter write, no clamp)
    f2, _, _ = search_batch(ti3, jnp.asarray(qb), jnp.asarray(ql))
    assert bool(f2.all())
