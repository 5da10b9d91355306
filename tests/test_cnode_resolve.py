"""Terminal resolve under 16-bit h-pointer hash collisions.

``resolve_terminal`` (``repro.core.walk``) scans a cnode's 16-bit hashes
first and compares key bytes only for hash-matching candidates, one round
at a time.  These tests bulk-load keys whose hashes collide into one
cnode and check that every key still resolves exactly:

* each colliding key returns its own value, also one that sits behind an
  earlier slot with the same hash;
* an absent key with the same hash as stored slots misses, and so does an
  over-width query whose first ``width`` bytes hash like them;
* the same holds on the index after ``save`` and ``StringIndex.load``,
  with the entry ids of the built index;
* ``StringIndex.resolve_rounds`` counts one round for a batch with no
  collision and more where a lane passes an equal-hash slot.
"""
import collections
import types

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import pad_queries, search_batch
from repro.core.builder import TAG_CNODE
from repro.core.strings import key_hash16, random_strings
from repro.core.tensor_index import _traverse
from repro.core.walk import item_payload, item_tag
from repro.index import IndexConfig, StringIndex

PREFIX = b"cluster/" + b"shared-long-prefix/" * 2


def _hash16(keys, width):
    qb, ql = pad_queries(keys, width)
    return key_hash16(qb, ql).tolist()


def _colliding(n_same=4, n_other=6):
    """``n_same`` suffixes of PREFIX that share one 16-bit hash, in key
    order, and ``n_other`` more whose hashes differ from each other's and
    from theirs."""
    cands = [PREFIX + b"%07d" % i for i in range(60000)]
    width = len(cands[0])
    by_hash = collections.defaultdict(list)
    for k, h in zip(cands, _hash16(cands, width)):
        by_hash[h].append(k)
    same = next(ks for ks in by_hash.values() if len(ks) >= n_same)[:n_same]
    other = [ks[0] for ks in by_hash.values() if len(ks) == 1][:n_other]
    return sorted(same), other, width


@pytest.fixture(scope="module")
def c():
    """The index and the key groups: ``stored`` three equal-hash keys,
    ``absent`` a fourth left out; ``other`` stored keys of the cluster
    with hashes of their own, ``unhashed`` one left out whose hash no
    stored key has."""
    same, other, width = _colliding()
    background = sorted(set(random_strings(np.random.default_rng(5), 300,
                                           2, 20)))
    keys = sorted(set(background) | set(same[:3]) | set(other[:-1]))
    vals = np.arange(len(keys), dtype=np.int64) * 5 + 3
    idx = StringIndex.bulk_load(
        keys, vals, IndexConfig(width=width, auto_merge_threshold=None))
    return types.SimpleNamespace(
        idx=idx, ref=dict(zip(keys, vals.tolist())), width=width,
        stored=same[:3], absent=same[3], other=other[:-1],
        unhashed=other[-1], background=background)


def _terminals(idx, keys):
    qb, ql = pad_queries(keys, idx.ti.width)
    item, _, _ = _traverse(idx.ti, jnp.asarray(qb), jnp.asarray(ql))
    item = np.asarray(item)
    return np.asarray(item_tag(item)), np.asarray(item_payload(item))


def test_colliding_keys_share_one_cnode(c):
    assert len(set(_hash16(c.stored + [c.absent], c.width))) == 1
    probe = c.stored + [c.absent] + c.other + [c.unhashed]
    tag, pay = _terminals(c.idx, probe)
    assert (tag == TAG_CNODE).all() and len(set(pay.tolist())) == 1


def test_every_colliding_key_finds_its_own_value(c):
    over = c.stored[0] + b"!"          # hashes like stored[0] at the width
    assert len(over) > c.width
    queries = (c.stored + c.other + [c.absent, over, c.unhashed]
               + c.background[:40])
    found, vals = c.idx.get_batch(queries)
    assert found.tolist() == [q in c.ref for q in queries]
    assert vals.tolist() == [c.ref.get(q, 0) for q in queries]
    assert not {c.absent, over, c.unhashed} & set(c.ref)


def test_collisions_resolve_on_a_loaded_index(c, as_form):
    queries = (c.stored[::-1] + [c.absent, c.stored[1] + b"xyz", c.unhashed]
               + c.other + c.background[::7])
    loaded = as_form(c.idx, "loaded")
    qb, ql = pad_queries(queries, loaded.ti.width)
    qb, ql = jnp.asarray(qb), jnp.asarray(ql)
    f_b, e_b, _ = search_batch(c.idx.ti, qb, ql)
    f_l, e_l, _ = search_batch(loaded.ti, qb, ql)
    assert np.asarray(f_l).tolist() == [q in c.ref for q in queries]
    assert (np.asarray(e_l) == np.asarray(e_b)).all()
    assert (np.asarray(f_l) == np.asarray(f_b)).all()
    found, vals = loaded.get_batch(queries)
    assert vals.tolist() == [c.ref.get(q, 0) for q in queries]


@pytest.mark.parametrize("pick,rounds", [
    (lambda c: c.other + c.stored[:1], 1),     # first of the equal hashes
    (lambda c: c.background[:30], 1),          # no cluster key at all
    (lambda c: c.other + c.stored[1:2], 2),    # behind one equal-hash slot
    (lambda c: c.stored[2:3] + c.other, 3),    # behind two
    (lambda c: [c.absent] + c.background[:5], 3),  # absent: all three
    (lambda c: [c.unhashed], 0),               # a cnode, no hash match
], ids=["first-of-equal", "background", "second", "third", "absent",
        "no-candidate"])
def test_resolve_rounds_count_collisions(c, pick, rounds):
    queries = pick(c)
    r0, s0 = c.idx.resolve_rounds, c.idx.host_syncs
    found, _ = c.idx.get_batch(queries)
    assert c.idx.host_syncs - s0 == 1
    assert c.idx.resolve_rounds - r0 == rounds
    assert found.tolist() == [q in c.ref for q in queries]
