"""Per-kernel Pallas (interpret) vs ref.py oracle — shape/dtype sweeps."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import StringSet, build_hpt
from repro.core.strings import random_strings
from repro.kernels import ops, ref


@pytest.fixture(scope="module")
def setup():
    rng = np.random.default_rng(11)
    keys = random_strings(rng, 700, 1, 40)
    ss = StringSet.from_list(keys, width=48)
    hpt = build_hpt(ss, rows=256, cols=128)
    return ss, jnp.asarray(hpt.cdf_tab), jnp.asarray(hpt.prob_tab), rng


@pytest.mark.parametrize("variant", ["gather", "onehot"])
@pytest.mark.parametrize("bsz,width", [(1, 8), (7, 16), (64, 48), (300, 33)])
def test_hpt_cdf_matches_ref(setup, variant, bsz, width):
    ss, cdf_tab, prob_tab, rng = setup
    sub = ss.take(np.arange(bsz) % len(ss)).pad_to(max(width, ss.width))
    qb = jnp.asarray(sub.bytes[:, :width] if width < sub.width else sub.bytes)
    ql = jnp.asarray(np.minimum(sub.lens, width))
    out = ops.hpt_cdf(qb, ql, 0, cdf_tab=cdf_tab, prob_tab=prob_tab,
                      variant=variant, block_b=64)
    want = ref.hpt_cdf_ref(qb, ql, 0, cdf_tab, prob_tab)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-6, atol=1e-7)


@pytest.mark.parametrize("rows", [64, 1024])
def test_hpt_cdf_rows_sweep(setup, rows):
    ss, _, _, rng = setup
    keys = random_strings(rng, 128, 1, 24)
    s2 = StringSet.from_list(keys, width=32)
    hpt = build_hpt(s2, rows=rows, cols=128)
    cdf_tab, prob_tab = jnp.asarray(hpt.cdf_tab), jnp.asarray(hpt.prob_tab)
    qb, ql = jnp.asarray(s2.bytes), jnp.asarray(s2.lens)
    out = ops.hpt_cdf(qb, ql, 0, cdf_tab=cdf_tab, prob_tab=prob_tab)
    want = ref.hpt_cdf_ref(qb, ql, 0, cdf_tab, prob_tab)
    assert (np.asarray(out) == np.asarray(want)).all()  # bit-exact gather path


def test_hpt_cdf_start_offsets(setup):
    ss, cdf_tab, prob_tab, rng = setup
    qb, ql = jnp.asarray(ss.bytes), jnp.asarray(ss.lens)
    start = jnp.asarray(rng.integers(0, 6, size=len(ss)), jnp.int32)
    out = ops.hpt_cdf(qb, ql, start, cdf_tab=cdf_tab, prob_tab=prob_tab)
    want = ref.hpt_cdf_ref(qb, ql, start, cdf_tab, prob_tab)
    assert (np.asarray(out) == np.asarray(want)).all()


def test_hpt_locate_matches_ref(setup):
    ss, cdf_tab, prob_tab, rng = setup
    B = len(ss)
    qb, ql = jnp.asarray(ss.bytes), jnp.asarray(ss.lens)
    alpha = jnp.asarray(rng.uniform(1, 500, B), jnp.float32)
    beta = jnp.asarray(rng.uniform(0, 4, B), jnp.float32)
    ns = jnp.asarray(rng.integers(8, 4096, B), jnp.int32)
    start = jnp.asarray(rng.integers(0, 4, B), jnp.int32)
    out = ops.hpt_locate(qb, ql, start, alpha, beta, ns, cdf_tab=cdf_tab, prob_tab=prob_tab)
    want = ref.hpt_locate_ref(qb, ql, start, alpha, beta, ns, cdf_tab, prob_tab)
    assert (np.asarray(out) == np.asarray(want)).all()
    assert (np.asarray(out) >= 1).all()
    assert (np.asarray(out) <= np.asarray(ns) - 2).all()


@pytest.mark.parametrize("K", [8, 16, 32])
@pytest.mark.parametrize("B", [1, 65, 512])
def test_cnode_probe_matches_ref(B, K):
    rng = np.random.default_rng(B * 31 + K)
    h = rng.integers(0, 1 << 16, size=(B, K)).astype(np.int32)
    qh = np.where(rng.random(B) < 0.6, h[np.arange(B), rng.integers(0, K, B)],
                  rng.integers(0, 1 << 16, B)).astype(np.int32)
    cnt = rng.integers(0, K + 1, B).astype(np.int32)
    frm = rng.integers(0, 3, B).astype(np.int32)
    out = ops.cnode_probe(jnp.asarray(h), jnp.asarray(qh), jnp.asarray(cnt), jnp.asarray(frm))
    want = ref.cnode_probe_ref(jnp.asarray(h), jnp.asarray(qh), jnp.asarray(cnt), jnp.asarray(frm))
    assert (np.asarray(out) == np.asarray(want)).all()


def test_kernel_matches_index_positions(setup):
    """Kernel-computed locate == the canonical jnp path used by the index."""
    from repro.core.hpt import positions_jnp

    ss, cdf_tab, prob_tab, rng = setup
    qb, ql = jnp.asarray(ss.bytes), jnp.asarray(ss.lens)
    B = len(ss)
    alpha, beta = jnp.float32(321.7), jnp.float32(1.0)
    m = jnp.int32(1024)
    kpos = ops.hpt_locate(qb, ql, 0, jnp.full((B,), alpha), jnp.full((B,), beta),
                          jnp.full((B,), m), cdf_tab=cdf_tab, prob_tab=prob_tab)
    jpos = positions_jnp(cdf_tab, prob_tab, qb, ql, 0, alpha, beta, m)
    assert (np.asarray(kpos) == np.asarray(jpos)).all()


# ---------------------------------------------------------------------------
# fused traversal engine: jnp vs pallas backend bit-identity (DESIGN.md §7)
# ---------------------------------------------------------------------------

from repro.core import (  # noqa: E402
    LITSBuilder, freeze, insert_batch, lookup_values, merge_delta,
    pad_queries, rank_batch, resolve_search_backend, scan_batch, search_batch,
)
from repro.core.strings import key_hash16  # noqa: E402
from repro.kernels.strops import hash16, hash32  # noqa: E402


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


@pytest.mark.parametrize("corpus", ["skewed", "longkey", "mixed"])
def test_backend_bit_identical(rng, corpus):
    keys, queries = {
        "skewed": _skewed_prefix_corpus,
        "longkey": _long_key_corpus,
        "mixed": _mixed_corpus,
    }[corpus](rng)
    b, ti = _build_index(keys)
    qb, ql = pad_queries(queries, ti.width)
    qb, ql = jnp.asarray(qb), jnp.asarray(ql)
    f_j, e_j, d_j = search_batch(ti, qb, ql, backend="jnp")
    f_p, e_p, d_p = search_batch(ti, qb, ql, backend="pallas")
    assert (np.asarray(f_j) == np.asarray(f_p)).all()
    assert (np.asarray(e_j) == np.asarray(e_p)).all()
    assert (np.asarray(d_j) == np.asarray(d_p)).all()
    # ground truth: found iff the query is a stored key
    present = np.array([q in set(keys) for q in queries])
    assert (np.asarray(f_j) == present).all()


def test_backend_bit_identical_with_delta_hits(rng):
    """Delta-buffer hits must agree across backends (delta probe is shared)."""
    keys = sorted(set(random_strings(rng, 300, 4, 16)))
    b, ti = _build_index(keys, delta_capacity=128)
    fresh = [b"delta-%04d" % i for i in range(80)]
    qb, ql = pad_queries(fresh, ti.width)
    vals = np.arange(80, dtype=np.int64) + 11
    ti, ins, _ = insert_batch(
        ti, jnp.asarray(qb), jnp.asarray(ql),
        jnp.asarray((vals & 0xFFFFFFFF).astype(np.uint32).view(np.int32)),
        jnp.asarray((vals >> 32).astype(np.int32)))
    assert int(ins.sum()) == 80
    queries = keys[:100] + fresh + [b"nope-%03d" % i for i in range(30)]
    qb, ql = pad_queries(queries, ti.width)
    qb, ql = jnp.asarray(qb), jnp.asarray(ql)
    out_j = search_batch(ti, qb, ql, backend="jnp")
    out_p = search_batch(ti, qb, ql, backend="pallas")
    for a, c in zip(out_j, out_p):
        assert (np.asarray(a) == np.asarray(c)).all()
    assert int(out_j[2].sum()) == 80  # exactly the delta keys


@pytest.mark.parametrize("corpus", ["skewed", "longkey", "mixed"])
def test_rank_backend_bit_identical(rng, corpus):
    """Fused Pallas rank == jnp reference (shared core.walk.rank_sorted)."""
    import bisect

    keys, queries = {
        "skewed": _skewed_prefix_corpus,
        "longkey": _long_key_corpus,
        "mixed": _mixed_corpus,
    }[corpus](rng)
    b, ti = _build_index(keys)
    qb, ql = pad_queries(queries, ti.width)
    qb, ql = jnp.asarray(qb), jnp.asarray(ql)
    r_j = np.asarray(rank_batch(ti, qb, ql, backend="jnp"))
    r_p = np.asarray(rank_batch(ti, qb, ql, backend="pallas"))
    assert (r_j == r_p).all()
    # ground truth for in-width queries (over-width rows carry the length
    # sentinel, whose tie-break intentionally differs from raw bisect)
    for q, got in zip(queries, r_j):
        if len(q) <= ti.width:
            assert got == bisect.bisect_left(keys, q), q


def test_scan_backend_bit_identical(rng):
    """scan_batch honors the backend and both engines agree bit-for-bit."""
    keys = sorted(set(random_strings(rng, 700, 2, 20)))
    b, ti = _build_index(keys)
    starts = keys[::13] + [k[:2] for k in keys[:40]] + [b"~~~", b"a"]
    qb, ql = pad_queries(starts, ti.width)
    qb, ql = jnp.asarray(qb), jnp.asarray(ql)
    e_j, v_j, d_j = scan_batch(ti, qb, ql, 11, backend="jnp")
    e_p, v_p, d_p = scan_batch(ti, qb, ql, 11, backend="pallas")
    assert (np.asarray(e_j) == np.asarray(e_p)).all()
    assert (np.asarray(v_j) == np.asarray(v_p)).all()
    assert (np.asarray(d_j) == np.asarray(d_p)).all()
    assert not np.asarray(d_j).any()  # empty delta: pure frozen stream
    # oracle: first window of >= start in sorted order
    got0 = [b.key_at(int(e)) for e, ok in
            zip(np.asarray(e_j)[0], np.asarray(v_j)[0]) if ok]
    assert got0 == [k for k in keys if k >= starts[0]][:11]


def test_scan_backend_bit_identical_with_live_delta(rng):
    """The fused scan kernel merges the LIVE delta (inserts + tombstones)
    bit-identically to the jnp reference (DESIGN.md §11)."""
    from repro.core import delete_batch

    keys = sorted(set(random_strings(rng, 500, 2, 20)))
    b, ti = _build_index(keys, delta_capacity=256)
    fresh = [b"dd-%03d" % i for i in range(60)] + \
        [keys[7][:-1] + b"\x00", keys[11] + b"!"]
    qb, ql = pad_queries(fresh, ti.width)
    z = jnp.zeros(len(fresh), jnp.int32)
    ti, ins, _ = insert_batch(ti, jnp.asarray(qb), jnp.asarray(ql), z + 3, z)
    assert np.asarray(ins).all()
    dead = keys[::9][:20] + fresh[::7][:5]          # base + delta tombstones
    qb, ql = pad_queries(dead, ti.width)
    ti, deleted, rej = delete_batch(ti, jnp.asarray(qb), jnp.asarray(ql))
    assert np.asarray(deleted).all() and not np.asarray(rej).any()
    starts = keys[::17] + fresh[::5] + dead[::3] + [b"", b"~~~", b"dd-"]
    qb, ql = pad_queries(starts, ti.width)
    qb, ql = jnp.asarray(qb), jnp.asarray(ql)
    for w in (1, 7, 16):
        e_j, v_j, d_j = scan_batch(ti, qb, ql, w, backend="jnp")
        e_p, v_p, d_p = scan_batch(ti, qb, ql, w, backend="pallas")
        assert (np.asarray(e_j) == np.asarray(e_p)).all()
        assert (np.asarray(v_j) == np.asarray(v_p)).all()
        assert (np.asarray(d_j) == np.asarray(d_p)).all()
    assert np.asarray(d_j).any(), "delta entries must appear in the scan"


def test_fused_levels_counter(rng):
    """Early-exit bookkeeping: per-query traversal depth is well-formed."""
    keys = sorted(set(random_strings(rng, 500, 2, 16)))
    b, ti = _build_index(keys)
    qb, ql = pad_queries(keys, ti.width)
    found, eid, levels = ops.fused_search(ti, jnp.asarray(qb), jnp.asarray(ql),
                                          interpret=True)
    lv = np.asarray(levels)
    assert (lv >= 1).all() and (lv <= ti.max_iters).all()
    assert bool(np.asarray(found).all())


# ---------------------------------------------------------------------------
# backend resolution + interpret caching / env overrides
# ---------------------------------------------------------------------------

def test_resolve_backend_env(monkeypatch):
    assert resolve_search_backend("pallas") == "pallas"
    monkeypatch.delenv("REPRO_SEARCH_BACKEND", raising=False)
    assert resolve_search_backend(None) == "jnp"
    monkeypatch.setenv("REPRO_SEARCH_BACKEND", "pallas")
    assert resolve_search_backend(None) == "pallas"
    with pytest.raises(ValueError):
        resolve_search_backend("avx512")


def test_interpret_default_cached(monkeypatch):
    ops._interpret_default.cache_clear()
    try:
        monkeypatch.setenv("REPRO_KERNEL_BACKEND", "native")
        assert ops._interpret_default() is False
        # cached: env change without cache_clear is ignored (once per process)
        monkeypatch.setenv("REPRO_KERNEL_BACKEND", "interpret")
        assert ops._interpret_default() is False
        ops._interpret_default.cache_clear()
        assert ops._interpret_default() is True
        ops._interpret_default.cache_clear()
        monkeypatch.setenv("REPRO_KERNEL_BACKEND", "bogus")
        with pytest.raises(ValueError):
            ops._interpret_default()
    finally:
        ops._interpret_default.cache_clear()


def test_interpret_refused_on_tpu_backend(monkeypatch):
    """On a TPU backend the Pallas interpreter is refused however it is
    requested: env var, explicit kernel backend, launcher config, or a raw
    ``interpret=True`` handed to a kernel wrapper."""
    from repro.configs.base import IndexRuntimeConfig

    monkeypatch.setattr(ops.jax, "default_backend", lambda: "tpu")
    ops._interpret_default.cache_clear()
    try:
        monkeypatch.delenv("REPRO_KERNEL_BACKEND", raising=False)
        assert ops._interpret_default() is False
        assert ops.resolve_interpret("native") is False
        with pytest.raises(RuntimeError, match="interpret"):
            ops.resolve_interpret("interpret")
        with pytest.raises(RuntimeError, match="interpret"):
            ops._resolve(True)
        with pytest.raises(RuntimeError, match="interpret"):
            IndexRuntimeConfig(kernel_mode="interpret").validate()
        ops._interpret_default.cache_clear()
        monkeypatch.setenv("REPRO_KERNEL_BACKEND", "interpret")
        with pytest.raises(RuntimeError, match="interpret"):
            ops._interpret_default()
    finally:
        ops._interpret_default.cache_clear()


def test_env_selected_pallas_end_to_end(rng, monkeypatch):
    """REPRO_SEARCH_BACKEND=pallas drives the whole search path."""
    keys = sorted(set(random_strings(rng, 200, 2, 12)))
    _, ti = _build_index(keys)
    qb, ql = pad_queries(keys, ti.width)
    monkeypatch.setenv("REPRO_SEARCH_BACKEND", "pallas")
    f, _, _ = search_batch(ti, jnp.asarray(qb), jnp.asarray(ql))
    assert bool(f.all())


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
    for backend in ("jnp", "pallas"):
        f, _, _ = search_batch(ti2, jnp.asarray(qb), jnp.asarray(ql),
                               backend=backend)
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
