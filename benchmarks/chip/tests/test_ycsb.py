"""The traffic generator: YCSB's scrambled zipfian, seeded and stratified."""
import numpy as np
import pytest

from lits_bench import ycsb

TRAFFIC_C = {"mix": {"read": 1.0}, "rate_ops_per_s": 2000,
             "request_distribution": "scrambled_zipfian"}


def test_same_seed_same_stream_and_another_seed_another_order():
    a = ycsb.make_stream(TRAFFIC_C, 10_000, 2.0, 2**40 + 7)
    b = ycsb.make_stream(TRAFFIC_C, 10_000, 2.0, 2**40 + 7)
    c = ycsb.make_stream(TRAFFIC_C, 10_000, 2.0, 8)
    for f in ("due", "item"):
        assert np.array_equal(getattr(a, f), getattr(b, f))
    assert not np.array_equal(a.item, c.item)
    # the same work in another order: the number of ops and the span of
    # arrivals do not depend on the seed
    assert len(a) == len(c) == 4000
    assert abs(a.due[-1] - c.due[-1]) < 1e-9
    assert np.all(np.diff(a.due) >= 0)
    assert a.item.min() >= 0 and a.item.max() < 10_000


def test_only_reads_are_made():
    with pytest.raises(ValueError):
        ycsb.make_stream(dict(TRAFFIC_C, mix={"read": 0.5, "update": 0.5}),
                         10_000, 1.0, 1)


def test_hottest_key_share_matches_the_zipfian_constant():
    """Rank 0 of a zipfian over 10^10 items with constant 0.99 draws
    1/zeta(10^10, 0.99) of the ops; scrambling moves it to one key."""
    rng = np.random.default_rng(3)
    items = ycsb.scrambled_zipfian(ycsb.stratified_uniform(rng, 400_000),
                                   1_000_000)
    share = np.bincount(items).max() / items.size
    assert abs(share - 1 / _zeta(ycsb.ZIPF_ITEMS, 0.99)) < 0.001
    # YCSB's precomputed ZETAN is that zeta, and a neighbouring constant
    # gives a share the test tells apart
    assert abs(ycsb.ZETAN / _zeta(ycsb.ZIPF_ITEMS, 0.99) - 1) < 1e-6
    assert abs(share - 1 / _zeta(ycsb.ZIPF_ITEMS, 0.9)) > 0.01


def _zeta(n: int, theta: float, m: int = 100_000) -> float:
    """sum_{i=1..n} i^-theta: the first m terms, then Euler-Maclaurin."""
    head = float(np.sum(np.arange(1, m, dtype=np.float64) ** -theta))
    tail = (n ** (1 - theta) - m ** (1 - theta)) / (1 - theta)
    return head + tail + (m ** -theta + n ** -theta) / 2 \
        + theta * m ** (-theta - 1) / 12


def test_zipfian_ranks_follow_the_closed_form():
    u = np.array([0.0, 0.5 / ycsb.ZETAN, 1.2 / ycsb.ZETAN, 0.999999])
    r = ycsb.zipfian_ranks(u)
    assert r[0] == 0 and r[1] == 0 and r[2] == 1
    assert r[3] > 10**6


def test_fnvhash64_is_ycsbs():
    """Utils.fnvhash64: FNV-1a over the 8 low-to-high bytes, then abs."""
    def ref(v):
        h = 0xCBF29CE484222325
        for _ in range(8):
            h ^= v & 0xFF
            v >>= 8
            h = (h * 1099511628211) & (2**64 - 1)
        return abs(h - 2**64 if h >= 2**63 else h)

    vals = [0, 1, 2, 255, 256, 10**9 + 7, 2**40 + 3]
    assert ycsb.fnvhash64(np.array(vals)).tolist() == [ref(v) for v in vals]
