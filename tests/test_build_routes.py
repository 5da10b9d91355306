"""The bulk build's counts of what it chose: sub-tries by the rule that
built them, and keys an mnode models past the HPT CDF's step cap.  URL
keys have suffixes longer than the cap, so the CDF reads only their first
``MAX_CDF_STEPS`` bytes; email keys (30 bytes at most) never reach it."""
import numpy as np
import pytest

from repro.core.builder import LITSBuilder, LITSConfig
from repro.core.hpt import MAX_CDF_STEPS
from repro.core.strings import StringSet
from repro.data import synthetic
from repro.index import IndexConfig, StringIndex

N_KEYS = 5_000


@pytest.fixture(scope="module", params=["email", "url"])
def built(request):
    keys = synthetic.load(request.param, N_KEYS, seed=7)
    index = StringIndex.bulk_load(
        keys, config=IndexConfig(delta_capacity=64, search_backend="jnp"))
    return request.param, keys, index


def test_which_rules_the_keys_reach(built):
    dataset, _keys, index = built
    counts = index.build_counts
    assert counts["subtries"]["pmss"] >= 1
    if dataset == "url":
        assert counts["subtries"]["heavy_slot"] >= 1
        assert counts["keys_past_cdf_cap"] >= 1
        assert index._builder.max_suffix_len > MAX_CDF_STEPS
    else:
        assert counts["keys_past_cdf_cap"] == 0
        assert index._builder.max_suffix_len <= MAX_CDF_STEPS


def test_counts_are_a_copy_and_gone_after_load(built, tmp_path):
    _dataset, keys, index = built
    index.build_counts["subtries"]["pmss"] = -1
    assert index.build_counts["subtries"]["pmss"] >= 1
    path = str(tmp_path / "snap.npz")
    index.save(path)
    loaded = StringIndex.load(path, index.config)
    assert loaded.build_counts is None
    assert loaded.get_batch(keys[:32])[0].all()


@pytest.mark.parametrize("length,past", [(40, False), (100, True)])
def test_every_key_past_the_cap_is_counted_at_the_root(length, past):
    rng = np.random.default_rng(length)
    n = 600
    mat = rng.integers(97, 123, size=(n, length), dtype=np.uint8)
    keys = sorted({bytes(r) for r in mat})
    b = LITSBuilder()
    b.bulkload(StringSet.from_list(keys))
    # the root is an mnode with an empty prefix: it models every key whole
    assert (b.keys_past_cdf_cap >= len(keys)) == past
    assert (b.keys_past_cdf_cap == 0) == (not past)


def test_the_lit_ablation_builds_no_pmss_subtrie():
    keys = synthetic.load("url", 2_000, seed=3)
    b = LITSBuilder(config=LITSConfig(use_subtrie=False))
    b.bulkload(StringSet.from_list(keys))
    assert b.subtries["pmss"] == 0
    assert sum(b.subtries.values()) >= 1
