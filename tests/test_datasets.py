"""Synthetic dataset generators: Table 1 statistics + Fig. 7 GPKL targeting."""
import numpy as np
import pytest

from repro.core import StringSet
from repro.core.gpkl import gpkl
from repro.core.strings import sort_order
from repro.data.synthetic import DATASETS, gpkl_targeted, load

# (min_len floor, avg range, max_len cap) loosely tracking paper Table 1
EXPECT = {
    "email": (10, (18, 34), 64),
    "idcard": (18, (18, 18.01), 18),
    "phone": (10, (11, 24), 24),
    "rands": (2, (20, 40), 61),
    "url": (12, (40, 110), 255),
    "wiki": (2, (8, 26), 64),
    "address": (4, (16, 34), 64),
    "reddit": (2, (7, 18), 40),
    "dblp": (10, (50, 110), 255),
}


@pytest.mark.parametrize("name", sorted(DATASETS))
def test_generators_unique_nulfree_ascii(name):
    keys = load(name, 500, seed=0)
    assert len(keys) >= 490
    assert len(set(keys)) == len(keys) or name in ("imdb", "geoname")
    for k in keys[:100]:
        assert 0 not in k
        assert all(c < 128 for c in k)


@pytest.mark.parametrize("name", sorted(EXPECT))
def test_generator_length_stats(name):
    keys = load(name, 1000, seed=1)
    lens = np.array([len(k) for k in keys])
    lo, (alo, ahi), hi = EXPECT[name]
    assert lens.min() >= lo - 2, (name, lens.min())
    assert alo <= lens.mean() <= ahi, (name, lens.mean())
    assert lens.max() <= hi + 4, (name, lens.max())


def test_idcard_structure():
    keys = load("idcard", 200, seed=2)
    for k in keys[:50]:
        assert len(k) == 18 and k.isdigit()
        y = int(k[6:10])
        assert 1950 <= y <= 2010


def test_gpkl_targeted_fig7_generator():
    """The paper's Fig. 7 iterative procedure raises GPKL toward the target."""
    rng = np.random.default_rng(0)
    keys0 = gpkl_targeted(rng, 400, target_gpkl=0.0, max_rounds=0)
    g0 = gpkl(StringSet.from_list(keys0, width=255))
    rng = np.random.default_rng(0)
    keys1 = gpkl_targeted(rng, 400, target_gpkl=g0 + 2.0, max_rounds=400)
    g1 = gpkl(StringSet.from_list(keys1, width=255))
    assert g1 > g0 + 1.0, (g0, g1)


def test_gpkl_direct_generator_hits_target():
    from benchmarks.fig7_pmss import gpkl_direct

    rng = np.random.default_rng(1)
    for target in (5.0, 11.0, 17.0):
        keys = gpkl_direct(rng, 1024, target)
        ss = StringSet.from_list(keys)
        g = gpkl(ss.take(sort_order(ss)))
        assert abs(g - target) < 2.5, (target, g)


def test_load_is_reproducible_across_processes():
    """The same (name, seed) yields the same keys in the same order in every
    process: the generator seed and the key order use no salted hash."""
    assert load("email", 8, seed=0)[:4] == [
        b"eri.razhrbmq13@yahoo.com",
        b"wiwfyxw.qikz43@hotmail.com",
        b"nxvu.kjwfs88@gmail.com",
        b"qeamjwz.uqozmrui02@hotmail.com",
    ]
