"""HPT model: CDF recursion, monotonicity property, Thm 3.1 error bound."""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.hpt import (
    HPT, build_hpt, get_cdf_jnp, get_cdf_np64, conditional_prob_error, uniform_hpt,
)
from repro.core.strings import StringSet, random_strings

key_st = st.lists(st.integers(1, 127), min_size=1, max_size=20).map(bytes)


@pytest.fixture(scope="module")
def trained():
    rng = np.random.default_rng(3)
    keys = random_strings(rng, 3000, 2, 24)
    ss = StringSet.from_list(keys, width=32)
    return build_hpt(ss, rows=256, cols=128)


def test_tables_are_distributions(trained):
    prob = trained.prob_tab.astype(np.float64)
    cdf = trained.cdf_tab.astype(np.float64)
    assert np.allclose(prob.sum(axis=1), 1.0, atol=1e-3)
    assert (np.diff(cdf, axis=1) >= -1e-7).all()
    # cdf is the exclusive cumsum of prob
    assert np.allclose(cdf[:, 1:], np.cumsum(prob, axis=1)[:, :-1], atol=1e-3)


@given(st.lists(key_st, min_size=2, max_size=16))
@settings(max_examples=150, deadline=None)
def test_cdf_monotone_in_key_order(trained, keys):
    """GetCDF is monotone in key order in exact arithmetic (DESIGN §5)."""
    keys = sorted(set(keys))
    ss = StringSet.from_list(keys, width=24)
    v = get_cdf_np64(trained, ss)
    assert (np.diff(v) >= -1e-12).all()


@given(st.lists(key_st, min_size=2, max_size=16))
@settings(max_examples=60, deadline=None)
def test_cdf_monotone_f32_jit(trained, keys):
    keys = sorted(set(keys))
    ss = StringSet.from_list(keys, width=24)
    v = np.asarray(get_cdf_jnp(
        jnp.asarray(trained.cdf_tab), jnp.asarray(trained.prob_tab),
        jnp.asarray(ss.bytes), jnp.asarray(ss.lens), 0))
    assert (np.diff(v) >= 0).all() or np.allclose(np.diff(v).min(), 0, atol=1e-7)


def test_uniform_hpt_equals_sm_model():
    """GetCDF with the uniform table == the paper's SM encoding (Eq. 3)."""
    from repro.core.baselines import SMModel

    hpt = uniform_hpt(1, 256)
    keys = [b"abc", b"zebra", b"a", b"hello world"]
    ss = StringSet.from_list(keys, width=16)
    got = get_cdf_np64(hpt, ss)
    want = SMModel().values(ss)
    assert np.allclose(got, want, atol=1e-9)


def test_prefix_skip_matches_substring(trained):
    """GetCDF(s, start=k) == GetCDF(s[k:]) — Alg. 2 line 35 semantics."""
    keys = [b"prefix-abcdef", b"prefix-zzz"]
    ss = StringSet.from_list(keys, width=24)
    skipped = get_cdf_np64(trained, ss, start=7)
    direct = get_cdf_np64(trained, StringSet.from_list([k[7:] for k in keys], width=24))
    assert np.allclose(skipped, direct)


def test_thm31_error_bound_on_popular_prefix():
    """Popular prefixes approximate prob(c|P) well (paper Thm 3.1)."""
    rng = np.random.default_rng(0)
    # skewed set: half the keys share the prefix 'aa', next char ~80/20 b/c
    keys = set()
    while len(keys) < 4000:
        if rng.random() < 0.5:
            nxt = b"b" if rng.random() < 0.8 else b"c"
            keys.add(b"aa" + nxt + bytes(rng.integers(100, 123, 6).astype(np.uint8)))
        else:
            keys.add(bytes(rng.integers(100, 123, 8).astype(np.uint8)))
    ss = StringSet.from_list(sorted(keys), width=16)
    hpt = build_hpt(ss, rows=1024, cols=128, smoothing=0.0)
    err = conditional_prob_error(hpt, ss, b"aa")
    assert err < 0.05  # paper reports 0.0006-0.006 for popular prefixes


def test_build_rejects_non_pow2_rows():
    ss = StringSet.from_list([b"ab"])
    with pytest.raises(ValueError):
        build_hpt(ss, rows=100)
