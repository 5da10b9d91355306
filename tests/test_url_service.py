"""``IndexService`` over URL keys: long keys (about 55 bytes) whose hosts
are drawn zipf(1.3), so a few hosts hold most keys and long shared
prefixes push the walk down deep critbit sub-tries.  Every answer is
compared with a host dict, for present keys, absent keys under the
hottest host, and absent keys that differ from a present one only at the
``.html`` suffix (one a prefix of the other among them)."""
import collections

import numpy as np
import pytest

from repro.data import synthetic
from repro.index import GetRequest, IndexConfig, Status
from repro.serve.service import IndexService, ServiceConfig

N_KEYS = 20_000


@pytest.fixture(scope="module")
def url_service():
    keys = synthetic.load("url", N_KEYS, seed=7)
    rng = np.random.default_rng(7)
    vals = rng.integers(np.iinfo(np.int64).min, np.iinfo(np.int64).max,
                        size=len(keys), dtype=np.int64)
    svc = IndexService.bulk_load(
        {"t": (keys, vals)},
        IndexConfig(delta_capacity=1024, search_backend="jnp",
                    auto_merge_threshold=None),
        ServiceConfig(max_batch=128, default_tenant="t",
                      merge_threshold=None))
    ref = dict(zip(keys, vals.tolist()))
    yield svc, keys, ref
    svc.close()


def _check(svc, ref, batch):
    got = svc.execute([GetRequest(k) for k in batch])
    for k, r in zip(batch, got):
        if k in ref:
            assert (r.status, r.value) == (Status.OK, ref[k]), k
        else:
            assert r.status == Status.NOT_FOUND, k


@pytest.mark.parametrize("group", [1, 17, 88, 128])
def test_gets_match_a_host_dict(url_service, group):
    svc, keys, ref = url_service
    rng = np.random.default_rng(group)
    for _ in range(3):
        _check(svc, ref, [keys[i] for i in rng.integers(0, len(keys), group)])


def test_absent_keys_under_the_hottest_host(url_service):
    svc, keys, ref = url_service
    (host, _n), = collections.Counter(
        k.split(b"/")[2] for k in keys).most_common(1)
    under = [k for k in keys if k.split(b"/")[2] == host]
    assert len(under) > N_KEYS // 10          # the skew the corpus is for
    absent = [b"http://" + host + b"/" + w for w in
              (b"", b"a", b"zzzzzzzz/1.html", b"\x7f")]
    # present paths under the hot host, with a file name never generated
    absent += [k.rsplit(b"/", 1)[0] + b"/%d.html" % (10_000 + i)
               for i, k in enumerate(under[:60])]
    assert not any(k in ref for k in absent)
    _check(svc, ref, absent + under[:60])


@pytest.mark.parametrize("change", [
    lambda k: k[:-1],                 # a prefix of a present key
    lambda k: k + b"l",               # a present key is its prefix
    lambda k: k[:-5] + b".HTML",      # same length, last bytes differ
    lambda k: k[:-5] + b"0.html",     # another file number on the same path
], ids=["htm", "htmll", "HTML", "file-number"])
def test_absent_keys_that_differ_at_the_suffix(url_service, change):
    svc, keys, ref = url_service
    rng = np.random.default_rng(3)
    near = [change(keys[i]) for i in rng.integers(0, len(keys), 88)]
    _check(svc, ref, near + [keys[i] for i in rng.integers(0, len(keys), 40)])


def test_walk_counters_on_long_skewed_keys(url_service):
    """Most levels of a URL walk are critbit sub-trie levels with no lane
    on a model node, so the model step runs on some iterations but not
    all (``test_service_tracing`` covers the counters' plumbing)."""
    svc, keys, ref = url_service
    rng = np.random.default_rng(11)
    s0 = svc.stats()
    for group in (17, 88):
        _check(svc, ref, [keys[i] for i in rng.integers(0, len(keys), group)])
    s1 = svc.stats()
    walk = s1.walk_iters - s0.walk_iters
    model = s1.model_step_iters - s0.model_step_iters
    assert 1 <= model < walk
