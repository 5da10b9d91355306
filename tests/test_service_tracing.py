"""Program spans and counters of the request path.

* a profiler trace of a few ``IndexService`` flushes holds the span tree
  ``lits.service.flush`` > ``lits.index.execute`` > ``lits.index.get.sync``
  on the flusher's thread, the flush span carrying its ``flush`` id and
  ``ops``;
* ``StringIndex.host_syncs`` counts one device sync per op group;
* ``ServiceStats.walk_iters`` and ``model_step_iters`` mirror the search
  walk's iteration counts, read in the get group's one sync;
* ``ServiceStats.resolve_rounds`` mirrors the terminal resolve's compare
  rounds, read in the same sync, and is None on the ``pallas`` backend;
* ``ServiceStats.queue_wait_ms_total`` sees a flusher held by its index,
  ``flush_ms_total`` the hold itself, and ``reset_stats`` zeroes them.
"""
import glob
import os
import threading
import time

import numpy as np
import pytest

from repro.core.strings import random_strings
from repro.index import (
    BatchResult, DeleteRequest, GetRequest, IndexConfig, OpResult, PutRequest,
    ScanRequest, Status, StringIndex, StringIndexBase,
)
from repro.serve.service import IndexService, ServiceConfig


def _corpus(rng, n=200):
    keys = sorted(set(random_strings(rng, n, 2, 20)))
    return keys, np.arange(len(keys), dtype=np.int64) * 3 + 1


def _service(rng, index=None, **kw):
    keys, vals = _corpus(rng)
    cfg = dict(max_batch=1024, default_tenant="t", merge_threshold=None)
    cfg.update(kw)
    svc = IndexService.bulk_load(
        {"t": (keys, vals)},
        IndexConfig(auto_merge_threshold=None, **(index or {})),
        ServiceConfig(**cfg))
    return svc, keys


def _lines(xplane_path):
    """``[[(name, start_ns, end_ns, stats)]]``: the ``lits.`` events of
    each host line (one line per thread)."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(xplane_path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                    dict(e.stats)) for e in line.events
                   if e.name.startswith("lits.")]
            if evs:
                out.append(evs)
    return out


def _inside(outer, evs, name):
    return [e for e in evs if e[0] == name
            and outer[1] <= e[1] and e[2] <= outer[2]]


def test_trace_holds_flush_span_tree(rng, tmp_path):
    import jax

    svc, keys = _service(rng)
    svc.execute([GetRequest(k) for k in keys[:4]])       # compile first
    jax.profiler.start_trace(str(tmp_path))
    try:
        for n in (3, 5, 7):
            got = svc.execute([GetRequest(k) for k in keys[:n]])
            assert all(r.status == Status.OK for r in got)
    finally:
        jax.profiler.stop_trace()
        svc.close()
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    flusher = [evs for evs in _lines(path)
               if any(e[0] == "lits.service.flush" for e in evs)]
    assert len(flusher) == 1, "every flush runs on the one flusher thread"
    evs = flusher[0]
    flushes = [e for e in evs if e[0] == "lits.service.flush"]
    assert [e[3]["ops"] for e in flushes] == [3, 5, 7]
    ids = [e[3]["flush"] for e in flushes]
    assert ids == sorted(ids) and len(set(ids)) == 3
    for k, f in enumerate(flushes):
        for name in ("lits.service.lock_wait", "lits.service.resolve"):
            assert len(_inside(f, evs, name)) == 1, name
        ex, = _inside(f, evs, "lits.index.execute")
        for name in ("lits.index.plan", "lits.index.get.encode",
                     "lits.index.get.dispatch", "lits.index.get.sync"):
            assert len(_inside(ex, evs, name)) == 1, name
        assert len(_inside(ex, evs, "lits.index.get.decode")) == 2
        # the loop turn that popped the flush's ops ends as it starts (the
        # first flush's turn began before the trace did, so is not in it)
        assert k == 0 or any(c[0] == "lits.service.coalesce"
                             and flushes[k - 1][2] <= c[1] <= c[2] <= f[1]
                             for c in evs)


@pytest.mark.parametrize("ops,syncs", [
    ("g", 1),          # one get group: one sync
    ("pg", 2),         # puts, then gets
    ("pdgs", 4),       # puts, deletes, gets, one scan window
    ("gss", 3),        # gets and two scan windows
])
def test_host_syncs_count_one_per_op_group(rng, ops, syncs):
    keys, vals = _corpus(rng, 120)
    idx = StringIndex.bulk_load(keys, vals,
                                IndexConfig(auto_merge_threshold=None))
    make = {"g": lambda i: GetRequest(keys[i]),
            "p": lambda i: PutRequest(b"new-%03d" % i, i),
            "d": lambda i: DeleteRequest(keys[i + 50]),
            "s": lambda i: ScanRequest(keys[i], 4 + len(ops) + i)}
    batch = [make[c](i) for i, c in enumerate(ops) for _ in range(3)]
    before = idx.host_syncs
    idx.execute(batch)
    assert idx.host_syncs - before == syncs


class _HeldIndex(StringIndexBase):
    """Answers every get NOT_FOUND; the first ``execute`` waits until the
    test releases it, holding the flusher."""

    def __init__(self):
        self.config = IndexConfig(auto_merge_threshold=None)
        self.entered = threading.Event()
        self.release = threading.Event()

    def execute(self, batch):
        self.entered.set()
        assert self.release.wait(30.0)
        return BatchResult([OpResult(Status.NOT_FOUND) for _ in batch])


def test_queue_wait_counts_a_held_flusher():
    hold_s, queued = 0.2, 5
    idx = _HeldIndex()
    svc = IndexService(idx, ServiceConfig(max_batch=64, max_delay_ms=0.0,
                                          merge_threshold=None))
    first = svc.submit(GetRequest(b"a"))
    assert idx.entered.wait(30.0)
    s0 = svc.stats()                   # the first op is popped and counted
    behind = svc.submit_many([GetRequest(b"b%d" % i) for i in range(queued)])
    time.sleep(hold_s)
    idx.release.set()
    first.result(30.0)
    for f in behind:
        f.result(30.0)
    s1 = svc.stats()
    svc.close()
    assert s1.completed == queued + 1 and s1.flushes == 2
    wait_ms = s1.queue_wait_ms_total - s0.queue_wait_ms_total
    assert wait_ms / queued >= hold_s * 1e3
    assert s1.mean_queue_wait_ms >= hold_s * 1e3 * queued / (queued + 1)
    assert s1.flush_ms_total >= hold_s * 1e3   # the held flush
    assert s1.host_syncs == 0          # a backend that does not count them
    assert s1.walk_iters is None and s1.model_step_iters is None
    assert s1.resolve_rounds is None


def test_reset_stats_zeroes_the_new_counters(rng):
    svc, keys = _service(rng)
    svc.execute([PutRequest(b"zz", 1), GetRequest(keys[0])])
    svc.execute([GetRequest(keys[1])])
    s = svc.stats()
    assert s.host_syncs == 3 and s.syncs_per_flush == 1.5
    assert s.queue_wait_ms_total > 0.0 and s.flush_ms_total > 0.0
    svc.reset_stats()
    s = svc.stats()
    assert (s.queue_wait_ms_total, s.flush_ms_total, s.host_syncs) == \
        (0.0, 0.0, 0)
    svc.execute([GetRequest(keys[2])])
    assert svc.stats().host_syncs == 1
    svc.close()


def test_walk_counters_ride_the_get_sync(rng):
    svc, keys = _service(rng)
    for n in (1, 9, 40):
        got = svc.execute([GetRequest(k) for k in keys[:n]]
                          + [GetRequest(b"absent-%d" % n)])
        assert [r.status for r in got] == [Status.OK] * n + [Status.NOT_FOUND]
    s = svc.stats()
    svc.close()
    assert s.flushes == 3 and s.syncs_per_flush == 1.0
    assert s.walk_iters >= s.model_step_iters >= 1


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_resolve_rounds_ride_the_get_sync(rng, backend):
    svc, keys = _service(rng, index=dict(search_backend=backend,
                                         kernel_backend="interpret"))
    idx = svc.index

    def gets(n):
        got = svc.execute([GetRequest(k) for k in keys[:n]]
                          + [GetRequest(b"absent-%d" % n)])
        assert [r.status for r in got] == \
            [Status.OK] * n + [Status.NOT_FOUND]

    gets(5)
    svc.reset_stats()
    at_reset = idx.resolve_rounds
    for n in (1, 9, 40):
        gets(n)
    s = svc.stats()
    svc.close()
    assert s.flushes == 3 and s.syncs_per_flush == 1.0
    if backend == "pallas":
        assert s.resolve_rounds is None and idx.resolve_rounds is None
    else:
        # every group holds a hit, so it resolves in one round or more
        assert s.resolve_rounds == idx.resolve_rounds - at_reset
        assert s.resolve_rounds >= s.host_syncs == 3
