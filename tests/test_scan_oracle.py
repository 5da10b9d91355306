"""Property-based differential oracle suite for delta-aware scans (ISSUE 5).

Generated put/get/delete/scan/compact sequences run against a host
``dict`` + sorted-list oracle, on a built index and on one loaded from a
snapshot, across many merge epochs.  Keys come from a skewed-prefix generator (heavy shared
prefixes — the paper's hard case — plus a uniform tail), so scan windows
constantly straddle the base/delta seam, tombstone shadows and resurrected
keys.

Design note: sequences share one long-lived index per form (state
carries over, like a soak test) instead of rebuilding per sequence — a
fresh bulk load per sequence would give every sequence novel pool shapes
and pay an XLA compile per op kind per sequence.  The oracle is exact
either way: every op's result is checked against the dict, and the
periodic full-range paginated sweep checks the complete sorted view.
Forced ``merge()`` points interleave the sequences, so scans are exercised
against freshly-compacted epochs AND half-full deltas.

The ``hypothesis`` entry point rides the same driver (the CI image may
only have the seeded-sampling fallback shim — tests/_hypothesis_fallback);
the deterministic sweep below guarantees >= 200 generated sequences run
regardless of which hypothesis implementation is present.
"""
import os
import tempfile

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.index import (
    DeleteRequest,
    GetRequest,
    IndexConfig,
    PutRequest,
    ScanRequest,
    Status,
    StringIndex,
)

WIDTH = 16
SCAN_WINDOW = 6
SWEEP_WINDOW = 16

# skewed prefixes: two hot groups, one warm, a cold tail and a root-level
# singleton — mirrors the prefix histograms of the paper's URL/email sets
_PREFIXES = (b"app/ev/", b"app/ev/", b"app/ev/", b"app/us/", b"app/us/",
             b"zz/", b"q", b"")


def _rand_key(rng) -> bytes:
    p = _PREFIXES[int(rng.integers(0, len(_PREFIXES)))]
    return p + b"%04d" % int(rng.integers(0, 60))


def _oracle_scan(oracle: dict, start: bytes, window: int):
    keys = sorted(k for k in oracle if k >= start)[:window]
    return [(k, oracle[k]) for k in keys]


class _Driver:
    """One long-lived (index, oracle) pair per form: ``"built"``, or
    ``"loaded"`` (saved and read back with ``StringIndex.load``)."""

    def __init__(self, form: str):
        rng = np.random.default_rng(0xC0FFEE)
        base = sorted({_rand_key(rng) for _ in range(120)})
        vals = rng.integers(0, 1 << 40, len(base)).astype(np.int64)
        cfg = IndexConfig(width=WIDTH, delta_capacity=256,
                          auto_merge_threshold=None)
        self.index = StringIndex.bulk_load(base, vals, cfg)
        if form == "loaded":
            with tempfile.TemporaryDirectory() as d:
                path = os.path.join(d, "index.npz")
                self.index.save(path)
                self.index = StringIndex.load(path, cfg)
        self.oracle = dict(zip(base, vals.tolist()))
        self.epochs_seen = {self.index.epoch}
        self.sequences = 0

    # -- one generated sequence --------------------------------------------

    def run_sequence(self, seed: int) -> None:
        rng = np.random.default_rng(seed)
        for _ in range(int(rng.integers(5, 13))):
            self._step(rng)
        self.sequences += 1

    def _step(self, rng) -> None:
        kind = ("put", "put", "put", "delete", "delete", "get", "scan",
                "scan", "scan")[int(rng.integers(0, 9))]
        k = _rand_key(rng)
        if kind == "put":
            v = int(rng.integers(0, 1 << 40))
            r = self.index.execute([PutRequest(k, v)]).results[0]
            if r.status == Status.REJECTED_FULL:
                self.merge()                      # pool full: compact, retry
                r = self.index.execute([PutRequest(k, v)]).results[0]
            assert r.ok, (k, r.status)
            self.oracle[k] = v
        elif kind == "delete":
            r = self.index.execute([DeleteRequest(k)]).results[0]
            if r.status == Status.REJECTED_FULL:
                self.merge()
                r = self.index.execute([DeleteRequest(k)]).results[0]
            want = Status.OK if k in self.oracle else Status.NOT_FOUND
            assert r.status == want, (k, r.status, want)
            self.oracle.pop(k, None)
        elif kind == "get":
            r = self.index.execute([GetRequest(k)]).results[0]
            if k in self.oracle:
                assert r.ok and r.value == self.oracle[k], (k, r.value)
            else:
                assert r.status == Status.NOT_FOUND, (k, r.status)
        else:
            # scan starts: a (possibly absent) key, a bare prefix, or the
            # range edges — every flavor of straddle
            start = (k, k[:3], b"", b"~")[int(rng.integers(0, 4))]
            r = self.index.execute([ScanRequest(start, SCAN_WINDOW)]).results[0]
            assert r.status == Status.OK
            assert list(r.entries) == _oracle_scan(self.oracle, start,
                                                   SCAN_WINDOW), start

    # -- epoch control + the full-view sweep --------------------------------

    def merge(self) -> None:
        self.index.merge()
        self.epochs_seen.add(self.index.epoch)

    def full_sweep(self) -> None:
        """Paginate the whole index (resume-key pagination, the scan_page
        plan) and require the complete sorted oracle view."""
        got, start = [], b""
        while True:
            res = self.index.execute([ScanRequest(start, SWEEP_WINDOW)])
            page = list(res.results[0].entries)
            got.extend(page)
            if len(page) < SWEEP_WINDOW:
                break
            start = page[-1][0] + b"\x00"
        assert got == sorted(self.oracle.items()), \
            "paginated full scan diverged from the oracle"


_DRIVERS = {}


def _driver(form: str) -> _Driver:
    if form not in _DRIVERS:
        _DRIVERS[form] = _Driver(form)
    return _DRIVERS[form]


# 130 built + 80 loaded = 210 generated sequences >= the 200 the acceptance
# criteria require
_N_SEQ = {"built": 130, "loaded": 80}


@pytest.mark.parametrize("form", ["built", "loaded"])
def test_scan_oracle_generated_sequences(form):
    drv = _driver(form)
    n = _N_SEQ[form]
    for s in range(n):
        drv.run_sequence(seed=0x5EED + 7919 * s)
        if (s + 1) % 25 == 0:
            drv.merge()           # epoch bump mid-run: scans must re-agree
            drv.full_sweep()
    drv.full_sweep()
    assert drv.sequences >= n
    assert len(drv.epochs_seen) >= 3, \
        "the suite must cross >= 2 merge epoch bumps"


@given(st.integers(min_value=0, max_value=2**31 - 1))
@settings(max_examples=24, deadline=None)
def test_scan_oracle_hypothesis(seed):
    """Hypothesis-driven entry point over the same differential driver
    (real hypothesis shrinks seeds on failure; the fallback shim samples
    them) — one drawn seed = one generated sequence on each form."""
    for form in ("built", "loaded"):
        _driver(form).run_sequence(seed)


def test_scan_oracle_post_epoch_consistency():
    """After everything, force one more merge on each form and require
    the fully-compacted view to equal the oracle (tombstones physically
    reconciled, resurrects preserved)."""
    for form in ("built", "loaded"):
        if form not in _DRIVERS:
            continue
        drv = _DRIVERS[form]
        drv.merge()
        assert drv.index.delta_fill == 0.0
        drv.full_sweep()
