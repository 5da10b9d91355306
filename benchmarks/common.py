"""Shared benchmark helpers: structures, datasets, timing."""
from __future__ import annotations

import functools
import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (
    AlwaysLIT, AlwaysTrie, LITSBuilder, StringSet, freeze, pad_queries,
    scan_batch, search_batch, uniform_hpt,
)
from repro.index import GetRequest, IndexConfig, StringIndex

STRUCTURES = ("LITS", "LIT", "TRIE", "SLIPP")


def make_builder(structure: str) -> LITSBuilder:
    """LITS = full paper system; LIT = no subtries; TRIE = pure critbit
    (ART/HOT stand-in); SLIPP = LIPP-style uniform (SM) model, no subtries."""
    if structure == "LITS":
        return LITSBuilder()
    if structure == "LIT":
        return LITSBuilder(pmss=AlwaysLIT())
    if structure == "TRIE":
        return LITSBuilder(pmss=AlwaysTrie())
    if structure == "SLIPP":
        return LITSBuilder(hpt=uniform_hpt(1, 256), pmss=AlwaysLIT())
    raise KeyError(structure)


@functools.lru_cache(maxsize=64)
def dataset(name: str, n: int, seed: int = 0):
    from repro.data.synthetic import load

    keys = sorted(set(load(name, n, seed)))
    return keys


def bulkload(structure: str, keys: List[bytes]):
    b = make_builder(structure)
    t0 = time.perf_counter()
    b.bulkload(StringSet.from_list(list(keys)), np.arange(len(keys), dtype=np.int64))
    return b, time.perf_counter() - t0


def device_read_mops(b, keys: List[bytes], n_queries: int = 8192,
                     reps: int = 5) -> float:
    """Batched jitted point-lookup throughput (Mops)."""
    ti = freeze(b)
    rng = np.random.default_rng(0)
    idx = rng.integers(0, len(keys), n_queries)
    qb, ql = pad_queries([keys[i] for i in idx], ti.width)
    qb, ql = jnp.asarray(qb), jnp.asarray(ql)
    found, _, _ = search_batch(ti, qb, ql)  # warmup + correctness
    assert bool(found.all())
    t0 = time.perf_counter()
    for _ in range(reps):
        out = search_batch(ti, qb, ql)
    jax.block_until_ready(out)
    dt = time.perf_counter() - t0
    return n_queries * reps / dt / 1e6


def device_scan_mops(b, keys: List[bytes], n_queries: int = 2048, window: int = 16,
                     reps: int = 3) -> float:
    """Batched jitted range-scan throughput (M entries/s)."""
    ti = freeze(b)
    rng = np.random.default_rng(1)
    idx = rng.integers(0, len(keys), n_queries)
    qb, ql = pad_queries([keys[i] for i in idx], ti.width)
    qb, ql = jnp.asarray(qb), jnp.asarray(ql)
    out = scan_batch(ti, qb, ql, window)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = scan_batch(ti, qb, ql, window)
    jax.block_until_ready(out)
    dt = time.perf_counter() - t0
    return n_queries * reps * window / dt / 1e6  # entries/s


def facade_index(structure: str, keys: List[bytes],
                 config: IndexConfig | None = None) -> StringIndex:
    """Bulk-load ``keys`` into a :class:`StringIndex` for a given structure
    variant (LITS/LIT/TRIE/SLIPP), via the power-user builder seam."""
    b, _ = bulkload(structure, keys)
    return StringIndex.from_builder(b, config)


def facade_read_mops(index: StringIndex, keys: List[bytes],
                     n_queries: int = 8192, reps: int = 5) -> float:
    """Typed facade point-lookup throughput (Mops): ``execute`` with
    GetRequests — includes batch planning and per-op result construction,
    i.e. the full API dispatch cost (compare against
    :func:`device_read_mops` for the raw free-function path)."""
    rng = np.random.default_rng(0)
    idx = rng.integers(0, len(keys), n_queries)
    batch = [GetRequest(keys[i]) for i in idx]
    res = index.execute(batch)  # warmup + correctness
    assert all(r.ok for r in res.results)
    t0 = time.perf_counter()
    for _ in range(reps):
        index.execute(batch)
    dt = time.perf_counter() - t0
    return n_queries * reps / dt / 1e6


def host_insert_kops(structure: str, loaded: List[bytes], to_insert: List[bytes]) -> float:
    b, _ = bulkload(structure, loaded)
    t0 = time.perf_counter()
    for i, k in enumerate(to_insert):
        b.insert(k, i)
    dt = time.perf_counter() - t0
    return len(to_insert) / dt / 1e3
