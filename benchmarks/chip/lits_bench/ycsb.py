"""YCSB core-workload traffic (Cooper et al., SoCC 2010), open loop.

Key choice is YCSB's ``ScrambledZipfianGenerator``: a zipfian rank over
10^10 items with constant 0.99 (Gray et al.'s closed form, as in
``ZipfianGenerator``), hashed with ``Utils.fnvhash64`` and taken modulo the
item count, so hot items are scattered over the key space instead of being
neighbours in key order.

The generator makes reads (YCSB's ``readproportion=1``, workload C), the
only op kind a committed cell sends.  Every seed does the same amount of
work in another order: the uniform draws behind arrival gaps and key ranks
are stratified (one draw per equal-probability stratum, jittered by the
seed, then shuffled).  So two seeds differ in which keys come when, not in
how much there is.
"""
from __future__ import annotations

import dataclasses
import zlib

import numpy as np

ZIPF_ITEMS = 10_000_000_000          # ScrambledZipfianGenerator.ITEM_COUNT
ZIPF_CONSTANT = 0.99                 # ZipfianGenerator.ZIPFIAN_CONSTANT
ZETAN = 26.46902820178302            # ScrambledZipfianGenerator.ZETAN
FNV_OFFSET_BASIS_64 = 0xCBF29CE484222325
FNV_PRIME_64 = 1099511628211

def fnvhash64(vals: np.ndarray) -> np.ndarray:
    """YCSB ``Utils.fnvhash64`` over int64 values, then ``Math.abs``."""
    v = np.asarray(vals, np.int64).view(np.uint64).copy()
    h = np.full(v.shape, FNV_OFFSET_BASIS_64, np.uint64)
    with np.errstate(over="ignore"):
        for _ in range(8):
            h ^= v & np.uint64(0xFF)
            v >>= np.uint64(8)
            h *= np.uint64(FNV_PRIME_64)
    return np.abs(h.view(np.int64))


def zipfian_ranks(u: np.ndarray, items: int = ZIPF_ITEMS,
                  theta: float = ZIPF_CONSTANT, zetan: float = ZETAN):
    """``ZipfianGenerator.nextLong`` for uniform draws ``u`` in [0, 1)."""
    zeta2 = 1.0 + 0.5 ** theta
    alpha = 1.0 / (1.0 - theta)
    eta = (1.0 - (2.0 / items) ** (1.0 - theta)) / (1.0 - zeta2 / zetan)
    uz = u * zetan
    r = (items * np.power(eta * u - eta + 1.0, alpha)).astype(np.int64)
    r = np.where(uz < zeta2, 1, r)
    return np.where(uz < 1.0, 0, r)


def scrambled_zipfian(u: np.ndarray, n_items: int) -> np.ndarray:
    """``ScrambledZipfianGenerator.nextValue`` over ``n_items`` items."""
    return fnvhash64(zipfian_ranks(u)) % n_items


def stratified_uniform(rng, n: int) -> np.ndarray:
    """One uniform draw in each of ``n`` equal strata of [0, 1), shuffled."""
    u = (np.arange(n) + rng.random(n)) / n
    return rng.permutation(u)


def seed_rng(seed: int, stream: str) -> np.random.Generator:
    """An independent stream per purpose; any non-negative seed, large ones
    included."""
    return np.random.default_rng((seed, zlib.crc32(stream.encode())))


@dataclasses.dataclass
class OpStream:
    """Reads in the order they are due; ``item`` indexes the corpus."""

    due: np.ndarray       # (n,) seconds from the window's start
    item: np.ndarray      # (n,) corpus row

    def __len__(self) -> int:
        return self.due.shape[0]


def make_stream(traffic: dict, n_items: int, seconds: float, seed: int,
                stream: str = "window") -> OpStream:
    """``rate * seconds`` reads, due over ``seconds``.  Arrivals are
    Poisson: exponential gaps (stratified), in a seeded order, scaled to
    span exactly ``seconds``."""
    if traffic["mix"] != {"read": 1.0}:
        raise ValueError(f"the generator makes reads only, not {traffic['mix']}")
    dist = traffic["request_distribution"]
    if dist != "scrambled_zipfian":
        raise ValueError(f"unknown request_distribution {dist!r}")
    n = max(int(round(traffic["rate_ops_per_s"] * seconds)), 1)
    rng = seed_rng(seed, stream)
    gaps = -np.log1p(-stratified_uniform(rng, n))
    due = np.cumsum(gaps)
    due = (due - due[0]) * (seconds * (n - 1) / n) / max(due[-1] - due[0],
                                                         1e-12)
    item = scrambled_zipfian(stratified_uniform(rng, n), n_items)
    return OpStream(due, item)
