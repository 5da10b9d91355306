"""Key corpora of the benchmark's configurations.

The ``email`` and ``url`` generators are copies of the LITS Table 1 shapes
in ``repro.data.synthetic``, kept here so that a change to the program
cannot move the yardstick.  A configuration's corpus comes from the corpus
seed in its file, and is what the index is bulk-loaded with.
"""
from __future__ import annotations

import dataclasses
import zlib
from typing import Callable, Dict, List

import numpy as np

_LOWER = b"abcdefghijklmnopqrstuvwxyz"


def _choice_str(rng, alphabet: bytes, n: int) -> bytes:
    a = np.frombuffer(alphabet, np.uint8)
    return a[rng.integers(0, len(a), n)].tobytes()


def _words(rng, n_words: int, lo=3, hi=9) -> List[bytes]:
    return [_choice_str(rng, _LOWER, rng.integers(lo, hi))
            for _ in range(n_words)]


def gen_email(rng, n: int) -> List[bytes]:
    """Faker-style emails: first.last##@domain.tld."""
    first = _words(rng, 400, 3, 8)
    last = _words(rng, 600, 4, 9)
    dom = [b"gmail.com", b"yahoo.com", b"hotmail.com", b"example.org",
           b"mail.net"]
    out = {}
    while len(out) < n:
        k = b"%s.%s%02d@%s" % (
            first[rng.integers(0, len(first))],
            last[rng.integers(0, len(last))],
            rng.integers(0, 100), dom[rng.integers(0, len(dom))],
        )
        out[k] = None
    return list(out)


def gen_url(rng, n: int) -> List[bytes]:
    """CommonCrawl-like URLs: one shared scheme prefix + skewed hosts."""
    tld = [b".com", b".org", b".net", b".de", b".io"]
    hosts = [b"www." + w + tld[rng.integers(0, len(tld))]
             for w in _words(rng, max(n // 50, 10), 5, 14)]
    paths = _words(rng, 500, 3, 10)
    out = {}
    while len(out) < n:
        h = hosts[min(int(rng.zipf(1.3)) - 1, len(hosts) - 1)]
        depth = rng.integers(1, 6)
        p = b"/".join(paths[rng.integers(0, len(paths))] for _ in range(depth))
        suffix = b"%d.html" % rng.integers(0, 10000)
        out[b"http://" + h + b"/" + p + b"/" + suffix] = None
    return list(out)


GENERATORS: Dict[str, Callable] = {"email": gen_email, "url": gen_url}


def generate(dataset: str, n: int, seed: int) -> List[bytes]:
    """``n`` unique keys in generation order (crc32 of the name, not
    ``hash()``, which is salted per process)."""
    rng = np.random.default_rng((zlib.crc32(dataset.encode()) & 0xFFFF, seed))
    return GENERATORS[dataset](rng, n)


@dataclasses.dataclass
class Corpus:
    """Keys as one padded byte matrix; values int64."""

    keys: np.ndarray     # (n, width) uint8, zero padded
    lens: np.ndarray     # (n,) int32
    values: np.ndarray   # (n,) int64

    def key(self, i: int) -> bytes:
        return self.keys[i, : self.lens[i]].tobytes()

    def key_list(self) -> List[bytes]:
        """Every key as bytes.  A fixed-width bytes view drops the zero
        padding, and the generators make no key that holds or ends in a
        zero byte."""
        return self.keys.view(f"S{self.keys.shape[1]}").ravel().tolist()

    def __len__(self) -> int:
        return self.keys.shape[0]


def build(dataset: str, n: int, seed: int) -> Corpus:
    keys = generate(dataset, n, seed)
    width = max(len(k) for k in keys)
    mat = np.zeros((len(keys), width), np.uint8)
    lens = np.fromiter((len(k) for k in keys), np.int32, len(keys))
    flat = np.frombuffer(b"".join(keys), np.uint8)
    rows = np.repeat(np.arange(len(keys)), lens)
    cols = np.arange(flat.size) - np.repeat(np.cumsum(lens) - lens, lens)
    mat[rows, cols] = flat
    i64 = np.iinfo(np.int64)
    rng = np.random.default_rng((zlib.crc32(b"values") & 0xFFFF, seed))
    values = rng.integers(i64.min, i64.max, size=len(keys), dtype=np.int64)
    return Corpus(mat, lens, values)


def save(corpus: Corpus, path: str) -> None:
    with open(path, "wb") as f:
        np.savez(f, keys=corpus.keys, lens=corpus.lens, values=corpus.values)


def load(path: str) -> Corpus:
    with np.load(path) as z:
        return Corpus(z["keys"], z["lens"], z["values"])


def key_stats(corpus: Corpus) -> dict:
    """Length statistics of the keys, as the configuration files state
    them."""
    lens = corpus.lens
    return {"min": int(lens.min()), "mean": round(float(lens.mean()), 2),
            "max": int(lens.max())}
