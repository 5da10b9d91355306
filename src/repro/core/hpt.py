"""Hash-enhanced Prefix Table (HPT) — the paper's core learned model (Sec. 3.2).

The HPT approximates ``prob(c | prefix)`` with a hashed prefix table and
computes a string CDF via the recursion of Eq. (1)/(2) (paper Alg. 1):

    cdf  += prob * HPT[hash(P_k)][c].cdf
    prob *= HPT[hash(P_k)][c].prob

Numerics contract
-----------------
The *structure* of the index (which slot a key maps to) is defined by the
float32 JAX implementation :func:`get_cdf_jnp`.  The host-side builder calls
the same jitted function when assigning keys to slots, so build-time and
query-time positions are bit-identical by construction.  ``get_cdf_np64`` is a
float64 numpy oracle used for analysis/tests only.

Monotonicity (tested property): ``GetCDF`` is monotone non-decreasing w.r.t.
lexicographic order *regardless of hash collisions*: at the first differing
character both strings consult the same row (identical preceding prefix ⇒
identical hash state) where ``cdf`` is cumulative in ``c``, and the residual
contribution of the remaining suffix is bounded by ``prob`` — extending a
string only adds non-negative terms.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .strings import StringSet
from .strops import FNV_PRIME

# Maximum number of characters the CDF walk consumes.  Beyond ~48 characters
# the running float32 ``prob`` underflows for any realistic distribution, so
# extra steps cannot change the result; 64 keeps a safety margin while
# bounding the device loop.  Nodes strip their common prefix first (paper
# Sec. 3.2), so per-node suffixes are short in practice.
MAX_CDF_STEPS = 64


@dataclasses.dataclass
class HPT:
    """The trained table.  ``cdf_tab[r, c] = cdf(c | row r)``, ``prob_tab`` its increments."""

    cdf_tab: np.ndarray  # (rows, cols) float32
    prob_tab: np.ndarray  # (rows, cols) float32

    @property
    def rows(self) -> int:
        return self.cdf_tab.shape[0]

    @property
    def cols(self) -> int:
        return self.cdf_tab.shape[1]

    def nbytes(self) -> int:
        return self.cdf_tab.nbytes + self.prob_tab.nbytes


def _check_pow2(x: int, name: str) -> None:
    if x & (x - 1) or x <= 0:
        raise ValueError(f"{name} must be a power of two, got {x}")


def rolling_hash_np(h: np.ndarray, c: np.ndarray) -> np.ndarray:
    """One rolling-hash step (uint32 wraparound); identical to the device one."""
    return ((h ^ c.astype(np.uint32)) * FNV_PRIME).astype(np.uint32)


def build_hpt(
    sample: StringSet,
    rows: int = 1024,
    cols: int = 128,
    smoothing: float = 0.5,
) -> HPT:
    """Construct the HPT from a key sample (paper: ~1% of the data set).

    ``smoothing`` is add-alpha smoothing on the per-row counts; the paper uses
    raw frequencies (smoothing=0).  A small alpha keeps unseen characters
    distinguishable (beyond-paper robustness tweak; rows never observed fall
    back to the uniform model, which is exactly the SM assumption).
    """
    _check_pow2(rows, "rows")
    if np.any(sample.bytes >= cols):
        raise ValueError(f"keys contain characters >= cols ({cols}); use cols=256")
    counts = np.zeros((rows, cols), dtype=np.float64)
    n, L = sample.bytes.shape
    h = np.zeros(n, dtype=np.uint32)
    mask = np.uint32(rows - 1)
    for k in range(min(L, MAX_CDF_STEPS)):
        active = sample.lens > k
        if not active.any():
            break
        c = sample.bytes[:, k]
        r = (h & mask).astype(np.int64)
        np.add.at(counts, (r[active], c[active].astype(np.int64)), 1.0)
        h = np.where(active, rolling_hash_np(h, c), h)
    counts += smoothing
    totals = counts.sum(axis=1, keepdims=True)
    empty = totals[:, 0] == 0
    if empty.any():  # only possible with smoothing == 0
        counts[empty] = 1.0
        totals = counts.sum(axis=1, keepdims=True)
    prob = counts / totals
    cdf = np.cumsum(prob, axis=1) - prob  # exclusive cumsum: cdf(c) = sum_{i<c} prob(i)
    return HPT(cdf.astype(np.float32), prob.astype(np.float32))


def uniform_hpt(rows: int = 1, cols: int = 128) -> HPT:
    """The uniform-next-character model — equivalent to the paper's SM baseline."""
    prob = np.full((rows, cols), 1.0 / cols, dtype=np.float64)
    cdf = np.cumsum(prob, axis=1) - prob
    return HPT(cdf.astype(np.float32), prob.astype(np.float32))


# ---------------------------------------------------------------------------
# CDF computation — canonical float32 JAX path
# ---------------------------------------------------------------------------

def get_cdf_impl(
    cdf_tab: jax.Array,  # (R, C) f32
    prob_tab: jax.Array,  # (R, C) f32
    qbytes: jax.Array,  # (B, L) uint8, zero padded
    qlens: jax.Array,  # (B,) int32
    start: jax.Array | int = 0,  # (B,) or scalar: position to start from (prefix skip)
    max_steps: int = MAX_CDF_STEPS,
) -> jax.Array:
    """Batched GetCDF (paper Alg. 1) over zero-padded query strings.

    ``start`` implements the per-node common-prefix skip: the walk begins at
    character ``start`` with a fresh hash state (paper Alg. 2, line 35:
    ``hpt.getCDF(s + prefixLen)``).
    """
    R, C = cdf_tab.shape
    B, L = qbytes.shape
    start = jnp.broadcast_to(jnp.asarray(start, jnp.int32), (B,))
    rowmask = jnp.uint32(R - 1)
    steps = min(max_steps, L)

    def body(k, carry):
        cdf, prob, h = carry
        pos = start + k
        # gather the k-th suffix character of every query (0 when past the end)
        c = jnp.take_along_axis(qbytes, jnp.minimum(pos, L - 1)[:, None], axis=1)[:, 0]
        c = jnp.minimum(c, jnp.uint8(C - 1)).astype(jnp.int32)
        active = pos < qlens
        r = (h & rowmask).astype(jnp.int32)
        cval = cdf_tab[r, c]
        pval = prob_tab[r, c]
        cdf = cdf + jnp.where(active, prob * cval, jnp.float32(0))
        prob = prob * jnp.where(active, pval, jnp.float32(1))
        h = jnp.where(active, (h ^ c.astype(jnp.uint32)) * FNV_PRIME, h)
        return cdf, prob, h

    cdf0 = jnp.zeros((B,), jnp.float32)
    prob0 = jnp.ones((B,), jnp.float32)
    h0 = jnp.zeros((B,), jnp.uint32)
    cdf, _, _ = jax.lax.fori_loop(0, steps, body, (cdf0, prob0, h0))
    return cdf


get_cdf_jnp = partial(jax.jit, static_argnames=("max_steps",))(get_cdf_impl)


def positions_impl(
    cdf_tab: jax.Array,
    prob_tab: jax.Array,
    qbytes: jax.Array,
    qlens: jax.Array,
    start: jax.Array | int,
    alpha: jax.Array,  # (B,) or scalar f32
    beta: jax.Array,
    nslots: jax.Array,  # (B,) or scalar int32
    max_steps: int = MAX_CDF_STEPS,
) -> jax.Array:
    """Slot position = clamp(floor(alpha*cdf + beta), 1, nslots-2) (paper Alg. 2 l.35-37)."""
    cdf = get_cdf_impl(cdf_tab, prob_tab, qbytes, qlens, start, max_steps)
    t = alpha * cdf
    t = t + beta
    pos = jnp.floor(t).astype(jnp.int32)
    nslots = jnp.asarray(nslots, jnp.int32)
    return jnp.clip(pos, 1, nslots - 2)


positions_jnp = partial(jax.jit, static_argnames=("max_steps",))(positions_impl)


# ---------------------------------------------------------------------------
# Numpy float64 oracle (analysis only — NOT used for index structure)
# ---------------------------------------------------------------------------

def get_cdf_np64(hpt: HPT, ss: StringSet, start: int = 0, max_steps: int = MAX_CDF_STEPS) -> np.ndarray:
    cdf_tab = hpt.cdf_tab.astype(np.float64)
    prob_tab = hpt.prob_tab.astype(np.float64)
    R, C = cdf_tab.shape
    n, L = ss.bytes.shape
    cdf = np.zeros(n, np.float64)
    prob = np.ones(n, np.float64)
    h = np.zeros(n, np.uint32)
    mask = np.uint32(R - 1)
    for k in range(start, min(L, start + max_steps)):
        active = ss.lens > k
        if not active.any():
            break
        c = np.minimum(ss.bytes[:, k], C - 1).astype(np.int64)
        r = (h & mask).astype(np.int64)
        cdf = cdf + np.where(active, prob * cdf_tab[r, c], 0.0)
        prob = prob * np.where(active, prob_tab[r, c], 1.0)
        h = np.where(active, rolling_hash_np(h, ss.bytes[:, k]), h)
    return cdf


def conditional_prob_error(hpt: HPT, full: StringSet, prefix: bytes, min_count: int = 1) -> float:
    """Mean |HPT[hash(P)][c].prob − prob(c|P)| for a given prefix (Thm 3.1 check)."""
    pl = len(prefix)
    pb = np.frombuffer(prefix, np.uint8)
    m = (full.lens > pl) & np.all(full.bytes[:, :pl] == pb[None, :], axis=1)
    nxt = full.bytes[m, pl]
    if nxt.size < min_count:
        return float("nan")
    emp = np.bincount(nxt, minlength=hpt.cols).astype(np.float64)
    emp = emp / emp.sum()
    h = np.zeros(1, np.uint32)
    for c in pb:
        h = rolling_hash_np(h, np.array([c], np.uint8))
    r = int(h[0] & np.uint32(hpt.rows - 1))
    approx = hpt.prob_tab[r].astype(np.float64)
    support = emp > 0
    return float(np.abs(approx[support] - emp[support]).mean())
