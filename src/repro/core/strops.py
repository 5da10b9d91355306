"""Device string primitives (jnp): byte gathers, compares and FNV hashes.

The traversal walk (:mod:`repro.core.walk`), the delta probe and mutate
paths (:mod:`repro.core.tensor_index`) and the mesh router
(:mod:`repro.distributed.index_service`) all compare and hash keys through
these functions, so every path agrees on key order and hash values.

Hash semantics contract
-----------------------
``hash16``/``hash32`` consume exactly ``min(len, width)`` bytes, where
``width`` is the padded matrix width.  The host mirror
(:func:`repro.core.strings.key_hash16`) has identical semantics for any
matrix of the same width, so build-time h-pointer hashes and query-time
hashes are bit-identical.  Keys longer than the index width are NOT
representable (``pad_queries`` marks them with the ``width+1`` length
sentinel and ``insert_batch`` rejects them), so a stored hash never covers
truncated bytes.

This module stays a leaf: it imports nothing from the rest of
:mod:`repro.core` (``walk`` and ``tensor_index`` import it).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# FNV-1a constants — the single definition (``repro.core.hpt`` imports it).
FNV_PRIME = np.uint32(0x01000193)
FNV_OFFSET = np.uint32(0x811C9DC5)


def gather_bytes(pool: jax.Array, off: jax.Array, width: int) -> jax.Array:
    """(B,) offsets -> (B, width) byte windows from a flat pool (clamped)."""
    idx = off[:, None] + jnp.arange(width, dtype=jnp.int32)[None, :]
    return jnp.take(pool, idx, mode="clip")


def str_eq(qbytes, qlens, pool, off, klen) -> jax.Array:
    """Exact string equality: bytes AND length must match."""
    W = qbytes.shape[1]
    kb = gather_bytes(pool, off, W)
    mask = jnp.arange(W)[None, :] < klen[:, None]
    kb = jnp.where(mask, kb, 0)
    return jnp.all(kb == qbytes, axis=1) & (qlens == klen)


def str_cmp_prefix(qbytes, pool, off, pl) -> jax.Array:
    """sign(strncmp(q, pool[off:], pl)) vectorized; q zero-padded."""
    W = qbytes.shape[1]
    kb = gather_bytes(pool, off, W)
    mask = jnp.arange(W)[None, :] < pl[:, None]
    kv = jnp.where(mask, kb, 0).astype(jnp.int32)
    qv = jnp.where(mask, qbytes, 0).astype(jnp.int32)
    neq = kv != qv
    any_neq = neq.any(axis=1)
    first = jnp.argmax(neq, axis=1)
    qd = jnp.take_along_axis(qv, first[:, None], axis=1)[:, 0]
    kd = jnp.take_along_axis(kv, first[:, None], axis=1)[:, 0]
    return jnp.sign(qd - kd) * any_neq


def _cmp_tail(va, vb, lencmp) -> jax.Array:
    """Shared strcmp tail: first differing byte decides, else the length
    tie-break.  ONE copy of the ordering rule for every full-key compare
    (`str_cmp_full`, `str_cmp_pools`) — the delta sort, the rank binary
    searches and the scan merge must all agree on it, so it must not fork.
    """
    neq = va != vb
    any_neq = neq.any(axis=1)
    first = jnp.argmax(neq, axis=1)
    ad = jnp.take_along_axis(va, first[:, None], axis=1)[:, 0]
    bd = jnp.take_along_axis(vb, first[:, None], axis=1)[:, 0]
    bytecmp = jnp.sign(ad - bd) * any_neq
    return jnp.where(any_neq, bytecmp, lencmp)


def str_cmp_full(qbytes, qlens, pool, off, klen) -> jax.Array:
    """Full strcmp sign; equal padded bytes resolve by length."""
    W = qbytes.shape[1]
    kb = gather_bytes(pool, off, W)
    mask = jnp.arange(W)[None, :] < klen[:, None]
    kv = jnp.where(mask, kb, 0).astype(jnp.int32)
    qv = qbytes.astype(jnp.int32)
    return _cmp_tail(qv, kv, jnp.sign(qlens - klen))


def str_cmp_pools(pool_a, off_a, len_a, pool_b, off_b, len_b,
                  width: int) -> jax.Array:
    """sign(strcmp(a, b)) between entries of TWO flat byte pools.

    Vectorized over (B,) offset/length vectors; both keys are gathered as
    ``width``-byte windows, masked past their true lengths, and compared
    byte-wise with a length tie-break — the same ordering rule as
    :func:`str_cmp_full` (which compares a padded query row against one
    pool).  Used by the delta-aware scan merge to order the live-delta
    stream against the frozen-base stream (DESIGN.md §11).
    """
    ka = gather_bytes(pool_a, off_a, width)
    kb = gather_bytes(pool_b, off_b, width)
    cols = jnp.arange(width)[None, :]
    va = jnp.where(cols < len_a[:, None], ka, 0).astype(jnp.int32)
    vb = jnp.where(cols < len_b[:, None], kb, 0).astype(jnp.int32)
    return _cmp_tail(va, vb, jnp.sign(len_a - len_b))


def _fnv1a(qbytes, qlens) -> jax.Array:
    """Rolling FNV-1a over min(len, width) bytes of each padded row."""
    B, W = qbytes.shape
    h = jnp.full((B,), FNV_OFFSET, jnp.uint32)

    def body(k, h):
        active = qlens > k
        c = qbytes[:, k].astype(jnp.uint32)
        nh = (h ^ c) * FNV_PRIME
        return jnp.where(active, nh, h)

    return jax.lax.fori_loop(0, W, body, h)


def hash16(qbytes, qlens) -> jax.Array:
    """Device mirror of strings.key_hash16 (bit-identical, same width)."""
    h = _fnv1a(qbytes, qlens)
    return ((h ^ (h >> jnp.uint32(16))) & jnp.uint32(0xFFFF)).astype(jnp.int32)


def hash32(qbytes, qlens) -> jax.Array:
    """Full 32-bit rolling hash (delta-buffer hash table)."""
    return _fnv1a(qbytes, qlens)
