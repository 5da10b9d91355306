"""Device-resident LITS: frozen SoA pools + jitted batched operations.

``freeze`` exports a :class:`TensorIndex` (a registered-dataclass pytree of
flat jax arrays) from a host :class:`~repro.core.builder.LITSBuilder`.  All
query-side operations are single jitted functions, composable under
``vmap``/``pjit``/``shard_map``:

* :func:`search_batch`   — paper Alg. 2, batched traversal
* :func:`base_search`    — traversal + terminal resolve, no delta probe
* :func:`rank_batch`     — ordered rank for range scans (binary search)
* :func:`scan_batch`     — delta-aware range scans (read-your-writes: a
  two-way merge of the frozen order with the live delta view, DESIGN.md §11)
* :func:`insert_batch`   — log-structured delta-buffer inserts (DESIGN.md §2)
* :func:`delete_batch`   — delta-buffer tombstones (shadow the frozen base;
  reconciled by :func:`merge_delta`, DESIGN.md §9)
* :func:`lookup_values`  — (lo, hi) 2×int32 value fetch

The traversal mirrors the host builder bit-for-bit: slot positions come from
the same float32 ``positions_impl`` the builder used at build time.  The
walk itself lives in :mod:`repro.core.walk`, the string primitives in
:mod:`repro.core.strops`.

.. note:: **Legacy surface.**  These free functions are the jitted
   primitives underneath :class:`repro.index.StringIndex` (DESIGN.md §8) —
   the supported application API that owns config resolution, batch
   planning, auto-compaction and snapshots.  New call sites should go
   through the facade; this module stays stable as the kernel-level seam
   the facade (and power users) compose.

"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .builder import (
    LITSBuilder,
    TAG_CNODE,
    TAG_EMPTY,
    TAG_ENTRY,
    TAG_MNODE,
    TAG_TRIE,
    PAYLOAD_BITS,
    PAYLOAD_MASK,
)
from .hpt import MAX_CDF_STEPS, get_cdf_impl
from .walk import rank_sorted, resolve_terminal, scan_merged, walk_terminal
from .strops import (
    gather_bytes as _gather_bytes, hash32 as _hash32, str_eq as _str_eq,
)


# the non-pytree (static) fields of TensorIndex — shared by everything that
# walks the dataclass generically (shard stacking/slicing, mesh placement,
# snapshot headers) so a new static field can't be missed in one copy
STATIC_FIELDS = ("width", "max_iters", "cnode_cap", "rank_iters",
                 "delta_probes", "cdf_steps")


@partial(
    jax.tree_util.register_dataclass,
    data_fields=[
        "items", "mn_slot_base", "mn_slot_cnt", "mn_prefix_off", "mn_prefix_len",
        "mn_alpha", "mn_beta", "cn_base", "cn_cnt", "ch_hash", "ch_ent",
        "tr_byte", "tr_mask", "tr_left", "tr_right",
        "key_bytes", "ent_off", "ent_len", "ent_val_lo", "ent_val_hi",
        "ent_sorted", "cdf_tab", "prob_tab", "root_item",
        "db_bytes", "db_used", "de_off", "de_len", "de_val_lo", "de_val_hi",
        "de_hash", "de_tomb", "de_count", "dh_slot", "ds_order",
        "delta_overflow", "epoch",
    ],
    meta_fields=list(STATIC_FIELDS),
)
@dataclasses.dataclass
class TensorIndex:
    # base structure
    items: jax.Array
    mn_slot_base: jax.Array
    mn_slot_cnt: jax.Array
    mn_prefix_off: jax.Array
    mn_prefix_len: jax.Array
    mn_alpha: jax.Array
    mn_beta: jax.Array
    cn_base: jax.Array
    cn_cnt: jax.Array
    ch_hash: jax.Array
    ch_ent: jax.Array
    tr_byte: jax.Array
    tr_mask: jax.Array
    tr_left: jax.Array
    tr_right: jax.Array
    key_bytes: jax.Array
    ent_off: jax.Array
    ent_len: jax.Array
    ent_val_lo: jax.Array
    ent_val_hi: jax.Array
    ent_sorted: jax.Array
    cdf_tab: jax.Array
    prob_tab: jax.Array
    root_item: jax.Array
    # delta buffer (log-structured device inserts)
    db_bytes: jax.Array
    db_used: jax.Array
    de_off: jax.Array
    de_len: jax.Array
    de_val_lo: jax.Array
    de_val_hi: jax.Array
    de_hash: jax.Array
    de_tomb: jax.Array           # per-entry tombstone flag (DELETE support)
    de_count: jax.Array
    dh_slot: jax.Array
    # incrementally-sorted view of the claimed delta region (DESIGN.md §11):
    # ds_order[:de_count] lists delta entry ids in lexicographic key order
    # (tombstones included — the scan merge consumes them to shadow base
    # entries).  Maintained by _mutate_batch, reset by merge_delta/freeze.
    ds_order: jax.Array
    delta_overflow: jax.Array
    # compaction epoch: increments at every merge_delta (snapshot format v3).
    # A data field (device scalar), NOT static metadata — a static field
    # would bake the epoch into every jit cache key and recompile the whole
    # op surface once per compaction.
    epoch: jax.Array
    # static metadata
    width: int
    max_iters: int
    cnode_cap: int
    rank_iters: int
    delta_probes: int
    cdf_steps: int

    @property
    def n_entries(self) -> int:
        return self.ent_off.shape[0]

    def nbytes(self) -> int:
        return sum(
            x.size * x.dtype.itemsize
            for x in jax.tree_util.tree_leaves(self)
            if hasattr(x, "dtype")
        )


# ---------------------------------------------------------------------------
# freeze
# ---------------------------------------------------------------------------

def _nz(a: np.ndarray, dtype) -> jnp.ndarray:
    """Pool view as a device array, padded to at least one element."""
    a = np.asarray(a, dtype=dtype)
    if a.shape[0] == 0:
        a = np.zeros(1, dtype=dtype)
    return jnp.asarray(a)


def freeze(
    b: LITSBuilder,
    delta_capacity: int = 4096,
    delta_bytes: int | None = None,
    delta_probes: int = 16,
    epoch: int = 0,
) -> TensorIndex:
    # both the height bound and the sorted entry order come from the
    # builder's incremental caches (exact after bulkload; maintained
    # per-dirty-subtree by insert_many/delete_many) — a merge refreeze
    # therefore costs O(touched sub-tries + memcpy), not an O(n) Python walk
    heights = b.height_bound()
    max_iters = int(heights["base"] + heights["trie"] + 4)
    n = max(b.ent_off.n, 1)
    rank_iters = int(math.ceil(math.log2(n))) + 2
    ent_sorted = np.asarray(b.sorted_eids(), dtype=np.int32)
    if ent_sorted.size == 0:
        ent_sorted = np.zeros(1, np.int32)
    key_pool = np.concatenate([b.key_bytes.view(), np.zeros(b.width + 1, np.uint8)])
    dcap = max(delta_capacity, 8)
    hcap = 1 << int(math.ceil(math.log2(dcap * 2)))
    dbcap = delta_bytes if delta_bytes is not None else dcap * max(b.width, 16) + b.width
    return TensorIndex(
        items=_nz(b.items.view(), np.int32),
        mn_slot_base=_nz(b.mn_slot_base.view(), np.int32),
        mn_slot_cnt=_nz(b.mn_slot_cnt.view(), np.int32),
        mn_prefix_off=_nz(b.mn_prefix_off.view(), np.int32),
        mn_prefix_len=_nz(b.mn_prefix_len.view(), np.int32),
        mn_alpha=_nz(b.mn_alpha.view(), np.float32),
        mn_beta=_nz(b.mn_beta.view(), np.float32),
        cn_base=_nz(b.cn_base.view(), np.int32),
        cn_cnt=_nz(b.cn_cnt.view(), np.int32),
        ch_hash=_nz(b.ch_hash.view().astype(np.int32), np.int32),
        ch_ent=_nz(b.ch_ent.view(), np.int32),
        tr_byte=_nz(b.tr_byte.view(), np.int32),
        tr_mask=_nz(b.tr_mask.view().astype(np.int32), np.int32),
        tr_left=_nz(b.tr_left.view(), np.int32),
        tr_right=_nz(b.tr_right.view(), np.int32),
        key_bytes=jnp.asarray(key_pool),
        ent_off=_nz(b.ent_off.view().astype(np.int32), np.int32),
        ent_len=_nz(b.ent_len.view(), np.int32),
        ent_val_lo=_nz((b.ent_val.view() & 0xFFFFFFFF).astype(np.uint32).view(np.int32), np.int32),
        ent_val_hi=_nz((b.ent_val.view() >> 32).astype(np.int32), np.int32),
        ent_sorted=jnp.asarray(ent_sorted),
        cdf_tab=jnp.asarray(b.hpt.cdf_tab if b.hpt is not None else np.zeros((1, 128), np.float32)),
        prob_tab=jnp.asarray(b.hpt.prob_tab if b.hpt is not None else np.full((1, 128), 1 / 128, np.float32)),
        root_item=jnp.asarray(np.int32(b.root_item)),
        db_bytes=jnp.zeros(dbcap, jnp.uint8),
        db_used=jnp.asarray(np.int32(0)),
        de_off=jnp.zeros(dcap, jnp.int32),
        de_len=jnp.zeros(dcap, jnp.int32),
        de_val_lo=jnp.zeros(dcap, jnp.int32),
        de_val_hi=jnp.zeros(dcap, jnp.int32),
        de_hash=jnp.zeros(dcap, jnp.uint32),
        de_tomb=jnp.zeros(dcap, bool),
        de_count=jnp.asarray(np.int32(0)),
        dh_slot=jnp.full(hcap, -1, jnp.int32),
        ds_order=jnp.zeros(dcap, jnp.int32),
        delta_overflow=jnp.asarray(False),
        epoch=jnp.asarray(np.int32(epoch)),
        width=int(b.width),
        max_iters=max_iters,
        cnode_cap=int(b.cfg.cnode_cap),
        rank_iters=rank_iters,
        delta_probes=delta_probes,
        cdf_steps=int(min(max(getattr(b, 'max_suffix_len', b.width), 1), MAX_CDF_STEPS)),
    )


def pack_queries(keys, width: int) -> np.ndarray:
    """Host helper: list[bytes] -> ONE (B, width + 4) uint8 buffer, a get
    group's whole device input: each row is the key's first ``width``
    bytes, zero-padded, then its length clipped to ``width + 1`` as 4
    little-endian bytes (:func:`unpack_queries` splits it on the device).

    The ``width + 1`` length is an over-width SENTINEL, not a length.  No
    stored key can have it (the host builder rejects over-width keys and
    :func:`insert_batch` refuses them), so ``_str_eq``'s length comparison
    makes an over-width query miss every stored key — device search
    degrades to a clean not-found instead of matching a truncated alias.
    """
    limit = width + 1
    rows = b"".join([k[:width].ljust(width, b"\0")
                     + min(len(k), limit).to_bytes(4, "little")
                     for k in keys])
    return np.frombuffer(bytearray(rows), np.uint8).reshape(
        len(keys), width + 4)


def pad_queries(keys, width: int) -> Tuple[np.ndarray, np.ndarray]:
    """Host helper: list[bytes] -> zero-padded (B, width) uint8 + lengths
    clipped to the ``width + 1`` sentinel (see :func:`pack_queries`)."""
    buf = pack_queries(keys, width)
    lens = np.ascontiguousarray(buf[:, width:]).view("<i4").reshape(-1)
    return np.ascontiguousarray(buf[:, :width]), lens.astype(np.int32)


def unpack_queries(packed: jax.Array, width: int):
    """Device side of :func:`pack_queries`: ``(qbytes, qlens)``, the
    (B, width) uint8 key bytes and the int32 lengths."""
    lb = packed[:, width:].astype(jnp.int32)
    qlens = lb[:, 0] | lb[:, 1] << 8 | lb[:, 2] << 16 | lb[:, 3] << 24
    return packed[:, :width], qlens


def _tag(item: jax.Array) -> jax.Array:
    return jax.lax.shift_right_logical(item, PAYLOAD_BITS) & 0x7


def _payload(item: jax.Array) -> jax.Array:
    return item & PAYLOAD_MASK


# ---------------------------------------------------------------------------
# search
# ---------------------------------------------------------------------------

def _traverse(ti: TensorIndex, qbytes: jax.Array, qlens: jax.Array):
    """Tagged-handle walk to terminal items (shared impl: core.walk).

    Returns ``(item, iters, model_iters)``: the terminal items and the
    walk's two iteration counts (see :func:`repro.core.walk.walk_terminal`).
    """
    item, _levels, iters, model_iters = walk_terminal(
        qbytes, qlens, ti.root_item,
        ti.items, ti.mn_slot_base, ti.mn_slot_cnt, ti.mn_prefix_off,
        ti.mn_prefix_len, ti.mn_alpha, ti.mn_beta,
        ti.tr_byte, ti.tr_mask, ti.tr_left, ti.tr_right,
        ti.key_bytes, ti.cdf_tab, ti.prob_tab,
        width=ti.width, max_iters=ti.max_iters, cdf_steps=ti.cdf_steps,
    )
    return item, iters, model_iters


def _resolve_terminal(ti: TensorIndex, qbytes, qlens, item):
    """EMPTY/ENTRY/CNODE -> (found, eid, rounds) (shared impl: core.walk)."""
    return resolve_terminal(
        qbytes, qlens, item,
        ti.cn_base, ti.cn_cnt, ti.ch_hash, ti.ch_ent,
        ti.key_bytes, ti.ent_off, ti.ent_len,
        cnode_cap=ti.cnode_cap,
    )


def _delta_lookup(ti: TensorIndex, qbytes, qlens):
    """Probe the delta buffer: (found, delta_entry_id)."""
    B = qbytes.shape[0]
    qh = _hash32(qbytes, qlens)
    hcap = ti.dh_slot.shape[0]

    def body(p, carry):
        found, did = carry
        slot = ((qh + p.astype(jnp.uint32)) & jnp.uint32(hcap - 1)).astype(jnp.int32)
        de = jnp.take(ti.dh_slot, slot)
        valid = de >= 0
        dei = jnp.maximum(de, 0)
        hm = valid & (jnp.take(ti.de_hash, dei) == qh)
        eq = hm & _str_eq(
            qbytes, qlens, ti.db_bytes, jnp.take(ti.de_off, dei), jnp.take(ti.de_len, dei)
        )
        take = eq & ~found
        return found | eq, jnp.where(take, de, did)

    return jax.lax.fori_loop(
        0, ti.delta_probes, body, (jnp.zeros(B, bool), jnp.full(B, -1, jnp.int32))
    )


# ---------------------------------------------------------------------------
# point lookup
# ---------------------------------------------------------------------------

def base_search_impl(ti: TensorIndex, qbytes, qlens):
    """Traversal + terminal resolve over the frozen base index (no delta probe).

    Traceable (usable inside jit / shard_map).  Returns
    ``(found, eid, iters, model_iters, rounds)``: the walk's two iteration
    counts (:func:`repro.core.walk.walk_terminal`) and the terminal
    resolve's compare rounds (:func:`repro.core.walk.resolve_terminal`).
    """
    item, iters, model_iters = _traverse(ti, qbytes, qlens)
    found, eid, rounds = _resolve_terminal(ti, qbytes, qlens, item)
    return found, eid, iters, model_iters, rounds


@jax.jit
def base_search(ti: TensorIndex, qbytes: jax.Array, qlens: jax.Array):
    """Jitted :func:`base_search_impl` (snapshot search, delta skipped)."""
    return base_search_impl(ti, qbytes, qlens)[:2]


def search_impl(ti: TensorIndex, qbytes, qlens):
    """Point lookup with the delta probe, traceable: ``(found, eid,
    is_delta, iters, model_iters, rounds)``, the counters those of
    :func:`base_search_impl`.

    The delta-buffer probe runs ahead of the base walk.  Tombstoned delta
    entries (see :func:`delete_batch`) shadow their base key: such queries
    report not-found.
    """
    dfound, did = _delta_lookup(ti, qbytes, qlens)
    # a tombstoned delta entry SHADOWS the base: the key is absent until a
    # put resurrects it or merge_delta reconciles the delete (DESIGN.md §9)
    dtomb = dfound & jnp.take(ti.de_tomb, jnp.maximum(did, 0))
    bfound, beid, iters, model_iters, rounds = base_search_impl(
        ti, qbytes, qlens)
    found = jnp.where(dfound, ~dtomb, bfound)
    eid = jnp.where(dfound, did, beid)
    return found, eid, dfound & ~dtomb, iters, model_iters, rounds


@jax.jit
def search_batch(ti: TensorIndex, qbytes: jax.Array, qlens: jax.Array):
    """Batched point lookup (:func:`search_impl`): (found, eid, is_delta)."""
    return search_impl(ti, qbytes, qlens)[:3]


@jax.jit
def lookup_values(ti: TensorIndex, eid: jax.Array, is_delta: jax.Array):
    e = jnp.maximum(eid, 0)
    base_lo = jnp.take(ti.ent_val_lo, jnp.minimum(e, ti.ent_val_lo.shape[0] - 1))
    base_hi = jnp.take(ti.ent_val_hi, jnp.minimum(e, ti.ent_val_hi.shape[0] - 1))
    d_lo = jnp.take(ti.de_val_lo, jnp.minimum(e, ti.de_val_lo.shape[0] - 1))
    d_hi = jnp.take(ti.de_val_hi, jnp.minimum(e, ti.de_val_hi.shape[0] - 1))
    return (
        jnp.where(is_delta, d_lo, base_lo),
        jnp.where(is_delta, d_hi, base_hi),
    )


@jax.jit
def _search_batch_jit(ti: TensorIndex, packed: jax.Array):
    """A get group's one program: :func:`pack_queries` rows in, ONE
    (B + 1, 3) int32 array out — rows ``(found, value lo, value hi)`` per
    query, then the counters ``(iters, model_iters, rounds)``."""
    qbytes, qlens = unpack_queries(packed, ti.width)
    found, eid, isd, iters, model_iters, rounds = search_impl(
        ti, qbytes, qlens)
    lo, hi = lookup_values(ti, eid, isd)        # inlined into this program
    return jnp.concatenate([
        jnp.stack([found.astype(jnp.int32), lo, hi], axis=1),
        jnp.stack([iters, model_iters, rounds]).astype(jnp.int32)[None]])


# ---------------------------------------------------------------------------
# ordered rank + scan (over the frozen sorted entry order)
# ---------------------------------------------------------------------------

@jax.jit
def rank_batch(ti: TensorIndex, qbytes: jax.Array,
               qlens: jax.Array) -> jax.Array:
    """First rank r such that key(ent_sorted[r]) >= query (binary search,
    :func:`repro.core.walk.rank_sorted`)."""
    return rank_sorted(
        qbytes, qlens, ti.ent_sorted, ti.ent_off, ti.ent_len, ti.key_bytes,
        rank_iters=ti.rank_iters,
    )


def _scan_n_base(ti: TensorIndex) -> jax.Array:
    """Live frozen-entry count for the scan merge: an EMPTY root means zero
    live base entries — ``ent_sorted`` then holds only the freeze pad
    sentinel (pools cannot be zero-sized), which must not scan.  The delta
    stream is NOT gated on this: a delta-only index (empty base, live
    delta) scans its unmerged inserts."""
    return jnp.where(ti.root_item != 0,
                     jnp.int32(ti.ent_sorted.shape[0]), jnp.int32(0))


@partial(jax.jit, static_argnames=("window",))
def _scan_batch_jit(ti: TensorIndex, qbytes: jax.Array, qlens: jax.Array,
                    window: int):
    return scan_merged(
        qbytes, qlens,
        ti.ent_sorted, ti.ent_off, ti.ent_len, ti.key_bytes, _scan_n_base(ti),
        ti.ds_order, ti.de_off, ti.de_len, ti.db_bytes, ti.de_tomb,
        ti.de_count, window=window, rank_iters=ti.rank_iters)


def scan_batch(ti: TensorIndex, qbytes: jax.Array, qlens: jax.Array,
               window: int = 16):
    """Delta-aware range scan: the next ``window`` keys >= query in the LIVE
    index order — read-your-writes (DESIGN.md §11).

    Returns ``(eids, valid, is_delta)``, each ``(B, window)``: a two-way
    merge of the frozen ``ent_sorted`` window with the sorted live-delta
    view, where unmerged delta inserts appear immediately and tombstoned
    keys are suppressed (a tombstone shadows its base entry; a resurrected
    put serves the delta value).  ``eids`` indexes the base entry pools
    where ``~is_delta`` and the delta pools where ``is_delta`` — exactly
    the :func:`lookup_values` contract, so value fetch is unchanged.
    """
    return _scan_batch_jit(ti, qbytes, qlens, window)


# ---------------------------------------------------------------------------
# delta-buffer inserts (log-structured; host merge = minor compaction)
# ---------------------------------------------------------------------------

def _delta_sort_order_impl(db_bytes, de_off, de_len, de_count,
                           width: int) -> jax.Array:
    """Sorted view of the claimed delta region: entry ids in key order.

    Keys are gathered as zero-masked ``width``-byte windows, packed 4 bytes
    per big-endian uint32 word (order-preserving), and lexsorted with the
    true length as the final tie-break — exactly the ``str_cmp_full``
    ordering rule (padded bytes first, then length), so ranks computed by
    :func:`repro.core.walk.rank_sorted` over this view agree with the
    frozen ``ent_sorted`` order.  Unclaimed tail slots (``>= de_count``)
    carry a claimed-last major key and never rank inside the live region.
    """
    dcap = de_off.shape[0]
    kb = _gather_bytes(db_bytes, de_off, width)
    cols = jnp.arange(width)[None, :]
    kb = jnp.where(cols < de_len[:, None], kb, 0)
    pad = (-width) % 4
    if pad:
        kb = jnp.concatenate([kb, jnp.zeros((dcap, pad), kb.dtype)], axis=1)
    w = kb.astype(jnp.uint32).reshape(dcap, -1, 4)
    packed = (w[:, :, 0] << 24) | (w[:, :, 1] << 16) | (w[:, :, 2] << 8) \
        | w[:, :, 3]
    unclaimed = (jnp.arange(dcap, dtype=jnp.int32)
                 >= de_count).astype(jnp.int32)
    # LSD radix order: one stable argsort per key, least significant first
    # (length tie-break, then the packed words from the last upwards, then
    # claimed entries first) — the permutation jnp.lexsort gives, but one
    # multi-operand TPU sort of all these keys takes minutes to compile at
    # a 16k-entry delta, and a chain of single-key sorts takes seconds
    keys = (de_len,) + tuple(
        packed[:, i] for i in range(packed.shape[1] - 1, -1, -1)
    ) + (unclaimed,)
    perm = jnp.arange(dcap, dtype=jnp.int32)
    for k in keys:
        perm = perm[jnp.argsort(k[perm], stable=True)]
    return perm


@partial(jax.jit, static_argnames=("width",))
def delta_sort_order(db_bytes, de_off, de_len, de_count, width: int):
    """Jitted :func:`_delta_sort_order_impl` — the snapshot-load seam for
    reconstructing ``ds_order`` from pre-v4 files (no view was stored)."""
    return _delta_sort_order_impl(db_bytes, de_off, de_len, de_count, width)


def _mutate_batch(ti: TensorIndex, kbytes: jax.Array, klens: jax.Array,
                  val_lo: jax.Array, val_hi: jax.Array, is_del: jax.Array):
    """Shared scan under :func:`insert_batch` and :func:`delete_batch`.

    Per-op ``is_del`` selects the mutation: puts upsert (base value update or
    new delta entry, clearing any tombstone — a put on a deleted key
    *resurrects* it); deletes set the tombstone on a matching delta entry, or
    claim a new tombstone entry when the key lives only in the frozen base
    (the base pool is immutable — shadowing is the only way to unpublish).

    Returns ``(new_ti, newly, match, prev_live, rejected)`` with per-op masks:
    ``newly`` — a fresh delta slot was claimed; ``match`` — an existing delta
    entry was hit; ``prev_live`` — that entry was live (not tombstoned)
    before this op; ``rejected`` — the op needed a slot and the pool was
    full (``Status.REJECTED_FULL`` at the facade).
    """
    B, W = kbytes.shape
    item, _iters, _model_iters = _traverse(ti, kbytes, klens)
    bfound, beid, _rounds = _resolve_terminal(ti, kbytes, klens, item)
    # update base values in-place (functional); deletes never touch values —
    # they shadow via the delta buffer so merge_delta can reconcile them
    do_base = bfound & ~is_del
    # ops that update no base entry scatter out of range (dropped): a
    # rewrite of entry 0 with its old value would race a real update of it
    upd_idx = jnp.where(do_base, beid, ti.ent_val_lo.shape[0])
    ent_val_lo = ti.ent_val_lo.at[upd_idx].set(val_lo, mode="drop")
    ent_val_hi = ti.ent_val_hi.at[upd_idx].set(val_hi, mode="drop")
    qh = _hash32(kbytes, klens)
    hcap = ti.dh_slot.shape[0]
    dcap = ti.de_off.shape[0]
    dbcap = ti.db_bytes.shape[0]

    def step(carry, x):
        (dh_slot, db_bytes, db_used, de_off, de_len, de_vlo, de_vhi, de_hash,
         de_tomb, de_count, overflow) = carry
        kb, kl, vlo, vhi, h, in_base, dele = x
        # probe for existing delta entry or first free slot
        def probe(p, pc):
            fslot, match_de, done = pc
            slot = ((h + p.astype(jnp.uint32)) & jnp.uint32(hcap - 1)).astype(jnp.int32)
            de = jnp.take(dh_slot, slot)
            free = de < 0
            dei = jnp.maximum(de, 0)
            key_eq = (~free) & (jnp.take(de_hash, dei) == h)
            # gather (not dynamic_slice): a tail entry whose W-window would
            # poke past the pool must not silently shift its read offset
            off2 = jnp.take(de_off, dei)
            kb2 = jnp.take(
                db_bytes,
                jnp.minimum(off2 + jnp.arange(W, dtype=jnp.int32), dbcap - 1))
            klen2 = jnp.take(de_len, dei)
            mask = jnp.arange(W) < klen2
            key_eq = key_eq & jnp.all(jnp.where(mask, kb2, 0) == kb) & (klen2 == kl)
            new_fslot = jnp.where((fslot < 0) & free, slot, fslot)
            new_match = jnp.where(key_eq & ~done, de, match_de)
            return new_fslot, new_match, done | free | key_eq
        fslot, match_de, _ = jax.lax.fori_loop(
            0, ti.delta_probes, probe, (jnp.int32(-1), jnp.int32(-1), jnp.asarray(False))
        )
        match = match_de >= 0
        mde = jnp.maximum(match_de, 0)
        was_live = match & ~jnp.take(de_tomb, mde)
        # matched entry: a put refreshes value + clears the tombstone
        # (resurrect); a delete sets the tombstone and keeps the stale value
        upd_val = match & ~dele
        de_vlo = de_vlo.at[mde].set(jnp.where(upd_val, vlo, jnp.take(de_vlo, mde)))
        de_vhi = de_vhi.at[mde].set(jnp.where(upd_val, vhi, jnp.take(de_vhi, mde)))
        de_tomb = de_tomb.at[mde].set(jnp.where(match, dele, jnp.take(de_tomb, mde)))
        fits = kl <= W  # over-width keys are unrepresentable: reject, don't truncate
        # a new slot is needed for: put of an unknown key, or delete of a
        # base-resident key with no delta entry yet (tombstone shadow)
        want_new = fits & (~match) & jnp.where(dele, in_base, ~in_base)
        can = want_new & (fslot >= 0) \
            & (de_count < dcap) & (db_used + kl <= dbcap)
        this_overflow = want_new & ~can
        # claim
        did = jnp.where(can, de_count, 0)
        dh_slot = dh_slot.at[jnp.where(can, fslot, hcap)].set(did, mode="drop")
        woff = jnp.where(can, db_used, 0)
        # scatter exactly kl live bytes: a W-wide window write would clamp at
        # the pool tail and corrupt earlier entries once db_used > dbcap - W
        wj = jnp.arange(W, dtype=jnp.int32)
        widx = jnp.where((wj < kl) & can, woff + wj, dbcap)
        db_bytes = db_bytes.at[widx].set(kb, mode="drop")
        de_off = de_off.at[did].set(jnp.where(can, woff, jnp.take(de_off, did)))
        de_len = de_len.at[did].set(jnp.where(can, kl, jnp.take(de_len, did)))
        de_vlo = de_vlo.at[did].set(jnp.where(can, vlo, jnp.take(de_vlo, did)))
        de_vhi = de_vhi.at[did].set(jnp.where(can, vhi, jnp.take(de_vhi, did)))
        de_hash = de_hash.at[did].set(jnp.where(can, h, jnp.take(de_hash, did)))
        de_tomb = de_tomb.at[did].set(jnp.where(can, dele, jnp.take(de_tomb, did)))
        db_used = jnp.where(can, db_used + kl, db_used)
        de_count = jnp.where(can, de_count + 1, de_count)
        ncarry = (dh_slot, db_bytes, db_used, de_off, de_len, de_vlo, de_vhi,
                  de_hash, de_tomb, de_count, overflow | this_overflow)
        return ncarry, (can, match, was_live, this_overflow)

    carry0 = (ti.dh_slot, ti.db_bytes, ti.db_used, ti.de_off, ti.de_len,
              ti.de_val_lo, ti.de_val_hi, ti.de_hash, ti.de_tomb, ti.de_count,
              ti.delta_overflow)
    carry, (newly, match, prev_live, rejected) = jax.lax.scan(
        step, carry0, (kbytes, klens, val_lo, val_hi, qh, bfound, is_del))
    (dh_slot, db_bytes, db_used, de_off, de_len, de_vlo, de_vhi, de_hash,
     de_tomb, de_count, overflow) = carry
    # maintain the sorted delta view (DESIGN.md §11): the claimed KEY SET
    # only changes when a fresh slot was claimed — in-place tombstone
    # toggles and value updates keep the order, so the re-sort is skipped
    ds_order = jax.lax.cond(
        jnp.any(newly),
        lambda: _delta_sort_order_impl(db_bytes, de_off, de_len, de_count, W),
        lambda: ti.ds_order)
    nti = dataclasses.replace(
        ti, ent_val_lo=ent_val_lo, ent_val_hi=ent_val_hi, dh_slot=dh_slot,
        db_bytes=db_bytes, db_used=db_used, de_off=de_off, de_len=de_len,
        de_val_lo=de_vlo, de_val_hi=de_vhi, de_hash=de_hash, de_tomb=de_tomb,
        de_count=de_count, ds_order=ds_order, delta_overflow=overflow,
    )
    return nti, bfound, newly, match, prev_live, rejected


@jax.jit
def insert_batch(ti: TensorIndex, kbytes: jax.Array, klens: jax.Array,
                 val_lo: jax.Array, val_hi: jax.Array):
    """Functional batched insert.

    Keys already in the base index get a value update; new keys go to the
    delta buffer.  A put on a tombstoned key resurrects it (clears the
    tombstone, reported in the inserted mask).  Returns
    (new_ti, inserted_mask, updated_mask).

    Keys longer than the index width (``klens > width``, the ``pad_queries``
    truncation sentinel) are REJECTED rather than stored truncated: a
    truncated alias would hash/compare equal to every other long key sharing
    its first ``width`` bytes and would corrupt :func:`merge_delta` (which
    replays the stored byte length).  This mirrors the host builder, where
    ``LITSBuilder.insert`` raises for over-width keys.  Byte-pool capacity is
    gated on the key's true length ``kl`` (not the padded width), so inserts
    that fit are no longer spuriously rejected near a full pool.
    """
    B = kbytes.shape[0]
    nti, in_base, newly, match, prev_live, _rej = _mutate_batch(
        ti, kbytes, klens, val_lo, val_hi, jnp.zeros(B, bool))
    ins = newly | (match & ~prev_live)          # fresh key or resurrect
    upd = prev_live | (in_base & ~match)        # live somewhere -> overwrite
    return nti, ins, upd


@jax.jit
def delete_batch(ti: TensorIndex, kbytes: jax.Array, klens: jax.Array):
    """Functional batched delete via delta-buffer tombstones (DESIGN.md §9).

    A key living in the delta buffer gets its tombstone flag set in place; a
    key living only in the frozen base claims a NEW delta entry carrying the
    tombstone (the base pool is immutable — the shadow is reconciled by
    :func:`merge_delta`, which replays tombstones as ``builder.delete``).
    Absent (or already-deleted) keys are a no-op.

    Returns (new_ti, deleted_mask, rejected_mask): ``deleted`` marks keys
    that existed and are now unpublished; ``rejected`` marks deletes that
    needed a tombstone slot when the delta pool was full (retry after
    compaction).  Over-width keys can never be stored, so they come back
    with both masks False (absent).
    """
    B = kbytes.shape[0]
    z = jnp.zeros(B, jnp.int32)
    nti, _in_base, newly, _match, prev_live, rejected = _mutate_batch(
        ti, kbytes, klens, z, z, jnp.ones(B, bool))
    return nti, newly | prev_live, rejected


def delta_fill_fraction(ti: TensorIndex) -> float:
    """Delta entry fill fraction — **forces a blocking device sync**.

    Hot paths (service stats polling, compaction policy) must use the
    host-side mirror instead (``StringIndex.delta_fill``, maintained by
    every mutating facade op); this function remains the legacy seam for
    code holding a bare :class:`TensorIndex`.
    """
    return float(jax.device_get(ti.de_count)) / ti.de_off.shape[0]


def merge_delta(builder: LITSBuilder, ti: TensorIndex, *,
                sync_base_values: bool = False) -> TensorIndex:
    """Minor compaction: bulk-replay the delta into the host builder, re-freeze.

    The replay is vectorized end to end (DESIGN.md §10):

    * ONE bundled scalar sync (``de_count``/``db_used``/``epoch``), then one
      ``device_get`` of the **live delta region only** — device-side slices,
      never the full pools;
    * tombstones replay as one ``builder.delete_many``, live entries as one
      upserting ``builder.insert_many`` — both defer the Alg. 3
      incCount/resize pass so a hot sub-trie rebuilds once per merge
      (``_rebuild_at`` stays sub-trie-local), and both maintain the
      builder's incremental sorted-order/height caches;
    * the refreeze is therefore *partial*: :func:`freeze` reuses those
      caches, so merge cost scales with the touched sub-tries (plus pool
      memcpys), not with index size.

    ``sync_base_values=True`` copies the device-resident base values
    (``ent_val_lo/hi`` — updated in place by :func:`insert_batch` for
    base-hit puts) back into the builder first.  Callers whose builder is in
    eid-lockstep with ``ti`` (every freeze-lineage builder) MUST pass it or
    in-place base updates silently revert at the merge; a builder freshly
    reconstructed from the live pools already carries current values.

    The returned index starts an empty delta buffer and carries
    ``epoch = ti.epoch + 1``.
    """
    cnt, used, epoch = (int(x) for x in jax.device_get(
        (ti.de_count, ti.db_used, ti.epoch)))
    if sync_base_values:
        # clamp to the overlap: after an aborted partial replay the builder
        # may hold MORE entries than ``ti`` exported — those never existed
        # on device, so their host values are already current
        n = min(builder.ent_val.n, ti.ent_val_lo.shape[0])
        if n:
            lo, hi = jax.device_get((ti.ent_val_lo[:n], ti.ent_val_hi[:n]))
            lo64 = np.asarray(lo, np.int32).view(np.uint32).astype(np.int64)
            hi64 = np.asarray(hi, np.int32).astype(np.int64)
            builder.ent_val.data[:n] = (hi64 << 32) | lo64
    if cnt:
        db, offs, lens, vlo, vhi, tomb = (np.asarray(x) for x in jax.device_get((
            ti.db_bytes[: max(used, 1)], ti.de_off[:cnt], ti.de_len[:cnt],
            ti.de_val_lo[:cnt], ti.de_val_hi[:cnt], ti.de_tomb[:cnt])))
        keys = [db[offs[i]: offs[i] + lens[i]].tobytes() for i in range(cnt)]
        vals = (vhi.astype(np.int64) << 32) \
            | vlo.view(np.uint32).astype(np.int64)
        tl = tomb.tolist()
        dead = [k for k, t in zip(keys, tl) if t]
        if dead:
            builder.delete_many(dead)
        live = ~tomb
        if live.any():
            builder.insert_many([k for k, t in zip(keys, tl) if not t],
                                vals[live])
    new_ti = freeze(builder, delta_capacity=ti.de_off.shape[0],
                    delta_bytes=ti.db_bytes.shape[0],
                    delta_probes=ti.delta_probes, epoch=epoch + 1)
    return new_ti
