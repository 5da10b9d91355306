"""The Alg. 2 walk over flat pools — ONE implementation for both backends.

``walk_terminal`` (tagged dispatch + HPT-CDF locate + critbit step, with the
early-exit convergence loop and per-query level counter) and
``resolve_terminal`` (cnode h-pointer hash scan, then one key-byte compare
per candidate) operate on flat arrays, so the exact same traced code runs

* in the jnp reference backend (:mod:`repro.core.tensor_index` unpacks the
  ``TensorIndex`` pytree), and
* inside the fused Pallas kernel body (:mod:`repro.kernels.traverse` loads
  the same pools from VMEM refs).

This is what makes the backend bit-identity contract (DESIGN.md §7)
structural: there is no second copy of the traversal to drift.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .builder import (
    PAYLOAD_BITS,
    PAYLOAD_MASK,
    TAG_CNODE,
    TAG_ENTRY,
    TAG_MNODE,
    TAG_TRIE,
)
from .hpt import positions_impl
from repro.kernels.strops import (
    hash16, str_cmp_full, str_cmp_pools, str_cmp_prefix, str_eq,
)


def item_tag(item: jax.Array) -> jax.Array:
    return jax.lax.shift_right_logical(item, PAYLOAD_BITS) & 0x7


def item_payload(item: jax.Array) -> jax.Array:
    return item & PAYLOAD_MASK


def walk_terminal(
    qbytes, qlens, root_item,
    items, mn_slot_base, mn_slot_cnt, mn_prefix_off, mn_prefix_len,
    mn_alpha, mn_beta, tr_byte, tr_mask, tr_left, tr_right,
    key_bytes, cdf_tab, prob_tab,
    *, width: int, max_iters: int, cdf_steps: int,
):
    """Run the tagged-handle walk until every query sits on a terminal item.

    Returns ``(item, levels, iters, model_iters)``: the terminal item per
    query, the number of levels each query stayed active (roofline
    accounting), and two scalars, the loop iterations the batch ran and how
    many of them ran the model-node step.  The ``while_loop`` exits as soon
    as no query is on a MNODE/TRIE, so a converged batch stops paying
    per-level cost.

    The model-node step (prefix compare, HPT CDF, slot position, ``items``
    gather) runs only in iterations where some query sits on a MNODE: its
    ``cdf_steps``-long CDF loop is the costliest part of an iteration, and
    the deep tail of a walk is critbit sub-trie levels, whose children are
    never model nodes.  Skipping it changes no result, because a query off
    a MNODE never takes ``mnext``.
    """
    B = qbytes.shape[0]
    item0 = jnp.broadcast_to(root_item, (B,)).astype(jnp.int32)

    def model_step(item):
        # ---- model-based node step (paper Alg. 2 `locate`) ----
        nid = jnp.minimum(item_payload(item), mn_slot_base.shape[0] - 1)
        pl = jnp.take(mn_prefix_len, nid)
        poff = jnp.take(mn_prefix_off, nid)
        m = jnp.take(mn_slot_cnt, nid)
        base = jnp.take(mn_slot_base, nid)
        cmp = str_cmp_prefix(qbytes, key_bytes, poff, pl)
        pos = positions_impl(
            cdf_tab, prob_tab, qbytes, qlens, pl,
            jnp.take(mn_alpha, nid), jnp.take(mn_beta, nid), m,
            max_steps=cdf_steps,  # §Perf H3: walk only as far as the
        )                         # longest mnode suffix actually stored
        pos = jnp.where(cmp < 0, 0, jnp.where(cmp > 0, m - 1, pos))
        return jnp.take(items, jnp.minimum(base + pos, items.shape[0] - 1))

    def cond(state):
        i, item, _, _ = state
        tag = item_tag(item)
        return (i < max_iters) & jnp.any((tag == TAG_MNODE) | (tag == TAG_TRIE))

    def body(state):
        i, item, levels, model_iters = state
        tag = item_tag(item)
        pay = item_payload(item)
        active = (tag == TAG_MNODE) | (tag == TAG_TRIE)
        on_model = jnp.any(tag == TAG_MNODE)
        mnext = jax.lax.cond(on_model, model_step, lambda it: it, item)
        # ---- critbit subtrie step ----
        tid = jnp.minimum(pay, tr_byte.shape[0] - 1)
        cb = jnp.take(tr_byte, tid)
        mk = jnp.take(tr_mask, tid)
        qc = jnp.take_along_axis(
            qbytes, jnp.minimum(cb, width - 1)[:, None], axis=1)[:, 0]
        qc = jnp.where(cb < jnp.minimum(qlens, width), qc.astype(jnp.int32), 0)
        bit = (qc & mk) != 0
        tnext = jnp.where(bit, jnp.take(tr_right, tid), jnp.take(tr_left, tid))
        item = jnp.where(tag == TAG_MNODE, mnext,
                         jnp.where(tag == TAG_TRIE, tnext, item))
        return (i + 1, item, levels + active.astype(jnp.int32),
                model_iters + on_model.astype(jnp.int32))

    iters, item, levels, model_iters = jax.lax.while_loop(
        cond, body, (jnp.int32(0), item0, jnp.zeros((B,), jnp.int32),
                     jnp.int32(0)))
    return item, levels, iters, model_iters


def resolve_terminal(
    qbytes, qlens, item,
    cn_base, cn_cnt, ch_hash, ch_ent, key_bytes, ent_off, ent_len,
    *, cnode_cap: int,
):
    """EMPTY/ENTRY/CNODE terminal item -> ``(found, eid, rounds)``.

    The 16-bit h-pointer hashes pick the candidates first: an ENTRY's one
    candidate is its own entry; a CNODE's are the slots among its first
    ``cnt`` (at most ``cnode_cap``) whose hash equals the query's, in slot
    order; an EMPTY item, or a CNODE with no hash match, has none and
    misses without touching a key byte.  Each round then gathers the key
    bytes of every lane's next candidate once and compares them
    (``str_eq``); a lane stops at its first equal candidate.  A lane goes
    on to a second round only if a candidate hashed equal but compared
    unequal, so ``rounds`` (a scalar: the ``while_loop``'s iterations) is 1
    for a batch with a hit and no 16-bit collision, and 0 for a batch with
    no candidate at all.
    """
    tag = item_tag(item)
    pay = item_payload(item)
    is_ent = tag == TAG_ENTRY
    cid = jnp.minimum(pay, cn_base.shape[0] - 1)
    base = jnp.take(cn_base, cid)
    cnt = jnp.where(tag == TAG_CNODE, jnp.take(cn_cnt, cid), 0)
    qh = hash16(qbytes, qlens)
    # (B, cnode_cap) candidates: the h-pointer hash scan, int gathers only;
    # an ENTRY is a one-slot node with no hash to check
    j = jnp.arange(cnode_cap, dtype=jnp.int32)[None, :]
    sidx = jnp.minimum(base[:, None] + j, ch_hash.shape[0] - 1)
    hmatch = (j < cnt[:, None]) & (jnp.take(ch_hash, sidx) == qh[:, None])
    pend = hmatch | (is_ent[:, None] & (j == 0))

    def cond(st):
        return jnp.any(st[1])

    def body(st):
        rounds, pend, found, eid = st
        live = jnp.any(pend, axis=1)
        slot = jnp.argmax(pend, axis=1).astype(jnp.int32)
        cand = jnp.where(is_ent, pay, jnp.take(
            ch_ent, jnp.minimum(base + slot, ch_ent.shape[0] - 1)))
        ce = jnp.minimum(cand, ent_off.shape[0] - 1)
        eq = live & str_eq(qbytes, qlens, key_bytes,
                           jnp.take(ent_off, ce), jnp.take(ent_len, ce))
        # a hit ends the lane; a collision moves it to its next candidate
        pend = pend & (j != slot[:, None]) & ~eq[:, None]
        return rounds + 1, pend, found | eq, jnp.where(eq, ce, eid)

    B = qbytes.shape[0]
    rounds, _, found, eid = jax.lax.while_loop(
        cond, body, (jnp.int32(0), pend, jnp.zeros((B,), bool),
                     jnp.full((B,), -1, jnp.int32)))
    return found, eid, rounds


def rank_sorted(
    qbytes, qlens, ent_sorted, ent_off, ent_len, key_bytes,
    *, rank_iters: int, n_live=None,
):
    """First rank r such that key(ent_sorted[r]) >= query (binary search).

    Flat-pool implementation shared by the jnp reference (`rank_batch`) and
    the fused Pallas rank kernel (:mod:`repro.kernels.rank`) — the same
    structural bit-identity contract as ``walk_terminal`` (DESIGN.md §7).

    ``n_live`` (a traced scalar) bounds the search to the first ``n_live``
    rows of ``ent_sorted`` — used by the delta-aware scan to rank into the
    live region of the incrementally-sorted delta view, whose tail slots
    are unclaimed.  ``None`` (the default) searches the whole table and
    traces exactly as before, so the base-rank path is unchanged.
    """
    B = qbytes.shape[0]
    n = ent_sorted.shape[0]
    lo = jnp.zeros(B, jnp.int32)
    hi = jnp.full(B, n, jnp.int32) if n_live is None else \
        jnp.broadcast_to(n_live.astype(jnp.int32), (B,))

    def body(_, carry):
        lo, hi = carry
        mid = (lo + hi) // 2
        e = jnp.take(ent_sorted, jnp.minimum(mid, n - 1))
        cmp = str_cmp_full(
            qbytes, qlens, key_bytes, jnp.take(ent_off, e), jnp.take(ent_len, e)
        )
        go_right = (cmp > 0) & (lo < hi)
        nlo = jnp.where(go_right, mid + 1, lo)
        nhi = jnp.where(go_right | (lo >= hi), hi, mid)
        return nlo, nhi

    lo, _ = jax.lax.fori_loop(0, rank_iters, body, (lo, hi))
    return lo


def delta_rank_iters(dcap: int) -> int:
    """Binary-search trip count covering a delta pool of ``dcap`` slots."""
    import math

    return int(math.ceil(math.log2(max(dcap, 2)))) + 2


def scan_merged(
    qbytes, qlens,
    ent_sorted, ent_off, ent_len, key_bytes, n_base,
    ds_order, de_off, de_len, db_bytes, de_tomb, n_delta,
    *, window: int, rank_iters: int,
):
    """Delta-aware range scan: two-way merge of the frozen order and the
    live delta view (DESIGN.md §11).

    The frozen stream is ``ent_sorted[rank(q):n_base]`` (``n_base`` is a
    traced scalar — 0 for an EMPTY root, where ``ent_sorted`` holds only
    the freeze pad sentinel); the delta stream is ``ds_order[rank(q):
    n_delta]``, the incrementally-sorted view over ALL claimed delta
    entries (live inserts and tombstones).  The merge rule:

    * a delta entry whose key equals the base candidate SHADOWS it (both
      pointers advance; the delta entry is emitted if live, swallowed if
      tombstoned) — this is how deletes hide base keys and resurrected
      puts serve their fresh value;
    * a strictly-smaller live delta entry is emitted (unmerged insert,
      visible immediately); a strictly-smaller tombstone is skipped (a
      delete of a delta-only key);
    * otherwise the base entry is emitted.

    Runs as ONE ``while_loop`` over the whole batch with an early-exit
    condition (a lane stops once its window is full or both streams are
    exhausted), so a converged batch stops paying per-step cost — the same
    shape as ``walk_terminal``.  Shared verbatim by the jnp reference
    (:func:`repro.core.tensor_index.scan_batch`) and the fused Pallas scan
    kernel (:mod:`repro.kernels.scan`): backend bit-identity is structural.

    Returns ``(eids, valid, is_delta)``, each ``(B, window)``; ``eids``
    indexes the base entry pools where ``~is_delta`` and the delta entry
    pools where ``is_delta`` (the :func:`lookup_values` contract).
    """
    B, W = qbytes.shape
    n_arr = ent_sorted.shape[0]
    d_arr = ds_order.shape[0]
    n_base = jnp.broadcast_to(jnp.asarray(n_base, jnp.int32), (B,))
    n_delta_s = jnp.asarray(n_delta, jnp.int32)
    n_delta = jnp.broadcast_to(n_delta_s, (B,))
    bi = rank_sorted(qbytes, qlens, ent_sorted, ent_off, ent_len, key_bytes,
                     rank_iters=rank_iters)
    cols = jnp.arange(window, dtype=jnp.int32)[None, :]

    def frozen_only():
        # EMPTY delta: the merge degenerates to the frozen stream — one
        # contiguous window gather (the legacy scan), no merge loop and no
        # delta rank, so zero-fill scans cost what the frozen-only engine
        # did.
        idx = bi[:, None] + cols
        valid = idx < n_base[:, None]
        eids = jnp.take(ent_sorted, jnp.minimum(idx, n_arr - 1))
        return (jnp.where(valid, eids, -1), valid,
                jnp.zeros((B, window), bool))

    def merged():
        di = rank_sorted(qbytes, qlens, ds_order, de_off, de_len, db_bytes,
                         rank_iters=delta_rank_iters(d_arr), n_live=n_delta)

        def cond(st):
            bi, di, k, _, _, _ = st
            return jnp.any((k < window) & ((bi < n_base) | (di < n_delta)))

        def body(st):
            bi, di, k, oe, ov, od = st
            b_ok = bi < n_base
            d_ok = di < n_delta
            active = (k < window) & (b_ok | d_ok)
            be = jnp.take(ent_sorted, jnp.minimum(bi, n_arr - 1))
            de = jnp.take(ds_order, jnp.minimum(di, d_arr - 1))
            cmp = str_cmp_pools(
                db_bytes, jnp.take(de_off, de), jnp.take(de_len, de),
                key_bytes, jnp.take(ent_off, be), jnp.take(ent_len, be), W)
            take_delta = d_ok & (~b_ok | (cmp <= 0))
            shadows = take_delta & b_ok & (cmp == 0)
            tomb = jnp.take(de_tomb, de)
            emit = active & jnp.where(take_delta, ~tomb, b_ok)
            val = jnp.where(take_delta, de, be)
            slot = emit[:, None] & (cols == k[:, None])
            oe = jnp.where(slot, val[:, None], oe)
            ov = ov | slot
            od = jnp.where(slot, take_delta[:, None], od)
            bi = bi + (active & (~take_delta | shadows)).astype(jnp.int32)
            di = di + (active & take_delta).astype(jnp.int32)
            return bi, di, k + emit.astype(jnp.int32), oe, ov, od

        st0 = (bi, di, jnp.zeros(B, jnp.int32),
               jnp.full((B, window), -1, jnp.int32),
               jnp.zeros((B, window), bool), jnp.zeros((B, window), bool))
        _, _, _, oe, ov, od = jax.lax.while_loop(cond, body, st0)
        return oe, ov, od

    return jax.lax.cond(n_delta_s > 0, merged, frozen_only)
