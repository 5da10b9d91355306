"""Host-side LITS builder: bulkload + dynamic operations (paper Sec. 3.1, Alg. 2/3).

The builder owns growable numpy pools (structure-of-arrays — the TPU
adaptation of the paper's tagged 64-bit pointers, see DESIGN.md §2) and
implements the paper's algorithms exactly:

* bulkload: sample → HPT → recursive top-down build with PMSS decisions,
* collision-driven model-based nodes (LIPP): no last-mile search,
* compact leaf nodes (≤16 key-sorted h-pointers, no pre-allocation — the
  paper's default variant),
* critbit tensor-subtries in place of HOT (DESIGN.md §2),
* insert/delete/update with path-count resizing (Alg. 3 incCount, 2× rule)
  and the >50 % heavy-slot rule,
* ordered traversal (scan iterator / collect).

Slot positions for HPT-modelled nodes are computed through the *same jitted
float32 function the device search uses* (:func:`repro.core.hpt.positions_jnp`),
making build-time and query-time slot assignment bit-identical.
"""
from __future__ import annotations

import dataclasses
import sys
from typing import Iterator, List, Optional, Sequence, Tuple

import numpy as np

from . import pmss as pmss_mod
from .gpkl import gpkl
from .hpt import HPT, MAX_CDF_STEPS, build_hpt, get_cdf_jnp, positions_jnp, uniform_hpt
from .strings import StringSet, group_cpl, key_hash16, sort_order, dedup_sorted

# ---------------------------------------------------------------------------
# Tagged 32-bit items (the paper's tagged 64-bit pointers, TPU adaptation)
# ---------------------------------------------------------------------------
TAG_EMPTY = 0
TAG_ENTRY = 1
TAG_MNODE = 2
TAG_CNODE = 3
TAG_TRIE = 4

PAYLOAD_BITS = 28
PAYLOAD_MASK = (1 << PAYLOAD_BITS) - 1


def make_item(tag: int, payload: int = 0) -> int:
    assert 0 <= payload <= PAYLOAD_MASK, "pool overflow: shard the index (DESIGN.md §2)"
    return (tag << PAYLOAD_BITS) | payload


def item_tag(item: int) -> int:
    return (int(item) >> PAYLOAD_BITS) & 0x7


def item_payload(item: int) -> int:
    return int(item) & PAYLOAD_MASK


class GrowArr:
    """Amortized-doubling 1-D numpy array."""

    def __init__(self, dtype, cap: int = 1024) -> None:
        self.data = np.zeros(cap, dtype=dtype)
        self.n = 0

    def _ensure(self, extra: int) -> None:
        need = self.n + extra
        if need > self.data.shape[0]:
            cap = max(need, self.data.shape[0] * 2)
            nd = np.zeros(cap, dtype=self.data.dtype)
            nd[: self.n] = self.data[: self.n]
            self.data = nd

    def append(self, v) -> int:
        self._ensure(1)
        self.data[self.n] = v
        self.n += 1
        return self.n - 1

    def extend(self, arr: np.ndarray) -> int:
        arr = np.asarray(arr, dtype=self.data.dtype)
        self._ensure(arr.shape[0])
        base = self.n
        self.data[base : base + arr.shape[0]] = arr
        self.n += arr.shape[0]
        return base

    def view(self) -> np.ndarray:
        return self.data[: self.n]

    @property
    def nbytes_live(self) -> int:
        return self.n * self.data.dtype.itemsize


@dataclasses.dataclass
class LITSConfig:
    cnode_cap: int = 16          # paper: w = 16 (Sec. 4.4)
    min_slots: int = 8
    slots_factor: float = 2.0    # paper: item array ≤ 2× elements (App. A.6)
    max_slots: int = 1 << 22
    heavy_slot_frac: float = 0.5  # paper's >50% rule -> subtrie
    resize_grow: float = 2.0      # Alg. 3 incCount: rebuild at 2× (LIPP rule)
    resize_shrink: float = 0.2
    use_subtrie: bool = True      # False => the paper's LIT ablation
    hpt_rows: int = 1024
    hpt_cols: int = 128
    smoothing: float = 0.5
    sample_frac: float = 0.01
    min_sample: int = 2048
    min_width: int = 16


class LITSBuilder:
    """Mutable host-side index; :meth:`freeze` exports the device TensorIndex."""

    def __init__(
        self,
        config: LITSConfig | None = None,
        hpt: HPT | None = None,
        host_model=None,
        pmss: pmss_mod.PMSS | None = None,
        rng: np.random.Generator | None = None,
    ) -> None:
        self.cfg = config or LITSConfig()
        self.hpt = hpt
        self.host_model = host_model  # RS/SRMI etc.: float64 host values (Fig. 14)
        self.pmss = pmss if pmss is not None else pmss_mod.PMSS()
        self.rng = rng or np.random.default_rng(0)
        self.width = self.cfg.min_width
        # pools
        self.key_bytes = GrowArr(np.uint8, 1 << 16)
        self.ent_off = GrowArr(np.int64)
        self.ent_len = GrowArr(np.int32)
        self.ent_val = GrowArr(np.int64)
        self.items = GrowArr(np.int32)
        self.mn_slot_base = GrowArr(np.int32)
        self.mn_slot_cnt = GrowArr(np.int32)
        self.mn_prefix_off = GrowArr(np.int64)
        self.mn_prefix_len = GrowArr(np.int32)
        self.mn_alpha = GrowArr(np.float32)
        self.mn_beta = GrowArr(np.float32)
        self.mn_nkeys = GrowArr(np.int32)
        self.cn_base = GrowArr(np.int32)
        self.cn_cnt = GrowArr(np.int32)
        self.ch_hash = GrowArr(np.uint16)
        self.ch_ent = GrowArr(np.int32)
        self.tr_byte = GrowArr(np.int32)
        self.tr_mask = GrowArr(np.uint8)
        self.tr_left = GrowArr(np.int32)
        self.tr_right = GrowArr(np.int32)
        self.root_item = make_item(TAG_EMPTY)
        self.n_keys = 0
        self.max_suffix_len = 1  # longest (key - node prefix) any mnode models
        # build counts since the builder was made: sub-tries by the rule that
        # chose them (the PMSS decision, the >50% heavy-slot rule, a group the
        # model cannot split), and keys an mnode models whose suffix is longer
        # than the MAX_CDF_STEPS bytes the HPT CDF reads
        self.subtries = {"pmss": 0, "heavy_slot": 0, "unsplittable": 0}
        self.keys_past_cdf_cap = 0
        self.op_reads = 0
        self.op_writes = 0
        self._cdf_cache_dev = None
        # incremental freeze substrate (DESIGN.md §10): the sorted entry order
        # and the height bound are maintained across mutations so a merge
        # refreeze never has to re-walk the whole structure.  ``None`` means
        # "unknown — recompute exactly on next use" (and cache the result).
        self._sorted_cache: Optional[np.ndarray] = None  # live eids, key order
        self._hb: Optional[dict] = None                  # {"base","trie"} bound
        # bulk-walk position memo (insert_many/delete_many): one batched
        # ``_positions`` call per DISTINCT mnode visited instead of one
        # jitted dispatch per key per level — per-row results are identical
        # to the single-key path (the same per-row float32 math bulkload
        # already batches), only the dispatch count changes
        self._bulk_pos: Optional[dict] = None

    # ------------------------------------------------------------------
    # model values / positions (device-consistent for the HPT path)
    # ------------------------------------------------------------------
    def _dev_tables(self):
        import jax.numpy as jnp

        if self._cdf_cache_dev is None:
            assert self.hpt is not None
            self._cdf_cache_dev = (jnp.asarray(self.hpt.cdf_tab), jnp.asarray(self.hpt.prob_tab))
        return self._cdf_cache_dev

    @staticmethod
    def _pad_pow2(n: int) -> int:
        p = 8
        while p < n:
            p *= 2
        return p

    def _values(self, bytes_mat: np.ndarray, lens: np.ndarray, start: int) -> np.ndarray:
        if self.host_model is not None:
            return self.host_model.values(StringSet(bytes_mat, lens), start)
        import jax.numpy as jnp

        cdf_tab, prob_tab = self._dev_tables()
        n = bytes_mat.shape[0]
        P = self._pad_pow2(n)
        qb = np.zeros((P, self.width), np.uint8)
        qb[:n, : bytes_mat.shape[1]] = bytes_mat[:, : self.width]
        ql = np.zeros(P, np.int32)
        ql[:n] = np.minimum(lens, self.width)
        out = get_cdf_jnp(cdf_tab, prob_tab, jnp.asarray(qb), jnp.asarray(ql), jnp.int32(start))
        return np.asarray(out)[:n]

    def _positions(
        self, bytes_mat: np.ndarray, lens: np.ndarray, start: int,
        alpha: float, beta: float, m: int,
    ) -> np.ndarray:
        if self.host_model is not None:
            v = self.host_model.values(StringSet(bytes_mat, lens), start)
            pos = np.floor(np.float64(alpha) * v + np.float64(beta)).astype(np.int64)
            return np.clip(pos, 1, m - 2).astype(np.int32)
        import jax.numpy as jnp

        cdf_tab, prob_tab = self._dev_tables()
        n = bytes_mat.shape[0]
        P = self._pad_pow2(n)
        qb = np.zeros((P, self.width), np.uint8)
        qb[:n, : bytes_mat.shape[1]] = bytes_mat[:, : self.width]
        ql = np.zeros(P, np.int32)
        ql[:n] = np.minimum(lens, self.width)
        pos = positions_jnp(
            cdf_tab, prob_tab, jnp.asarray(qb), jnp.asarray(ql), jnp.int32(start),
            jnp.float32(alpha), jnp.float32(beta), jnp.int32(m),
        )
        return np.asarray(pos)[:n]

    def _node_pos(self, nid: int, q: np.ndarray, qlen: int, pl: int,
                  m: int) -> int:
        """Model slot position of one key at mnode ``nid``.

        Single-key callers pay one jitted ``_positions`` dispatch; inside a
        bulk walk (``insert_many``/``delete_many``) the whole batch's
        positions for this node are computed ONCE and memoized — per-row
        math is identical, so the returned position is bit-identical to the
        single-key path."""
        bp = self._bulk_pos
        if bp is not None:
            tab = bp["memo"].get(nid)
            if tab is None:
                tab = self._positions(
                    bp["bytes"], bp["lens"], pl,
                    float(self.mn_alpha.data[nid]),
                    float(self.mn_beta.data[nid]), m)
                bp["memo"][nid] = tab
            return int(tab[bp["row"]])
        return int(self._positions(
            q[None, :], np.array([qlen], np.int32), pl,
            float(self.mn_alpha.data[nid]), float(self.mn_beta.data[nid]), m,
        )[0])

    def _bulk_matrix(self, keys: Sequence[bytes]):
        """(N, width) zero-padded byte matrix + lengths for a bulk walk."""
        W = self.width
        qb = np.zeros((len(keys), W), np.uint8)
        ql = np.zeros(len(keys), np.int32)
        for i, k in enumerate(keys):
            kb = np.frombuffer(k[:W], np.uint8)
            qb[i, : kb.shape[0]] = kb
            ql[i] = len(k)
        return qb, ql

    # ------------------------------------------------------------------
    # entry helpers
    # ------------------------------------------------------------------
    def _add_entry_bytes(self, key: np.ndarray, klen: int, val: int) -> int:
        off = self.key_bytes.extend(key[:klen])
        self.ent_off.append(off)
        self.ent_len.append(klen)
        self.ent_val.append(val)
        return self.ent_off.n - 1

    def key_at(self, eid: int) -> bytes:
        off = int(self.ent_off.data[eid])
        ln = int(self.ent_len.data[eid])
        return self.key_bytes.data[off : off + ln].tobytes()

    def entry_matrix(self, eids: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        eids = np.asarray(eids, np.int64)
        offs = self.ent_off.data[eids]
        lens = self.ent_len.data[eids]
        W = self.width
        idx = offs[:, None] + np.arange(W)[None, :]
        idx = np.minimum(idx, max(self.key_bytes.n - 1, 0))
        mat = self.key_bytes.data[idx]
        mask = np.arange(W)[None, :] < lens[:, None]
        return (mat * mask).astype(np.uint8), lens.astype(np.int32)

    # ------------------------------------------------------------------
    # bulkload (paper Sec. 3.1)
    # ------------------------------------------------------------------
    def bulkload(
        self, keys: StringSet, values: np.ndarray | None = None, width: int | None = None
    ) -> None:
        n = len(keys)
        order = sort_order(keys)
        ss = keys.take(order)
        uniq = dedup_sorted(ss)
        if len(uniq) != len(ss):
            ss = ss.take(uniq)
            order = order[uniq]
        vals = (values[order] if values is not None else np.arange(len(ss), dtype=np.int64))
        maxlen = int(ss.lens.max(initial=1))
        if width is None:
            width = maxlen + 8  # headroom for post-bulkload inserts
        elif width < maxlen:
            raise ValueError(f"width {width} < longest key {maxlen}")
        self.width = max(self.cfg.min_width, width)
        ss = ss.pad_to(self.width)
        if self.hpt is None and self.host_model is None:
            k = max(min(len(ss), self.cfg.min_sample), int(len(ss) * self.cfg.sample_frac))
            sample_idx = self.rng.choice(len(ss), size=min(k, len(ss)), replace=False)
            self.hpt = build_hpt(
                ss.take(sample_idx), self.cfg.hpt_rows, self.cfg.hpt_cols, self.cfg.smoothing
            )
        # register all entries (packed bytes, key order)
        flat = []
        for i in range(len(ss)):
            flat.append(ss.bytes[i, : ss.lens[i]])
        offs = np.zeros(len(ss), np.int64)
        pos = self.key_bytes.n
        for i, f in enumerate(flat):
            offs[i] = pos
            pos += f.shape[0]
        if flat:
            self.key_bytes.extend(np.concatenate(flat))
        ent_base = self.ent_off.extend(offs)
        self.ent_len.extend(ss.lens)
        self.ent_val.extend(vals)
        eids = ent_base + np.arange(len(ss), dtype=np.int64)
        sys.setrecursionlimit(max(sys.getrecursionlimit(), 100000))
        self.root_item = self._build_group(eids, ss.bytes, ss.lens, force_mnode=True)
        self.n_keys = len(ss)
        # entries were registered in sorted key order -> the ordered-traversal
        # eid sequence is exactly ``eids``; heights are computed lazily (the
        # first freeze walks once and caches)
        self._sorted_cache = eids.copy()
        self._hb = None

    # ------------------------------------------------------------------
    # recursive group build with PMSS decision
    # ------------------------------------------------------------------
    def _build_group(
        self,
        eids: np.ndarray,
        bytes_mat: np.ndarray | None = None,
        lens: np.ndarray | None = None,
        force_mnode: bool = False,
    ) -> int:
        n = len(eids)
        if n == 0:
            return make_item(TAG_EMPTY)
        if bytes_mat is None:
            bytes_mat, lens = self.entry_matrix(eids)
        if n == 1:
            return make_item(TAG_ENTRY, int(eids[0]))
        if n <= self.cfg.cnode_cap and not force_mnode:
            return self._build_cnode(eids, bytes_mat, lens)
        ss = StringSet(bytes_mat, lens)
        if self.cfg.use_subtrie and not force_mnode:
            g = gpkl(ss)
            if self.pmss.decide(g, n) == "trie":
                return self._build_trie(eids, bytes_mat, lens, "pmss")
        return self._build_mnode(eids, bytes_mat, lens)

    def _build_mnode(self, eids: np.ndarray, bytes_mat: np.ndarray, lens: np.ndarray) -> int:
        n = len(eids)
        pl = group_cpl(StringSet(bytes_mat, lens))
        pl = min(pl, self.width - 1)
        v = self._values(bytes_mat, lens, pl).astype(np.float64)
        vmin, vmax = float(v.min()), float(v.max())
        if not (vmax > vmin):  # model cannot split this group -> trie (strengthened 50% rule)
            return self._build_trie(eids, bytes_mat, lens, "unsplittable")
        m = int(np.clip(int(self.cfg.slots_factor * n), self.cfg.min_slots, self.cfg.max_slots))
        alpha = np.float32((m - 3) / (vmax - vmin))
        beta = np.float32(1.0 - float(alpha) * vmin)
        pos = self._positions(bytes_mat, lens, pl, float(alpha), float(beta), m)
        self.max_suffix_len = max(self.max_suffix_len, int((lens - pl).max()))
        self.keys_past_cdf_cap += int(((lens - pl) > MAX_CDF_STEPS).sum())
        base = self.items.extend(np.zeros(m, np.int32))
        nid = self.mn_slot_base.append(base)
        self.mn_slot_cnt.append(m)
        self.mn_prefix_off.append(self.ent_off.data[eids[0]])
        self.mn_prefix_len.append(pl)
        self.mn_alpha.append(alpha)
        self.mn_beta.append(beta)
        self.mn_nkeys.append(n)
        # cut the sorted keys into blocks whose position ranges do not
        # overlap.  GetCDF is monotone in exact arithmetic, but float32
        # rounding can put a key one slot BELOW its predecessor; such keys
        # share one block, and every slot of the block's range holds the
        # same child item (an alias), so each key is found at its own
        # position and slot order stays key order (see _set_item,
        # iter_subtree).  With monotone positions a block is one slot.
        pos = pos.astype(np.int64)
        pmax = np.maximum.accumulate(pos)
        smin = np.minimum.accumulate(pos[::-1])[::-1]
        cut = np.flatnonzero(pmax[:-1] < smin[1:]) + 1
        starts = np.concatenate([[0], cut])
        ends = np.concatenate([cut, [n]])
        for s, e in zip(starts, ends):
            sub = eids[s:e]
            if e - s == 1:
                child = make_item(TAG_ENTRY, int(sub[0]))
            elif (e - s) > self.cfg.heavy_slot_frac * n or (e - s) == n:
                child = self._build_trie(sub, bytes_mat[s:e], lens[s:e],
                                         "heavy_slot")
            else:
                child = self._build_group(sub, bytes_mat[s:e], lens[s:e])
            self.items.data[base + int(pos[s:e].min()):
                            base + int(pmax[e - 1]) + 1] = child
        return make_item(TAG_MNODE, nid)

    def _build_cnode(self, eids: np.ndarray, bytes_mat: np.ndarray, lens: np.ndarray) -> int:
        hashes = key_hash16(bytes_mat, lens)
        base = self.ch_hash.extend(hashes.astype(np.uint16))
        self.ch_ent.extend(eids.astype(np.int32))
        cid = self.cn_base.append(base)
        self.cn_cnt.append(len(eids))
        return make_item(TAG_CNODE, cid)

    def _build_trie(self, eids: np.ndarray, bytes_mat: np.ndarray, lens: np.ndarray,
                    route: str) -> int:
        self.subtries[route] += 1

        def rec(lo: int, hi: int) -> int:
            if hi - lo == 1:
                return make_item(TAG_ENTRY, int(eids[lo]))
            sub = bytes_mat[lo:hi]
            neq = (sub != sub[0:1]).any(axis=0)
            if not neq.any():  # duplicate keys cannot reach here (deduped)
                raise AssertionError("duplicate keys in trie build")
            p = int(neq.argmax())
            vals = sub[:, p].astype(np.int32)
            diff = int(vals.min()) ^ int(vals.max())
            b = diff.bit_length() - 1
            mask = 1 << b
            bits = (vals & mask) != 0
            split = int(bits.argmax())  # sorted keys => bits monotone 0..0 1..1
            left = rec(lo, lo + split)
            right = rec(lo + split, hi)
            tid = self.tr_byte.append(p)
            self.tr_mask.append(mask)
            self.tr_left.append(left)
            self.tr_right.append(right)
            return make_item(TAG_TRIE, tid)

        return rec(0, len(eids))

    # ------------------------------------------------------------------
    # host search (oracle; device path lives in tensor_index.py)
    # ------------------------------------------------------------------
    def _pad_query(self, key: bytes) -> Tuple[np.ndarray, int]:
        q = np.zeros(self.width, np.uint8)
        kb = np.frombuffer(key[: self.width], np.uint8)
        q[: kb.shape[0]] = kb
        return q, len(key)

    def _trie_descend(self, item: int, q: np.ndarray, qlen: int) -> int:
        while item_tag(item) == TAG_TRIE:
            tid = item_payload(item)
            cb = int(self.tr_byte.data[tid])
            c = int(q[cb]) if cb < min(qlen, self.width) else 0
            if c & int(self.tr_mask.data[tid]):
                item = int(self.tr_right.data[tid])
            else:
                item = int(self.tr_left.data[tid])
        return item

    def host_search(self, key: bytes) -> Tuple[bool, int]:
        self.op_reads += 1
        q, qlen = self._pad_query(key)
        item = self.root_item
        while True:
            tag = item_tag(item)
            if tag == TAG_EMPTY:
                return False, -1
            if tag == TAG_ENTRY:
                eid = item_payload(item)
                return (self.key_at(eid) == key), eid
            if tag == TAG_CNODE:
                cid = item_payload(item)
                base, cnt = int(self.cn_base.data[cid]), int(self.cn_cnt.data[cid])
                h = int(key_hash16(q[None, :], np.array([qlen], np.int32))[0])
                for j in range(cnt):
                    if int(self.ch_hash.data[base + j]) == h:
                        eid = int(self.ch_ent.data[base + j])
                        if self.key_at(eid) == key:
                            return True, eid
                return False, -1
            if tag == TAG_TRIE:
                item = self._trie_descend(item, q, qlen)
                continue
            # model-based node
            nid = item_payload(item)
            pl = int(self.mn_prefix_len.data[nid])
            poff = int(self.mn_prefix_off.data[nid])
            prefix = self.key_bytes.data[poff : poff + pl].tobytes()
            kp = key[:pl] if len(key) >= pl else key + b""
            base = int(self.mn_slot_base.data[nid])
            m = int(self.mn_slot_cnt.data[nid])
            if kp < prefix:
                item = int(self.items.data[base])
            elif kp > prefix:
                item = int(self.items.data[base + m - 1])
            else:
                pos = self._node_pos(nid, q, qlen, pl, m)
                item = int(self.items.data[base + pos])

    def get(self, key: bytes) -> Optional[int]:
        found, eid = self.host_search(key)
        return int(self.ent_val.data[eid]) if found else None

    # ------------------------------------------------------------------
    # insert / delete / update (paper Alg. 3)
    # ------------------------------------------------------------------
    def _insert_walk(self, key: bytes, val: int):
        """Structural insert without the Alg. 3 incCount/resize pass.

        Returns ``(inserted, path, loc, eid)``: ``path`` is the mnode chain
        walked (for the caller's deferred resize), ``loc`` the item slot whose
        content changed (the sub-trie-local dirty root for incremental height
        maintenance), and ``eid`` the new entry id — or, on a duplicate key,
        the EXISTING entry id (so bulk callers can upsert without re-walking).
        """
        if len(key) > self.width:
            raise ValueError("key longer than index width; rebuild with larger width")
        self.op_writes += 1
        q, qlen = self._pad_query(key)
        path: List[Tuple[int, int]] = []  # (mnode id, item location of that mnode)
        loc = -1  # -1 = root_item, else index into items pool
        item = self.root_item
        while True:
            tag = item_tag(item)
            if tag == TAG_EMPTY:
                eid = self._add_entry_bytes(q, qlen, val)
                self._set_item(loc, make_item(TAG_ENTRY, eid))
                return True, path, loc, eid
            if tag == TAG_ENTRY:
                eid = item_payload(item)
                if self.key_at(eid) == key:
                    return False, path, loc, eid
                neid = self._add_entry_bytes(q, qlen, val)
                pair = np.array([eid, neid], np.int64)
                bm, ls = self.entry_matrix(pair)
                o = sort_order(StringSet(bm, ls))
                self._set_item(loc, self._build_cnode(pair[o], bm[o], ls[o]))
                return True, path, loc, neid
            if tag == TAG_CNODE:
                inserted, eid = self._cnode_insert(loc, item, key, q, qlen, val)
                return inserted, path, loc, eid
            if tag == TAG_TRIE:
                inserted, eid = self._trie_insert(loc, item, key, q, qlen, val)
                return inserted, path, loc, eid
            nid = item_payload(item)
            path.append((nid, loc))
            pl = int(self.mn_prefix_len.data[nid])
            poff = int(self.mn_prefix_off.data[nid])
            prefix = self.key_bytes.data[poff : poff + pl].tobytes()
            kp = key[:pl]
            base = int(self.mn_slot_base.data[nid])
            m = int(self.mn_slot_cnt.data[nid])
            if kp < prefix:
                loc = base
            elif kp > prefix:
                loc = base + m - 1
            else:
                pos = self._node_pos(nid, q, qlen, pl, m)
                lo, hi = self._order_span(base, m, pos, key)
                if lo < hi:
                    eid = self._merge_insert(base, lo, hi, q, qlen, val)
                    return True, path, base + lo, eid
                loc = base + pos
            item = int(self.items.data[loc])

    def _edge_key(self, item: int, last: bool) -> Optional[bytes]:
        """Smallest (``last=False``) or largest key under ``item``; None
        when the subtree holds no key."""
        while True:
            tag, p = item_tag(item), item_payload(item)
            if tag == TAG_EMPTY:
                return None
            if tag == TAG_ENTRY:
                return self.key_at(p)
            if tag == TAG_CNODE:  # h-pointers are kept in key order
                j = int(self.cn_base.data[p]) + (
                    int(self.cn_cnt.data[p]) - 1 if last else 0)
                return self.key_at(int(self.ch_ent.data[j]))
            if tag == TAG_TRIE:
                item = int((self.tr_right if last else self.tr_left).data[p])
                continue
            base, m = int(self.mn_slot_base.data[p]), int(self.mn_slot_cnt.data[p])
            nz = np.flatnonzero(self.items.data[base : base + m])
            if nz.size == 0:
                return None
            item = int(self.items.data[base + nz[-1 if last else 0]])

    def _order_span(self, base: int, m: int, pos: int,
                    key: bytes) -> Tuple[int, int]:
        """Slot range an insert of ``key`` at slot ``pos`` must merge into
        one aliased block (see _build_mnode) to keep slot order = key
        order: the blocks left of ``pos`` holding larger keys and right of
        it holding smaller ones, which a float32 position dip can produce.
        ``(pos, pos)`` when the order holds."""
        slots = self.items.data[base : base + m]
        own = int(slots[pos])
        lo = hi = pos
        for step in (-1, 1):
            j = pos + step
            while 0 <= j < m:
                it = int(slots[j])
                edge = None if it in (0, own) else \
                    self._edge_key(it, last=step < 0)  # skip EMPTY, aliases
                if edge is not None:
                    if edge < key if step < 0 else edge > key:
                        break
                    lo, hi = min(lo, j), max(hi, j)
                j += step
        if (lo, hi) != (pos, pos):  # widen to whole alias blocks at both ends
            while lo > 0 and slots[lo - 1] == slots[lo] != 0:
                lo -= 1
            while hi < m - 1 and slots[hi + 1] == slots[hi] != 0:
                hi += 1
        return lo, hi

    def _merge_insert(self, base: int, lo: int, hi: int, q, qlen, val) -> int:
        """Insert a new key together with every key in slots ``lo..hi``:
        one child built from their union, aliased over the whole range."""
        eid = self._add_entry_bytes(q, qlen, val)
        eids, seen = [eid], set()
        for j in range(lo, hi + 1):
            it = int(self.items.data[base + j])
            if it and it not in seen:
                seen.add(it)
                eids.extend(self.iter_subtree(it))
        eids.sort(key=self.key_at)
        child = self._build_group(np.asarray(eids, np.int64))
        self.items.data[base + lo : base + hi + 1] = child
        return eid

    def insert(self, key: bytes, val: int) -> bool:
        inserted, path, _loc, eid = self._insert_walk(key, val)
        if not inserted:
            return False
        self.n_keys += 1
        self._note_inserted(key, eid)
        self._hb = None  # structure changed: height bound recomputed on demand
        # incCount + resize (Alg. 3): rebuild topmost node violating the 2x rule
        for nid, nloc in path:
            self.mn_nkeys.data[nid] += 1
        for nid, nloc in path:
            if self.mn_nkeys.data[nid] >= self.cfg.resize_grow * self.mn_slot_cnt.data[nid]:
                self._rebuild_at(nloc, make_item(TAG_MNODE, nid))
                break
        return True

    def _cnode_insert(self, loc: int, item: int, key: bytes, q, qlen, val):
        cid = item_payload(item)
        base, cnt = int(self.cn_base.data[cid]), int(self.cn_cnt.data[cid])
        eids = self.ch_ent.data[base : base + cnt].astype(np.int64)
        keys = [self.key_at(int(e)) for e in eids]
        import bisect

        p = bisect.bisect_left(keys, key)
        if p < cnt and keys[p] == key:
            return False, int(eids[p])
        neid = self._add_entry_bytes(q, qlen, val)
        new_eids = np.insert(eids, p, neid)
        bm, ls = self.entry_matrix(new_eids)
        if cnt < self.cfg.cnode_cap:
            # no-pre-allocation variant: fresh slab of cnt+1 (paper Sec. 3.3 default)
            self._set_item(loc, self._build_cnode(new_eids, bm, ls))
        else:
            # full: PMSS decides model-based node vs subtrie (paper Sec. 3.4 scenario 2)
            self._set_item(loc, self._build_group(new_eids, bm, ls))
        return True, neid

    def _trie_insert(self, loc: int, item: int, key: bytes, q, qlen, val):
        leaf = self._trie_descend(item, q, qlen)
        leid = item_payload(leaf)
        lkey = self.key_at(leid)
        if lkey == key:
            return False, leid
        lq = np.zeros(self.width, np.uint8)
        lb = np.frombuffer(lkey, np.uint8)
        lq[: lb.shape[0]] = lb
        diff = q.astype(np.int32) ^ lq.astype(np.int32)
        p = int((diff != 0).argmax())
        b = int(diff[p]).bit_length() - 1
        mask = 1 << b
        newdir = 1 if (int(q[p]) & mask) else 0
        neid = self._add_entry_bytes(q, qlen, val)
        # walk again, stopping where the new crit node belongs (djb critbit insert)
        cur_loc, cur = loc, item
        while item_tag(cur) == TAG_TRIE:
            tid = item_payload(cur)
            cb, cm = int(self.tr_byte.data[tid]), int(self.tr_mask.data[tid])
            if (cb, -cm) > (p, -mask):  # new discriminating bit is more significant
                break
            c = int(q[cb]) if cb < min(qlen, self.width) else 0
            if c & cm:
                cur_loc, cur = ("trie_r", tid), int(self.tr_right.data[tid])
            else:
                cur_loc, cur = ("trie_l", tid), int(self.tr_left.data[tid])
        nitem = make_item(TAG_ENTRY, neid)
        left, right = (cur, nitem) if newdir else (nitem, cur)
        tid = self.tr_byte.append(p)
        self.tr_mask.append(mask)
        self.tr_left.append(left)
        self.tr_right.append(right)
        self._set_item(cur_loc, make_item(TAG_TRIE, tid))
        return True, neid

    def _set_item(self, loc, item: int) -> None:
        if loc == -1:
            self.root_item = item
        elif isinstance(loc, tuple):
            kind, tid = loc
            if kind == "trie_l":
                self.tr_left.data[tid] = item
            else:
                self.tr_right.data[tid] = item
        else:
            old = self.items.data[loc]
            self.items.data[loc] = item
            if item_tag(old) != TAG_EMPTY:
                # the slots of one block alias one child (_build_mnode): a
                # non-empty child has one parent, so equal neighbours are
                # exactly its aliases
                for step in (-1, 1):
                    j = loc + step
                    while 0 <= j < self.items.n and self.items.data[j] == old:
                        self.items.data[j] = item
                        j += step

    def _rebuild_at(self, loc, item: int) -> None:
        eids = np.array(list(self.iter_subtree(item)), np.int64)
        self._set_item(loc, self._build_group(eids))

    def _delete_walk(self, key: bytes):
        """Structural delete without the shrink-resize pass.

        Returns ``(removed, path, loc, eid)`` — ``eid`` is the entry id that
        was unlinked (the entry pool keeps the dead bytes; only the structure
        forgets them), ``loc`` the dirty item slot, as in :meth:`_insert_walk`.
        """
        self.op_writes += 1
        q, qlen = self._pad_query(key)
        path: List[Tuple[int, int]] = []
        loc = -1
        item = self.root_item
        while True:
            tag = item_tag(item)
            if tag == TAG_EMPTY:
                return False, path, loc, -1
            if tag == TAG_ENTRY:
                eid = item_payload(item)
                if self.key_at(eid) != key:
                    return False, path, loc, -1
                self._set_item(loc, make_item(TAG_EMPTY))
                return True, path, loc, eid
            if tag == TAG_CNODE:
                cid = item_payload(item)
                base, cnt = int(self.cn_base.data[cid]), int(self.cn_cnt.data[cid])
                eids = self.ch_ent.data[base : base + cnt].astype(np.int64)
                keep = [int(e) for e in eids if self.key_at(int(e)) != key]
                if len(keep) == cnt:
                    return False, path, loc, -1
                gone = next(int(e) for e in eids if self.key_at(int(e)) == key)
                if len(keep) == 1:
                    self._set_item(loc, make_item(TAG_ENTRY, keep[0]))
                else:
                    arr = np.array(keep, np.int64)
                    bm, ls = self.entry_matrix(arr)
                    self._set_item(loc, self._build_cnode(arr, bm, ls))
                return True, path, loc, gone
            if tag == TAG_TRIE:
                removed, eid = self._trie_delete(loc, item, key, q, qlen)
                return removed, path, loc, eid
            nid = item_payload(item)
            path.append((nid, loc))
            pl = int(self.mn_prefix_len.data[nid])
            poff = int(self.mn_prefix_off.data[nid])
            prefix = self.key_bytes.data[poff : poff + pl].tobytes()
            kp = key[:pl]
            base = int(self.mn_slot_base.data[nid])
            m = int(self.mn_slot_cnt.data[nid])
            if kp < prefix:
                loc = base
            elif kp > prefix:
                loc = base + m - 1
            else:
                pos = self._node_pos(nid, q, qlen, pl, m)
                loc = base + pos
            item = int(self.items.data[loc])

    def delete(self, key: bytes) -> bool:
        removed, path, _loc, eid = self._delete_walk(key)
        if not removed:
            return False
        self.n_keys -= 1
        self._note_removed(eid)
        self._hb = None  # structure changed: height bound recomputed on demand
        for nid, _ in path:
            self.mn_nkeys.data[nid] -= 1
        for nid, nloc in path:
            m = int(self.mn_slot_cnt.data[nid])
            if (
                m > self.cfg.min_slots
                and self.mn_nkeys.data[nid] < self.cfg.resize_shrink * m
                and self.mn_nkeys.data[nid] >= 0
            ):
                self._rebuild_at(nloc, make_item(TAG_MNODE, nid))
                break
        return True

    def _trie_delete(self, loc, item: int, key: bytes, q, qlen):
        # walk, remembering parent side, then splice the sibling up.
        parent = None  # (tid, side)
        cur = item
        while item_tag(cur) == TAG_TRIE:
            tid = item_payload(cur)
            cb, cm = int(self.tr_byte.data[tid]), int(self.tr_mask.data[tid])
            c = int(q[cb]) if cb < min(qlen, self.width) else 0
            side = 1 if (c & cm) else 0
            parent = (tid, side)
            cur = int(self.tr_right.data[tid]) if side else int(self.tr_left.data[tid])
        if item_tag(cur) != TAG_ENTRY or self.key_at(item_payload(cur)) != key:
            return False, -1
        gone = item_payload(cur)
        tid, side = parent  # parent is not None: a trie item always has >= 2 leaves
        sibling = int(self.tr_left.data[tid]) if side else int(self.tr_right.data[tid])
        # find grandparent link to tid
        gp_loc, gcur = loc, item
        while True:
            gtid = item_payload(gcur)
            if gtid == tid:
                self._set_item(gp_loc, sibling)
                return True, gone
            cb, cm = int(self.tr_byte.data[gtid]), int(self.tr_mask.data[gtid])
            c = int(q[cb]) if cb < min(qlen, self.width) else 0
            if c & cm:
                gp_loc, gcur = ("trie_r", gtid), int(self.tr_right.data[gtid])
            else:
                gp_loc, gcur = ("trie_l", gtid), int(self.tr_left.data[gtid])

    def update(self, key: bytes, val: int) -> bool:
        self.op_writes += 1
        found, eid = self.host_search(key)
        if not found:
            return False
        self.ent_val.data[eid] = val
        return True

    # ------------------------------------------------------------------
    # bulk replay ops (merge_delta's vectorized path, DESIGN.md §10)
    # ------------------------------------------------------------------
    def _item_at(self, loc) -> int:
        if loc == -1:
            return int(self.root_item)
        if isinstance(loc, tuple):
            kind, tid = loc
            return int(self.tr_left.data[tid] if kind == "trie_l"
                       else self.tr_right.data[tid])
        return int(self.items.data[loc])

    def _rank_in(self, sorted_arr: np.ndarray, key: bytes) -> int:
        """First index i with key_at(sorted_arr[i]) >= key (binary search —
        O(log n) key compares against the incremental sorted order)."""
        lo, hi = 0, sorted_arr.shape[0]
        while lo < hi:
            mid = (lo + hi) // 2
            if self.key_at(int(sorted_arr[mid])) < key:
                lo = mid + 1
            else:
                hi = mid
        return lo

    def _note_inserted(self, key: bytes, eid: int) -> None:
        # single-op path: invalidate rather than splice — an O(n) np.insert
        # per key would tax legacy per-key workloads; the bulk ops maintain
        # the cache with ONE batched splice instead
        self._sorted_cache = None

    def _note_removed(self, eid: int) -> None:
        self._sorted_cache = None

    def insert_many(self, keys: Sequence[bytes], vals: np.ndarray) -> np.ndarray:
        """Bulk upsert: insert each new key, overwrite the value of existing
        ones.  Returns the per-key inserted mask (False = value update).

        This is the merge-replay path (Alg. 3 amortized): structural edits
        run per key, but the incCount/resize pass is DEFERRED to one sweep at
        the end — a hot sub-trie touched by many replayed keys rebuilds once,
        not once per key — and the sorted order / height bound are updated
        with one batched splice + dirty-subtree-local walks, so the following
        ``freeze`` never re-walks the whole index.
        """
        n0 = len(keys)
        inserted = np.zeros(n0, bool)
        if n0 == 0:
            return inserted
        sorted_arr = self.sorted_eids()
        hb = dict(self.height_bound())
        # invalidate until the batch COMPLETES: a mid-batch exception leaves
        # the structure partially replayed, and a stale cache would let the
        # next freeze publish an order missing those keys — None forces an
        # exact re-walk instead.  Restored (maintained) on success below.
        self._sorted_cache = None
        self._hb = None
        # process in key order so the batched np.insert below keeps ties
        # (equal insertion ranks) in sorted order
        order = sorted(range(n0), key=lambda i: keys[i])
        paths: List[List[Tuple[int, int]]] = []
        dirty: dict = {}        # dirty item slot -> mnode depth of that slot
        ranks: List[int] = []
        new_eids: List[int] = []
        qb, ql = self._bulk_matrix(keys)
        self._bulk_pos = {"bytes": qb, "lens": ql, "row": 0, "memo": {}}
        try:
            for i in order:
                key = keys[i]
                self._bulk_pos["row"] = i
                ok, path, loc, eid = self._insert_walk(key, int(vals[i]))
                if not ok:
                    self.ent_val.data[eid] = int(vals[i])  # upsert: refresh
                    continue
                inserted[i] = True
                self.n_keys += 1
                new_eids.append(eid)
                ranks.append(self._rank_in(sorted_arr, key))
                for nid, _ in path:
                    self.mn_nkeys.data[nid] += 1
                paths.append(path)
                dirty[loc] = len(path)
        finally:
            self._bulk_pos = None
        # deferred Alg. 3 resize: topmost violating node per touched path.
        # The guard skips nodes an earlier rebuild already restructured
        # (their slot no longer holds the recorded mnode item).
        for path in paths:
            for depth, (nid, nloc) in enumerate(path):
                if self.mn_nkeys.data[nid] >= \
                        self.cfg.resize_grow * self.mn_slot_cnt.data[nid]:
                    if self._item_at(nloc) == make_item(TAG_MNODE, nid):
                        self._rebuild_at(nloc, make_item(TAG_MNODE, nid))
                        dirty[nloc] = depth
                    break
        if new_eids:
            sorted_arr = np.insert(sorted_arr, np.asarray(ranks, np.int64),
                                   np.asarray(new_eids, np.int64))
        self._sorted_cache = sorted_arr
        self._update_height_bound(hb, dirty)
        return inserted

    def delete_many(self, keys: Sequence[bytes]) -> np.ndarray:
        """Bulk delete with the same deferred-resize/batched-splice scheme as
        :meth:`insert_many`.  Returns the per-key removed mask."""
        n0 = len(keys)
        removed_mask = np.zeros(n0, bool)
        if n0 == 0:
            return removed_mask
        sorted_arr = self.sorted_eids()
        hb = dict(self.height_bound())
        self._sorted_cache = None   # see insert_many: restored on success
        self._hb = None
        paths: List[List[Tuple[int, int]]] = []
        dirty: dict = {}
        gone: List[int] = []
        qb, ql = self._bulk_matrix(keys)
        self._bulk_pos = {"bytes": qb, "lens": ql, "row": 0, "memo": {}}
        try:
            for i in range(n0):
                self._bulk_pos["row"] = i
                ok, path, loc, eid = self._delete_walk(keys[i])
                if not ok:
                    continue
                removed_mask[i] = True
                self.n_keys -= 1
                gone.append(eid)
                for nid, _ in path:
                    self.mn_nkeys.data[nid] -= 1
                paths.append(path)
                dirty[loc] = len(path)
        finally:
            self._bulk_pos = None
        for path in paths:
            for depth, (nid, nloc) in enumerate(path):
                m = int(self.mn_slot_cnt.data[nid])
                if (m > self.cfg.min_slots
                        and self.mn_nkeys.data[nid] < self.cfg.resize_shrink * m
                        and self.mn_nkeys.data[nid] >= 0):
                    if self._item_at(nloc) == make_item(TAG_MNODE, nid):
                        self._rebuild_at(nloc, make_item(TAG_MNODE, nid))
                        dirty[nloc] = depth
                    break
        if gone:
            sorted_arr = sorted_arr[
                ~np.isin(sorted_arr, np.asarray(gone, np.int64))]
        self._sorted_cache = sorted_arr
        self._update_height_bound(hb, dirty)
        return removed_mask

    def _update_height_bound(self, hb: dict, dirty: dict) -> None:
        """Fold dirty-subtree heights into the cached bound.  Unchanged
        regions are covered by the previous bound; deletes can only shrink a
        region, so the max stays a valid (possibly loose) upper bound —
        ``max_iters`` derived from it only bounds traversal loops."""
        for loc, depth in dirty.items():
            b, t = self._subtree_heights(self._item_at(loc), depth)
            hb["base"] = max(hb["base"], b)
            hb["trie"] = max(hb["trie"], t)
        self._hb = hb

    # ------------------------------------------------------------------
    # incremental freeze substrate: sorted order + height bound caches
    # ------------------------------------------------------------------
    def sorted_eids(self) -> np.ndarray:
        """Live entry ids in key order (== ``iter_subtree(root)``), cached
        and maintained incrementally across mutations."""
        if self._sorted_cache is None:
            self._sorted_cache = np.fromiter(
                self.iter_subtree(self.root_item), dtype=np.int64, count=-1)
        return self._sorted_cache

    def height_bound(self) -> dict:
        """Upper bound on ``heights()`` (exact after bulkload / full walk;
        maintained per-dirty-subtree by the bulk ops).  ``freeze`` derives
        the traversal iteration bound from this, so merges never pay a
        whole-index walk."""
        if self._hb is None:
            self._hb = self.heights()
        return self._hb

    # ------------------------------------------------------------------
    # ordered traversal (scan substrate) + stats
    # ------------------------------------------------------------------
    def iter_subtree(self, item: int) -> Iterator[int]:
        tag = item_tag(item)
        if tag == TAG_EMPTY:
            return
        if tag == TAG_ENTRY:
            yield item_payload(item)
            return
        if tag == TAG_CNODE:
            cid = item_payload(item)
            base, cnt = int(self.cn_base.data[cid]), int(self.cn_cnt.data[cid])
            for j in range(cnt):
                yield int(self.ch_ent.data[base + j])
            return
        if tag == TAG_TRIE:
            tid = item_payload(item)
            yield from self.iter_subtree(int(self.tr_left.data[tid]))
            yield from self.iter_subtree(int(self.tr_right.data[tid]))
            return
        nid = item_payload(item)
        base, m = int(self.mn_slot_base.data[nid]), int(self.mn_slot_cnt.data[nid])
        prev = None
        for p in range(m):
            it = int(self.items.data[base + p])
            if it != prev:      # aliased slots of one block: visit once
                yield from self.iter_subtree(it)
            prev = it

    def scan(self, begin: bytes, count: int) -> List[Tuple[bytes, int]]:
        """Host range scan: first ``count`` entries with key >= begin."""
        out: List[Tuple[bytes, int]] = []
        for eid in self.iter_subtree(self.root_item):
            k = self.key_at(eid)
            if k >= begin:
                out.append((k, int(self.ent_val.data[eid])))
                if len(out) >= count:
                    break
        return out

    def heights(self) -> dict:
        """Paper Table 3: (base height, trie height) by depth-first walk."""
        base_h, trie_h = self._subtree_heights(self.root_item, 0)
        return {"base": base_h, "trie": trie_h}

    def _subtree_heights(self, item: int, base_depth: int) -> Tuple[int, int]:
        """(base, trie) height of the subtree under ``item``, with mnode/cnode
        levels counted from ``base_depth`` (the slot's depth in the index)."""
        base_h = trie_h = 0
        stack = [(item, base_depth, 0)]
        while stack:
            item, bd, td = stack.pop()
            tag = item_tag(item)
            if tag in (TAG_EMPTY,):
                continue
            if tag == TAG_ENTRY:
                base_h = max(base_h, bd)
                trie_h = max(trie_h, td)
                continue
            if tag == TAG_CNODE:
                base_h = max(base_h, bd + 1)
                trie_h = max(trie_h, td)
                continue
            if tag == TAG_TRIE:
                tid = item_payload(item)
                stack.append((int(self.tr_left.data[tid]), bd, td + 1))
                stack.append((int(self.tr_right.data[tid]), bd, td + 1))
                continue
            nid = item_payload(item)
            base, m = int(self.mn_slot_base.data[nid]), int(self.mn_slot_cnt.data[nid])
            for p in range(m):
                it = int(self.items.data[base + p])
                if it:
                    stack.append((it, bd + 1, td))
        return base_h, trie_h

    def space_bytes(self) -> dict:
        pools = {
            "keys": self.key_bytes.nbytes_live,
            "entries": self.ent_off.nbytes_live + self.ent_len.nbytes_live + self.ent_val.nbytes_live,
            "items": self.items.nbytes_live,
            "mnodes": sum(
                g.nbytes_live
                for g in (self.mn_slot_base, self.mn_slot_cnt, self.mn_prefix_off,
                          self.mn_prefix_len, self.mn_alpha, self.mn_beta, self.mn_nkeys)
            ),
            "cnodes": self.cn_base.nbytes_live + self.cn_cnt.nbytes_live
            + self.ch_hash.nbytes_live + self.ch_ent.nbytes_live,
            "tries": self.tr_byte.nbytes_live + self.tr_mask.nbytes_live
            + self.tr_left.nbytes_live + self.tr_right.nbytes_live,
            "hpt": self.hpt.nbytes() if self.hpt is not None else 0,
        }
        pools["total"] = sum(pools.values())
        return pools
