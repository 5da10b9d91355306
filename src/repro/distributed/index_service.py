"""Distributed LITS query service: key-range partition + all_to_all routing.

The sorted keys are cut into ``n_shards`` equal contiguous ranges (DESIGN.md
§5).  Each shard holds an independent LITS over its key range, all built on
one global HPT; all shards' pools are padded to a common size and stacked
with a leading shard axis, so the whole service is one pytree sharded over
the ``data`` mesh axis.

Query path (one ``shard_map`` program, this is the collective pattern a
1000-node deployment runs):

  1. every device compares its resident queries with the first key of each
     shard (``split keys``, replicated) -> owner shard,
  2. ``all_to_all`` scatters queries to owners (fixed per-destination
     capacity, overflow reported),
  3. owners run the local jitted LITS search,
  4. ``all_to_all`` returns (found, value) results to the askers.

Routing compares bytes, so it is exact: a float32 CDF computed on the device
can round differently from the host's, and a key next to a CDF boundary would
then be sent to the wrong shard.
"""
from __future__ import annotations

import dataclasses
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import LITSBuilder, StringSet, freeze, lookup_values
from repro.core.strings import sort_order
from repro.core.tensor_index import (
    STATIC_FIELDS, TensorIndex, base_search_impl, pad_queries,
    resolve_search_backend, scan_batch,
)
from repro.index import StringIndexBase
from repro.kernels.strops import str_cmp_full


class RoutingOverflowError(RuntimeError):
    """A routed query batch exceeded a shard's per-destination capacity."""


@dataclasses.dataclass
class ShardedIndex:
    stacked: TensorIndex          # every leaf has a leading [n_shards] dim
    split_bytes: np.ndarray       # (n_shards-1, width) u8: first key of
    split_lens: np.ndarray        # shards 1.. (zero-padded) and its length
    n_shards: int
    width: int


def build_sharded(keys: List[bytes], values: np.ndarray, n_shards: int,
                  **builder_kw) -> ShardedIndex:
    ss = StringSet.from_list(keys)
    n = len(ss)
    if n < n_shards:
        raise ValueError(f"{n} keys cannot fill {n_shards} shards")
    order = sort_order(ss)
    ss = ss.take(order)
    values = np.asarray(values)[order]
    # one global HPT (trained on everything) shared by all shards: pools are
    # stacked, so every shard must index the same CDF table
    probe = LITSBuilder(**builder_kw)
    probe.bulkload(StringSet(ss.bytes.copy(), ss.lens.copy()), values.copy())
    hpt = probe.hpt
    width = probe.width
    cuts = [int(round(i * n / n_shards)) for i in range(n_shards + 1)]
    tis = []
    for s in range(n_shards):
        m = slice(cuts[s], cuts[s + 1])
        b = LITSBuilder(hpt=hpt, **{k: v for k, v in builder_kw.items() if k != "hpt"})
        sub = StringSet(ss.bytes[m], ss.lens[m])
        b.bulkload(sub, values[m], width=width)
        tis.append(freeze(b))
    stacked = _stack_indices(tis)
    split_bytes = np.zeros((n_shards - 1, width), np.uint8)
    split_bytes[:, : ss.width] = ss.bytes[cuts[1:-1], :width]
    return ShardedIndex(stacked, split_bytes, ss.lens[cuts[1:-1]].copy(),
                        n_shards, width)


def _stack_indices(tis: List[TensorIndex]) -> TensorIndex:
    """Pad every pool to the max size across shards, stack on a new axis 0."""
    import dataclasses as dc

    data_fields = [f.name for f in dc.fields(TensorIndex)
                   if f.name not in STATIC_FIELDS]
    out = {}
    for name in data_fields:
        leaves = [np.asarray(jax.device_get(getattr(t, name))) for t in tis]
        if leaves[0].ndim == 0:
            out[name] = jnp.asarray(np.stack(leaves))
            continue
        mx = max(l.shape[0] for l in leaves)
        padded = []
        for l in leaves:
            if l.shape[0] < mx:
                pad = np.zeros((mx - l.shape[0],) + l.shape[1:], l.dtype)
                l = np.concatenate([l, pad], axis=0)
            padded.append(l)
        out[name] = jnp.asarray(np.stack(padded))
    meta = dict(
        width=tis[0].width,
        max_iters=max(t.max_iters for t in tis),
        cnode_cap=tis[0].cnode_cap,
        rank_iters=max(t.rank_iters for t in tis),
        delta_probes=tis[0].delta_probes,
        cdf_steps=max(t.cdf_steps for t in tis),
    )
    return TensorIndex(**out, **meta)


def _slice_shard(stacked: TensorIndex, s) -> TensorIndex:
    import dataclasses as dc

    kw = {}
    for f in dc.fields(TensorIndex):
        v = getattr(stacked, f.name)
        if f.name in STATIC_FIELDS:
            kw[f.name] = v
        else:
            kw[f.name] = v[s] if hasattr(v, "ndim") else v
    return TensorIndex(**kw)


def make_service_fn(sidx: ShardedIndex, mesh, axis: str = "data",
                    per_dest_capacity: int = 256, shard_axes=None,
                    backend: str | None = None,
                    interpret: bool | None = None):
    """Returns a jitted shard_map fn: (qbytes, qlens) -> (found, lo, hi, overflow).

    ``axis`` is the partition axis of the index (all_to_all routing axis);
    ``shard_axes`` (default: just ``axis``) are the mesh axes the *query rows*
    are sharded over — extra axes act as serving replicas (the index is
    replicated across them).  ``backend`` selects the local traversal engine
    (DESIGN.md §7); ``None`` resolves from ``REPRO_SEARCH_BACKEND``.
    ``interpret`` overrides the Pallas execution mode (None -> env).
    """
    from jax.sharding import PartitionSpec as P

    shard_axes = (axis,) if shard_axes is None else tuple(shard_axes)
    backend = resolve_search_backend(backend)

    n = sidx.n_shards
    C = per_dest_capacity
    W = sidx.width
    split_pool = jnp.asarray(sidx.split_bytes.reshape(-1))
    split_lens = [int(x) for x in sidx.split_lens]

    def local(stk: TensorIndex, qbytes, qlens):
        # stk leaves carry a leading [1] local shard dim
        ti = _slice_shard(stk, 0)
        Q = qbytes.shape[0]
        # owner = number of shards whose first key is <= the query
        owner = jnp.zeros(Q, jnp.int32)
        for j, ln in enumerate(split_lens):
            c = str_cmp_full(qbytes, qlens, split_pool,
                             jnp.full(Q, j * W, jnp.int32),
                             jnp.full(Q, ln, jnp.int32))
            owner += (c >= 0).astype(jnp.int32)
        # pack queries into per-destination buffers of capacity C
        order = jnp.argsort(owner)
        so, sq, sl = owner[order], qbytes[order], qlens[order]
        first = jnp.searchsorted(so, so, side="left")
        slot = jnp.arange(Q, dtype=jnp.int32) - first.astype(jnp.int32)
        ok = slot < C
        sendq = jnp.zeros((n, C, W), jnp.uint8).at[so, slot].set(
            sq * ok[:, None].astype(jnp.uint8), mode="drop")
        sendl = jnp.zeros((n, C), jnp.int32).at[so, slot].set(
            jnp.where(ok, sl, 0), mode="drop")
        overflow = jnp.sum(~ok)
        # route to owners
        recvq = jax.lax.all_to_all(sendq, axis, 0, 0, tiled=False)
        recvl = jax.lax.all_to_all(sendl, axis, 0, 0, tiled=False)
        rq = recvq.reshape(n * C, W)
        rl = recvl.reshape(n * C)
        # §Perf H3: serving snapshots are immutable — skip the delta-buffer
        # probe (16 hash probes x W-byte compares per query in search_batch).
        found, eid, *_ = base_search_impl(ti, rq, rl, backend, interpret)
        lo, hi = lookup_values(ti, eid, jnp.zeros_like(found))
        found = found & (rl > 0)
        # send results home
        backf = jax.lax.all_to_all(found.reshape(n, C), axis, 0, 0)
        backlo = jax.lax.all_to_all(lo.reshape(n, C), axis, 0, 0)
        backhi = jax.lax.all_to_all(hi.reshape(n, C), axis, 0, 0)
        # unpack to original query order
        gather_f = backf[so, slot] & ok
        gather_lo = jnp.where(gather_f, backlo[so, slot], 0)
        gather_hi = jnp.where(gather_f, backhi[so, slot], 0)
        inv = jnp.argsort(order)
        return gather_f[inv], gather_lo[inv], gather_hi[inv], overflow[None]

    qspec = P(shard_axes)
    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(axis), qspec, qspec),
        out_specs=(qspec, qspec, qspec, qspec),
        check_vma=False,
    )
    return jax.jit(fn)


# ---------------------------------------------------------------------------
# StringIndex over the mesh (DESIGN.md §8)
# ---------------------------------------------------------------------------

class DistributedStringIndex(StringIndexBase):
    """A :class:`repro.index.StringIndexBase` implementation over a device mesh.

    Wraps a :class:`ShardedIndex` + its routed ``shard_map`` service into
    the same typed batched-op surface as the local
    :class:`repro.index.StringIndex`: ``get_batch`` / ``execute`` with
    per-op :class:`~repro.index.Status` codes.  Serving snapshots are
    immutable (delta probes are skipped shard-side), so PUTs and DELETEs
    report ``Status.UNSUPPORTED`` — rebuild via :meth:`build` to ingest.
    SCANs are served (:meth:`scan_entries`): each shard runs the same
    delta-aware ``scan_batch`` engine as the local index (with an empty
    delta this reduces to the frozen order), and because shards are
    contiguous key ranges (DESIGN.md §5), per-shard windows concatenate
    in shard order into the global window.  Front it with
    :class:`repro.serve.service.IndexService` (DESIGN.md §9) to serve it
    as an async multi-tenant request plane — the service treats both
    implementations identically.

    Construction places every stacked pool over the mesh partition axis
    (``NamedSharding(mesh, P(axis))``), so callers no longer hand-roll the
    per-field ``device_put`` loop.
    """

    def __init__(self, sidx: ShardedIndex, mesh, axis: str = "data",
                 per_dest_capacity: int = 256, shard_axes=None,
                 config=None):
        from jax.sharding import NamedSharding, PartitionSpec as P

        from repro.index import IndexConfig

        self.config = config or IndexConfig()
        self.mesh = mesh
        self.axis = axis
        self.shard_axes = (axis,) if shard_axes is None else tuple(shard_axes)
        # spread the stacked index over the mesh (leading shard axis -> axis)
        put = {}
        for f in dataclasses.fields(TensorIndex):
            v = getattr(sidx.stacked, f.name)
            if f.name in STATIC_FIELDS:
                put[f.name] = v
            else:
                put[f.name] = jax.device_put(v, NamedSharding(mesh, P(axis)))
        self.sidx = dataclasses.replace(sidx, stacked=TensorIndex(**put))
        self._per_dest_capacity = per_dest_capacity
        self._rows = int(np.prod([mesh.shape[a] for a in self.shard_axes]))
        self._shard_host: dict = {}   # shard id -> host entry-pool mirrors
        #                               (immutable snapshot: cache is safe)
        self._fn = make_service_fn(
            self.sidx, mesh, axis=axis, per_dest_capacity=per_dest_capacity,
            shard_axes=shard_axes, backend=self.config.search_backend,
            interpret=self.config.resolved_interpret())

    @classmethod
    def build(cls, keys: List[bytes], values: np.ndarray, n_shards: int,
              mesh=None, **kw) -> "DistributedStringIndex":
        """Bulk load: key-range partition -> per-shard LITS -> mesh placement."""
        sidx = build_sharded(keys, values, n_shards)
        if mesh is None:
            from repro.launch.mesh import make_mesh

            mesh = make_mesh((n_shards,), ("data",))
        return cls(sidx, mesh, **kw)

    @property
    def width(self) -> int:
        return self.sidx.width

    @property
    def n_shards(self) -> int:
        return self.sidx.n_shards

    def get_batch(self, keys) -> Tuple[np.ndarray, np.ndarray]:
        """Routed point lookups: (found mask, int64 values; misses hold 0).

        The query batch is padded to a multiple of the query-shard row
        count (zero-length pads can never match — ``found &= qlens > 0``
        shard-side), routed with ``all_to_all``, searched locally on the
        owner shard, and routed back.

        Raises :class:`RoutingOverflowError` if any destination shard
        received more than ``per_dest_capacity`` queries: the dropped
        queries would otherwise come back as silently-wrong NOT_FOUNDs.
        """
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core.tensor_index import pad_queries

        B = len(keys)
        if B == 0:
            return np.zeros(0, bool), np.zeros(0, np.int64)
        Bp = ((B + self._rows - 1) // self._rows) * self._rows
        qb, ql = pad_queries(list(keys), self.sidx.width)
        qbp = np.zeros((Bp, qb.shape[1]), np.uint8)
        qbp[:B] = qb
        qlp = np.zeros(Bp, np.int32)
        qlp[:B] = ql
        sharding = NamedSharding(self.mesh, P(self.shard_axes))
        qbp = jax.device_put(jnp.asarray(qbp), sharding)
        qlp = jax.device_put(jnp.asarray(qlp), sharding)
        found, lo, hi, overflow = self._fn(self.sidx.stacked, qbp, qlp)
        n_dropped = int(np.asarray(overflow).sum())
        if n_dropped:
            raise RoutingOverflowError(
                f"{n_dropped} queries exceeded per_dest_capacity="
                f"{self._per_dest_capacity} on their owner shard; raise the "
                f"capacity or split the batch")
        found = np.asarray(found)[:B]
        lo = np.asarray(lo)[:B].view(np.uint32).astype(np.int64)
        hi = np.asarray(hi)[:B].astype(np.int64)
        return found, np.where(found, (hi << 32) | lo, 0)

    # -- range scans over the mesh (DESIGN.md §11) --------------------------

    def _shard_host_entries(self, s: int):
        """Host mirrors of shard ``s``'s entry pools (scan results carry
        real key bytes).  Serving snapshots are immutable, so the copies
        are fetched once per shard and cached for the index's lifetime."""
        if s not in self._shard_host:
            ti = _slice_shard(self.sidx.stacked, s)
            pool, eo, el = jax.device_get(
                (ti.key_bytes, ti.ent_off, ti.ent_len))
            self._shard_host[s] = (np.asarray(pool), np.asarray(eo),
                                   np.asarray(el))
        return self._shard_host[s]

    def scan_entries(self, starts, window: int):
        """Range scans: per-query lists of ``(key, value)`` pairs — the next
        ``window`` keys >= each start across ALL shards.

        Every shard runs the local ``scan_batch`` engine on its slice
        (backend per ``config``), pinned to the FROZEN stream: like the
        shard-side GET path, serving scans skip the delta region — a
        hand-built stacked index carrying unmerged delta entries must not
        scan keys that shard-side GETs cannot see (and whose bytes live
        outside the cached base-pool mirrors).  Shards are contiguous key
        ranges (§5), so shard ``s``'s window sorts entirely before shard
        ``s+1``'s — per-shard windows concatenate in shard order and the
        first ``window`` survivors are the global answer.  Shards whose
        range ends below a query return empty windows and drop out; a
        smarter router would skip them up front (future work), correctness
        does not depend on it.
        """
        B = len(starts)
        if B == 0:
            return []
        qb, ql = pad_queries(list(starts), self.sidx.width)
        qb, ql = jnp.asarray(qb), jnp.asarray(ql)
        backend = resolve_search_backend(self.config.search_backend)
        interpret = self.config.resolved_interpret()
        out = [[] for _ in range(B)]
        for s in range(self.sidx.n_shards):
            if all(len(o) >= window for o in out):
                break
            ti = _slice_shard(self.sidx.stacked, s)
            # frozen-only: zero the delta stream bound (§11 — the scan
            # merge short-circuits to the contiguous frozen window)
            ti = dataclasses.replace(ti, de_count=jnp.zeros((), jnp.int32))
            eids, valid, _isd = scan_batch(ti, qb, ql, window,
                                           backend=backend,
                                           interpret=interpret)
            vlo, vhi = lookup_values(ti, jnp.maximum(eids, 0),
                                     jnp.zeros_like(valid))
            eids, valid, vlo, vhi = (np.asarray(x) for x in jax.device_get(
                (eids, valid, vlo, vhi)))
            if not valid.any():
                continue    # nothing from this shard: skip the (cached)
                #             full-pool host mirror fetch entirely
            vals = (vhi.astype(np.int64) << 32) \
                | vlo.view(np.uint32).astype(np.int64)
            pool, eo, el = self._shard_host_entries(s)
            for i in range(B):
                room = window - len(out[i])
                if room <= 0:
                    continue
                for e, ok, v in zip(eids[i].tolist(), valid[i].tolist(),
                                    vals[i].tolist()):
                    if not ok or room <= 0:
                        break
                    out[i].append((pool[eo[e]: eo[e] + el[e]].tobytes(), v))
                    room -= 1
        return out

    def execute(self, batch):
        """Typed batch entry point (GETs + SCANs on the read-only mesh service).

        Failures stay data (the StringIndexBase contract): mutating ops
        (PUT/DELETE) report ``Status.UNSUPPORTED``, and a batch that trips
        a shard's routing capacity marks every get
        ``Status.ROUTING_OVERFLOW`` (the dropped subset is unknowable once
        routed — retry with a smaller batch or a larger
        ``per_dest_capacity``).  Scans run through :meth:`scan_entries`
        (shard-local delta-aware engine + ordered-range concatenation).
        """
        from repro.index import (
            BatchResult, GetRequest, OpResult, ScanRequest, Status,
        )

        results = [None] * len(batch)
        gets = [(i, r) for i, r in enumerate(batch) if isinstance(r, GetRequest)]
        scans = [(i, r) for i, r in enumerate(batch)
                 if isinstance(r, ScanRequest)]
        for i, r in enumerate(batch):
            if not isinstance(r, (GetRequest, ScanRequest)):
                results[i] = OpResult(Status.UNSUPPORTED)
        if gets:
            try:
                found, vals = self.get_batch([r.key for _, r in gets])
            except RoutingOverflowError:
                overflowed = OpResult(Status.ROUTING_OVERFLOW)
                for i, _ in gets:
                    results[i] = overflowed
            else:
                self._map_get_results(gets, found, vals, self.sidx.width,
                                      results)
        if scans:
            default_w = getattr(self.config, "scan_window", 16)
            by_window = {}
            for i, r in scans:
                w = default_w if r.window is None else r.window
                by_window.setdefault(w, []).append((i, r))
            for w, group in by_window.items():
                entries = self.scan_entries([r.start for _, r in group], w)
                for (i, _r), ent in zip(group, entries):
                    results[i] = OpResult(Status.OK, entries=tuple(ent))
        return BatchResult(results=results, n_get=len(gets),
                           n_put=0, n_scan=len(scans), n_delete=0,
                           merged=False, delta_fill=0.0)
