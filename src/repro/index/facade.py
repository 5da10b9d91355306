"""`StringIndex` — the first-class LITS index facade (DESIGN.md §8).

One object owns the full index lifecycle that was previously scattered over
~10 free functions and two environment variables:

* :class:`IndexConfig` — unified configuration (width, delta-pool sizing,
  auto-compaction policy, scan window).
* :meth:`StringIndex.bulk_load` — paper Sec. 3.1 bulkload to a frozen
  device index.
* Typed batched ops — :class:`GetRequest` / :class:`PutRequest` /
  :class:`ScanRequest` / :class:`DeleteRequest` in, :class:`BatchResult`
  out, with per-op :class:`Status` codes (failures are data, not
  exceptions).  Deletes are delta-buffer tombstones reconciled at
  ``merge_delta`` (DESIGN.md §9).
* :meth:`StringIndex.execute` — plans a mixed batch into grouped fused
  dispatches: **one** ``insert_batch`` for all puts, **one**
  ``search_batch`` for all gets, one ``scan_batch`` per distinct window —
  and runs ``merge_delta`` automatically when the delta fill fraction
  crosses the configured threshold.
* :meth:`StringIndex.save` / :meth:`StringIndex.load` — versioned pytree
  snapshots (:mod:`repro.index.snapshot`).

Batch semantics (the planning contract tested in
tests/test_string_index.py): within one ``execute`` call, **puts apply
first**, then gets and scans observe the post-put index — i.e. the batch is
equivalent to the legacy sequence ``insert_batch(all puts)`` →
``search_batch(all gets)`` → ``scan_batch(all scans)``, bit-identically.
Gets see fresh puts through the delta probe, and scans are
**read-your-writes** too (DESIGN.md §11): ``scan_batch`` merges the live
delta view into the frozen order, so unmerged inserts appear immediately
and deleted keys never scan — point and range reads agree on every epoch.

The free functions in :mod:`repro.core.tensor_index` remain supported as
the kernel-level seam underneath this facade (legacy surface — see the
deprecation note in that module's docstring).
"""
from __future__ import annotations

import dataclasses
import enum
from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core import LITSBuilder, LITSConfig, StringSet
from repro.core.tensor_index import (
    TensorIndex,
    delete_batch,
    freeze,
    insert_batch,
    lookup_values,
    merge_delta,
    pack_queries,
    pad_queries,
    scan_batch,
    _search_batch_jit,
)
from .snapshot import load_index, save_index


# ---------------------------------------------------------------------------
# unified configuration
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class IndexConfig:
    """All index policy in one place (DESIGN.md §8)."""

    width: Optional[int] = None          # None: longest bulk-load key + headroom
    delta_capacity: int = 4096           # delta-buffer entry pool size
    delta_bytes: Optional[int] = None    # delta byte pool (None: capacity-derived)
    delta_probes: int = 16               # open-addressing probe bound
    # accepts only None or "jnp": there is one traversal.  Kept because the
    # on-chip benchmark's config files still pass "search_backend": "jnp";
    # it goes once they drop the key.
    search_backend: Optional[str] = None
    auto_merge_threshold: Optional[float] = 0.75  # None disables auto-compaction
    scan_window: int = 16                # default ScanRequest window
    builder: Optional[LITSConfig] = None  # host build policy (cnode cap, HPT shape)

    def __post_init__(self):
        if self.search_backend not in (None, "jnp"):
            raise ValueError(
                f"search_backend={self.search_backend!r}: the only traversal "
                "is 'jnp' (or leave it unset)")


# ---------------------------------------------------------------------------
# typed requests / responses
# ---------------------------------------------------------------------------

class Status(enum.IntEnum):
    """Per-op result codes: failures surface as data, never exceptions."""

    OK = 0
    NOT_FOUND = 1            # GET: key absent
    REJECTED_OVER_WIDTH = 2  # key longer than the index width (unrepresentable)
    REJECTED_FULL = 3        # PUT: delta pool full (merge and retry)
    UNSUPPORTED = 4          # op not available on this implementation
    ROUTING_OVERFLOW = 5     # distributed: batch exceeded a shard's routing
    #                          capacity — results indeterminate, retry smaller
    OVERLOADED = 6           # service admission control shed this op (queue
    #                          full) — back off and retry (DESIGN.md §9)
    FORBIDDEN = 7            # tenant-isolation violation (e.g. a scan cursor
    #                          forged for another tenant's namespace)


@dataclasses.dataclass(frozen=True, slots=True)
class GetRequest:
    key: bytes


@dataclasses.dataclass(frozen=True, slots=True)
class PutRequest:
    key: bytes
    value: int


@dataclasses.dataclass(frozen=True, slots=True)
class ScanRequest:
    start: bytes
    window: Optional[int] = None   # None -> IndexConfig.scan_window


@dataclasses.dataclass(frozen=True, slots=True)
class DeleteRequest:
    key: bytes


Request = Union[GetRequest, PutRequest, ScanRequest, DeleteRequest]


@dataclasses.dataclass(frozen=True, slots=True)
class OpResult:
    status: Status
    value: Optional[int] = None       # GET hit: the stored 64-bit value
    updated: bool = False             # PUT: key existed, value was updated
    entries: Optional[Tuple[Tuple[bytes, int], ...]] = None  # SCAN results

    @property
    def ok(self) -> bool:
        return self.status == Status.OK


# interned payload-free results: execute() returns thousands of these per
# batch, and a frozen dataclass is immutable, so sharing instances is safe
_PUT_OK = OpResult(Status.OK)
_PUT_UPDATED = OpResult(Status.OK, updated=True)
_DELETED = OpResult(Status.OK)
_NOT_FOUND = OpResult(Status.NOT_FOUND)
_REJECTED_OVER_WIDTH = OpResult(Status.REJECTED_OVER_WIDTH)
_REJECTED_FULL = OpResult(Status.REJECTED_FULL)
OVERLOADED_RESULT = OpResult(Status.OVERLOADED)


@dataclasses.dataclass(frozen=True)
class MergeTicket:
    """One open merge epoch (``begin_merge`` → ``run_merge`` →
    ``commit_merge``/``abort_merge``, DESIGN.md §10).

    ``ti`` is the immutable pytree snapshot the off-lock replay reads;
    mutations applied to the live index meanwhile are journaled on the
    facade and re-drained at commit."""

    ti: TensorIndex
    epoch: int
    builder_fresh: bool   # builder was reconstructed for this merge (values
    #                       already current — no device val-sync needed)


@dataclasses.dataclass
class BatchResult:
    """``execute`` output: per-op results in request order + batch effects."""

    results: List[OpResult]
    n_get: int = 0
    n_put: int = 0
    n_scan: int = 0
    n_delete: int = 0
    merged: bool = False              # auto-compaction ran during this batch
    delta_fill: float = 0.0           # fill fraction after the batch

    def statuses(self) -> List[Status]:
        return [r.status for r in self.results]


# ---------------------------------------------------------------------------
# 64-bit value packing (device pools store values as lo/hi int32 pairs)
# ---------------------------------------------------------------------------

def _coalesce_journal(journal: list) -> list:
    """Concatenate CONSECUTIVE same-kind journal batches (arrival order
    preserved) so the commit re-drain pays one device dispatch + one host
    sync per run of puts/deletes instead of one per flushed batch — the
    commit pause is the only pause the request path can observe."""
    out: list = []
    for kind, qb, ql, lo, hi in journal:
        if out and out[-1][0] == kind:
            k, pqb, pql, plo, phi = out[-1]
            out[-1] = (k, np.concatenate([pqb, qb]), np.concatenate([pql, ql]),
                       None if lo is None else np.concatenate([plo, lo]),
                       None if hi is None else np.concatenate([phi, hi]))
        else:
            out.append((kind, qb, ql, lo, hi))
    return out


def _pad_batch_pow2(qb, ql, lo, hi):
    """Pad a re-drain batch to the next power-of-two row count so commit
    replays hit a small set of bucketed jit shapes.  Pad rows use the
    over-width length sentinel (``width + 1``, see ``pad_queries``): no
    stored key can have it, so ``_mutate_batch`` resolves them as pure
    no-ops (no match, no new slot, no overflow latch)."""
    real = qb.shape[0]
    cap = 1 << max(real - 1, 0).bit_length()
    if cap == real:
        return qb, ql, lo, hi
    pad = cap - real
    qb = np.concatenate([qb, np.zeros((pad, qb.shape[1]), qb.dtype)])
    ql = np.concatenate([ql, np.full(pad, qb.shape[1] + 1, ql.dtype)])
    if lo is not None:
        lo = np.concatenate([lo, np.zeros(pad, lo.dtype)])
        hi = np.concatenate([hi, np.zeros(pad, hi.dtype)])
    return qb, ql, lo, hi


def _split_np(vals: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    v = np.asarray(vals, np.int64)
    lo = (v & 0xFFFFFFFF).astype(np.uint32).view(np.int32)
    hi = (v >> 32).astype(np.int32)
    return lo, hi


def _split_values(vals: np.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    lo, hi = _split_np(vals)
    return jnp.asarray(lo), jnp.asarray(hi)


def _join_values(lo, hi) -> np.ndarray:
    lo = np.asarray(lo, np.int32).view(np.uint32).astype(np.int64)
    hi = np.asarray(hi, np.int32).astype(np.int64)
    return (hi << 32) | lo


# ---------------------------------------------------------------------------
# the facade
# ---------------------------------------------------------------------------

class StringIndexBase:
    """Minimal contract every StringIndex implementation provides.

    Implemented by the local single-device :class:`StringIndex` and by the
    mesh-distributed
    :class:`repro.distributed.index_service.DistributedStringIndex`.
    """

    config: IndexConfig
    # device->host syncs on the request path (one per get, put, delete or
    # scan group); an implementation that does not count them reads 0
    host_syncs: int = 0
    # search-walk loop iterations over all get groups, and those of them
    # that ran the model-node step (read in the get group's one sync);
    # None where not counted (the mesh index)
    walk_iters: Optional[int] = None
    model_step_iters: Optional[int] = None
    # terminal-resolve compare rounds over all get groups (one per group
    # unless a 16-bit h-pointer hash collides); None where not counted
    resolve_rounds: Optional[int] = None
    # device interactions of get groups: program launches plus explicit
    # host<->device transfers (three per group); None where not counted
    device_calls: Optional[int] = None

    def execute(self, batch: Sequence[Request]) -> BatchResult:
        raise NotImplementedError

    def get_batch(self, keys: Sequence[bytes]) -> Tuple[np.ndarray, np.ndarray]:
        raise NotImplementedError

    @staticmethod
    def _map_get_results(gets, found, vals, width: int, results) -> None:
        """(found, values) arrays -> per-op OpResults, written into
        ``results`` at each get's original batch position.  The single
        copy of the hit/miss/over-width mapping, shared by every
        implementation so the typed surfaces cannot drift."""
        for (i, req), f, v in zip(gets, found.tolist(), vals.tolist()):
            if len(req.key) > width:
                results[i] = _REJECTED_OVER_WIDTH
            elif f:
                results[i] = OpResult(Status.OK, value=v)
            else:
                results[i] = _NOT_FOUND


class StringIndex(StringIndexBase):
    """Single-device LITS over the HPT + sub-trie + PMSS hybrid (PAPER.md §3–§5)."""

    walk_iters: int
    model_step_iters: int
    resolve_rounds: int
    device_calls: int

    def __init__(self, builder: Optional[LITSBuilder], ti: TensorIndex,
                 config: IndexConfig):
        self._builder = builder        # None after load(): rebuilt lazily on merge
        self.ti = ti
        self.config = config
        self.merge_count = 0
        self.host_syncs = 0
        self.walk_iters = self.model_step_iters = self.resolve_rounds = 0
        self.device_calls = 0
        self._host_pool = None         # lazy (key_bytes, ent_off, ent_len) copies
        # None = no merge in flight; a list = the epoch-merge journal: every
        # mutation applied between begin_merge() and commit_merge() is
        # recorded here and re-drained onto the merged index at commit
        # (DESIGN.md §10 — the re-drain invariant)
        self._merge_journal: Optional[list] = None
        # fill fraction, latched overflow flag and compaction epoch mirrored
        # on host: every delta mutation goes through put_batch/delete_batch/
        # merge on this object, so the mirrors stay exact and read paths
        # (stats polling included) never pay a device sync for them — ONE
        # bundled sync here at construction
        import jax

        de_count, overflow, epoch = jax.device_get(
            (ti.de_count, ti.delta_overflow, ti.epoch))
        self._delta_fill = float(de_count) / ti.de_off.shape[0]
        self._overflowed = bool(overflow)
        self._epoch = int(epoch)

    # -- lifecycle ----------------------------------------------------------

    @classmethod
    def bulk_load(cls, keys: Sequence[bytes],
                  values: Optional[np.ndarray] = None,
                  config: Optional[IndexConfig] = None) -> "StringIndex":
        """Paper Sec. 3.1: sample -> HPT -> collision-driven build -> freeze."""
        cfg = config or IndexConfig()
        builder = LITSBuilder(config=cfg.builder)
        vals = (np.asarray(values, np.int64) if values is not None
                else np.arange(len(keys), dtype=np.int64))
        builder.bulkload(StringSet.from_list(list(keys)), vals, width=cfg.width)
        ti = freeze(builder, delta_capacity=cfg.delta_capacity,
                    delta_bytes=cfg.delta_bytes, delta_probes=cfg.delta_probes)
        return cls(builder, ti, cfg)

    @classmethod
    def from_builder(cls, builder: LITSBuilder,
                     config: Optional[IndexConfig] = None) -> "StringIndex":
        """Wrap an already bulk-loaded host builder (custom PMSS/HPT/host
        model variants — the power-user seam the benchmarks use)."""
        cfg = config or IndexConfig()
        ti = freeze(builder, delta_capacity=cfg.delta_capacity,
                    delta_bytes=cfg.delta_bytes, delta_probes=cfg.delta_probes)
        return cls(builder, ti, cfg)

    def save(self, path: str) -> None:
        """Versioned snapshot of the full pytree (base + live delta buffer)."""
        save_index(self.ti, path)

    @classmethod
    def load(cls, path: str,
             config: Optional[IndexConfig] = None) -> "StringIndex":
        """Restore a snapshot.  ``config`` supplies *runtime* policy only
        (merge threshold, scan window); the structural parameters
        (width, delta sizing) come from the snapshot itself."""
        ti = load_index(path)
        return cls(None, ti, config or IndexConfig())

    # -- introspection ------------------------------------------------------

    @property
    def width(self) -> int:
        return self.ti.width

    @property
    def n_entries(self) -> int:
        return self.ti.n_entries

    @property
    def delta_fill(self) -> float:
        return self._delta_fill

    @property
    def build_counts(self) -> Optional[dict]:
        """The host builder's counts since it was made: ``subtries`` by the
        rule that built them (``pmss``, ``heavy_slot``, ``unsplittable``)
        and ``keys_past_cdf_cap``.  ``None`` when no builder is held (after
        :meth:`load`, until a merge rebuilds one)."""
        b = self._builder
        if b is None:
            return None
        return {"subtries": dict(b.subtries),
                "keys_past_cdf_cap": b.keys_past_cdf_cap}

    @property
    def epoch(self) -> int:
        """Compaction epoch (host mirror of ``ti.epoch``; bumps per merge)."""
        return self._epoch

    @property
    def delta_overflowed(self) -> bool:
        """A delta mutation was rejected for pool space (latched until the
        next merge).  Distinct from ``delta_fill``: the byte pool or the
        probe bound can reject while the entry count is still low, so
        compaction policy must watch both."""
        return self._overflowed

    def nbytes(self) -> int:
        return self.ti.nbytes()

    # -- batched primitives (each is ONE fused dispatch) --------------------

    def get_batch(self, keys: Sequence[bytes]) -> Tuple[np.ndarray, np.ndarray]:
        """Point lookups: (found bool mask, int64 values; misses hold 0)."""
        if not keys:
            return np.zeros(0, bool), np.zeros(0, np.int64)
        import jax

        # three device calls: one transfer in, one program, one copy out
        with TraceAnnotation("lits.index.get.encode"):
            packed = jax.device_put(pack_queries(keys, self.ti.width))
            self.device_calls += 1
        with TraceAnnotation("lits.index.get.dispatch"):
            out = _search_batch_jit(self.ti, packed)
            self.device_calls += 1
        # ONE host sync for the whole get group
        with TraceAnnotation("lits.index.get.sync"):
            out = jax.device_get(out)
            self.device_calls += 1
        self.host_syncs += 1
        iters, model_iters, rounds = out[-1].tolist()
        self.walk_iters += iters
        self.model_step_iters += model_iters
        self.resolve_rounds += rounds
        with TraceAnnotation("lits.index.get.decode"):
            found = out[:-1, 0].astype(bool)
            return found, np.where(found, _join_values(out[:-1, 1],
                                                       out[:-1, 2]), 0)

    def put_batch(self, keys: Sequence[bytes],
                  values: Sequence[int]) -> Tuple[np.ndarray, np.ndarray, bool]:
        """Upserts: (inserted mask, updated mask, auto-merge ran).

        New keys go to the device delta buffer; existing keys (base or
        delta) get their value updated in place.  Crossing the configured
        fill threshold triggers minor compaction (``merge_delta``).
        """
        if not len(keys):
            return np.zeros(0, bool), np.zeros(0, bool), False
        import jax

        with TraceAnnotation("lits.index.put"):
            qb, ql = pad_queries(list(keys), self.ti.width)
            lo_np, hi_np = _split_np(np.asarray(values, np.int64))
            self.ti, ins, upd = insert_batch(
                self.ti, jnp.asarray(qb), jnp.asarray(ql),
                jnp.asarray(lo_np), jnp.asarray(hi_np))
            # ONE host sync: op masks + the delta state the merge policy needs
            with TraceAnnotation("lits.index.put.sync"):
                ins, upd, de_count, overflow = jax.device_get(
                    (ins, upd, self.ti.de_count, self.ti.delta_overflow))
            self.host_syncs += 1
            self._delta_fill = float(de_count) / self.ti.de_off.shape[0]
            self._overflowed = bool(overflow)
            if self._merge_journal is not None:
                # epoch merge in flight: journal the ACCEPTED ops (rejected /
                # over-width ops already reported failure — re-draining them
                # would resurrect work the caller was told did not happen)
                acc = ins | upd
                if acc.any():
                    self._merge_journal.append(
                        ("put", qb[acc], ql[acc], lo_np[acc], hi_np[acc]))
            merged = self._maybe_merge(bool(overflow))
        return ins, upd, merged

    def delete_batch(self, keys: Sequence[bytes]) -> Tuple[np.ndarray, np.ndarray, bool]:
        """Deletes: (deleted mask, rejected-full mask, auto-merge ran).

        Deletes are delta-buffer tombstones (DESIGN.md §9): a key in the
        delta gets its tombstone set in place; a key living only in the
        frozen base claims a new shadowing tombstone entry, reconciled as a
        physical ``builder.delete`` at the next ``merge_delta``.  Gets AND
        scans observe the delete immediately — the scan merge consumes the
        tombstone to suppress its base entry (DESIGN.md §11).
        """
        if not len(keys):
            return np.zeros(0, bool), np.zeros(0, bool), False
        import jax

        with TraceAnnotation("lits.index.delete"):
            qb, ql = pad_queries(list(keys), self.ti.width)
            self.ti, deleted, rejected = delete_batch(
                self.ti, jnp.asarray(qb), jnp.asarray(ql))
            # ONE host sync: op masks + the delta state the merge policy needs
            with TraceAnnotation("lits.index.delete.sync"):
                deleted, rejected, de_count, overflow = jax.device_get(
                    (deleted, rejected, self.ti.de_count,
                     self.ti.delta_overflow))
            self.host_syncs += 1
            self._delta_fill = float(de_count) / self.ti.de_off.shape[0]
            self._overflowed = bool(overflow)
            if self._merge_journal is not None and deleted.any():
                # journal only EFFECTIVE deletes (absent keys are no-ops on
                # the merged index too; rejected tombstones were reported)
                self._merge_journal.append(
                    ("delete", qb[deleted], ql[deleted], None, None))
            merged = self._maybe_merge(bool(overflow))
        return deleted, rejected, merged

    def scan_batch(self, starts: Sequence[bytes], window: int):
        """Delta-aware range scans: ``(eids, valid, is_delta)``, each
        ``(B, window)`` — read-your-writes (DESIGN.md §11).  Unmerged delta
        inserts appear in order, tombstoned keys are suppressed; ``eids``
        index the base pools where ``~is_delta`` and the delta pools where
        ``is_delta`` (the ``lookup_values`` contract)."""
        qb, ql = pad_queries(list(starts), self.ti.width)
        return scan_batch(self.ti, jnp.asarray(qb), jnp.asarray(ql), window)

    # -- single-op conveniences --------------------------------------------

    def get(self, key: bytes) -> Optional[int]:
        found, vals = self.get_batch([key])
        return int(vals[0]) if found[0] else None

    def put(self, key: bytes, value: int) -> OpResult:
        return self.execute([PutRequest(key, value)]).results[0]

    def delete(self, key: bytes) -> OpResult:
        return self.execute([DeleteRequest(key)]).results[0]

    def scan(self, start: bytes,
             window: Optional[int] = None) -> List[Tuple[bytes, int]]:
        res = self.execute([ScanRequest(start, window)]).results[0]
        return list(res.entries or ())

    # -- the batched entry point -------------------------------------------

    def execute(self, batch: Sequence[Request]) -> BatchResult:
        """Plan + run a mixed GET/PUT/SCAN/DELETE batch as grouped fused dispatches.

        Puts apply first (one ``insert_batch``), then deletes (one
        ``delete_batch`` — a delete beats a put of the same key within a
        batch), then gets (one ``search_batch``) and scans (one
        ``scan_batch`` per distinct window) observe the post-mutation
        index.  Per-op failures come back as :class:`Status` codes; the
        only exceptions raised are for malformed requests (unknown op
        types).

        In a profiler trace the call is a ``lits.index.execute`` span over
        ``lits.index.plan`` and one span per op group (``lits.index.get.*``,
        ``put``, ``delete``, ``scan``), each group's one ``device_get``
        under its own ``.sync`` span; ``host_syncs`` counts those syncs.
        """
        with TraceAnnotation("lits.index.execute"):
            return self._execute(batch)

    def _execute(self, batch: Sequence[Request]) -> BatchResult:
        results: List[Optional[OpResult]] = [None] * len(batch)
        gets: List[Tuple[int, GetRequest]] = []
        puts: List[Tuple[int, PutRequest]] = []
        dels: List[Tuple[int, DeleteRequest]] = []
        scans: List[Tuple[int, ScanRequest]] = []
        with TraceAnnotation("lits.index.plan"):
            for i, req in enumerate(batch):
                if isinstance(req, GetRequest):
                    gets.append((i, req))
                elif isinstance(req, PutRequest):
                    puts.append((i, req))
                elif isinstance(req, DeleteRequest):
                    dels.append((i, req))
                elif isinstance(req, ScanRequest):
                    scans.append((i, req))
                else:
                    raise TypeError(
                        f"unknown request type: {type(req).__name__}")

        merged = False
        width = self.ti.width
        if puts:
            ins, upd, merged = self.put_batch(
                [r.key for _, r in puts], [r.value for _, r in puts])
            for (i, req), in_, up in zip(puts, ins.tolist(), upd.tolist()):
                if len(req.key) > width:
                    results[i] = _REJECTED_OVER_WIDTH
                elif in_ or up:
                    results[i] = _PUT_UPDATED if up else _PUT_OK
                else:
                    results[i] = _REJECTED_FULL

        if dels:
            deleted, rejected, dmerged = self.delete_batch(
                [r.key for _, r in dels])
            merged = merged or dmerged
            for (i, req), d, rej in zip(dels, deleted.tolist(),
                                        rejected.tolist()):
                if len(req.key) > width:
                    results[i] = _REJECTED_OVER_WIDTH
                elif d:
                    results[i] = _DELETED
                elif rej:
                    results[i] = _REJECTED_FULL
                else:
                    results[i] = _NOT_FOUND

        if gets:
            found, vals = self.get_batch([r.key for _, r in gets])
            with TraceAnnotation("lits.index.get.decode"):
                self._map_get_results(gets, found, vals, width, results)

        if scans:
            import jax

            by_window: Dict[int, List[Tuple[int, ScanRequest]]] = {}
            for i, req in scans:
                w = self.config.scan_window if req.window is None else req.window
                by_window.setdefault(w, []).append((i, req))
            pool, ent_off, ent_len = self._host_entries()
            for w, group in by_window.items():
                with TraceAnnotation("lits.index.scan"):
                    eids, valid, isd = self.scan_batch(
                        [r.start for _, r in group], w)
                    vlo, vhi = lookup_values(
                        self.ti, jnp.maximum(eids, 0), isd)
                    fetch = [eids, valid, isd, vlo, vhi]
                    if self._delta_fill > 0.0:
                        # delta entries may appear in the window: gather
                        # their key bytes device-side (the frozen host pool
                        # mirror cannot serve them), bundled into the sync
                        e = jnp.minimum(jnp.maximum(eids, 0),
                                        self.ti.de_off.shape[0] - 1)
                        doff = jnp.take(self.ti.de_off, e)
                        didx = jnp.minimum(
                            doff[..., None]
                            + jnp.arange(self.ti.width, dtype=jnp.int32),
                            self.ti.db_bytes.shape[0] - 1)
                        fetch += [jnp.take(self.ti.de_len, e),
                                  jnp.take(self.ti.db_bytes, didx)]
                    # ONE host sync per scan group
                    with TraceAnnotation("lits.index.scan.sync"):
                        got = jax.device_get(fetch)
                    self.host_syncs += 1
                    eids, valid, isd, vlo, vhi = got[:5]
                    dlen, dbytes = got[5:] if len(got) > 5 else (None, None)
                    vals = _join_values(vlo, vhi)
                    for row, (i, req) in enumerate(group):
                        entries = []
                        for col, (e, v, ok, d) in enumerate(zip(
                                eids[row].tolist(), vals[row].tolist(),
                                valid[row].tolist(), isd[row].tolist())):
                            if not ok:
                                continue
                            if d:
                                key = dbytes[row, col, : dlen[row, col]]
                            else:
                                key = pool[ent_off[e]: ent_off[e] + ent_len[e]]
                            entries.append((key.tobytes(), v))
                        results[i] = OpResult(Status.OK,
                                              entries=tuple(entries))

        return BatchResult(
            results=results,  # type: ignore[arg-type]
            n_get=len(gets), n_put=len(puts), n_scan=len(scans),
            n_delete=len(dels), merged=merged, delta_fill=self._delta_fill,
        )

    # -- compaction (epoch-based, DESIGN.md §10) ----------------------------

    def merge(self) -> None:
        """Minor compaction, synchronous: replay the delta buffer into the
        host builder, re-freeze, swap.  Runs automatically from
        ``execute``/``put_batch`` when the fill fraction crosses
        ``config.auto_merge_threshold``.  Composed from the epoch seams
        below — concurrent callers (the service's maintenance thread) use
        them directly to keep the expensive middle step off the index lock.
        """
        ticket = self.begin_merge()
        try:
            new_ti = self.run_merge(ticket)
        except BaseException:
            self.abort_merge(ticket)
            raise
        self.commit_merge(ticket, new_ti)

    def begin_merge(self) -> MergeTicket:
        """Open a merge epoch: snapshot the current index and start the
        mutation journal.  Cheap (no device work) — callers hold their
        serialization lock only for this and for :meth:`commit_merge`;
        :meth:`run_merge` runs lock-free while mutations keep landing on
        the live index (journaled for the commit re-drain).  One merge may
        be open at a time."""
        if self._merge_journal is not None:
            raise RuntimeError("a merge epoch is already open")
        self._merge_journal = []
        return MergeTicket(ti=self.ti, epoch=self._epoch,
                           builder_fresh=self._builder is None)

    def run_merge(self, ticket: MergeTicket) -> TensorIndex:
        """The expensive middle step, safe OUTSIDE the caller's index lock:
        bulk-replay the ticket's delta snapshot into the host builder and
        re-freeze.  Touches only the ticket's (immutable) pytree and the
        builder — never the live ``self.ti``."""
        builder = self._ensure_builder(ticket.ti)
        # a freeze-lineage builder is in eid-lockstep with the snapshot, so
        # device-side in-place base value updates must be copied back; a
        # builder reconstructed just now already read the live values
        return merge_delta(builder, ticket.ti,
                           sync_base_values=not ticket.builder_fresh)

    def commit_merge(self, ticket: MergeTicket, new_ti: TensorIndex) -> int:
        """Swap the merged base in and re-drain the journal: every mutation
        accepted between begin and commit replays onto ``new_ti`` in arrival
        order, so the swap is invisible to readers and writers (the §10
        re-drain invariant).  Returns the number of re-drained ops — the
        measure of the commit pause, bounded by write traffic during the
        merge, not by index size."""
        import jax

        journal, self._merge_journal = self._merge_journal or [], None
        redrained = 0
        for kind, qb, ql, lo, hi in _coalesce_journal(journal):
            real = qb.shape[0]
            redrained += real
            # pad to a power-of-two bucket: coalesced batches would otherwise
            # be novel (B, W) shapes whose first dispatch pays an XLA compile
            # UNDER the commit lock — the very pause this protocol bounds.
            # Pad rows carry the over-width length sentinel (width + 1),
            # which _mutate_batch rejects without mutating anything.
            qb, ql, lo, hi = _pad_batch_pow2(qb, ql, lo, hi)
            for attempt in (0, 1):
                if kind == "put":
                    new_ti, ins, upd = insert_batch(
                        new_ti, jnp.asarray(qb), jnp.asarray(ql),
                        jnp.asarray(lo), jnp.asarray(hi))
                    clean = bool(jax.device_get(jnp.all((ins | upd)[:real])))
                else:
                    new_ti, _, rej = delete_batch(
                        new_ti, jnp.asarray(qb), jnp.asarray(ql))
                    clean = not bool(jax.device_get(jnp.any(rej[:real])))
                if clean:
                    break
                if attempt:
                    # a retry against an EMPTY delta still rejected: the
                    # journal batch itself exceeds the pool.  These ops were
                    # acknowledged — dropping them silently is not an option,
                    # so fail the commit loudly (the live index still holds
                    # every write; only the merged base is discarded)
                    raise RuntimeError(
                        "re-drain rejected acknowledged ops even after a "
                        "fold-down merge; delta pool too small for the "
                        "journal batch")
                # the fresh delta pool filled mid-re-drain (journal bigger
                # than capacity): fold it down and replay this batch again
                new_ti = merge_delta(self._ensure_builder(), new_ti,
                                     sync_base_values=True)
        self.ti = new_ti
        self.merge_count += 1
        self._host_pool = None
        de_count, overflow, epoch = jax.device_get(
            (new_ti.de_count, new_ti.delta_overflow, new_ti.epoch))
        self._delta_fill = float(de_count) / new_ti.de_off.shape[0]
        self._overflowed = bool(overflow)
        self._epoch = int(epoch)
        return redrained

    def abort_merge(self, ticket: MergeTicket) -> None:
        """Close a merge epoch without swapping: the live index (which kept
        absorbing writes) stays current; the journal is discarded."""
        self._merge_journal = None

    def _maybe_merge(self, overflow: bool) -> bool:
        thr = self.config.auto_merge_threshold
        if thr is None or self._merge_journal is not None:
            # policy disabled (delta epoch pinned — on overflow, further
            # puts come back Status.REJECTED_FULL until the caller invokes
            # merge() explicitly), or a merge epoch is already open (this
            # mutation was just journaled; the commit re-drain covers it)
            return False
        if overflow or self._delta_fill >= thr:
            self.merge()
            return True
        return False

    def _ensure_builder(self, ti: Optional[TensorIndex] = None) -> LITSBuilder:
        """The host builder; reconstructed from ``ti``'s (default: the live)
        base pools after ``load`` (a snapshot carries no host state).  Only
        the LIVE entries (``ent_sorted``) are replayed — the pools may carry
        dead bytes from pre-snapshot deletes, and resurrecting those would
        undo them.  The rebuilt builder retrains its HPT, so post-merge
        entry ids may differ from the pre-snapshot lineage — key->value
        results are unaffected."""
        if self._builder is None:
            import jax

            ti = self.ti if ti is None else ti
            pool, ent_off, ent_len = self._host_entries()
            eids, lo, hi, root = jax.device_get(
                (ti.ent_sorted, ti.ent_val_lo, ti.ent_val_hi, ti.root_item))
            if int(root) == 0:  # TAG_EMPTY root: no live entries at all —
                # freeze pads ent_sorted with a [0] SENTINEL then, and pool
                # slot 0 may hold a dead (deleted) key that must NOT come back
                from repro.core.hpt import uniform_hpt

                b = LITSBuilder(config=self.config.builder,
                                hpt=uniform_hpt())
                b.width = ti.width
                b._sorted_cache = np.zeros(0, np.int64)
                self._builder = b
                return b
            eids = np.asarray(eids, np.int64)
            vals = _join_values(lo, hi)
            keys = [pool[ent_off[i]: ent_off[i] + ent_len[i]].tobytes()
                    for i in eids]
            b = LITSBuilder(config=self.config.builder)
            b.bulkload(StringSet.from_list(keys), vals[eids], width=ti.width)
            self._builder = b
        return self._builder

    # -- host-side key pool (scans return real key bytes) -------------------

    def _host_entries(self):
        if self._host_pool is None:
            import jax

            self._host_pool = (
                np.asarray(jax.device_get(self.ti.key_bytes)),
                np.asarray(jax.device_get(self.ti.ent_off)),
                np.asarray(jax.device_get(self.ti.ent_len)),
            )
        return self._host_pool

    def _entry_key(self, eid: int) -> bytes:
        pool, ent_off, ent_len = self._host_entries()
        return pool[ent_off[eid]: ent_off[eid] + ent_len[eid]].tobytes()
