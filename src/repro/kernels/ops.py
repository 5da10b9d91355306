"""Jit'd public wrappers for the Pallas kernels.

On CPU (this container) the kernels execute in ``interpret=True`` mode; on a
real TPU backend they compile natively.  ``interpret`` is resolved ONCE per
process (cached) from the default backend, overridable without code edits via
the ``REPRO_KERNEL_BACKEND`` environment variable:

* ``REPRO_KERNEL_BACKEND=interpret`` — force interpreter mode (CPU only),
* ``REPRO_KERNEL_BACKEND=native``    — force native Mosaic compilation,
* unset / ``auto``                   — interpret unless ``jax.default_backend()``
  is ``tpu``.

Interpret mode is refused on a TPU backend (``RuntimeError``), however it is
asked for: a kernel must never run in the interpreter on the chip unnoticed.

Tests that need to flip the mode mid-process call
``_interpret_default.cache_clear()`` after changing the env var.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from .cnode_probe import cnode_probe_pallas
from .hpt_cdf import hpt_cdf_pallas
from .hpt_locate import hpt_locate_pallas
from .rank import fused_rank_pallas
from .scan import fused_scan_pallas
from .traverse import fused_search_pallas

KERNEL_BACKENDS = ("auto", "interpret", "native")


def _checked(interpret: bool) -> bool:
    """Pass ``interpret`` through; refuse the interpreter on a TPU backend."""
    if interpret and jax.default_backend() == "tpu":
        raise RuntimeError(
            "Pallas interpret mode was requested on a TPU backend; kernels "
            "compile natively there (unset REPRO_KERNEL_BACKEND / "
            "kernel_backend, or use 'native')")
    return interpret


def _resolve(interpret: bool | None) -> bool:
    return _checked(_interpret_default() if interpret is None else interpret)


@functools.lru_cache(maxsize=1)
def _interpret_default() -> bool:
    mode = os.environ.get("REPRO_KERNEL_BACKEND", "auto").strip().lower()
    if mode in ("interpret", "cpu"):
        return _checked(True)
    if mode in ("native", "mosaic", "tpu"):
        return False
    if mode not in ("", "auto"):
        raise ValueError(
            f"REPRO_KERNEL_BACKEND={mode!r}: expected auto|interpret|native")
    return jax.default_backend() != "tpu"


def resolve_interpret(mode: str | None = None) -> bool:
    """Explicit kernel-backend name -> interpret flag; ``None``/"auto" -> env.

    This is the config-over-env seam used by :class:`repro.index.IndexConfig`:
    an explicit ``kernel_backend`` in the config wins over the
    ``REPRO_KERNEL_BACKEND`` environment variable, which remains the
    process-wide default.
    """
    if mode is None:
        return _interpret_default()
    m = mode.strip().lower()
    if m in ("", "auto"):
        return _interpret_default()
    if m in ("interpret", "cpu"):
        return _checked(True)
    if m in ("native", "mosaic", "tpu"):
        return False
    raise ValueError(
        f"unknown kernel backend {mode!r}; expected one of {KERNEL_BACKENDS}")


def hpt_cdf(qbytes, qlens, start=0, *, cdf_tab, prob_tab, variant: str = "gather",
            block_b: int = 256, max_steps: int = 64, interpret: bool | None = None):
    """Batched HPT GetCDF via the Pallas kernel."""
    B = qbytes.shape[0]
    start = jnp.broadcast_to(jnp.asarray(start, jnp.int32), (B,))
    return hpt_cdf_pallas(
        qbytes, jnp.asarray(qlens, jnp.int32), start, cdf_tab, prob_tab,
        block_b=block_b, max_steps=max_steps, variant=variant,
        interpret=_resolve(interpret),
    )


def hpt_locate(qbytes, qlens, start, alpha, beta, nslots, *, cdf_tab, prob_tab,
               block_b: int = 256, max_steps: int = 64, interpret: bool | None = None):
    """Fused CDF + linear model + clamp -> slot positions."""
    B = qbytes.shape[0]
    bc = lambda v, dt: jnp.broadcast_to(jnp.asarray(v, dt), (B,))
    return hpt_locate_pallas(
        qbytes, bc(qlens, jnp.int32), bc(start, jnp.int32), bc(alpha, jnp.float32),
        bc(beta, jnp.float32), bc(nslots, jnp.int32), cdf_tab, prob_tab,
        block_b=block_b, max_steps=max_steps,
        interpret=_resolve(interpret),
    )


def cnode_probe(hashes, qhash, cnt, frm=None, *, block_b: int = 512,
                interpret: bool | None = None):
    """First matching h-pointer slot per query (or -1)."""
    return cnode_probe_pallas(
        hashes, qhash, cnt, frm, block_b=block_b,
        interpret=_resolve(interpret),
    )


def fused_rank(ti, qbytes, qlens, *, block_b: int = 256,
               interpret: bool | None = None):
    """Fused ordered-rank over a :class:`~repro.core.tensor_index.TensorIndex`.

    Returns (B,) int32 ranks into ``ti.ent_sorted`` — bit-identical to the
    jnp reference (`rank_batch`, shared impl ``core.walk.rank_sorted``).
    ``ti`` is duck-typed to avoid a core import.
    """
    return fused_rank_pallas(
        qbytes, jnp.asarray(qlens, jnp.int32), ti.ent_sorted, ti.ent_off,
        ti.ent_len, ti.key_bytes, rank_iters=ti.rank_iters, block_b=block_b,
        interpret=_resolve(interpret),
    )


def fused_scan(ti, qbytes, qlens, *, window: int, block_b: int = 256,
               interpret: bool | None = None):
    """Fused delta-aware scan over a :class:`~repro.core.tensor_index.TensorIndex`.

    Returns ``(eids, valid, is_delta)`` windows — bit-identical to the jnp
    reference (`scan_batch`, shared impl ``core.walk.scan_merged``): the
    frozen order and the sorted live-delta view merge inside one kernel,
    tombstones suppressing shadowed base entries (DESIGN.md §11).  ``ti``
    is duck-typed to avoid a core import; the EMPTY-root gate (zero live
    base entries — the pad sentinel must not scan) is applied here so the
    kernel sees only stream bounds.
    """
    n_base = jnp.where(ti.root_item != 0,
                       jnp.int32(ti.ent_sorted.shape[0]), jnp.int32(0))
    return fused_scan_pallas(
        qbytes, jnp.asarray(qlens, jnp.int32), n_base, ti.ent_sorted,
        ti.ent_off, ti.ent_len, ti.key_bytes, ti.de_count, ti.ds_order,
        ti.de_off, ti.de_len, ti.db_bytes, ti.de_tomb,
        window=window, rank_iters=ti.rank_iters, block_b=block_b,
        interpret=_resolve(interpret),
    )


def fused_search(ti, qbytes, qlens, *, block_b: int = 256,
                 interpret: bool | None = None):
    """Whole-walk fused traversal over a :class:`~repro.core.tensor_index.TensorIndex`.

    Returns ``(found, eid, levels)`` — bit-identical to the jnp reference
    (DESIGN.md §7), excluding the delta buffer (that probe stays host-side
    jnp in ``search_batch``).  ``ti`` is duck-typed to avoid a core import.
    """
    return fused_search_pallas(
        qbytes, jnp.asarray(qlens, jnp.int32), ti.root_item, ti.items,
        ti.mn_slot_base, ti.mn_slot_cnt, ti.mn_prefix_off, ti.mn_prefix_len,
        ti.mn_alpha, ti.mn_beta, ti.tr_byte, ti.tr_mask, ti.tr_left,
        ti.tr_right, ti.cn_base, ti.cn_cnt, ti.ch_hash, ti.ch_ent,
        ti.key_bytes, ti.ent_off, ti.ent_len, ti.cdf_tab, ti.prob_tab,
        width=ti.width, max_iters=ti.max_iters, cnode_cap=ti.cnode_cap,
        cdf_steps=ti.cdf_steps, block_b=block_b,
        interpret=_resolve(interpret),
    )
