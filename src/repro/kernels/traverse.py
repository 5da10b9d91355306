"""Pallas kernel: the FUSED LITS traversal engine (paper Alg. 2, whole walk).

One ``pallas_call`` runs the *entire* point-lookup per query block without
leaving on-chip memory:

* tagged-handle dispatch (mnode / critbit-trie / entry / cnode / empty),
* HPT-CDF walk + per-node linear model + slot clamp (``locate``),
* critbit subtrie step,
* compact-leaf 16-bit h-pointer hash scan (the paper's AVX-512 analogue),
* one string-equality compare per hash-matching candidate against the key
  pool.

The level-synchronous jnp reference in :mod:`repro.core.tensor_index`
launches one XLA gather cascade per level and re-touches HBM for every
query's bytes at every level; here all pools are pinned as VMEM-resident
tables and the walk is a single ``while_loop`` whose **early-exit
convergence condition** stops the block as soon as every lane has reached a
terminal item (a per-query ``levels`` counter is returned for roofline
accounting).

Bit-exactness contract (DESIGN.md §7): the kernel body calls the *same*
walk implementation the jnp backend uses — :mod:`repro.core.walk`
(``walk_terminal``/``resolve_terminal`` over flat pools, themselves built on
:func:`repro.core.hpt.positions_impl` and :mod:`repro.kernels.strops`) — so
``(found, eid)`` is bit-identical to the reference by construction, not by
tolerance: there is no second copy of the traversal to drift.

Off-TPU the kernel executes with ``interpret=True`` (resolved once per
process in :mod:`repro.kernels.ops`); on TPU the tables' BlockSpecs map
every pool whole into VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.walk import resolve_terminal, walk_terminal

DEFAULT_BLOCK_B = 256


def _fused_kernel(
    qbytes_ref, qlens_ref, root_ref,
    items_ref, mn_base_ref, mn_cnt_ref, mn_poff_ref, mn_plen_ref,
    mn_alpha_ref, mn_beta_ref,
    tr_byte_ref, tr_mask_ref, tr_left_ref, tr_right_ref,
    cn_base_ref, cn_cnt_ref, ch_hash_ref, ch_ent_ref,
    key_bytes_ref, ent_off_ref, ent_len_ref,
    cdf_tab_ref, prob_tab_ref,
    found_ref, eid_ref, levels_ref,
    *, width: int, max_iters: int, cnode_cap: int, cdf_steps: int,
):
    qbytes = qbytes_ref[...]                 # (BB, W) uint8
    qlens = qlens_ref[...][:, 0]             # (BB,)
    root = root_ref[0, 0]
    items = items_ref[0, :]
    mn_base = mn_base_ref[0, :]
    mn_cnt = mn_cnt_ref[0, :]
    mn_poff = mn_poff_ref[0, :]
    mn_plen = mn_plen_ref[0, :]
    mn_alpha = mn_alpha_ref[0, :]
    mn_beta = mn_beta_ref[0, :]
    tr_byte = tr_byte_ref[0, :]
    tr_mask = tr_mask_ref[0, :]
    tr_left = tr_left_ref[0, :]
    tr_right = tr_right_ref[0, :]
    cn_base = cn_base_ref[0, :]
    cn_cnt = cn_cnt_ref[0, :]
    ch_hash = ch_hash_ref[0, :]
    ch_ent = ch_ent_ref[0, :]
    key_bytes = key_bytes_ref[0, :]
    ent_off = ent_off_ref[0, :]
    ent_len = ent_len_ref[0, :]
    cdf_tab = cdf_tab_ref[...]
    prob_tab = prob_tab_ref[...]

    # the SAME walk + resolve the jnp backend runs (core.walk) — fused here
    # into one on-chip program with the early-exit convergence loop; the
    # walk's iteration counts and the resolve's rounds are the jnp path's
    # counters, unused here
    item, levels, _iters, _model_iters = walk_terminal(
        qbytes, qlens, root,
        items, mn_base, mn_cnt, mn_poff, mn_plen, mn_alpha, mn_beta,
        tr_byte, tr_mask, tr_left, tr_right,
        key_bytes, cdf_tab, prob_tab,
        width=width, max_iters=max_iters, cdf_steps=cdf_steps,
    )
    found, out_eid, _rounds = resolve_terminal(
        qbytes, qlens, item,
        cn_base, cn_cnt, ch_hash, ch_ent, key_bytes, ent_off, ent_len,
        cnode_cap=cnode_cap,
    )
    found_ref[...] = found.astype(jnp.int32)[:, None]
    eid_ref[...] = out_eid[:, None]
    levels_ref[...] = levels[:, None]


@functools.partial(
    jax.jit,
    static_argnames=("width", "max_iters", "cnode_cap", "cdf_steps",
                     "block_b", "interpret"),
)
def fused_search_pallas(
    qbytes: jax.Array,       # (B, W) uint8, zero padded
    qlens: jax.Array,        # (B,) int32
    root_item: jax.Array,    # scalar int32
    items: jax.Array,
    mn_slot_base: jax.Array, mn_slot_cnt: jax.Array,
    mn_prefix_off: jax.Array, mn_prefix_len: jax.Array,
    mn_alpha: jax.Array, mn_beta: jax.Array,
    tr_byte: jax.Array, tr_mask: jax.Array,
    tr_left: jax.Array, tr_right: jax.Array,
    cn_base: jax.Array, cn_cnt: jax.Array,
    ch_hash: jax.Array, ch_ent: jax.Array,
    key_bytes: jax.Array, ent_off: jax.Array, ent_len: jax.Array,
    cdf_tab: jax.Array, prob_tab: jax.Array,
    *,
    width: int, max_iters: int, cnode_cap: int, cdf_steps: int,
    block_b: int = DEFAULT_BLOCK_B,
    interpret: bool = True,
):
    """Whole-walk fused search: returns (found bool, eid int32, levels int32).

    Pools are passed flat; every table rides whole into the kernel (one
    ``(1, N)`` VMEM-resident block), while queries stream in ``block_b``
    row blocks over the grid.
    """
    B, W = qbytes.shape
    assert W == width, (W, width)
    Bp = ((B + block_b - 1) // block_b) * block_b
    qb = jnp.zeros((Bp, W), qbytes.dtype).at[:B].set(qbytes)
    ql = jnp.zeros((Bp, 1), jnp.int32).at[:B, 0].set(qlens.astype(jnp.int32))
    root = jnp.broadcast_to(jnp.asarray(root_item, jnp.int32), (1, 1))
    tables = [
        items, mn_slot_base, mn_slot_cnt, mn_prefix_off, mn_prefix_len,
        mn_alpha, mn_beta, tr_byte, tr_mask, tr_left, tr_right,
        cn_base, cn_cnt, ch_hash, ch_ent, key_bytes, ent_off, ent_len,
    ]
    tables2d = [t.reshape(1, -1) for t in tables]
    R, C = cdf_tab.shape

    def _blockspec(shape):
        return pl.BlockSpec(shape, lambda i: (0, 0))

    qspec = pl.BlockSpec((block_b, W), lambda i: (i, 0))
    vspec = pl.BlockSpec((block_b, 1), lambda i: (i, 0))
    in_specs = (
        [qspec, vspec, _blockspec((1, 1))]
        + [_blockspec(t.shape) for t in tables2d]
        + [_blockspec((R, C)), _blockspec((R, C))]
    )
    out_specs = (vspec, vspec, vspec)
    out_shape = tuple(
        jax.ShapeDtypeStruct((Bp, 1), jnp.int32) for _ in range(3)
    )
    found, eid, levels = pl.pallas_call(
        functools.partial(
            _fused_kernel, width=width, max_iters=max_iters,
            cnode_cap=cnode_cap, cdf_steps=cdf_steps,
        ),
        grid=(Bp // block_b,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(qb, ql, root, *tables2d, cdf_tab, prob_tab)
    return found[:B, 0] != 0, eid[:B, 0], levels[:B, 0]
