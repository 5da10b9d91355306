"""LITS core — the paper's contribution as a composable JAX module.

The free functions re-exported here (``search_batch``/``insert_batch``/
``rank_batch``/``scan_batch``/``merge_delta``/...) are the **legacy
kernel-level surface**: stable, jitted primitives over the frozen
:class:`TensorIndex` pytree.  Application code should prefer
:class:`repro.index.StringIndex` (DESIGN.md §8), which owns config
resolution, mixed-batch planning, auto-compaction and versioned snapshots
on top of exactly these functions — the two surfaces are bit-identical by
construction.
"""
from .builder import LITSBuilder, LITSConfig, TAG_CNODE, TAG_EMPTY, TAG_ENTRY, TAG_MNODE, TAG_TRIE
from .gpkl import gpkl, local_gpkl, pkl
from .hpt import HPT, build_hpt, get_cdf_jnp, get_cdf_np64, positions_jnp, uniform_hpt
from .pmss import PMSS, AlwaysLIT, AlwaysTrie
from .strings import StringSet, sort_order
from .tensor_index import (
    TensorIndex,
    base_search,
    delete_batch,
    freeze,
    insert_batch,
    lookup_values,
    merge_delta,
    pad_queries,
    rank_batch,
    scan_batch,
    search_batch,
)

__all__ = [
    "LITSBuilder", "LITSConfig", "HPT", "build_hpt", "uniform_hpt",
    "get_cdf_jnp", "get_cdf_np64", "positions_jnp", "gpkl", "local_gpkl", "pkl",
    "PMSS", "AlwaysLIT", "AlwaysTrie", "StringSet", "sort_order",
    "TensorIndex", "freeze", "search_batch", "base_search", "insert_batch",
    "delete_batch", "lookup_values", "merge_delta", "pad_queries",
    "rank_batch", "scan_batch",
    "TAG_EMPTY", "TAG_ENTRY", "TAG_MNODE", "TAG_CNODE", "TAG_TRIE",
]
