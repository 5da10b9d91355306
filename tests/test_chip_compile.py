"""Compile-only guards for the TPU v5e: the main-path programs at the sizes
``chip_smoke.py`` runs, and the routed mesh service on a 2x2 mesh.

Nothing here runs on a chip: each test compiles for a *described* v5e
topology, which the installed TPU compiler accepts without one attached.
The topology is described inside a fixture, never at import, so test
collection does not load the TPU library.
"""
import dataclasses
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.core.tensor_index import (
    STATIC_FIELDS, TensorIndex, _scan_batch_jit, _search_batch_jit,
    delete_batch, insert_batch, lookup_values, rank_batch,
)
from repro.data.synthetic import load
from repro.index import IndexConfig, StringIndex

N_KEYS = 1_000_000        # chip_smoke.py's default --n
DELTA_CAPACITY = 16384    # chip_smoke.DELTA_CAPACITY
PUTS, DELETES, GETS, SCANS, WINDOW = 1024, 256, 2048, 16, 64   # one flush
DELTA_POOLS = {"db_bytes", "de_off", "de_len", "de_val_lo", "de_val_hi",
               "de_hash", "de_tomb", "dh_slot", "ds_order"}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def small_index():
    keys = load("email", 4000, seed=0)
    return StringIndex.bulk_load(keys, np.arange(len(keys)),
                                 IndexConfig(delta_capacity=DELTA_CAPACITY))


def _abstract(ti: TensorIndex, sharding, scale: float = 1.0) -> TensorIndex:
    """``ti`` as shapes on ``sharding``; base pools grown by ``scale``."""
    kw = {}
    for f in dataclasses.fields(TensorIndex):
        v = getattr(ti, f.name)
        if f.name in STATIC_FIELDS:
            kw[f.name] = v
            continue
        shape = v.shape
        if shape and f.name not in DELTA_POOLS | {"cdf_tab", "prob_tab"}:
            shape = (math.ceil(shape[0] * scale),) + shape[1:]
        kw[f.name] = jax.ShapeDtypeStruct(shape, v.dtype, sharding=sharding)
    return TensorIndex(**kw)


@pytest.fixture(scope="module")
def chip_index(small_index, one_chip):
    """An abstract index with the pool sizes of N_KEYS bulk-loaded keys."""
    ti = _abstract(small_index.ti, one_chip, N_KEYS / small_index.n_entries)
    return dataclasses.replace(
        ti, rank_iters=int(math.ceil(math.log2(N_KEYS))) + 2)


def _queries(b, width, sharding):
    return (jax.ShapeDtypeStruct((b, width), jnp.uint8, sharding=sharding),
            jax.ShapeDtypeStruct((b,), jnp.int32, sharding=sharding))


def _hlo(lowered):
    return lowered.compile().as_text()


def test_search_and_lookup_compile_for_v5e(chip_index, one_chip):
    """The get group's one program on its packed input
    (``pack_queries``), and ``lookup_values``, which scans still run."""
    packed = jax.ShapeDtypeStruct((GETS, chip_index.width + 4), jnp.uint8,
                                  sharding=one_chip)
    assert _hlo(_search_batch_jit.lower(chip_index, packed))
    eid = jax.ShapeDtypeStruct((GETS,), jnp.int32, sharding=one_chip)
    isd = jax.ShapeDtypeStruct((GETS,), jnp.bool_, sharding=one_chip)
    assert _hlo(lookup_values.lower(chip_index, eid, isd))


def test_scan_compiles_for_v5e(chip_index, one_chip):
    qb, ql = _queries(SCANS, chip_index.width, one_chip)
    assert _hlo(_scan_batch_jit.lower(chip_index, qb, ql, window=WINDOW))


@pytest.mark.parametrize("op", ["insert", "delete"])
def test_mutate_compiles_for_v5e(chip_index, one_chip, op):
    """At the smoke test's 16384-entry delta: the delta-view sort once
    took minutes to compile here (one 13-operand lexsort)."""
    if op == "insert":
        qb, ql = _queries(PUTS, chip_index.width, one_chip)
        v = jax.ShapeDtypeStruct((PUTS,), jnp.int32, sharding=one_chip)
        lowered = insert_batch.lower(chip_index, qb, ql, v, v)
    else:
        qb, ql = _queries(DELETES, chip_index.width, one_chip)
        lowered = delete_batch.lower(chip_index, qb, ql)
    assert "sort" in _hlo(lowered)


def test_routed_service_compiles_on_2x2_mesh(topo):
    from repro.distributed.index_service import build_sharded, make_service_fn
    from repro.launch.mesh import make_mesh

    keys = load("email", 2000, seed=1)
    sidx = build_sharded(keys, np.arange(len(keys)), n_shards=4)
    mesh = make_mesh((4,), ("data",), devices=topo.devices[:4])
    stacked = _abstract(sidx.stacked, NamedSharding(mesh, P("data")))
    fn = make_service_fn(dataclasses.replace(sidx, stacked=stacked), mesh,
                         per_dest_capacity=GETS // 4)
    qb, ql = _queries(GETS, sidx.width, NamedSharding(mesh, P("data")))
    assert "all-to-all" in _hlo(fn.lower(stacked, qb, ql))


def test_rank_compiles_for_v5e(chip_index, one_chip):
    """The ordered rank alone (``rank_batch``): a binary search of
    ``rank_iters`` steps over the 1M-key sorted entry order."""
    qb, ql = _queries(SCANS, chip_index.width, one_chip)
    assert "while" in _hlo(rank_batch.lower(chip_index, qb, ql))
