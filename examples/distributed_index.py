"""Distributed LITS through the StringIndex facade: one key-range shard per
device + all_to_all query routing (DESIGN.md §5, §8).

`DistributedStringIndex` is the mesh implementation of the same typed
batched-op surface as the local `StringIndex` — construction owns the
shard build, device placement, and the routed shard_map service.  The shard
count is the number of devices JAX sees: 4 on a 2x2 TPU v5e host, or as many
CPU devices as XLA is told to fake:

    PYTHONPATH=src python examples/distributed_index.py
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python examples/distributed_index.py
"""
import time

import jax
import numpy as np

from repro.core.strings import random_strings
from repro.distributed.index_service import DistributedStringIndex
from repro.index import GetRequest, Status


def main() -> None:
    rng = np.random.default_rng(0)
    keys = sorted(set(random_strings(rng, 50000, 4, 24)))
    vals = np.arange(len(keys), dtype=np.int64)
    n = len(jax.devices())
    print(f"{len(keys)} keys -> {n} key-range shards on {jax.devices()}")
    index = DistributedStringIndex.build(keys, vals, n_shards=n,
                                         per_dest_capacity=2048)

    Q = n * 2048
    qkeys = [keys[i] for i in rng.integers(0, len(keys), Q)]
    found, got = index.get_batch(qkeys)           # compile + warm
    t0 = time.perf_counter()
    for _ in range(5):
        found, got = index.get_batch(qkeys)
    dt = (time.perf_counter() - t0) / 5
    kv = dict(zip(keys, vals.tolist()))
    ok = all(got[j] == kv[k] for j, k in enumerate(qkeys[:2000]))
    print(f"routed+searched {Q} queries in {dt * 1e3:.1f} ms "
          f"({Q / dt / 1e6:.2f} Mops), found={int(found.sum())}/{Q}, "
          f"values_ok={ok}")

    # the typed surface works identically against the mesh implementation
    res = index.execute([GetRequest(qkeys[0]), GetRequest(b"definitely-missing")])
    print(f"typed execute on the mesh: {[r.status.name for r in res.results]}, "
          f"value={res.results[0].value}")
    assert res.results[0].status == Status.OK
    assert res.results[1].status == Status.NOT_FOUND


if __name__ == "__main__":
    main()
