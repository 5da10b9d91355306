"""Distributed LITS query service: CDF routing + all_to_all (8 fake devices).

Runs in a subprocess because XLA device count must be fixed before jax init
(smoke tests in this process must see exactly ONE device).
"""
import json
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax
import jax.numpy as jnp
import numpy as np
from repro.core.strings import random_strings
from repro.core.tensor_index import pad_queries
from repro.distributed.index_service import build_sharded, make_service_fn

rng = np.random.default_rng(5)
keys = sorted(set(random_strings(rng, 4000, 3, 24)))
vals = np.arange(len(keys), dtype=np.int64) * 11 + 5
sidx = build_sharded(keys, vals, n_shards=8)
from repro.launch.mesh import make_mesh
mesh = make_mesh((8,), ("data",))

# spread the stacked index over the mesh (leading shard axis -> 'data')
from jax.sharding import NamedSharding, PartitionSpec as P
import dataclasses as dc
from repro.core.tensor_index import STATIC_FIELDS
stk = sidx.stacked
put = {}
for f in dc.fields(type(stk)):
    v = getattr(stk, f.name)
    if f.name in STATIC_FIELDS:
        put[f.name] = v
    else:
        put[f.name] = jax.device_put(v, NamedSharding(mesh, P("data")))
stk = type(stk)(**put)
sidx = dc.replace(sidx, stacked=stk)

fn = make_service_fn(sidx, mesh, per_dest_capacity=256)
Q = 8 * 512
qidx = rng.integers(0, len(keys), Q)
queries = [keys[i] for i in qidx]
# sprinkle misses
for j in range(0, Q, 17):
    queries[j] = queries[j] + b"~miss"
qb, ql = pad_queries(queries, sidx.width)
qb = jax.device_put(jnp.asarray(qb), NamedSharding(mesh, P("data")))
ql = jax.device_put(jnp.asarray(ql), NamedSharding(mesh, P("data")))
found, lo, hi, overflow = fn(stk, qb, ql)
found = np.asarray(found); lo = np.asarray(lo).view(np.uint32).astype(np.int64)
hi = np.asarray(hi).astype(np.int64)
got_vals = (hi << 32) | lo
kv = dict(zip(keys, vals.tolist()))
errors = 0
for j, q in enumerate(queries):
    if q in kv:
        if not found[j] or got_vals[j] != kv[q]:
            errors += 1
    else:
        if found[j]:
            errors += 1
# --- the same service through the StringIndex facade (DESIGN.md §8) ---
from repro.distributed.index_service import DistributedStringIndex
from repro.index import GetRequest, PutRequest, Status

dsi = DistributedStringIndex(sidx, mesh, per_dest_capacity=256)
f2, v2 = dsi.get_batch(queries)
facade_errors = int((f2 != found).sum()) + int((v2[found] != got_vals[found]).sum())
res = dsi.execute([GetRequest(queries[1]), GetRequest(b"definitely-missing"),
                   PutRequest(b"x", 1)])
facade_statuses = [r.status.name for r in res.results]
# --- routed range scans: per-shard windows concatenate in shard order ---
from repro.index import ScanRequest
scan_starts = [keys[0], keys[len(keys) // 2], keys[-3], keys[-1] + b"~"]
scan_errors = 0
sres = dsi.execute([ScanRequest(s, 10) for s in scan_starts])
for s, r in zip(scan_starts, sres.results):
    expect = [(k, kv[k]) for k in keys if k >= s][:10]
    if r.status.name != "OK" or list(r.entries) != expect:
        scan_errors += 1
print(json.dumps({
    "errors": int(errors),
    "n": Q,
    "overflow": int(np.asarray(overflow).sum()),
    "hits": int(found.sum()),
    "facade_errors": facade_errors,
    "facade_statuses": facade_statuses,
    "facade_first_ok": res.results[0].value == kv.get(queries[1]),
    "scan_errors": scan_errors,
}))
"""


@pytest.mark.slow
def test_sharded_service_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["errors"] == 0, rec
    assert rec["overflow"] == 0
    assert 0 < rec["hits"] < rec["n"]
    # the facade path must agree with the raw service_fn bit-for-bit
    assert rec["facade_errors"] == 0, rec
    assert rec["facade_statuses"] == ["OK", "NOT_FOUND", "UNSUPPORTED"], rec
    assert rec["facade_first_ok"] is True, rec
    # routed scans: shard windows concatenated in shard order == the
    # global sorted window (incl. cross-shard straddles and off-the-end)
    assert rec["scan_errors"] == 0, rec
