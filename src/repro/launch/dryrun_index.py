import os
os.environ["XLA_FLAGS"] = os.environ.get(
    "DRYRUN_XLA_FLAGS", "--xla_force_host_platform_device_count=512"
)
"""Dry-run for the distributed LITS query service on the production mesh.

Topology: the index is key-range-partitioned 16 ways over ``data`` and
replicated across ``model`` (and ``pod``): each model column is a full
serving replica; queries are row-sharded over every mesh axis.  One step =
route (all_to_all over data) -> local LITS search -> return (all_to_all).

This is the paper-representative roofline cell (§Perf H3).
"""
import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.strings import random_strings
from repro.distributed.index_service import build_sharded, make_service_fn
from repro.launch.dryrun import parse_collectives, roofline_terms
from repro.launch.mesh import make_production_mesh


def run(multi_pod: bool, n_keys: int, q_per_device: int, out_dir: str,
        per_dest_capacity: int = 512) -> dict:
    mesh_name = "2x16x16" if multi_pod else "16x16"
    n_dev = 512 if multi_pod else 256
    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    rng = np.random.default_rng(0)
    keys = sorted(set(random_strings(rng, n_keys, 4, 24)))
    vals = np.arange(len(keys), dtype=np.int64)
    sidx = build_sharded(keys, vals, n_shards=16)
    axes = tuple(a for a in ("pod", "data", "model") if a in mesh.axis_names)
    fn = make_service_fn(sidx, mesh, axis="data", shard_axes=axes,
                         per_dest_capacity=per_dest_capacity)
    Q = q_per_device * n_dev
    qspec = jax.ShapeDtypeStruct((Q, sidx.width), jnp.uint8)
    lspec = jax.ShapeDtypeStruct((Q,), jnp.int32)
    import dataclasses as dc

    stk_spec = {}
    for f in dc.fields(type(sidx.stacked)):
        v = getattr(sidx.stacked, f.name)
        if f.name in ("width", "max_iters", "cnode_cap", "rank_iters", "delta_probes", "cdf_steps"):
            stk_spec[f.name] = v
        else:
            stk_spec[f.name] = jax.ShapeDtypeStruct(v.shape, v.dtype)
    stk_spec = type(sidx.stacked)(**stk_spec)
    t_build = time.time() - t0
    lowered = fn.lower(stk_spec, qspec, lspec)
    compiled = lowered.compile()
    t_compile = time.time() - t0 - t_build
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    coll = parse_collectives(compiled.as_text())
    flops = float(cost.get("flops", 0))
    byts = float(cost.get("bytes accessed", 0))
    terms = roofline_terms(flops, byts, coll["total_bytes"])
    rec = {
        "arch": "lits-query-service", "shape": f"q{q_per_device}_n{n_keys}",
        "mesh": mesh_name, "kind": "index-serve", "n_devices": n_dev,
        "queries_per_step": Q, "build_s": round(t_build, 2),
        "compile_s": round(t_compile, 2),
        "memory": {"total_per_device": int(
            (getattr(mem, "argument_size_in_bytes", 0) or 0)
            + (getattr(mem, "output_size_in_bytes", 0) or 0)
            + (getattr(mem, "temp_size_in_bytes", 0) or 0))},
        "flops_per_device": flops, "hlo_bytes_per_device": byts,
        "collectives": coll, "roofline": terms,
        "dominant": max(terms, key=terms.get),
        "coll_bytes_per_query": coll["total_bytes"] / q_per_device,
    }
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"lits-query_{rec['shape']}_{mesh_name}.json"), "w") as f:
        json.dump(rec, f, indent=2)
    print(f"[ok] lits-query {rec['shape']} {mesh_name}: compile={rec['compile_s']}s "
          f"dominant={rec['dominant']} coll/query={rec['coll_bytes_per_query']:.0f}B "
          f"terms={{{', '.join(f'{k}={v:.3e}' for k, v in terms.items())}}}")
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--keys", type=int, default=200000)
    ap.add_argument("--q-per-device", type=int, default=4096)
    ap.add_argument("--capacity", type=int, default=512)
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()
    run(args.multi_pod, args.keys, args.q_per_device, args.out, args.capacity)


if __name__ == "__main__":
    main()
