"""CPU tests of the on-chip benchmark's harness.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests

They run the harness at a tiny size with the chip check skipped; no number
they produce is a device measurement.
"""
import copy
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(BENCH))
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

# the committed cells, cut to a size a CPU test holds: fewer keys and a
# small flush bound, so that few shapes need warming
TINY_CONFIG = {"recordcount": 3000,
               "index": {"delta_capacity": 512, "search_backend": "jnp"},
               "service": {"max_batch": 8, "max_delay_ms": 2.0,
                           "merge_threshold": None}}
TINY_TRAFFIC = {
    "ycsb-c": {"rate_ops_per_s": 300, "warmup_seconds": 0.3,
               "warm_shapes": {"get_max_group": 8}},
}


def _json(path):
    with open(path) as f:
        return json.load(f)


def make_root(tmp, extra_traffic=None):
    """A root with ``BENCHMARK.json`` whose cells point at tiny copies of
    the committed configurations and traffic mixes."""
    bench = _json(os.path.join(ROOT, "BENCHMARK.json"))
    os.makedirs(os.path.join(tmp, "traffic"), exist_ok=True)
    for c in bench["configs"]:
        conf = _json(os.path.join(ROOT, c["file"]))
        conf.update(copy.deepcopy(TINY_CONFIG))
        c["file"] = f"{c['name']}.json"
        with open(os.path.join(tmp, c["file"]), "w") as f:
            json.dump(conf, f)
    for name, over in TINY_TRAFFIC.items():
        t = _json(os.path.join(BENCH, "traffic", f"{name}.json"))
        t.update(over)
        with open(os.path.join(tmp, "traffic", f"{name}.json"), "w") as f:
            json.dump(t, f)
    for name, t in (extra_traffic or {}).items():
        with open(os.path.join(tmp, "traffic", f"{name}.json"), "w") as f:
            json.dump(t, f)
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return tmp


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory):
    return make_root(str(tmp_path_factory.mktemp("root")))


@pytest.fixture(scope="session")
def cache_dir(tmp_path_factory):
    return str(tmp_path_factory.mktemp("cache"))


def run_tiny(root, cache, workload, seed=3, seconds=1.0, trace=False,
             wrap_index=None, bench=None):
    import time

    from lits_bench import harness, spec

    if bench is not None:
        with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
            json.dump(bench, f)
    cell = spec.load_cell(workload, root=root,
                          traffic_dir=os.path.join(root, "traffic"))
    return harness.run_cell(cell, seed, seconds, trace,
                            t_start=time.perf_counter(), require_chip=False,
                            wrap_index=wrap_index, cache_dir=cache,
                            trace_dir=os.path.join(cache, "trace"))
