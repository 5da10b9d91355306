"""A whole run on the CPU at a tiny size, with the chip check skipped:
the result line, the metrics the cell lists, and a cell added as a file."""
import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH, ROOT, make_root, run_tiny

RESULT_KEYS = ["correct", "attempted", "failed", "metrics", "device",
               "checks"]


def _bench(root=ROOT):
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _listed(bench, kind, cell, e2e=None):
    return {m["name"] for m in bench[kind]
            if cell in m.get("workloads", [cell])
            and (e2e is None or m["moves"] in e2e)}


@pytest.mark.parametrize("seed", [0, 2**32 + 9])
def test_result_line(tiny_root, cache_dir, seed):
    workload = "email-c"
    out, lines = run_tiny(tiny_root, cache_dir, workload, seed=seed)
    assert list(out) == RESULT_KEYS          # the checks come last
    assert out["correct"] is True
    assert out["failed"] == 0 and out["attempted"] > 0
    assert out["device"]["platform"] == "cpu"
    assert {"kind", "count", "memory_peak_bytes"} <= set(out["device"])
    bench = _bench(tiny_root)
    e2e = _listed(bench, "end_to_end", workload)
    assert set(out["metrics"]) == e2e
    for name, m in out["metrics"].items():
        assert m["value"] > 0 and isinstance(m["unit"], str)
    assert lines == [f"check {k}: {v['value']} (limit {v['limit']})"
                     for k, v in out["checks"].items()]
    json.dumps(out)


def test_traced_run_reports_the_per_layer_metrics(tiny_root, cache_dir):
    out, _ = run_tiny(tiny_root, cache_dir, "email-c", trace=True)
    assert out["correct"] is True
    bench = _bench()
    per_layer = _listed(bench, "per_layer", "email-c",
                        _listed(bench, "end_to_end", "email-c"))
    # no device plane in a CPU trace: the device metrics read nothing
    host = {m for m in per_layer if not m.startswith("device_")}
    assert set(out["metrics"]) == host


def test_a_traffic_mix_added_as_a_file(tmp_path, cache_dir):
    """A new cell needs a traffic file and a BENCHMARK.json entry, and no
    edit of the harness."""
    extra = {"follows": "YCSB workloads/workloadc at a lower rate",
             "mix": {"read": 1.0},
             "request_distribution": "scrambled_zipfian",
             "rate_ops_per_s": 200, "warmup_seconds": 0.3,
             "warm_shapes": {"get_max_group": 8}}
    root = make_root(str(tmp_path), extra_traffic={"ycsb-c-slow": extra})
    bench = _bench(root)
    bench["workloads"].append({"name": "email-c-slow",
                               "config": bench["configs"][0]["name"],
                               "traffic": "ycsb-c-slow", "chips": 1,
                               "why": "test cell"})
    out, _ = run_tiny(root, cache_dir, "email-c-slow", bench=bench)
    assert out["correct"] is True and out["attempted"] == 200
    assert {"ops_per_s", "p50_ms"} <= set(out["metrics"])


class _GcWatch:
    """Delegates to the index; notes whether the collector was on at each
    flush."""

    def __init__(self, inner):
        object.__setattr__(self, "inner", inner)
        object.__setattr__(self, "gc_on", [])

    def __getattr__(self, name):
        return getattr(self.inner, name)

    def __setattr__(self, name, value):
        setattr(self.inner, name, value)

    def execute(self, batch):
        import gc

        self.gc_on.append(gc.isenabled())
        return self.inner.execute(batch)


def test_the_collector_is_off_in_the_window_only(tiny_root, cache_dir):
    import gc

    seen = []

    def wrap(idx, _c, _p):
        seen.append(_GcWatch(idx))
        return seen[-1]

    out, _ = run_tiny(tiny_root, cache_dir, "email-c", wrap_index=wrap)
    assert out["correct"] is True and gc.isenabled()
    on = seen[0].gc_on
    # the shapes are warmed with the collector on, the windows run with it off
    assert on[0] is True and on[-1] is False


def test_the_first_run_serves_the_index_a_restart_loads(tiny_root,
                                                         tmp_path):
    from lits_bench import harness, spec

    cell = spec.load_cell("email-c", root=tiny_root,
                          traffic_dir=os.path.join(tiny_root, "traffic"))
    cold = harness.load_index(cell, str(tmp_path))
    warm = harness.load_index(cell, str(tmp_path))
    assert "bulk_build_s" in cold.times and "bulk_build_s" not in warm.times
    assert "snapshot_load_s" in cold.times
    assert cold.index.nbytes() == warm.index.nbytes()


def test_exits_non_zero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"))
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "email-c", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not any(line.startswith("{") for line in p.stdout.splitlines())
