"""The ``url-c`` cell at a tiny size on the CPU: it loads by name, a run
of it is correct, and the control is not.  (``test_benchmark_json``
holds every configuration's ``reduced`` keys to ``BENCHMARK.json``'s.)"""
import functools
import json
import os

import pytest

from conftest import BENCH, ROOT, TINY_TRAFFIC, make_root, run_tiny
from lits_bench import spec
from lits_bench.control import ReferenceIndex

CELL = "url-c"


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _cell_entry():
    cell, = [w for w in _bench()["workloads"] if w["name"] == CELL]
    return cell


@pytest.fixture(scope="module")
def url_root(tmp_path_factory):
    """A tiny root whose ``url-c`` traffic is the committed file with the
    tiny overrides the committed cells get."""
    traffic = _cell_entry()["traffic"]
    with open(os.path.join(BENCH, "traffic", f"{traffic}.json")) as f:
        t = json.load(f)
    t.update(TINY_TRAFFIC["ycsb-c"])
    return make_root(str(tmp_path_factory.mktemp("url_root")),
                     extra_traffic={traffic: t})


def test_the_cell_loads_by_name():
    cell = spec.load_cell(CELL)
    assert cell.chips == 1 and cell.config_name == "ycsb-url-4m"
    assert cell.config["dataset"] == "url"
    assert cell.traffic["mix"] == {"read": 1.0}
    assert cell.traffic["warm_shapes"]["get_max_group"] == \
        cell.config["service"]["max_batch"]
    email = spec.load_cell("email-c")
    # the two cells differ in their keys and their rate alone
    for k in ("recordcount", "corpus_seed", "tenant", "index", "service",
              "guarantees"):
        assert cell.config[k] == email.config[k], k
    assert {m.name for m in cell.end_to_end} == \
        {m.name for m in email.end_to_end}


def test_a_tiny_run_is_correct(url_root, cache_dir):
    out, _ = run_tiny(url_root, cache_dir, CELL)
    assert out["correct"] is True
    assert out["failed"] == 0 and out["attempted"] > 0
    assert {"ops_per_s", "p50_ms", "hbm_bytes_per_key", "setup_s"} <= \
        set(out["metrics"])


def test_the_control_is_not_correct(url_root, cache_dir):
    wrap = functools.partial(ReferenceIndex, value_bits=32)
    out, _ = run_tiny(url_root, cache_dir, CELL, wrap_index=wrap)
    assert out["correct"] is False
    assert out["checks"]["wrong_answers"]["value"] > 0

