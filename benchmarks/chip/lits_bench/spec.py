"""The cell a run measures, found by name: ``BENCHMARK.json`` names the
cell's configuration and traffic mix and lists the metrics; each of those
lives in a file of its own under ``benchmarks/chip/``.  Adding a
configuration, a traffic mix or a metric adds a file and edits none."""
from __future__ import annotations

import dataclasses
import hashlib
import importlib.util
import json
import os
from typing import Callable, List

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(BENCH_DIR))


@dataclasses.dataclass
class Metric:
    name: str
    unit: str
    read: Callable      # RunData -> Optional[float]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict        # the configuration file
    config_hash: str    # of the file's bytes
    traffic_name: str
    traffic: dict       # the traffic file
    end_to_end: List[Metric]
    per_layer: List[Metric]


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_reader(name: str, metrics_dir: str) -> Callable:
    """``metrics/<name>.py``'s ``read``."""
    path = os.path.join(metrics_dir, f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"metric_{name}", path)
    if spec is None or not os.path.exists(path):
        raise FileNotFoundError(f"no reader for metric {name!r} at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _metrics(entries: List[dict], cell: str, metrics_dir: str,
             end_to_end_of_cell=None) -> List[Metric]:
    out = []
    for m in entries:
        if "workloads" in m and cell not in m["workloads"]:
            continue
        if end_to_end_of_cell is not None and \
                m["moves"] not in end_to_end_of_cell:
            continue
        out.append(Metric(m["name"], m["unit"],
                          load_reader(m["name"], metrics_dir)))
    return out


def load_cell(workload: str, root: str = ROOT,
              traffic_dir: str = os.path.join(BENCH_DIR, "traffic"),
              metrics_dir: str = os.path.join(BENCH_DIR, "metrics")) -> Cell:
    """The cell ``workload`` of ``<root>/BENCHMARK.json``."""
    bench = _json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; "
                       f"BENCHMARK.json has {sorted(cells)}")
    w = cells[workload]
    confs = {c["name"]: c for c in bench["configs"]}
    conf_path = os.path.join(root, confs[w["config"]]["file"])
    with open(conf_path, "rb") as f:
        raw = f.read()
    traffic = _json(os.path.join(traffic_dir, f"{w['traffic']}.json"))
    e2e = _metrics(bench["end_to_end"], workload, metrics_dir)
    per_layer = _metrics(bench["per_layer"], workload, metrics_dir,
                         {m.name for m in e2e})
    return Cell(workload, int(w["chips"]), w["config"], json.loads(raw),
                hashlib.sha256(raw).hexdigest()[:16], w["traffic"], traffic,
                e2e, per_layer)


def tree_hash(path: str) -> str:
    """Hash of the files under ``path`` (names and bytes; caches of
    compiled Python left out): the program a snapshot was built by."""
    h = hashlib.sha256()
    for dirpath, dirnames, files in os.walk(path):
        dirnames[:] = sorted(d for d in dirnames if d != "__pycache__")
        for fn in sorted(files):
            if fn.endswith((".pyc", ".pyo")):
                continue
            full = os.path.join(dirpath, fn)
            h.update(os.path.relpath(full, path).encode() + b"\0")
            with open(full, "rb") as f:
                h.update(f.read())
    return h.hexdigest()[:16]
