"""From a profiler trace to the numbers the per-layer metrics read.

``read_xplane`` pulls two kinds of events out of a ``.xplane.pb``: the
device's programs (``XLA Modules`` lines of ``/device:*`` planes) and the
benchmark's own host spans (names starting with ``bench.``).  The device's
busy intervals are its programs' intervals: the ``XLA Ops`` lines hold
about a thousand events per search program, too many to read in a run's
time, and the gaps between the ops of one program came to 0.3% of busy
time in a recorded v5e trace (``tests/fixtures``).  ``summarize`` reduces
them over the measured window, which the span ``bench.window`` marks: busy
time as the union of those intervals, device time per program, and idle
time between them, each gap put down to the innermost benchmark span the
host was in at its middle.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
NO_SPAN = "host outside benchmark spans"

Interval = Tuple[float, float]          # (start_ns, end_ns)


@dataclasses.dataclass
class TraceEvents:
    ops: Dict[str, List[Interval]]                   # device -> busy intervals
    modules: Dict[str, List[Tuple[str, float, float]]]  # device -> programs
    spans: List[Tuple[str, float, float]]            # benchmark host spans

    def to_json(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_json(cls, d: dict) -> "TraceEvents":
        return cls({k: [tuple(x) for x in v] for k, v in d["ops"].items()},
                   {k: [tuple(x) for x in v] for k, v in d["modules"].items()},
                   [tuple(x) for x in d["spans"]])


def find_xplane(trace_dir: str) -> Optional[str]:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    return paths[-1] if paths else None


def program_name(module: str) -> str:
    """``jit__search_batch_jit(123)`` -> ``jit__search_batch_jit``."""
    return re.sub(r"\(\d+\)$", "", module)


def read_xplane(path: str) -> TraceEvents:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops: Dict[str, List[Interval]] = {}
    modules: Dict[str, List[Tuple[str, float, float]]] = {}
    spans: List[Tuple[str, float, float]] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == "XLA Modules":
                    modules[plane.name] = [
                        (program_name(e.name), e.start_ns, e.end_ns)
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name, e.start_ns, e.end_ns))
    for dev, mods in modules.items():
        ops[dev] = [(s, t) for _, s, t in mods]
    return TraceEvents(ops, modules, spans)


def _union(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
    """Merged intervals clipped to [lo, hi]."""
    out: List[List[float]] = []
    for s, t in sorted(intervals):
        s, t = max(s, lo), min(t, hi)
        if t <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], t)
        else:
            out.append([s, t])
    return [(s, t) for s, t in out]


def _labels(spans: List[Tuple[str, float, float]],
            points: List[float]) -> List[str]:
    """For each point, the innermost (shortest) benchmark span covering it
    (a sweep over spans sorted by start)."""
    spans = sorted((x for x in spans if x[0] != WINDOW_SPAN),
                   key=lambda x: x[1])
    order = sorted(range(len(points)), key=points.__getitem__)
    out = [NO_SPAN] * len(points)
    active: List[Tuple[str, float, float]] = []
    j = 0
    for i in order:
        at = points[i]
        while j < len(spans) and spans[j][1] <= at:
            active.append(spans[j])
            j += 1
        active = [x for x in active if x[2] >= at]
        if active:
            out[i] = min(active, key=lambda x: x[2] - x[1])[0]
    return out


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float                       # averaged over the devices
    program_s: Dict[str, float]         # device seconds per program
    idle_by_span: Dict[str, float]      # idle seconds per host span
    longest_gaps: List[Tuple[str, float]]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def summarize(ev: TraceEvents, top: int = 10) -> Optional[TraceSummary]:
    """None when the trace holds no window span or no device op."""
    win = [(s, t) for n, s, t in ev.spans if n == WINDOW_SPAN]
    if not win or not any(ev.ops.values()):
        return None
    lo, hi = win[0]
    busy_total = 0.0
    idle_by: Dict[str, float] = {}
    gaps: List[Tuple[str, float]] = []
    spans = [x for x in ev.spans if x[2] > lo and x[1] < hi]
    devices = [d for d, v in ev.ops.items() if v]
    for dev in devices:
        merged = _union(ev.ops[dev], lo, hi)
        busy_total += sum(t - s for s, t in merged)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        idle = [(s, t) for s, t in zip(edges[0::2], edges[1::2]) if t > s]
        names = _labels(spans, [(s + t) / 2 for s, t in idle])
        for name, (s, t) in zip(names, idle):
            idle_by[name] = idle_by.get(name, 0.0) + (t - s) / 1e9
            gaps.append((name, (t - s) / 1e9))
    program_s: Dict[str, float] = {}
    for dev in devices:
        for name, s, t in ev.modules.get(dev, []):
            d = min(t, hi) - max(s, lo)
            if d > 0:
                program_s[name] = program_s.get(name, 0.0) + d / 1e9
    gaps.sort(key=lambda g: -g[1])
    return TraceSummary(
        window_s=(hi - lo) / 1e9,
        busy_s=busy_total / len(devices) / 1e9,
        program_s=program_s,
        idle_by_span={k: v / len(devices) for k, v in idle_by.items()},
        longest_gaps=gaps[:top],
    )


def program_seconds(summary: TraceSummary, pattern: str) -> Optional[float]:
    """Device seconds of the programs whose name contains ``pattern``;
    None when no such program ran."""
    hits = [v for k, v in summary.program_s.items() if pattern in k]
    return sum(hits) if hits else None
