"""The reduction from a trace to busy time, program time and idle gaps."""
import gzip
import os
import shutil

import pytest

from lits_bench import trace

DEV = "/device:TPU:0"
FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "tpu_v5e_flushes.xplane.pb.gz")


def _events():
    # window 0..100 ns; ops overlap (10-30, 20-40) and stick out of it
    return trace.TraceEvents(
        ops={DEV: [(10, 30), (20, 40), (60, 70), (95, 120), (-5, 2)]},
        modules={DEV: [("jit__search_batch_jit", 10, 40),
                       ("jit_lookup_values", 60, 70),
                       ("jit__search_batch_jit", 95, 120)]},
        spans=[(trace.WINDOW_SPAN, 0, 100),
               ("bench.facade_execute", 35, 75),
               ("bench.facade.get_batch", 38, 72),
               ("bench.generator.submit", 80, 90)])


def test_busy_union_program_time_and_idle_gaps():
    s = trace.summarize(_events())
    assert s.window_s == pytest.approx(100e-9)
    # union: [0,2] + [10,40] + [60,70] + [95,100] = 47 ns
    assert s.busy_s == pytest.approx(47e-9)
    assert s.idle_share == pytest.approx(0.53)
    assert s.program_s["jit__search_batch_jit"] == pytest.approx(35e-9)
    assert s.program_s["jit_lookup_values"] == pytest.approx(10e-9)
    # gaps: 2-10 (mid 6, no span), 40-60 (mid 50, get_batch, the
    # innermost), 70-95 (mid 82.5, generator)
    assert s.idle_by_span == pytest.approx({
        trace.NO_SPAN: 8e-9, "bench.facade.get_batch": 20e-9,
        "bench.generator.submit": 25e-9})
    assert [g[0] for g in s.longest_gaps] == [
        "bench.generator.submit", "bench.facade.get_batch", trace.NO_SPAN]
    assert trace.program_seconds(s, "_search_batch_jit") == \
        pytest.approx(35e-9)
    assert trace.program_seconds(s, "_scan_batch_jit") is None


def test_no_window_or_no_device_op_reads_nothing():
    ev = _events()
    assert trace.summarize(trace.TraceEvents(ev.ops, ev.modules, [])) is None
    assert trace.summarize(trace.TraceEvents({}, {}, ev.spans)) is None


def test_json_round_trip():
    ev = _events()
    assert trace.summarize(trace.TraceEvents.from_json(ev.to_json())) == \
        trace.summarize(ev)


def test_recorded_tpu_trace(tmp_path):
    """A trace recorded on a TPU v5e: three flushes of gets and one scan
    through IndexService, inside a window span."""
    path = tmp_path / "t.xplane.pb"
    with gzip.open(FIXTURE) as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    ev = trace.read_xplane(str(path))
    assert list(ev.ops) == [DEV]
    s = trace.summarize(ev)
    assert 0 < s.busy_s < s.window_s
    names = set(s.program_s)
    assert any("_search_batch_jit" in n for n in names)
    assert any("_scan_batch_jit" in n for n in names)
    assert "bench.facade_execute" in {n for n, _s, _t in ev.spans}
    assert sum(s.idle_by_span.values()) == pytest.approx(
        s.window_s - s.busy_s, rel=1e-6)
