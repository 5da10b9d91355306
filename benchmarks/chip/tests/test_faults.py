"""The check must fail a broken timed path: a run with the path broken
underneath the service, and the control (the reference itself in the
index's place, at lower precision), must come out with ``correct`` false.
A cell on one chip has no exchange between chips to leave out, and a cell
of gets alone has no state to leave unchanged."""
import functools

import pytest

from conftest import run_tiny
from lits_bench.control import ReferenceIndex


class _Broken:
    """Delegates to the index, with ``execute`` broken by ``fault``."""

    def __init__(self, inner, fault):
        object.__setattr__(self, "inner", inner)
        object.__setattr__(self, "fault", fault)
        object.__setattr__(self, "calls", 0)

    def __getattr__(self, name):
        return getattr(self.inner, name)

    def __setattr__(self, name, value):
        setattr(self.inner, name, value)

    def execute(self, batch):
        from repro.index import BatchResult, OpResult

        object.__setattr__(self, "calls", self.calls + 1)
        if self.fault == "half_left_out":
            # the first half runs; the rest get answers copied from it
            h = max(len(batch) // 2, 1)
            res = self.inner.execute(batch[:h]).results
            return BatchResult([res[i % h] for i in range(len(batch))])
        res = self.inner.execute(batch)
        if self.fault == "answer_altered" and self.calls == 40:
            # one answer, changed where it is produced
            for i, r in enumerate(res.results):
                if r.value is not None:
                    res.results[i] = OpResult(r.status, value=r.value + 1)
                    break
        return res


@pytest.mark.parametrize("workload,fault", [
    ("email-c", "half_left_out"), ("email-c", "answer_altered")])
def test_a_broken_timed_path_is_not_correct(tiny_root, cache_dir, workload,
                                            fault):
    out, lines = run_tiny(tiny_root, cache_dir, workload,
                          wrap_index=lambda idx, _c, _p: _Broken(idx, fault))
    assert out["correct"] is False
    assert out["checks"]["wrong_answers"]["value"] > 0


def test_the_control_is_not_correct(tiny_root, cache_dir):
    wrap = functools.partial(ReferenceIndex, value_bits=32)
    out, _ = run_tiny(tiny_root, cache_dir, "email-c", wrap_index=wrap)
    assert out["correct"] is False
    assert out["checks"]["wrong_answers"]["value"] > 0


def test_the_reference_in_place_at_full_precision_is_correct(
        tiny_root, cache_dir):
    """The control's only fault is the one it was given."""
    out, _ = run_tiny(tiny_root, cache_dir, "email-c",
                      wrap_index=functools.partial(ReferenceIndex,
                                                   value_bits=64))
    assert out["correct"] is True
