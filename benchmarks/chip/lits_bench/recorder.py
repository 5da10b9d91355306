"""A thin wrapper around the index object handed to ``IndexService``.

It changes nothing the service or the index does.  It records each
``execute`` call (the flush's requests, in queue order, and its host wall
time) and marks each call, and the facade's per-group primitives, with a
``TraceAnnotation`` so that a device trace can say what the host was doing
in a gap.  The recorded flushes are what the reference replays: a flush's
puts apply before its gets and scans, so the reference needs the flush
boundaries to know what each answer should be.
"""
from __future__ import annotations

import time
from typing import List, Tuple

import jax

FACADE_SPAN = "bench.facade_execute"
# the facade's per-group primitives, each annotated on the instance
GROUP_SPANS = ("get_batch", "put_batch", "delete_batch", "scan_batch")


def _annotated(name: str, fn):
    def call(*a, **kw):
        with jax.profiler.TraceAnnotation(name):
            return fn(*a, **kw)
    return call


class RecordingIndex:
    """Delegates every attribute to ``inner``; records ``execute``."""

    def __init__(self, inner):
        self.inner = inner
        self.flushes: List[Tuple[list, float, float]] = []  # (ops, t0, t1)
        for name in GROUP_SPANS:
            fn = getattr(inner, name, None)
            if fn is not None:
                setattr(inner, name, _annotated(f"bench.facade.{name}", fn))

    # the service reads and replaces ``config`` (it parks the facade's
    # auto-merge while it owns the index): keep it the inner index's own
    @property
    def config(self):
        return self.inner.config

    @config.setter
    def config(self, value):
        self.inner.config = value

    def __getattr__(self, name):
        return getattr(self.inner, name)

    def execute(self, batch):
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(FACADE_SPAN):
            res = self.inner.execute(batch)
        self.flushes.append((list(batch), t0, time.perf_counter()))
        return res

    def take_flushes(self) -> List[Tuple[list, float, float]]:
        """The flushes recorded since the last call, oldest first."""
        out = self.flushes[:]
        del self.flushes[: len(out)]
        return out
