"""The control: the reference put in the index's place under the service,
computed in the nearest lower precision than the configuration states.

The configuration stores int64 values; the control stores 32 of their 64
bits (int32, the step a later change might take to halve the value pools).
It must make ``correct`` false."""
from __future__ import annotations

from repro.index import BatchResult, OpResult, Status, StringIndexBase

from .oracle import Oracle, get_key


class ReferenceIndex(StringIndexBase):
    def __init__(self, index, corpus, prefix: bytes, value_bits: int = 32):
        self.config = index.config
        self.delta_fill, self.delta_overflowed, self.epoch = 0.0, False, 0
        keys = [prefix + k for k in corpus.key_list()]
        self.oracle = Oracle(keys, corpus.values.tolist(), value_bits)

    def execute(self, batch) -> BatchResult:
        out = []
        for r in batch:
            status, val = self.oracle.get(get_key(r))
            out.append(OpResult(Status[status], value=val))
        return BatchResult(out)
