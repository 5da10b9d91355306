"""The plain reference: a host dict from each loaded key to its value
(after ``chip_smoke.Oracle``).  It imports nothing of the program.
``Oracle.value_bits`` below 64 makes the control: the same reference
storing values in fewer bits."""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

# what the reference answers to a get: ("OK", value) or ("NOT_FOUND", None)
Answer = Tuple[str, Optional[int]]


class Oracle:
    def __init__(self, keys: List[bytes], vals: List[int],
                 value_bits: int = 64):
        self.value_bits = value_bits
        self.kv: Dict[bytes, int] = dict(zip(keys, map(self._store, vals)))

    def _store(self, v: int) -> int:
        if self.value_bits >= 64:
            return v
        half = 1 << (self.value_bits - 1)
        return ((v + half) % (2 * half)) - half     # keep the low bits

    def get(self, k: bytes) -> Answer:
        v = self.kv.get(k)
        return ("NOT_FOUND", None) if v is None else ("OK", v)


def get_key(req) -> bytes:
    """The key of a get request of the program's API, read by attribute
    names alone; any other request is not the reference's to answer."""
    if type(req).__name__ != "GetRequest":
        raise TypeError(f"the reference answers gets only, not {req!r}")
    return req.key
