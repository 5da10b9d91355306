"""hbm_bytes_per_key: bytes of the live device arrays after the window
(the index's pools, its delta buffer and whatever else the process keeps
on the chip) over the live keys.  Loaded programs and the allocator's
peak are left out; the result line's ``memory_peak_bytes`` has the peak."""


def read(run):
    if not run.array_bytes or not run.live_keys:
        return None
    return run.array_bytes / run.live_keys
