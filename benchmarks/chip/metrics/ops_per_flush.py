"""ops_per_flush: ops per coalesced flush over the window, from the
service's own counters (``ServiceStats.completed / flushes``)."""


def read(run):
    if not run.flushes:
        return None
    return run.completed / run.flushes
