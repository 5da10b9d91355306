"""ops_per_s: ops answered correctly by the close of the window, over the
window's length (host clock)."""


def read(run):
    return run.correct_by_close / run.seconds
