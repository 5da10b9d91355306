"""setup_s: process start to the window's start (host clock): imports,
corpus and snapshot load (or build), service start, warm-up."""


def read(run):
    return run.setup_s
