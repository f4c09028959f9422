"""setup_s: process start to the window's start (host clock): imports,
device build from the seed, programming, warm-up and compilation or its
load from the cache."""


def read(run):
    return {"setup_s": run.setup_s}
