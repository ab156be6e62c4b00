"""Set-up: process start to the window's start (weights, engine, warm-up,
compilation where the cache misses)."""


def read(run):
    return run.setup_s
