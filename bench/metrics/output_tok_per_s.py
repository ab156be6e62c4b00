"""Tokens emitted inside the window, over the window."""


def read(run):
    n = sum(1 for r in run.records for t in r.token_times
            if run.t0 <= t <= run.t1)
    return n / (run.t1 - run.t0) if n else None
