"""Shared arithmetic of the metric readers in ``bench/metrics``."""
from __future__ import annotations

from typing import List, Optional

import numpy as np


def pct(xs, q) -> Optional[float]:
    xs = [x for x in xs if x is not None]
    return float(np.percentile(xs, q)) if xs else None


def measured(run):
    return [r for r in run.records if r.measured]


def ttfts_s(run) -> List[float]:
    """Time from due to first token of every measured request; one that
    never produced a token counts with the time it waited."""
    out = []
    for r in measured(run):
        first = r.token_times[0] if r.token_times else run.t_end
        out.append(first - r.due)
    return out


def itls_s(run) -> List[float]:
    """Every gap between consecutive tokens: of each request due in an
    open-loop window, whenever it came; in a closed backlog, of gaps
    that lie inside the window."""
    out = []
    closed = run.mix["kind"] == "closed"
    for r in (run.records if closed else measured(run)):
        t = r.token_times
        for a, b in zip(t, t[1:]):
            if not closed or (a >= run.t0 and b <= run.t1):
                out.append(b - a)
    return out


def in_window(run, t0=None, t1=None):
    t0 = run.t0 if t0 is None else t0
    t1 = run.t1 if t1 is None else t1
    return [k for k in run.ticks if t0 <= k[0] <= t1]


def window_flops(run) -> float:
    """Model FLOPs of every token the window processed: prompts whose
    prefill started in it (one-shot chunk and streamed tail) and every
    token generated in it, counted by the configuration's family."""
    c, f = run.config, run.family
    total = 0.0
    for r in run.records:
        if r.prefill_start is not None and run.t0 <= r.prefill_start \
                <= run.t1:
            chunk = min(r.n_prompt, run.prefill_chunk)
            total += f.prefill_flops(c, chunk)
            total += sum(f.decode_flops(c, n)
                         for n in range(chunk + 1, r.n_prompt + 1))
        for i, t in enumerate(r.token_times):
            if i and run.t0 <= t <= run.t1:
                total += f.decode_flops(c, r.n_prompt + i)
    return total


def decode_ms_per_call(run) -> Optional[float]:
    """Device time per call of the decode program in the traced window."""
    if run.trace is None:
        return None
    d = run.trace["programs"]["decode"]
    return d["device_s"] / d["calls"] * 1e3 if d["calls"] else None
