"""Required work, and the least time the chip needs for it.

What a step of a model requires depends on its architecture, so each
family's module (``bench/families/<family>.py``) counts it from the
configuration's shapes: ``decode_flops``, ``prefill_flops`` and
``decode_step_work``.  FLOPs count a multiply-add as two operations.
Bytes are what a step must read at least: every weight it multiplies
by, and the cached state of the live tokens.  Neither counts what an
implementation adds (padding, a gather of a slot's whole capacity), so
a roofline share read against these numbers means the same whatever
computes the step.
"""
from __future__ import annotations

BF16 = 2


def roofline_seconds(flops: float, nbytes: float, peaks: dict):
    """The least time for the work, and which bound sets it."""
    t_c = flops / peaks["bf16_flops"]
    t_m = nbytes / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
