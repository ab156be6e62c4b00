"""Reduction of a profiler trace to device metrics.

:func:`load` reads the ``.xplane.pb`` that ``jax.profiler`` writes into
plain :class:`Event` records; :func:`reduce` turns them into the busy
time of each chip, the device time of each program, the time of the
collectives inside the decode program, the operations that took most
time, and the idle gaps, each labelled by the benchmark's host
annotation (``bench.*``) that covers it.

Programs are found by their jit names: ``_prefill_fn`` (one-shot
prefill) and ``_fused_paged`` (paged decode and greedy sampling).
Under tensor parallelism the program jits every step through one
wrapper, so its modules are all called ``jit_wrapped``; there the
wrapped program that ran most often is the decode step (every tick runs
at least one decode step and at most one prefill) and the others are
prefills.  A trace with no decode program is an error, never a zero.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
ANNOTATION_PREFIX = "bench."
PREFILL_NAMES = ("_prefill_fn",)
DECODE_NAMES = ("_fused_paged",)
WRAPPED = "jit_wrapped"
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all")


@dataclasses.dataclass
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float
    stats: dict = dataclasses.field(default_factory=dict)

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str) -> List[Event]:
    """Events of the device planes' op and module lines, and of the host
    plane's ``bench.*`` annotations."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(DEVICE_PREFIX):
            lines = (OPS_LINE, MODULES_LINE)
        elif plane.name == HOST_PLANE:
            lines = None
        else:
            continue
        for line in plane.lines:
            if lines is not None and line.name not in lines:
                continue
            for e in line.events:
                if lines is None and not e.name.startswith(
                        ANNOTATION_PREFIX):
                    continue
                stats = {}
                if lines is not None:
                    stats = {k: v for k, v in e.stats
                             if k in ("program_id", "hlo_module",
                                      "hlo_op", "long_name")}
                out.append(Event(plane.name, line.name, e.name,
                                 float(e.start_ns), float(e.duration_ns),
                                 stats))
    return out


def short_name(name: str) -> str:
    """An HLO op event's name is its whole instruction; keep the part
    before " = " (``%fusion.12``)."""
    return name.split(" = ", 1)[0][:100]


def _union(intervals: List[Tuple[float, float]]) -> List[List[float]]:
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _clip(s, e, t0, t1):
    return max(s, t0), min(e, t1)


def classify_modules(modules: List[Event]) -> Dict[Tuple, str]:
    """Map each module key (name, program id) to "decode", "prefill" or
    "other"."""
    calls = collections.Counter((m.name, m.stats.get("program_id"))
                                for m in modules)
    label = {}
    wrapped = [k for k in calls if WRAPPED in k[0]]
    top_wrapped = max(wrapped, key=lambda k: calls[k]) if wrapped else None
    for k in calls:
        if any(n in k[0] for n in DECODE_NAMES) or k == top_wrapped:
            label[k] = "decode"
        elif any(n in k[0] for n in PREFILL_NAMES) or k in wrapped:
            label[k] = "prefill"
        else:
            label[k] = "other"
    return label


def _innermost(annotations: List[Event], starts: List[float], t: float):
    """Name of the latest-starting annotation that covers time ``t``."""
    for a in reversed(annotations[:bisect.bisect_right(starts, t)]):
        if a.end_ns >= t:
            return a.name[len(ANNOTATION_PREFIX):]
    return "other"


def reduce(events: List[Event], t0_ns: Optional[float] = None,
           t1_ns: Optional[float] = None, top: int = 10) -> dict:
    """Device metrics of the traced window [t0_ns, t1_ns] (default: the
    span of the ``bench.window`` annotation, else of all events).

    Times are averaged over the chips found; ``programs`` gives calls
    and device seconds per program label."""
    chips = sorted({e.plane for e in events
                    if e.plane.startswith(DEVICE_PREFIX)})
    if not chips:
        raise ValueError("the trace holds no TPU device plane")
    host = sorted((e for e in events if e.plane == HOST_PLANE),
                  key=lambda e: e.start_ns)
    if t0_ns is None or t1_ns is None:
        win = [e for e in host if e.name == ANNOTATION_PREFIX + "window"]
        src = win or events
        t0_ns = min(e.start_ns for e in src)
        t1_ns = max(e.end_ns for e in src)
    window = t1_ns - t0_ns
    if window <= 0:
        raise ValueError("empty traced window")
    modules = [e for e in events if e.line == MODULES_LINE
               and e.end_ns > t0_ns and e.start_ns < t1_ns]
    labels = classify_modules(modules)
    if "decode" not in labels.values():
        raise ValueError("no decode program in the trace; module names: "
                         f"{sorted({m.name for m in modules})[:10]}")
    starts = [a.start_ns for a in host]
    n = len(chips)
    busy = 0.0
    programs: Dict[str, Dict[str, float]] = collections.defaultdict(
        lambda: {"calls": 0, "device_s": 0.0})
    op_time: Dict[str, float] = collections.Counter()
    idle: Dict[str, float] = collections.Counter()
    gaps: List[Tuple[float, str]] = []
    collective = 0.0
    for chip in chips:
        decode_iv = []
        for m in modules:
            if m.plane != chip:
                continue
            lab = labels[(m.name, m.stats.get("program_id"))]
            s, e = _clip(m.start_ns, m.end_ns, t0_ns, t1_ns)
            programs[lab]["calls"] += 1 / n
            programs[lab]["device_s"] += (e - s) / 1e9 / n
            if lab == "decode":
                decode_iv.append((m.start_ns, m.end_ns))
        decode_iv.sort()
        d_starts = [s for s, _ in decode_iv]
        ops = [e for e in events if e.plane == chip and e.line == OPS_LINE
               and e.end_ns > t0_ns and e.start_ns < t1_ns]
        merged = _union([_clip(o.start_ns, o.end_ns, t0_ns, t1_ns)
                         for o in ops])
        busy += sum(e - s for s, e in merged) / 1e9
        for o in ops:
            s, e = _clip(o.start_ns, o.end_ns, t0_ns, t1_ns)
            op_time[short_name(o.name)] += (e - s) / 1e9 / n
            if COLLECTIVE.search(short_name(o.name)) or COLLECTIVE.search(
                    str(o.stats.get("hlo_op", ""))):
                i = bisect.bisect_right(d_starts, o.start_ns) - 1
                if i >= 0 and decode_iv[i][1] >= o.start_ns:
                    collective += (e - s) / 1e9 / n
        edges = [t0_ns] + [x for iv in merged for x in iv] + [t1_ns]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                lab = _innermost(host, starts, (s + e) / 2)
                idle[lab] += (e - s) / 1e9 / n
                gaps.append(((e - s) / 1e9, lab))
    gaps.sort(reverse=True)
    return {
        "chips": n,
        "window_s": window / 1e9,
        "busy_s": busy / n,
        "programs": {k: dict(v) for k, v in programs.items()},
        "collective_s": collective,
        "top_ops": sorted(op_time.items(), key=lambda kv: -kv[1])[:top],
        "idle_by_label": sorted(idle.items(), key=lambda kv: -kv[1]),
        "longest_gaps": [[lab, s] for s, lab in gaps[:top]],
    }
