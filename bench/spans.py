"""The program's own spans and scopes in a traced run.

``bench/trace.py`` reads the device's op and module lines and the
benchmark's host annotations (``bench.*``).  The program writes into
the same profiler trace: each host phase of its scheduler is an
annotation ``repro.<track>.<name>`` (``repro.obs.tracer.Phase``), the
``micro_step`` and ``spec_verify`` spans carry ``live_tokens`` (the KV
positions the step needs) and ``kv_read_tokens`` (the positions its
attention reads), and the regions of its jits are named scopes
(``attn``, ``kv_write``, ``mlp``, ``head``) that reach each device
operation's ``op_name``; a model family may name more scopes of its
own (its module's ``SCOPES``).  This module reads them from the xplane
that ``bench/run.py`` writes under ``bench_traces/<cell>``, once per
run, for the readers in ``bench/metrics``.  A trace of a program that
writes none of them gives empty lists, and the readers then return None.

On a TPU the ``op_name`` is the ``tf_op`` stat of each operation's
event *metadata*, which ``jax.profiler.ProfileData`` does not expose;
:func:`op_names` reads it from the xplane's protobuf wire format.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import os
from typing import Dict, List, Optional

import harness

T = harness.load_module("trace")

TRACES = os.path.join(harness.ROOT, "bench_traces")
PROGRAM_PREFIX = "repro."
TICK = "repro.scheduler.tick"
STEPS = ("repro.scheduler.micro_step", "repro.scheduler.spec_verify")
DEVICE_WAIT = "repro.scheduler.device_wait"
SCOPES = ("attn", "kv_write", "mlp", "head")
OP_NAME_STAT = "tf_op"      # the op's HLO metadata op_name


@dataclasses.dataclass
class Spans:
    events: List[T.Event]      # what ``trace.load`` keeps, ops with scope
    program: List[T.Event]     # ``repro.*`` host spans, args in stats


def parse_name(name: str):
    """``TraceMe`` may keep its arguments in the event's name
    (``name#k=v,k2=v2#``): the name before ``#`` and those arguments."""
    if "#" not in name:
        return name, {}
    base, rest = name.split("#", 1)
    args = {}
    for kv in rest.rstrip("#").split(","):
        if "=" in kv:
            k, v = kv.split("=", 1)
            args[k] = v
    return base, args


def scope_of(op_name: str, scopes=SCOPES) -> Optional[str]:
    """The innermost of ``scopes`` in an op's ``op_name``
    (``jit(f)/while/body/closed_call/attn/dot_general``)."""
    for part in reversed(op_name.split("/")):
        if part in scopes:
            return part
    return None


def _varint(buf, i: int):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of one protobuf message: an int for a
    varint, a memoryview for a length-delimited field; fixed-width
    fields are skipped."""
    i, end = 0, len(buf)
    while i < end:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            v, i = _varint(buf, i)
        elif kind == 2:
            n, i = _varint(buf, i)
            v, i = buf[i:i + n], i + n
        elif kind in (1, 5):
            i += 8 if kind == 1 else 4
            continue
        else:
            raise ValueError(f"protobuf wire type {kind} in an xplane")
        yield key >> 3, v


def _map_values(entry):
    """The value (field 2) of a protobuf map entry."""
    return next((v for f, v in _fields(entry) if f == 2), b"")


def op_names(data: bytes) -> Dict[str, Dict[str, str]]:
    """For each device plane of a serialized ``XSpace``, the ``tf_op``
    stat of each event metadata, keyed by the metadata's name (the name
    its events carry).  Field numbers are those of
    ``tsl/profiler/protobuf/xplane.proto``: XSpace.planes 1; XPlane
    name 2, event_metadata 4, stat_metadata 5; XEventMetadata name 2,
    stats 5; XStatMetadata name 2; XStat metadata_id 1, str_value 5,
    ref_value 7 (a string kept once, as a stat metadata's name)."""
    out = {}
    for f, plane in _fields(memoryview(data)):
        if f != 1:
            continue
        name, events, stat_names = "", [], {}
        for pf, v in _fields(plane):
            if pf == 2:
                name = bytes(v).decode()
            elif pf == 4:
                events.append(_map_values(v))
            elif pf == 5:
                sm = dict(_fields(_map_values(v)))
                stat_names[sm.get(1, 0)] = bytes(sm.get(2, b"")).decode()
        if not name.startswith(T.DEVICE_PREFIX):
            continue
        plane_ops = out.setdefault(name, {})
        for em in events:
            op, ev_name = None, ""
            for ef, ev in _fields(em):
                if ef == 2:
                    ev_name = bytes(ev).decode()
                elif ef == 5:
                    st = dict(_fields(ev))
                    if stat_names.get(st.get(1)) == OP_NAME_STAT:
                        op = (bytes(st[5]).decode() if 5 in st
                              else stat_names.get(st.get(7), ""))
            if op:
                plane_ops[ev_name] = op
    return out


def load(path: str, scopes=SCOPES) -> Spans:
    """The events ``trace.load`` reads, each op with the innermost of
    ``scopes`` in ``stats["scope"]`` where it has one, and the program's
    host spans with their arguments in ``stats``."""
    from jax.profiler import ProfileData
    with open(path, "rb") as f:
        data = f.read()
    ops = op_names(data)
    events, program = [], []
    keep = ("program_id", "hlo_module", "hlo_op", "long_name")
    for plane in ProfileData.from_serialized_xspace(data).planes:
        device = plane.name.startswith(T.DEVICE_PREFIX)
        if not device and plane.name != T.HOST_PLANE:
            continue
        found: Dict[str, Optional[str]] = {}
        for line in plane.lines:
            if device and line.name not in (T.OPS_LINE, T.MODULES_LINE):
                continue
            for e in line.events:
                name = e.name
                if device:
                    stats = {k: v for k, v in e.stats if k in keep}
                    if line.name == T.OPS_LINE:
                        if name not in found:
                            found[name] = scope_of(
                                ops.get(plane.name, {}).get(name, ""),
                                scopes)
                        if found[name]:
                            stats["scope"] = found[name]
                elif name.startswith(T.ANNOTATION_PREFIX):
                    stats = {}
                elif name.startswith(PROGRAM_PREFIX):
                    name, stats = parse_name(name)
                    stats.update((k, v) for k, v in e.stats)
                    program.append(T.Event(plane.name, line.name, name,
                                           float(e.start_ns),
                                           float(e.duration_ns), stats))
                    continue
                else:
                    continue
                events.append(T.Event(plane.name, line.name, name,
                                      float(e.start_ns),
                                      float(e.duration_ns), stats))
    return Spans(events, program)


_CACHE: Dict[tuple, Spans] = {}


def of(run) -> Optional[Spans]:
    """The spans of ``run``'s trace (read once), or None for an untraced
    run or one whose trace is gone.  Ops are booked to :data:`SCOPES` and
    to the scopes ``run``'s model family names (its ``SCOPES``)."""
    if run.trace is None:
        return None
    try:
        path = T.find_xplane(os.path.join(TRACES, run.cell))
    except FileNotFoundError:
        return None
    scopes = SCOPES + tuple(getattr(run.family, "SCOPES", ()))
    key = (path, scopes)
    if key not in _CACHE:
        _CACHE.clear()
        _CACHE[key] = load(path, scopes)
    return _CACHE[key]


def window(sp: Spans):
    """The traced window: the ``bench.window`` annotation, else the span
    of every event."""
    win = [e for e in sp.events
           if e.name == T.ANNOTATION_PREFIX + "window"]
    src = win or sp.events + sp.program
    return min(e.start_ns for e in src), max(e.end_ns for e in src)


def _inside(sp: Spans, name: str, t0: float, t1: float) -> List[T.Event]:
    return sorted((e for e in sp.program if e.name == name
                   and e.start_ns >= t0 and e.end_ns <= t1),
                  key=lambda e: e.start_ns)


def tick_host_ms(sp: Spans) -> List[float]:
    """For each of the window's scheduler ticks, its duration less the
    time it waited for the device (its ``device_wait`` spans): the
    host's own time in the tick, in ms."""
    t0, t1 = window(sp)
    waits = _inside(sp, DEVICE_WAIT, t0, t1)
    starts = [w.start_ns for w in waits]
    out = []
    for t in _inside(sp, TICK, t0, t1):
        i = bisect.bisect_left(starts, t.start_ns)
        waited = 0.0
        while i < len(waits) and waits[i].start_ns < t.end_ns:
            waited += waits[i].dur_ns
            i += 1
        out.append((t.dur_ns - waited) / 1e6)
    return out


def kv_read_useful_share(sp: Spans) -> Optional[float]:
    """Live KV positions the window's steps needed over the positions
    their attention read, in %."""
    t0, t1 = window(sp)
    live = read = 0.0
    for name in STEPS:
        for s in _inside(sp, name, t0, t1):
            if "kv_read_tokens" in s.stats and "live_tokens" in s.stats:
                live += float(s.stats["live_tokens"])
                read += float(s.stats["kv_read_tokens"])
    return 100.0 * live / read if read else None


def _leaves(ops: List[T.Event]) -> List[T.Event]:
    """Ops that contain no other op on their line (a ``while`` that holds
    its body's ops is not a leaf)."""
    ops = sorted(ops, key=lambda o: (o.start_ns, -o.dur_ns))
    return [o for o, nxt in zip(ops, ops[1:] + [None])
            if nxt is None or nxt.start_ns >= o.end_ns
            or nxt.end_ns > o.end_ns]


def scope_ms_per_decode(sp: Spans) -> Dict[str, float]:
    """For each named scope found, the device time of its leaf ops
    inside the decode program's calls in the window, per decode call,
    in ms (over every chip's calls, so a mean over the chips)."""
    t0, t1 = window(sp)
    modules = [e for e in sp.events if e.line == T.MODULES_LINE
               and e.end_ns > t0 and e.start_ns < t1]
    labels = T.classify_modules(modules)
    calls = 0
    total: Dict[str, float] = collections.Counter()
    by_chip = collections.defaultdict(list)
    for o in sp.events:
        if o.line == T.OPS_LINE and o.end_ns > t0 and o.start_ns < t1:
            by_chip[o.plane].append(o)
    for chip, ops in by_chip.items():
        decode = sorted((m.start_ns, m.end_ns) for m in modules
                        if m.plane == chip and labels[
                            (m.name, m.stats.get("program_id"))] == "decode")
        calls += len(decode)
        starts = [s for s, _ in decode]
        for o in _leaves(ops):
            scope = o.stats.get("scope")
            if scope is None:
                continue
            i = bisect.bisect_right(starts, o.start_ns) - 1
            if i >= 0 and decode[i][1] >= o.end_ns:
                total[scope] += min(o.end_ns, t1) - max(o.start_ns, t0)
    return {k: v / calls / 1e6 for k, v in total.items()} if calls else {}


def idle_by_label(sp: Spans) -> list:
    """``trace.reduce``'s idle time by label, with the program's spans
    among the labels: a gap is labelled by the innermost ``bench.*`` or
    ``repro.*`` span that covers it (``repro.scheduler.emit``), and
    ``engine_step`` keeps what no program span covers."""
    host = [dataclasses.replace(e, name=T.ANNOTATION_PREFIX + e.name)
            for e in sp.program if e.plane == T.HOST_PLANE]
    return T.reduce(sp.events + host)["idle_by_label"]


def note_idle(run, sp: Spans) -> None:
    """Put the program-labelled idle time in ``run.notes`` (the run's
    log line ``bench: notes``), largest first, with the share under
    ``repro.*`` labels."""
    idle = idle_by_label(sp)
    total = sum(s for _, s in idle)
    prog = sum(s for lab, s in idle if lab.startswith(PROGRAM_PREFIX))
    run.notes["idle_gaps_program"] = [[lab, s] for lab, s in idle[:12]]
    run.notes["idle_program_share"] = 100.0 * prog / total if total else 0.0
