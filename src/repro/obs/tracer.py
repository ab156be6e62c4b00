"""Span-based tracer with Chrome/Perfetto ``trace_event`` export.

Records the per-request serving lifecycle (``queued -> prefill ->
decode -> finish``, with ``preempt``/``resume`` excursions), per-tick
scheduler phases, speculative verify launches, and trainer steps as
*spans* — named intervals on named tracks — plus point-in-time instant
events.  The export (:meth:`Tracer.to_perfetto`) is the Chrome
``trace_event`` JSON array format, so a run's timeline opens directly
in https://ui.perfetto.dev or ``chrome://tracing``.

Like ``serving/metrics.py``, the clock is injected: tests and the
benchmark harness drive a virtual clock and get deterministic
timestamps.  All bookkeeping is host-side Python (list appends); there
is no device sync anywhere near a jit boundary.

:class:`Phase` puts a host phase on the profiler's clock as well: it is
always a ``jax.profiler`` annotation named ``repro.<track>.<name>``, so
any profiler capture (xprof, TensorBoard, ``jax.profiler.start_trace``)
shows it beside the device's operations, and it is a span of a
:class:`Tracer` when one is given.  ``jax.profiler`` is imported when
the first phase opens, so importing this module needs no jax.

Track model: one Perfetto *thread* per track (``track()`` get-or-
creates a tid and emits the ``thread_name`` metadata event).  Spans on
the same track nest by containment — Perfetto stacks an ``X`` event
inside any enclosing one — which is exactly the scheduler's
``tick > micro_step`` shape and the request's sequential
``queued > prefill > decode`` phases.
"""
from __future__ import annotations

import json
import time
from contextlib import contextmanager
from typing import Any, Callable, Dict, List, Optional


class Span:
    """An open interval on a track; closed by :meth:`Tracer.end`."""
    __slots__ = ("track", "name", "cat", "t0", "args", "closed")

    def __init__(self, track: int, name: str, cat: str, t0: float,
                 args: Optional[Dict[str, Any]]):
        self.track = track
        self.name = name
        self.cat = cat
        self.t0 = t0
        # ``begin`` hands us its own fresh **kwargs dict, so aliasing
        # (not copying) keeps the per-span cost at object construction
        self.args = args if args else {}
        self.closed = False


class Phase:
    """A host phase: an annotation ``repro.<track>.<name>`` on the
    profiler's clock (a ``TraceMe``: about a microsecond when no
    profiler session is active), and a span on ``tracer``'s track when
    a tracer is given.  With ``step`` set it is a
    ``StepTraceAnnotation`` carrying that step number.  Use it as a
    context manager; :meth:`set` adds arguments before it closes (stats
    on the profiler's event, ``args`` of the Perfetto span)."""
    __slots__ = ("_ann", "_tracer", "_track", "_name", "_cat", "_args",
                 "_span")

    def __init__(self, tracer: Optional["Tracer"], track: str, name: str,
                 cat: str = "", step: Optional[int] = None, **args):
        from jax import profiler
        full = f"repro.{track}.{name}"
        self._ann = (profiler.TraceAnnotation(full, **args) if step is None
                     else profiler.StepTraceAnnotation(full, step_num=step,
                                                       **args))
        self._tracer = tracer
        self._track, self._name, self._cat = track, name, cat
        self._args = args
        self._span: Optional[Span] = None

    def __enter__(self) -> "Phase":
        self._ann.__enter__()
        if self._tracer is not None:
            self._span = self._tracer.begin(self._track, self._name,
                                            self._cat, **self._args)
        return self

    def set(self, **args):
        self._ann.set_metadata(**args)
        if self._span is not None:
            self._span.args.update(args)

    def __exit__(self, *exc):
        if self._span is not None:
            self._tracer.end(self._span)
        self._ann.__exit__(*exc)


class Tracer:
    def __init__(self, clock: Callable[[], float] = time.monotonic,
                 process: str = "repro", max_events: int = 500_000):
        self.clock = clock
        self.process = process
        self.max_events = max_events
        self.dropped = 0
        self._t0 = clock()
        self._events: List[Dict[str, Any]] = []
        self._tracks: Dict[str, int] = {}

    # ------------------------------------------------------------ tracks
    def track(self, name: str) -> int:
        """Get-or-create the track (Perfetto thread) named ``name``."""
        tid = self._tracks.get(name)
        if tid is None:
            tid = len(self._tracks) + 1
            self._tracks[name] = tid
        return tid

    # ------------------------------------------------------------ events
    def _us(self, t: float) -> int:
        return int(round((t - self._t0) * 1e6))

    def _emit(self, ev: Dict[str, Any]):
        if len(self._events) >= self.max_events:
            self.dropped += 1
            return
        self._events.append(ev)

    def begin(self, track: str, name: str, cat: str = "",
              **args) -> Span:
        """Open a span on ``track`` (close it with :meth:`end`).  Used
        for non-lexical intervals — a request's ``decode`` phase opens
        at its first token and closes ticks later at finish."""
        return Span(self.track(track), name, cat, self.clock(), args)

    def end(self, span: Span, **more_args):
        """Close ``span`` (idempotent: a double-end is ignored so
        lifecycle teardown paths — finish vs preempt — can both try)."""
        if span.closed:
            return
        span.closed = True
        t1 = self.clock()
        if more_args:
            span.args.update(more_args)
        ts0 = int(round((span.t0 - self._t0) * 1e6))
        dur = int(round((t1 - self._t0) * 1e6)) - ts0
        ev = {"ph": "X", "name": span.name, "pid": 1, "tid": span.track,
              "ts": ts0, "dur": dur if dur > 0 else 0}
        if span.cat:
            ev["cat"] = span.cat
        if span.args:
            ev["args"] = span.args
        self._emit(ev)

    @contextmanager
    def span(self, track: str, name: str, cat: str = "", **args):
        s = self.begin(track, name, cat, **args)
        try:
            yield s
        finally:
            self.end(s)

    def instant(self, track: str, name: str, cat: str = "", **args):
        ev = {"ph": "i", "name": name, "pid": 1, "tid": self.track(track),
              "ts": self._us(self.clock()), "s": "t"}
        if cat:
            ev["cat"] = cat
        if args:
            ev["args"] = args
        self._emit(ev)

    # ------------------------------------------------------------ export
    @property
    def n_events(self) -> int:
        return len(self._events)

    def events_for(self, track: str) -> List[Dict[str, Any]]:
        """All closed events on ``track`` in emission order (tests and
        lifecycle-reconstruction assertions)."""
        tid = self._tracks.get(track)
        return [e for e in self._events if e["tid"] == tid]

    def to_perfetto(self) -> Dict[str, Any]:
        """Chrome ``trace_event`` JSON object: metadata (process/thread
        names) + every recorded event.  ``json.dumps`` of the return
        value is a file Perfetto opens as-is."""
        meta: List[Dict[str, Any]] = [{
            "ph": "M", "name": "process_name", "pid": 1, "tid": 0,
            "args": {"name": self.process}}]
        for name, tid in sorted(self._tracks.items(), key=lambda kv: kv[1]):
            meta.append({"ph": "M", "name": "thread_name", "pid": 1,
                         "tid": tid, "args": {"name": name}})
            # sort_index keeps track order stable (scheduler first,
            # then requests in arrival order)
            meta.append({"ph": "M", "name": "thread_sort_index", "pid": 1,
                         "tid": tid, "args": {"sort_index": tid}})
        return {"traceEvents": meta + list(self._events),
                "displayTimeUnit": "ms",
                "otherData": {"dropped_events": self.dropped}}

    def to_json(self, indent: Optional[int] = None) -> str:
        return json.dumps(self.to_perfetto(), indent=indent)
