"""Lifecycle observability: metrics registry + trace spans + exporters.

One :class:`Observability` handle bundles the two surfaces every
lifecycle phase instruments against:

- :class:`~repro.obs.registry.MetricsRegistry` — counters, gauges,
  fixed-bucket histograms; snapshot to a dict, Prometheus text, or
  JSON.
- :class:`~repro.obs.tracer.Tracer` — per-request lifecycle spans,
  per-tick scheduler spans, trainer step spans; exports
  Chrome/Perfetto ``trace_event`` JSON.

Wiring: pass ``obs=Observability(clock=...)`` to
``serving.InferenceEngine``, ``core.Gateway``, or
``training.Trainer`` (all default to ``obs=None``: nothing is kept in
memory).  Components *push* cheap events (span begin/end, histogram
observations) on their host-side paths and *pull* expensive state
(pool occupancy, usage aggregates) via their ``collect_metrics``
hooks at snapshot time.  The scheduler's host phases are
:class:`~repro.obs.tracer.Phase` annotations on the profiler's clock
whether or not ``obs`` is attached.  Importing this package imports no
jax, and nothing here touches a device — instrumentation stays off the
jit hot path by construction.  See docs/observability.md for the metric
catalog and how to open a trace.
"""
from __future__ import annotations

import time
from typing import Callable, Optional

from repro.obs.registry import (DEFAULT_TIME_BUCKETS, MetricsRegistry,
                                UNIT_SUFFIXES, validate_metric_name)
from repro.obs.tracer import Phase, Span, Tracer

__all__ = ["Observability", "MetricsRegistry", "Tracer", "Span", "Phase",
           "validate_metric_name", "UNIT_SUFFIXES",
           "DEFAULT_TIME_BUCKETS"]


class Observability:
    """Registry + tracer pair sharing one (injectable) clock."""

    def __init__(self, clock: Callable[[], float] = time.monotonic,
                 registry: Optional[MetricsRegistry] = None,
                 tracer: Optional[Tracer] = None,
                 process: str = "repro"):
        self.clock = clock
        self.registry = registry if registry is not None else \
            MetricsRegistry()
        self.tracer = tracer if tracer is not None else \
            Tracer(clock=clock, process=process)

    # ------------------------------------------------------------ dumps
    def write_metrics(self, path: str, fmt: str = "prometheus") -> str:
        """Write the registry snapshot to ``path`` (``prometheus`` text
        or ``json``); returns the path."""
        text = (self.registry.to_json(indent=2) if fmt == "json"
                else self.registry.to_prometheus())
        with open(path, "w", encoding="utf-8") as f:
            f.write(text)
        return path

    def write_trace(self, path: str) -> str:
        """Write the Perfetto ``trace_event`` JSON to ``path``."""
        with open(path, "w", encoding="utf-8") as f:
            f.write(self.tracer.to_json())
        return path
