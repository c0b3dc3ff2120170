"""Feed observability: trace spans, unified metrics, currency accounting.

``FeedObs`` is the per-feed bundle every pipeline component shares: a
``MetricsRegistry`` (always on — counters/gauges are lock-free attribute
updates, histograms a tiny per-instrument lock) and an optional
``Tracer`` (opt-in via ``.options(trace=...)``; ``obs.emit`` is a no-op
when tracing is off, so instrumentation sites never branch on policy).

``obs.span(...)`` is the one way a site measures work it can wrap: its
pair of clock reads gives the duration that the site adds to its
always-on counter and, when tracing is on, the ring span's ``dur``, so
the two never disagree.  A traced span also opens a
``jax.profiler.TraceAnnotation`` of the same name, so under a running
``jax.profiler`` trace it lands on the emitting thread's host line, on
the profiler's clock, beside the device's operations.

Lock discipline (feedlint R6, docs/CONCURRENCY.md): histogram
``observe`` and span ``emit`` must run with no core lock held
(``blocking-ok`` step locks exempt, with declared lock-order edges);
counter/gauge updates are allowed anywhere.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, Optional, Tuple

import jax

from repro.core.obs.health import (FeedHealthModel, HealthReport,
                                   HealthSpec, STATE_CODE)
from repro.core.obs.metrics import (Counter, Gauge, Histogram,
                                    HistogramSnapshot, MetricsRegistry,
                                    MetricValue, ROWS_BOUNDS,
                                    SECONDS_BOUNDS, mangle, percentile_of)
from repro.core.obs.profile import (HOP_ORDER, HopStats, JourneyProfiler,
                                    ProfileReport, ProfileSpec)
from repro.core.obs.server import ObsServer, http_get
from repro.core.obs.trace import Tracer, TraceSpec, write_jsonl


class Span:
    """One measured stretch of work (``with obs.span(...) as sp``).

    After the block, ``sp.dur`` is its wall seconds (``perf_counter``)
    and ``sp.cpu`` the emitting thread's CPU seconds over it
    (``thread_time``; read only when tracing or asked for with
    ``cpu=True``, else 0).  ``sp.id`` is the span's own id (0 untraced),
    which child spans name as their ``parent``.  ``ids`` and ``extra``
    may be filled in inside the block: the ring span is appended on
    exit.  Untraced, a span costs its clock reads: no annotation, no
    dict."""

    __slots__ = ("_tracer", "name", "ids", "parent", "extra", "_cpu",
                 "_ann", "_t", "_c", "_m", "id", "dur", "cpu")

    def __init__(self, tracer: Optional[Tracer], name: str,
                 ids: Tuple[int, ...], parent: int, cpu: bool,
                 extra: Dict[str, Any]):
        self._tracer = tracer
        self.name = name
        self.ids = ids
        self.parent = parent
        self.extra = extra
        self._cpu = cpu or tracer is not None
        self._ann = None
        self.id = 0
        self.dur = 0.0
        self.cpu = 0.0

    def __enter__(self) -> "Span":
        if self._tracer is not None:
            self.id = self._tracer.new_id()
            self._ann = jax.profiler.TraceAnnotation(self.name)
            self._ann.__enter__()
            self._m = time.monotonic()
        # the CPU reading nests inside the wall reading, so cpu <= dur
        self._t = time.perf_counter()
        if self._cpu:
            self._c = time.thread_time()
        return self

    def __exit__(self, *exc) -> bool:
        if self._cpu:
            self.cpu = time.thread_time() - self._c
        self.dur = time.perf_counter() - self._t
        tr = self._tracer
        if tr is not None:
            self._ann.__exit__(*exc)
            extra = self.extra
            extra["id"] = self.id
            extra["cpu"] = self.cpu
            if self.parent:
                extra["parent"] = self.parent
            tr.emit(self.name, self.ids, self._m, self.dur, **extra)
        return False


class FeedObs:
    """One feed's observability bundle: registry (always) + tracer
    (when a ``TraceSpec`` is enabled)."""

    def __init__(self, trace: Optional[TraceSpec] = None):
        self.registry = MetricsRegistry()
        self.trace_spec: Optional[TraceSpec] = trace
        self.tracer: Optional[Tracer] = \
            Tracer(trace.capacity) if trace is not None else None

    def enable_trace(self, spec: TraceSpec) -> None:
        self.trace_spec = spec
        self.tracer = Tracer(spec.capacity)

    @property
    def tracing(self) -> bool:
        return self.tracer is not None

    def new_span(self) -> int:
        """Fresh span id, or 0 when tracing is off (0 never collides —
        real ids start at 1)."""
        tr = self.tracer
        return tr.new_id() if tr is not None else 0

    def emit(self, name: str, spans: Tuple[int, ...] = (), t0: float = 0.0,
             dur: float = 0.0, **extra: Any) -> None:
        """Emit one span; no-op when tracing is off.  Subject to
        feedlint R6: never call while holding a core lock."""
        tr = self.tracer
        if tr is not None:
            tr.emit(name, spans, t0, dur, **extra)

    def span(self, name: str, ids: Tuple[int, ...] = (),
             parent: Optional[int] = None, cpu: bool = False,
             **extra: Any) -> Span:
        """Measure the ``with`` block: ``sp.dur`` (and ``sp.cpu`` with
        ``cpu=True``) feed the site's counter; with tracing on the block
        is also a profiler annotation and a ring span named ``name``
        with ``parent`` (the enclosing span's ``id``; children are left
        out of journeys).  Subject to feedlint R6 like ``emit``."""
        return Span(self.tracer, name, ids, parent or 0, cpu, extra)

    def drain_trace(self) -> List[Dict[str, Any]]:
        tr = self.tracer
        return tr.drain() if tr is not None else []


__all__ = ["FeedObs", "Span", "MetricsRegistry", "MetricValue", "Counter", "Gauge",
           "Histogram", "HistogramSnapshot", "Tracer", "TraceSpec",
           "SECONDS_BOUNDS", "ROWS_BOUNDS", "mangle", "percentile_of",
           "write_jsonl",
           "FeedHealthModel", "HealthReport", "HealthSpec", "STATE_CODE",
           "HOP_ORDER", "HopStats", "JourneyProfiler", "ProfileReport",
           "ProfileSpec", "ObsServer", "http_get"]
