"""Unified observability layer: metrics registry, op-lifecycle tracing,
SLO monitoring.

``Observability`` is the per-frontend bundle the serving/persistence stack
threads through itself: one ``Registry`` (counters/gauges/histograms — the
substrate behind every ``stats()`` dict and ``BENCH_*.json`` histogram row),
one ``Tracer`` (enqueue→batch-form→dispatch→publish→flush→ack spans; off by
default, enabled explicitly or via ``REPRO_TRACE=1``; mirrored into the JAX
profiler's host plane while enabled), and one ``SloMonitor`` the frontend
ticks alongside the scrubber. An enabled bundle also records Python's
garbage collections as ``gc`` spans (``trace.GcSpans``).

``now()`` is the one clock helper every op timestamp goes through —
``enqueue_t``/``done_t`` stamping, span timing, and SLO window rotation all
share it, so sojourn histograms and bench percentiles are measuring the
same thing.
"""
from __future__ import annotations

import gc
import os
import time
import weakref

from .registry import Counter, Gauge, Histogram, Registry
from .slo import SloMonitor, SloRule
from .trace import GcSpans, Span, Tracer, export_chrome_trace
from .blackbox import FlightRecorder, TELEMETRY_SLO_RULES, \
    telemetry_slo_extra

__all__ = ["Counter", "Gauge", "GcSpans", "Histogram", "Registry",
           "SloMonitor", "SloRule", "Span", "Tracer", "export_chrome_trace",
           "FlightRecorder", "TELEMETRY_SLO_RULES", "telemetry_slo_extra",
           "Observability", "now", "trace_enabled_from_env"]

#: the single op-timestamp clock (satellite: sojourn-timing unification)
now = time.perf_counter


def trace_enabled_from_env() -> bool:
    return os.environ.get("REPRO_TRACE", "") not in ("", "0")


def _unhook(hook):
    if hook in gc.callbacks:
        gc.callbacks.remove(hook)


class Observability:
    """Registry + tracer + SLO monitor for one frontend (or shard).

    ``trace=None`` defers to ``REPRO_TRACE`` so benches and CI can turn
    span capture on without plumbing a flag through every constructor.
    The same switch is the ``trace`` property. While it is on, the bundle's
    one ``gc.callbacks`` hook records collections as ``gc`` spans; turning
    it off, ``close()``, or dropping the bundle removes the hook."""

    def __init__(self, trace=None, trace_capacity: int = 1 << 16,
                 slo_rules=(), slo_interval: int = 64):
        self.registry = Registry()
        if trace is None:
            trace = trace_enabled_from_env()
        self.tracer = Tracer(enabled=bool(trace), capacity=trace_capacity,
                             clock=now)
        # ring evictions surface as a counter (satellite: silent telemetry
        # loss is itself observable — pair with TELEMETRY_SLO_RULES)
        self.tracer.drop_counter = self.registry.scope("trace").counter(
            "dropped")
        self.slo = SloMonitor(self.registry, rules=slo_rules,
                              eval_interval=slo_interval, clock=now,
                              tracer=self.tracer)
        self.clock = now
        self._gc = None                 # the collector hook, made when on
        self.trace = self.tracer.enabled

    @property
    def trace(self) -> bool:
        return self.tracer.enabled

    @trace.setter
    def trace(self, on: bool):
        self.tracer.enabled = bool(on)
        if not on:
            if self._gc is not None:
                _unhook(self._gc)
            return
        if self._gc is None:
            self._gc = GcSpans(self.tracer)
            # the hook holds the tracer, not the bundle: a dropped bundle
            # is collected and its finalizer takes the hook out
            weakref.finalize(self, _unhook, self._gc)
        if self._gc not in gc.callbacks:
            gc.callbacks.append(self._gc)

    def close(self):
        """Remove the bundle's collector hook (tracing stops with it)."""
        self.trace = False

    def now(self) -> float:
        return self.clock()

    def snapshot(self) -> dict:
        """Registry snapshot + last SLO snapshot + tracer stats — the
        export surface for ``obs_snapshot()`` / bench artifacts."""
        return {"metrics": self.registry.snapshot(),
                "slo": self.slo.snapshot(),
                "trace": self.tracer.stats()}
