"""Device trace: capture with the JAX profiler, reduce to metrics.

``capture`` wraps ``jax.profiler.start_trace``/``stop_trace``. ``load``
reads the newest ``*.xplane.pb`` with ``jax.profiler.ProfileData`` into a
flat event list; ``reduce`` turns that list into device busy time, the
device time of named operations and the device's idle gaps, each gap
attributed to the innermost host span (``TraceAnnotation``) open at its
midpoint. The reduction sees only ``Event`` tuples, so it is tested on a
small recorded event list without a chip.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import shutil
from typing import Dict, List, Optional, Tuple

#: device planes of the profiler trace, and the line holding the ops
DEVICE_PLANE_PREFIX = "/device:TPU:"
DEVICE_OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
#: the harness's own host spans (bench/loop.py, bench/harness.py)
HOST_SPANS = ("window", "fe.step", "generate", "check")


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def start(trace_dir: str):
    """Start the profiler with device and host tracing; Python function
    tracing stays off (it slows every Python call of the host path)."""
    import jax
    shutil.rmtree(trace_dir, ignore_errors=True)
    os.makedirs(trace_dir, exist_ok=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def short_name(name: str) -> str:
    """An XLA op event is named by its whole HLO instruction; keep the
    instruction's name (``%fused_probe.1 = ...`` -> ``fused_probe.1``)."""
    return name.split(" = ", 1)[0].lstrip("%")


def stop():
    import jax
    jax.profiler.stop_trace()


def load(trace_dir: str) -> List[Event]:
    """Device-op events of every TPU plane and the harness's host spans."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")),
                   key=os.path.getmtime)
    if not files:
        raise RuntimeError(f"no xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(files[-1])
    out = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            for line in plane.lines:
                if line.name != DEVICE_OPS_LINE:
                    continue
                for e in line.events:
                    out.append(Event(plane.name, line.name,
                                     short_name(e.name), float(e.start_ns),
                                     float(e.duration_ns)))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name in HOST_SPANS:
                        out.append(Event(plane.name, line.name, e.name,
                                         float(e.start_ns),
                                         float(e.duration_ns)))
    return out


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float                          # mean over the device planes
    devices: int
    op_seconds: Dict[str, float]           # device time by op name
    op_counts: Dict[str, int]
    idle_by_span: Dict[str, float]         # idle seconds by host activity
    longest_gaps: List[Tuple[str, float]]

    def kernel_seconds(self, patterns) -> Tuple[float, List[str]]:
        """Device time of the ops whose name contains any pattern, and the
        names that matched."""
        names = [n for n in self.op_seconds
                 if any(p in n for p in patterns)]
        return sum(self.op_seconds[n] for n in names), sorted(names)


def reduce(events: List[Event], window: Optional[Tuple[float, float]] = None
           ) -> Reduced:
    """Reduce events to busy time, op times and attributed idle gaps over
    the window (default: the harness's ``window`` span)."""
    if window is None:
        spans = [e for e in events if e.name == "window"
                 and not e.plane.startswith(DEVICE_PLANE_PREFIX)]
        if not spans:
            raise RuntimeError("the trace holds no 'window' span")
        w = max(spans, key=lambda e: e.dur_ns)
        window = (w.start_ns, w.end_ns)
    w0, w1 = window
    dev = [e for e in events if e.plane.startswith(DEVICE_PLANE_PREFIX)]
    planes = sorted({e.plane for e in dev})
    host = [e for e in events if not e.plane.startswith(DEVICE_PLANE_PREFIX)
            and e.name != "window"]
    op_s: Dict[str, float] = {}
    op_n: Dict[str, int] = {}
    busy_total = 0.0
    gaps_all: List[Tuple[float, float]] = []
    for p in planes:
        iv = []
        for e in dev:
            if e.plane != p:
                continue
            s, t = max(e.start_ns, w0), min(e.end_ns, w1)
            if t <= s:
                continue
            iv.append((s, t))
            op_s[e.name] = op_s.get(e.name, 0.0) + (t - s) * 1e-9
            op_n[e.name] = op_n.get(e.name, 0) + 1
        merged = _union(iv)
        busy_total += sum(t - s for s, t in merged) * 1e-9
        prev = w0
        for s, t in merged + [(w1, w1)]:
            if s > prev:
                gaps_all.append((prev, s))
            prev = max(prev, t)
    host.sort(key=lambda e: e.start_ns)
    starts = [e.start_ns for e in host]
    idle: Dict[str, float] = {}
    named_gaps = []
    for s, t in gaps_all:
        name = _span_at(host, starts, 0.5 * (s + t))
        idle[name] = idle.get(name, 0.0) + (t - s) * 1e-9
        named_gaps.append((name, (t - s) * 1e-9))
    named_gaps.sort(key=lambda g: -g[1])
    return Reduced(window_s=(w1 - w0) * 1e-9,
                   busy_s=busy_total / max(len(planes), 1),
                   devices=len(planes), op_seconds=op_s, op_counts=op_n,
                   idle_by_span=idle, longest_gaps=named_gaps[:10])


def _span_at(host: List[Event], starts: List[float], t: float) -> str:
    """The innermost (shortest) harness span open at time ``t``; ``host``
    is sorted by start, and the harness's spans nest at most a few deep."""
    best = None
    i = bisect.bisect_right(starts, t) - 1
    for e in host[max(i - 8, 0):i + 1]:
        if e.start_ns <= t <= e.end_ns and (best is None
                                            or e.dur_ns < best.dur_ns):
            best = e
    return best.name if best is not None else "outside harness spans"


def breakdown(r: Reduced) -> dict:
    """The result line's ``breakdown``: the ten device ops that took most
    time, and idle seconds by what the host was doing."""
    ops = sorted(r.op_seconds.items(), key=lambda kv: -kv[1])[:10]
    idle = sorted(r.idle_by_span.items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[n, s] for n, s in idle]}
