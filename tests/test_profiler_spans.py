"""The frontend's spans on the JAX profiler's clock: a tiny ``DashFrontend``
with tracing on serves one read tick under the CPU profiler, inside an
annotation of the caller's own, and the profiler's host plane holds each
stage of the tick, nested where it ran."""
import gc
import glob
import os
import time

import jax
import numpy as np
from jax.profiler import ProfileData, TraceAnnotation

from repro.core import DashConfig
from repro.core.table import DashEH
from repro.obs import Observability, Tracer
from repro.serving.frontend import INSERT, READ, DashFrontend, Op
from tests.conftest import unique_keys

CFG = DashConfig(max_segments=32, dir_depth_max=7, num_buckets=16,
                 num_slots=8)
HOST_PLANE = "/host:CPU"
READ_TICK = ("tick", "read.form", "read_batch", "read.keys", "read.recover",
             "read.dispatch", "read.wait", "read.finish")


def _host_events(trace_dir):
    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    assert len(files) == 1, files
    out = {}
    for plane in ProfileData.from_file(files[0]).planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            for e in line.events:
                out.setdefault(e.name, []).append(
                    (e.start_ns, e.start_ns + e.duration_ns))
    return out


def _inside(inner, outer):
    return outer[0] <= inner[0] and inner[1] <= outer[1]


def _profile(trace_dir):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def test_read_tick_spans_land_nested_on_the_host_plane(tmp_path):
    obs = Observability(trace=True)
    fe = DashFrontend(DashEH(CFG), obs=obs)
    keys = unique_keys(np.random.default_rng(3), 200)
    for k in keys:
        fe.submit(Op(INSERT, int(k), 1))
    fe.drain()
    for k in keys[:64]:                     # build the read program first
        fe.submit(Op(READ, int(k)))
    fe.drain()
    for k in keys[64:128]:
        fe.submit(Op(READ, int(k)))
    _profile(str(tmp_path))
    try:
        with TraceAnnotation("fe.step"):
            assert fe.step()
            gc.collect()
    finally:
        jax.profiler.stop_trace()
        obs.close()
    ev = _host_events(str(tmp_path))
    for name in READ_TICK + ("gc",):
        assert name in ev, (name, sorted(ev))
    (step,) = ev["fe.step"]
    (tick,) = ev["tick"]
    (batch,) = ev["read_batch"]
    assert _inside(tick, step)
    assert _inside(batch, tick)
    for name in ("read.keys", "read.recover", "read.dispatch", "read.wait",
                 "read.finish"):
        (span,) = ev[name]
        assert _inside(span, batch), name
    assert ev["read.dispatch"][0][1] <= ev["read.wait"][0][0]
    assert ev["read.wait"][0][1] <= ev["read.finish"][0][0]
    assert any(_inside(g, step) and not _inside(g, tick) for g in ev["gc"])
    # the ring holds the same tick, with the batch's size on its span
    ring = {sp.name: sp for sp in obs.tracer.spans()}
    assert ring["read_batch"].args["n"] == 64
    assert ring["read.wait"].parent == ring["read_batch"].sid


def test_a_cross_tick_span_outlives_the_callers_annotation(tmp_path):
    """A span begun inside the caller's annotation and ended after that
    annotation closed (a write batch held across ticks) lands on the host
    plane from its begin to its end."""
    tr = Tracer(enabled=True)
    _profile(str(tmp_path))
    try:
        with TraceAnnotation("fe.step"):
            cross = tr.begin("write_batch")
            with tr.span("insert_round"):
                time.sleep(0.001)
        time.sleep(0.005)
        with TraceAnnotation("later"):
            tr.end(cross)
    finally:
        jax.profiler.stop_trace()
    ev = _host_events(str(tmp_path))
    (step,) = ev["fe.step"]
    (later,) = ev["later"]
    (batch,) = ev["write_batch"]
    (rnd,) = ev["insert_round"]
    assert step[0] <= batch[0] <= rnd[0] and rnd[1] <= step[1]
    assert later[0] <= batch[1] <= later[1]          # where end() ran
    ring_ns = (cross.t1 - cross.t0) * 1e9
    assert batch[1] - batch[0] >= 5e6
    assert abs((batch[1] - batch[0]) - ring_ns) < 1e6
