"""Unified observability layer (ISSUE 8): metrics registry accuracy,
op-lifecycle span causality across publish/flush/SMO, bounded trace
memory, Chrome-trace export schema, SLO windows + rules, and the one-clock
sojourn unification in the serving frontend."""
import gc
import json
import math

import numpy as np
import pytest

from repro import obs as obs_mod
from repro import persist
from repro.core import DashConfig
from repro.core.table import DashEH
from repro.obs import (FlightRecorder, Histogram, Observability, Registry,
                       SloRule, Tracer, export_chrome_trace)
from repro.persist.chaos import CHAOS_CFG
from repro.serving import frontend as fe
from repro.serving.frontend import INSERT, READ, DashFrontend, Op
from tests.conftest import unique_keys

CFG = DashConfig(max_segments=32, dir_depth_max=7, num_buckets=16,
                 num_slots=8)

#: log-bucket geometry bound: half-bucket ratio at 16 buckets/octave
BUCKET_ERR = 2.0 ** (1.0 / (2 * 16)) - 1          # ~2.2%


# ---------------------------------------------------------------------------
# histogram accuracy
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dist", ["lognormal", "uniform", "bimodal"])
def test_histogram_percentiles_match_numpy(dist):
    rng = np.random.default_rng(hash(dist) % 2**31)
    if dist == "lognormal":
        vs = rng.lognormal(-9.0, 1.5, 20_000)            # us..ms sojourns
    elif dist == "uniform":
        vs = rng.uniform(1e-6, 1e-2, 20_000)
    else:
        # 12k/8k mix keeps p50 inside the fast mode (a 50/50 split would
        # put the median rank exactly at the mode boundary, where exact
        # interpolation and bucket extraction legitimately diverge)
        vs = np.concatenate([rng.normal(50e-6, 5e-6, 12_000),
                             rng.normal(5e-3, 5e-4, 8_000)])
        vs = np.abs(vs) + 1e-9
    h = Histogram("t")
    h.observe_many(vs)
    assert h.n == vs.size
    for q in (50, 90, 99):
        exact = float(np.percentile(vs, q))
        approx = h.percentile(q)
        # geometric buckets + midpoint extraction: half-bucket worst case,
        # plus sample-vs-bucket rank rounding — 2x the geometry bound is a
        # comfortable yet tight envelope
        assert abs(approx - exact) / exact <= 2 * BUCKET_ERR + 0.01, \
            (dist, q, approx, exact)
    assert h.percentile(100) == vs.max()
    snap = h.snapshot()
    assert snap["n"] == vs.size
    assert snap["mean"] == pytest.approx(vs.mean())
    assert snap["max"] == vs.max()


def test_histogram_scalar_and_vector_paths_agree():
    rng = np.random.default_rng(7)
    vs = rng.lognormal(-8, 2, 500)
    h1, h2 = Histogram("a"), Histogram("b")
    for v in vs:
        h1.observe(float(v))
    h2.observe_many(vs)
    assert (h1.counts == h2.counts).all()
    assert h1.n == h2.n and h1.vmin == h2.vmin and h1.vmax == h2.vmax


def test_histogram_merge_and_empty():
    h = Histogram("e")
    assert math.isnan(h.percentile(50))
    a, b = Histogram("a"), Histogram("b")
    a.observe_many([1e-5] * 10)
    b.observe_many([1e-3] * 10)
    a.merge(b)
    assert a.n == 20
    assert a.percentile(50) == pytest.approx(1e-5, rel=3 * BUCKET_ERR)
    assert a.percentile(99) == pytest.approx(1e-3, rel=3 * BUCKET_ERR)


# ---------------------------------------------------------------------------
# registry: scopes, ingest, shard aggregation
# ---------------------------------------------------------------------------

def test_registry_scope_ingest_aggregate():
    r = Registry()
    s = r.scope("frontend")
    s.counter("acks").inc(5)
    s.gauge("depth").set(3)
    r.ingest({"published": 7, "degraded": False, "name": "x"},
             prefix="stats.")
    snap = r.snapshot()
    assert snap["frontend.acks"] == 5
    assert snap["stats.published"] == 7
    assert snap["stats.degraded"] == 0
    assert "stats.name" not in snap                    # strings skipped
    # per-shard mirrors: counters=True lands values in Counters so the
    # fleet aggregate SUMS (gauges would take the last shard)
    shards = []
    for i in range(3):
        sr = Registry()
        sr.ingest({"flushed_bytes": 100 * (i + 1)}, prefix="wb.",
                  counters=True)
        shards.append(sr)
    agg = Registry.aggregate(shards)
    assert agg.snapshot()["wb.flushed_bytes"] == 600
    # type collisions are programming errors, caught loudly
    with pytest.raises(AssertionError):
        r.gauge("frontend.acks")


# ---------------------------------------------------------------------------
# tracer: ring bound, span stack, links
# ---------------------------------------------------------------------------

def test_tracer_ring_is_bounded():
    tr = Tracer(enabled=True, capacity=64)
    for i in range(1000):
        sp = tr.begin("op", "t", i=i)
        tr.end(sp)
    assert len(tr.spans()) == 64
    assert tr.recorded == 1000
    assert tr.dropped == 1000 - 64
    assert tr.spans()[-1].args["i"] == 999             # newest retained
    st = tr.stats()
    assert st["trace_buffered"] == 64 and st["trace_dropped"] == 936


def test_tracer_disabled_is_noop():
    tr = Tracer(enabled=False)
    sp = tr.begin("x")
    assert sp is None
    tr.end(sp)                                          # None-safe
    tr.instant("y")
    with tr.span("z"):
        assert tr.current() is None
    assert tr.spans() == [] and tr.recorded == 0


def test_tracer_nesting_and_links():
    tr = Tracer(enabled=True)
    with tr.span("outer", "t") as out:
        with tr.span("inner", "t") as inn:
            assert inn.parent == out.sid
        det = tr.begin("detached", "t")
        assert det.parent == out.sid                    # stack-top parent
        tr.end(det)
    ack = tr.begin("ack", "t", parent=None)
    Tracer.link(ack, out, None, det.sid)                # Nones skipped
    tr.end(ack)
    assert set(ack.links) == {out.sid, det.sid}


def test_disabled_span_is_one_shared_noop_context():
    tr = Tracer(enabled=False)
    a, b = tr.span("x"), tr.span("y", "t", n=3)
    assert a is b                                   # nothing built per call
    with a as sp:
        assert sp is None and tr.current() is None
    assert tr.spans() == [] and tr.recorded == 0


def test_enabled_spans_mirror_into_the_profiler(monkeypatch):
    from repro.obs import trace as trace_mod
    log = []

    class Annotation:                               # takes no kwargs
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            log.append(("enter", self.name))

        def __exit__(self, *exc):
            log.append(("exit", self.name))

    monkeypatch.setattr(trace_mod, "_TraceAnnotation", Annotation)
    tr = Tracer(enabled=True)
    with tr.span("outer", "t", n=7):
        with tr.span("inner"):
            pass
        tr.instant("mark")
        cross = tr.begin("cross")
    tr.end(cross)                           # closes out of nesting order
    assert log == [("enter", "outer"), ("enter", "inner"),
                   ("exit", "inner"), ("enter", "mark"), ("exit", "mark"),
                   ("enter", "cross"), ("exit", "outer"), ("exit", "cross")]
    by_name = {sp.name: sp for sp in tr.spans()}
    assert by_name["outer"].args == {"n": 7}        # args stay in the ring
    assert all(sp.ann is None for sp in tr.spans())
    log.clear()
    Tracer(enabled=False).span("off").__enter__()
    assert log == []


def _hooks(hook):
    return sum(cb is hook for cb in gc.callbacks)


def test_gc_spans_and_the_hook_leaves_on_close():
    obs = Observability(trace=True)
    hook = obs._gc
    assert _hooks(hook) == 1
    for _ in range(2):            # frontends sharing a bundle share its hook
        DashFrontend(DashEH(CFG), obs=obs)
    assert _hooks(hook) == 1
    with obs.tracer.span("outer") as out:
        gc.collect()
    full = [sp for sp in obs.tracer.spans() if sp.name == "gc"
            and sp.parent == out.sid and sp.args["generation"] == 2]
    assert full and full[-1].args["collected"] >= 0
    assert out.t0 <= full[-1].t0 <= full[-1].t1 <= out.t1
    assert full[-1].cat == "runtime"
    obs.close()
    assert _hooks(hook) == 0 and not obs.trace
    recorded = obs.tracer.recorded
    gc.collect()
    assert obs.tracer.recorded == recorded
    obs.trace = True                                # the one switch, again
    assert _hooks(hook) == 1
    obs.trace = False
    assert _hooks(hook) == 0
    dropped = Observability(trace=True)
    hook = dropped._gc
    del dropped                                     # never closed
    gc.collect()
    assert _hooks(hook) == 0
    off = Observability(trace=False)
    assert off._gc is None


def test_gc_hook_changes_no_tracer_state_and_overflow_is_counted():
    tr = Tracer(enabled=True)
    hook = obs_mod.GcSpans(tr, slots=2)
    with tr.span("outer") as out:
        for gen in range(3):                # what gc.callbacks would call
            hook("start", {"generation": gen})
            hook("stop", {"generation": gen, "collected": gen})
        assert tr.recorded == 0 and tr._next_sid == out.sid + 1
    gcs = [sp for sp in tr.spans() if sp.name == "gc"]
    assert [sp.args["generation"] for sp in gcs] == [1, 2] and hook.lost == 1
    assert all(sp.parent == out.sid for sp in gcs)
    assert len({sp.sid for sp in tr.spans()}) == len(tr.spans()) == 3


def test_traced_durable_frontend_survives_collections_everywhere(tmp_path):
    """Collections inside the tracer's and the flight recorder's own calls
    (their clocks run one, and the thresholds are low) through several
    flushes of a recorder kept over budget: no flush raises, span ids stay
    unique, and every write is acknowledged."""
    def collecting_clock():
        gc.collect(0)
        return obs_mod.now()

    t = persist.create(str(tmp_path / "t.pool"), CHAOS_CFG)
    t.writeback.attach_recorder(FlightRecorder(capacity_bytes=8192,
                                               clock=collecting_clock))
    obs = Observability(trace=True, trace_capacity=1 << 20)
    obs.tracer.clock = collecting_clock
    f = DashFrontend(t, obs=obs)
    keys = unique_keys(np.random.default_rng(12), 240)
    writes = [Op(INSERT, int(k), int(k & 0x7FFFFFFF)) for k in keys]
    thresholds = gc.get_threshold()
    gc.set_threshold(5, 50, 1000)
    try:
        for i in range(0, len(writes), 60):
            for op in writes[i:i + 60]:
                assert f.submit(op)
            f.drain()
    finally:
        gc.set_threshold(*thresholds)
        obs.close()
    assert all(op.status == fe.INSERTED for op in writes)
    assert f.recorder.evicted > 0 and f.recorder.windows_written >= 4
    spans = obs.tracer.spans()
    assert len({sp.sid for sp in spans}) == len(spans)
    assert sum(sp.name == "gc" for sp in spans) > 0
    assert sum(sp.name == "flush" for sp in spans) >= 4


# ---------------------------------------------------------------------------
# chrome trace export schema
# ---------------------------------------------------------------------------

def test_chrome_trace_schema(tmp_path):
    tr = Tracer(enabled=True)
    with tr.span("parent", "cat") as p:
        with tr.span("child", "cat"):
            pass
    tr.instant("mark", "cat", note=1)
    ack = tr.begin("ack", "cat")
    Tracer.link(ack, p)
    tr.end(ack)
    path = str(tmp_path / "trace.json")
    doc = export_chrome_trace(tr, path)
    on_disk = json.load(open(path))
    assert on_disk == doc
    evs = doc["traceEvents"]
    assert doc["metadata"]["recorded"] == 4
    for e in evs:
        assert e["ph"] in ("X", "i", "s", "f")
        assert isinstance(e["ts"], (int, float))
        assert "pid" in e and "tid" in e and "name" in e
        if e["ph"] == "X":
            assert e["dur"] >= 0
        if e["ph"] == "i":
            assert e["s"] == "t"
    # each link renders as a flow start/finish pair with matching id
    starts = [e for e in evs if e["ph"] == "s"]
    finishes = [e for e in evs if e["ph"] == "f"]
    assert len(starts) == len(finishes) == 1
    assert starts[0]["id"] == finishes[0]["id"]
    # args carry the span graph for programmatic verification
    by_sid = {e["args"]["sid"]: e for e in evs if e["ph"] in ("X", "i")}
    child = next(e for e in by_sid.values() if e["name"] == "child")
    assert by_sid[child["args"]["parent"]]["name"] == "parent"
    ack_ev = next(e for e in by_sid.values() if e["name"] == "ack")
    assert p.sid in ack_ev["args"]["links"]


# ---------------------------------------------------------------------------
# SLO monitor: windows, rates, rules, health dwell
# ---------------------------------------------------------------------------

def test_slo_windows_rates_and_rules():
    clk = [0.0]
    reg = Registry()
    mon = obs_mod.SloMonitor(
        reg, rules=[SloRule("p99_read", "read_sojourn.p99_s", max=1e-3),
                    SloRule("flush_rate", "rates.fb_per_s", min=1.0)],
        eval_interval=4, clock=lambda: clk[0])
    h = reg.histogram("frontend.read_sojourn_s")
    c = reg.counter("frontend.flush_bytes")
    mon.watch_histogram("read_sojourn", h)
    mon.watch_rate("fb_per_s", c)
    # window 1: fast reads, healthy flush rate -> no violations
    h.observe_many([50e-6] * 100)
    c.inc(1000)
    for _ in range(4):
        clk[0] += 0.25
        mon.tick()
    snap = mon.snapshot()
    assert snap["read_sojourn"]["n"] == 100
    assert snap["read_sojourn"]["p99_s"] < 1e-3
    assert snap["rates"]["fb_per_s"] == pytest.approx(1000.0, rel=0.01)
    assert snap["violations"] == []
    # window 2: slow tail + stalled flushes -> both rules fire
    h.observe_many([5e-3] * 100)
    for _ in range(4):
        clk[0] += 0.25
        mon.tick()
    snap = mon.snapshot()
    assert snap["read_sojourn"]["n"] == 100             # windowed, not cum.
    names = {v["rule"] for v in snap["violations"]}
    assert names == {"p99_read", "flush_rate"}
    assert snap["violation_count"] == 2
    # callable extra evaluated only on eval ticks
    calls = []
    for _ in range(4):
        clk[0] += 0.25
        mon.tick(lambda: calls.append(1) or {"queue_depth": 9})
    assert len(calls) == 1
    assert mon.snapshot()["queue_depth"] == 9


def test_slo_health_dwell():
    clk = [0.0]
    reg = Registry()
    mon = obs_mod.SloMonitor(reg, eval_interval=1, clock=lambda: clk[0])
    mon.note_health(0)
    clk[0] = 2.0
    mon.note_health(1)                                  # 2 s at state 0
    clk[0] = 3.0
    mon.tick({"health": 1})
    snap = mon.snapshot()
    assert snap["health"] == 1
    assert snap["health_dwell_s"][0] == pytest.approx(2.0)
    assert snap["health_dwell_s"][1] == pytest.approx(1.0)
    assert snap["health_dwell_s"][1] >= 0               # never negative


def test_slo_rule_missing_field_never_fires():
    r = SloRule("x", "a.b.c", max=1.0)
    assert r.check({}) is None
    assert r.check({"a": {"b": {"c": float("nan")}}}) is None
    hit = r.check({"a": {"b": {"c": 2.0}}})
    assert hit["rule"] == "x" and hit["value"] == 2.0


# ---------------------------------------------------------------------------
# frontend integration: one clock, histograms mirror exact samples
# ---------------------------------------------------------------------------

def test_frontend_sojourn_unified_through_obs_clock():
    t = DashEH(CFG)
    f = DashFrontend(t)
    keys = unique_keys(np.random.default_rng(5), 600)
    writes = [Op(INSERT, int(k), int(k & 0x7FFFFFFF)) for k in keys]
    reads = [Op(READ, int(k)) for k in keys[:200]]
    for op in writes + reads:
        f.submit(op)
    f.drain()
    # every completed op went through obs.now() twice; the registry
    # histograms saw exactly the latencies the ops themselves carry
    rh = f.obs.registry.get("frontend.read_sojourn_s")
    wh = f.obs.registry.get("frontend.write_sojourn_s")
    assert rh.n == len(reads) == 200
    assert wh.n == len(writes) == 600
    assert rh.total == pytest.approx(sum(op.latency for op in reads))
    assert wh.vmax == max(op.latency for op in writes)
    snap = f.obs_snapshot()
    assert snap["metrics"]["stats.published"] == f.stats()["published"]
    assert snap["slo"]["tick"] > 0
    assert "read_sojourn" in snap["slo"]


def test_frontend_read_tick_spans():
    obs = Observability(trace=True)
    f = DashFrontend(DashEH(CFG), obs=obs)
    keys = unique_keys(np.random.default_rng(6), 300)
    for k in keys:
        f.submit(Op(INSERT, int(k), 1))
    f.drain()
    obs.tracer.clear()
    for k in keys:
        f.submit(Op(READ, int(k)))
    steps = 0
    while f.busy:
        f.step()
        steps += 1
    obs.close()
    spans = [sp for sp in obs.tracer.spans() if sp.name != "gc"]
    by_sid = {sp.sid: sp for sp in spans}
    ticks = [sp for sp in spans if sp.name == "tick"]
    batches = [sp for sp in spans if sp.name == "read_batch"]
    assert len(ticks) == steps == 2 and len(batches) == 2
    assert sum(sp.args["n"] for sp in batches) == 300
    for sp in spans:
        if sp.name in ("read.form", "read_batch"):
            assert by_sid[sp.parent].name == "tick", sp.name
        elif sp.name.startswith("read."):
            assert by_sid[sp.parent].name == "read_batch", sp.name
            assert by_sid[sp.parent].t0 <= sp.t0 <= sp.t1 \
                <= by_sid[sp.parent].t1
    stages = {sp.name for sp in spans}
    assert {"read.form", "read.keys", "read.recover", "read.dispatch",
            "read.wait", "read.finish"} <= stages
    assert "read.verify" not in stages          # nothing written since


def test_slo_evaluation_is_a_span():
    obs = Observability(trace=True, slo_interval=3)
    for _ in range(7):
        obs.slo.tick()
    obs.close()
    assert [sp.name for sp in obs.tracer.spans()
            if sp.name != "gc"] == ["slo.evaluate"] * 2


def test_frontend_slo_extra_and_stats_fields():
    # slo_interval=1 forces an evaluation (with the frontend's extra) on
    # every tick — the extra fields must land in the snapshot
    f = DashFrontend(DashEH(CFG), obs=Observability(slo_interval=1))
    ks = unique_keys(np.random.default_rng(9), 400)
    for k in ks:
        f.submit(Op(INSERT, int(k), 1))
    f.drain()
    st = f.stats()
    assert st["readonly_events"] == 0
    snap = f.obs.slo.snapshot()
    assert snap["health"] == fe.HEALTHY
    assert "limbo_depth" in snap and "queue_depth" in snap


# ---------------------------------------------------------------------------
# span causality across publish + flush + SMO (durable split storm)
# ---------------------------------------------------------------------------

def _storm_frontend(tmp_path, n=900):
    p = str(tmp_path / "t.pool")
    t = persist.create(p, CHAOS_CFG)
    obs = Observability(trace=True)
    f = DashFrontend(t, obs=obs)
    keys = unique_keys(np.random.default_rng(11), n)
    for k in keys:
        f.submit(Op(INSERT, int(k), int(k & 0x7FFFFFFF)))
    for k in keys[:64]:
        f.submit(Op(READ, int(k)))
    f.drain()
    return f, keys


def test_span_causality_publish_flush_smo(tmp_path):
    f, _ = _storm_frontend(tmp_path)
    assert f.smo_stages > 0                      # the storm actually split
    spans = f.obs.tracer.spans()
    by_sid = {s.sid: s for s in spans}
    by_name = {}
    for s in spans:
        by_name.setdefault(s.name, []).append(s)
    # flush-on-publish rendered literally: every flush nests in a publish
    assert by_name["flush"], "durable storm produced no flush spans"
    for fl in by_name["flush"]:
        assert by_sid[fl.parent].name == "publish"
        if "bytes" in fl.args:
            assert fl.args["bytes"] >= 0
    # redo-log commit instants parent to their flush span
    for rl in by_name.get("redo_log_commit", []):
        assert by_sid[rl.parent].name == "flush"
    # staged SMO: every smo_stage belongs to one smo umbrella span carrying
    # the task descriptor, and the umbrella outlives all its stages
    assert by_name.get("smo"), "no smo umbrella spans"
    for um in by_name["smo"]:
        assert um.args["kind"] in ("eh_bulk_split", "lh_split_next")
    for st in by_name["smo_stage"]:
        um = by_sid[st.parent]
        assert um.name == "smo"
        assert um.t0 <= st.t0 and st.t1 <= um.t1
    # every ack links back to its batch span; write acks additionally link
    # the publish (and flush, when one ran) that made the batch durable
    acks = by_name["ack"]
    assert acks
    write_acks = 0
    for a in acks:
        linked = [by_sid[l] for l in a.links if l in by_sid]
        names = {s.name for s in linked}
        assert names & {"read_batch", "write_batch"}, a.args
        if a.args.get("kind") == INSERT:
            write_acks += 1
            assert "publish" in names, a.args
            assert "flush" in names, a.args
    assert write_acks > 0


def test_chrome_export_of_storm_is_valid(tmp_path):
    f, _ = _storm_frontend(tmp_path, n=600)
    path = str(tmp_path / "storm.json")
    doc = f.obs.tracer.export_chrome_trace(path)
    reparsed = json.load(open(path))
    assert reparsed["traceEvents"] == doc["traceEvents"]
    kinds = {e["ph"] for e in doc["traceEvents"]}
    assert "X" in kinds and "s" in kinds and "f" in kinds
    names = {e["name"] for e in doc["traceEvents"]}
    assert {"publish", "flush", "ack"} <= names


def test_tracing_disabled_by_default_and_free(tmp_path, monkeypatch):
    monkeypatch.delenv("REPRO_TRACE", raising=False)
    p = str(tmp_path / "t.pool")
    f = DashFrontend(persist.create(p, CHAOS_CFG))
    assert not f.obs.tracer.enabled
    for k in unique_keys(np.random.default_rng(2), 300):
        f.submit(Op(INSERT, int(k), 1))
    f.drain()
    assert f.obs.tracer.recorded == 0
    # metrics still flow with tracing off
    assert f.obs.registry.get("frontend.write_sojourn_s").n == 300
