"""Online resize: read latency during a fill-driven split storm.

The ISSUE-3 acceptance scenario: a stream of insert bursts (fresh keys,
sized to drive bulk splits) interleaved with read bursts (zipfian over the
loaded keys) is served twice —

  * ``baseline``  — ``StopTheWorldFrontend``: one FIFO, writes run the
    inline ``DashTable.insert`` retry loop (split storms complete inside
    the write batch), reads behind a storm wait it out.
  * ``frontend``  — ``DashFrontend``: reads pin the epoch-published
    snapshot and are served between the staged SMO dispatches; only
    version-changed queries pay a live retry.

Reported: p50/p99 read sojourn latency (enqueue -> completion), offered
throughput, split/SMO counters, and the copy-on-write publish volume
(published bytes per write batch + publish wall time, vs the whole-state
copy the pre-COW frontend paid per publish). Acceptance gates, asserted
before the JSON artifact is written, at equal offered load and with
identical split count + final logical state:

  * frontend p99 read sojourn <= 0.5x the stop-the-world baseline;
  * COW publish volume <= 0.25x the whole-state-copy volume.

Emits ``BENCH_online_resize.json``.
"""
from __future__ import annotations

import time

import numpy as np

from repro.core import DashConfig, DashEH, layout
from repro.serving.frontend import (INSERT, READ, DashFrontend, Op,
                                    StopTheWorldFrontend)
from repro.workloads import ycsb
from .common import (Row, enable_compilation_cache, export_trace,
                     histogram_rows, write_artifact)

ARTIFACT = "BENCH_online_resize.json"

CFG = DashConfig(max_segments=64, dir_depth_max=9)
N_LOAD = 16_384          # pre-loaded key space the reads draw from
N_FRESH = 16_384         # fresh keys driving the storm
BATCH = 256              # admission batch size (both systems)
READS_PER_ROUND = 3      # read bursts per insert burst


def _stream(loaded: np.ndarray, fresh: np.ndarray, rng: np.random.Generator):
    """Rounds of one insert burst + READS_PER_ROUND read bursts (zipfian
    over the loaded space) — the arrival pattern both systems serve."""
    ranks = ycsb.zipfian_ranks(
        rng, loaded.size, (fresh.size // BATCH) * READS_PER_ROUND * BATCH)
    r = 0
    for i in range(0, fresh.size, BATCH):
        chunk = [Op(INSERT, int(k), ycsb.expected_value(int(k)))
                 for k in fresh[i:i + BATCH]]
        for _ in range(READS_PER_ROUND):
            chunk += [Op(READ, int(loaded[j]))
                      for j in ranks[r:r + BATCH]]
            r += BATCH
        yield chunk


def _drive(fe, loaded, fresh, rng):
    """Serve the stream chunk-by-chunk (closed loop: each round's ops are
    admitted together, the system drains before the next arrives — reads of
    a round race exactly that round's storm). Returns wall seconds and the
    ops served, each stamped with its own latency."""
    t0 = time.perf_counter()
    served = []
    for chunk in _stream(loaded, fresh, rng):
        for op in chunk:
            assert fe.submit(op)
        served += chunk
        fe.drain()
    return time.perf_counter() - t0, served


def _lat_stats(lat_s):
    lat = np.asarray(lat_s) * 1e6
    return {"p50_us": float(np.percentile(lat, 50)),
            "p99_us": float(np.percentile(lat, 99)),
            "mean_us": float(lat.mean()), "n": int(lat.size)}


def run():
    enable_compilation_cache()
    rng = np.random.default_rng(0x0E51)
    space = ycsb.load_keys(rng, N_LOAD + N_FRESH)
    loaded, fresh = space[:N_LOAD], space[N_LOAD:]
    load_vals = np.asarray([ycsb.expected_value(int(k)) for k in loaded],
                           dtype=np.uint32)

    # --- warmup: compile every trace both paths use, at the measured table
    # scale (the retry-loop capacity traces depend on the directory size, so
    # a small warmup table would leave the first measured run paying jit)
    warm_keys = ycsb.load_keys(np.random.default_rng(1), 4096)
    for cls in (StopTheWorldFrontend, DashFrontend):
        t = DashEH(CFG)
        t.insert(loaded, load_vals)
        fe = cls(t, max_batch=BATCH, queue_depth=1 << 16)
        _drive(fe, loaded, warm_keys, np.random.default_rng(2))

    report = {"config": {"n_load": N_LOAD, "n_fresh": N_FRESH,
                         "batch": BATCH, "reads_per_round": READS_PER_ROUND,
                         "max_segments": CFG.max_segments}}
    rows = []
    tables = {}
    for tag, cls in (("baseline", StopTheWorldFrontend),
                     ("frontend", DashFrontend)):
        t = DashEH(CFG)
        t.insert(loaded, load_vals)
        fe = cls(t, max_batch=BATCH, queue_depth=1 << 16)
        wall, served = _drive(fe, loaded, fresh, np.random.default_rng(3))
        stats = _lat_stats([op.latency for op in served if op.kind == READ])
        stats["write_p99_us"] = _lat_stats(
            [op.latency for op in served if op.kind != READ])["p99_us"]
        stats["wall_s"] = wall
        stats["ops_per_s"] = len(served) / wall
        stats["splits"] = int(np.asarray(t.state.n_splits))
        if tag == "frontend":
            stats["snapshot_reads"] = fe.snapshot_reads
            stats["retried_reads"] = fe.retried_reads
            stats["smo_stages"] = fe.smo_stages
            stats["published_versions"] = fe.registry.published
            stats["reclaimed_versions"] = fe.registry.reclaimed
            # COW publish accounting (frontend.stats() is the one surface)
            fes = fe.stats()
            pub = max(fes["published"], 1)
            stats["publish_bytes"] = fes["publish_bytes"]
            stats["publish_bytes_per_batch"] = fes["publish_bytes"] / pub
            stats["planes_copied"] = fes["planes_copied"]
            stats["planes_aliased"] = fes["planes_aliased"]
            stats["hint_misses"] = fes["hint_misses"]
            # the counterfactual: what the pre-COW whole-state copy would
            # have moved for the same publish cadence at equal offered load
            whole = layout.state_nbytes(t.state)
            stats["whole_copy_bytes_per_batch"] = whole
            stats["publish_volume_ratio"] = (
                fes["publish_bytes"] / (pub * whole))
            # obs histogram rows (ISSUE-8): the registry's log-bucketed
            # sojourn histograms must agree with the exact-sample
            # percentiles above within 10% — the bucket geometry bounds
            # the error at ±2.2%, so a miss means the frontend stopped
            # feeding the histogram the latencies its ops carry
            h = fe.obs.registry.get("frontend.read_sojourn_s").snapshot()
            stats["read_sojourn_hist"] = {
                "n": h["n"], "p50_us": h["p50"] * 1e6,
                "p90_us": h["p90"] * 1e6, "p99_us": h["p99"] * 1e6,
                "max_us": h["max"] * 1e6}
            assert h["n"] == stats["n"], (h["n"], stats["n"])
            for q in ("p50", "p99"):
                exact = stats[f"{q}_us"]
                approx = h[q] * 1e6
                err = abs(approx - exact) / exact
                assert err <= 0.10, \
                    f"hist {q} {approx:.1f}us vs exact {exact:.1f}us " \
                    f"({err:.1%} > 10%)"
            report["histograms"] = histogram_rows(fe.obs, "frontend.")
            report["slo"] = fe.obs.slo.snapshot()
            tp = export_trace(fe.obs, "online_resize")
            if tp:
                stats["trace_path"] = tp
                stats["trace"] = fe.obs.tracer.stats()
        report[tag] = stats
        tables[tag] = t
        rows.append(Row(f"online_resize/{tag}_read", stats["p50_us"],
                        f"p99={stats['p99_us']:.0f}us "
                        f"{stats['ops_per_s']:.0f} ops/s"))

    # identical final logical state (same keys landed in both tables) and
    # identical structural work — asserted before any gate is quoted
    assert tables["baseline"].n_items == tables["frontend"].n_items
    assert report["baseline"]["splits"] == report["frontend"]["splits"], \
        (report["baseline"]["splits"], report["frontend"]["splits"])
    f_b, _ = tables["baseline"].search(space)
    f_f, _ = tables["frontend"].search(space)
    assert np.asarray(f_b).all() and np.asarray(f_f).all()

    ratio = report["frontend"]["p99_us"] / report["baseline"]["p99_us"]
    thr = report["frontend"]["ops_per_s"] / report["baseline"]["ops_per_s"]
    report["p99_ratio"] = ratio
    report["throughput_ratio"] = thr
    # acceptance gate 1: overlapping reads with the storm at equal offered
    # load must at least halve tail read latency
    assert ratio <= 0.5, f"p99 ratio {ratio:.3f} > 0.5"
    rows.append(Row("online_resize/p99_ratio", ratio,
                    f"frontend/baseline p99; throughput x{thr:.2f}"))
    # acceptance gate 2: COW publish volume is O(dirty segments) — <= 0.25x
    # the whole-state copy the pre-COW publish cadence would have moved
    vratio = report["frontend"]["publish_volume_ratio"]
    assert vratio <= 0.25, f"publish volume ratio {vratio:.3f} > 0.25"
    assert report["frontend"]["hint_misses"] == 0
    rows.append(Row("online_resize/publish_volume_ratio", vratio,
                    f"{report['frontend']['publish_bytes_per_batch']:.0f}B/"
                    f"batch vs {report['frontend']['whole_copy_bytes_per_batch']}B"
                    " whole-copy"))

    write_artifact(ARTIFACT, report)
    return rows


if __name__ == "__main__":
    for r in run():
        print(r.csv())
