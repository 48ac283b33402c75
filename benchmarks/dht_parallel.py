"""Device-resident DHT hot path vs the host-mirror baseline (ISSUE-9 gate).

All measurements run in subprocesses with 8 fake CPU devices (the real
shard_map path, like tests/test_distributed.py). Four sections:

  * **verify storm** — the identical insert+read op schedule is served by
    ``ShardFrontend(verify_mode="device")`` (one-dispatch snapshot probe +
    in-program version verify + device-resident insert rounds) and by
    ``verify_mode="host"`` (host-mirrored plane diff per read batch,
    O(batch) statuses pulled per insert round). Final stacked states are
    asserted BIT-IDENTICAL before any number is quoted. Gates: device read
    p99 <= 0.5x host, device ``host_plane_bytes`` == 0 (the PR 8 counter
    meters every plane byte the host-mirror verify copies).
  * **bulk splits** — ``split_for`` (plan + phase1 + phase2 inside one
    shard_map dispatch) vs the retained per-shard host loop
    (``_split_for_host``: host sub-state rebuild per shard) from identical
    states, identical resulting states asserted. Gate: >= 2x.
  * **lazy reopen** — 8-shard write, ``os._exit`` kill, then
    ``persist.reopen_shards()`` (lazy default) + first query, timed
    end-to-end against a clean-close reopen; eager recovery reported as
    contrast. Gate: dirty time-to-first-query <= 1.5x clean.
  * **per-shard histograms** — the device frontend's per-shard
    read-sojourn registries (``Registry.aggregate`` fleet view) are
    cross-checked against the exact sample percentiles within 10%, like
    ``online_resize`` does for its frontend histogram.

Emits ``BENCH_dht_parallel.json`` (gated in scripts/check_bench.py).
"""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import tempfile

import numpy as np

from .common import Row, write_artifact

ARTIFACT = "BENCH_dht_parallel.json"

ENV = {**os.environ, "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
       "JAX_PLATFORMS": "cpu", "PYTHONPATH": "src"}

CFG_KW = dict(max_segments=256, dir_depth_max=12, init_depth=1,
              num_buckets=16, num_slots=8)
BATCH = 256
N_LOAD = 8192
N_FRESH = 8192
# 3 read batches per round keeps the sojourn distribution's p50 strictly
# inside a mode: with 2, exactly half the reads land in the fast first
# batch and the median sits ON the mode boundary, where the histogram's
# inverted-CDF quantile and np.percentile's interpolation legitimately
# diverge by >10%
READS_PER_ROUND = 3

POOL_CFG_KW = dict(max_segments=32, dir_depth_max=8)
POOL_N = 3000
FIRST_QUERY = 64


def _sub(fn: str, *args, timeout=1800) -> dict:
    code = (f"from benchmarks.dht_parallel import {fn}; "
            f"{fn}({', '.join(map(repr, args))})")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=ENV, timeout=timeout)
    assert r.returncode == 0, f"{fn} failed:\n{r.stdout[-2000:]}\n{r.stderr[-4000:]}"
    for ln in r.stdout.splitlines():
        if ln.startswith("RESULT "):
            return json.loads(ln[len("RESULT "):])
    raise AssertionError(f"{fn}: no RESULT line\n{r.stdout}\n{r.stderr}")


# ---------------------------------------------------------------------------
# worker: verify storm + bulk-split micro + per-shard histograms
# ---------------------------------------------------------------------------

def _storm_main():
    import time
    import jax
    import jax.numpy as jnp
    from repro.core import DashConfig, layout
    from repro.distributed import DistributedDash, ShardFrontend
    from repro.launch.mesh import make_test_mesh
    from repro.serving.frontend import INSERT, READ, Op
    from repro.workloads import ycsb

    cfg = DashConfig(**CFG_KW)
    mesh = make_test_mesh(2, 4)
    rng = np.random.default_rng(0xD47)
    space = np.unique(rng.integers(1, 2**63, 80000, dtype=np.uint64))
    loaded, fresh = space[:N_LOAD], space[N_LOAD:N_LOAD + N_FRESH]
    warm = space[N_LOAD + N_FRESH:N_LOAD + N_FRESH + 2 * BATCH]
    lvals = np.asarray([ycsb.expected_value(int(k)) for k in loaded],
                       np.uint32)

    def stream(keys_in, rng2):
        ranks = ycsb.zipfian_ranks(
            rng2, loaded.size,
            max(1, keys_in.size // BATCH) * READS_PER_ROUND * BATCH)
        r = 0
        for i in range(0, keys_in.size, BATCH):
            chunk = [Op(INSERT, int(k), ycsb.expected_value(int(k)))
                     for k in keys_in[i:i + BATCH]]
            for _ in range(READS_PER_ROUND):
                chunk += [Op(READ, int(loaded[j])) for j in ranks[r:r + BATCH]]
                r += BATCH
            yield chunk

    def drive(fe, keys_in, seed):
        t0 = time.perf_counter()
        served = []
        for chunk in stream(keys_in, np.random.default_rng(seed)):
            for op in chunk:
                assert fe.submit(op)
            served += chunk
            fe.drain()
        return time.perf_counter() - t0, served

    def lat_stats(lat_s):
        lat = np.asarray(lat_s) * 1e6
        return {"p50_us": float(np.percentile(lat, 50)),
                "p90_us": float(np.percentile(lat, 90)),
                "p99_us": float(np.percentile(lat, 99)),
                "max_us": float(lat.max()),
                "mean_us": float(lat.mean()), "n": int(lat.size)}

    report = {"config": {**CFG_KW, "batch": BATCH, "n_load": N_LOAD,
                         "n_fresh": N_FRESH,
                         "reads_per_round": READS_PER_ROUND}}
    finals, fes = {}, {}
    for tag in ("device", "host"):
        d = DistributedDash(cfg, mesh, axes=("data", "model"), capacity=256)
        d.insert(loaded, lvals)
        # warm through a THROWAWAY frontend: the jitted tick programs live
        # on the shared DistributedDash, but the warm-up sojourns (which
        # embed multi-second compile stalls) stay out of the measured
        # frontend's latency samples and per-shard histograms
        warm_fe = ShardFrontend(d, max_batch=BATCH, queue_depth=1 << 16,
                                verify_mode=tag)
        drive(warm_fe, warm, 2)
        # pre-warm this mode's split program with a BATCH-sized key set —
        # in-storm splits take the insert batch's (n_shards, q_local) query
        # shape, so a smaller warm set would leave the storm's first split
        # dispatch to compile inside the measured window — then put the
        # state back
        base = jax.tree.map(jnp.copy, d.state)
        if tag == "device":
            d.split_for(space[20000:20000 + BATCH])
        else:
            d._split_for_host(space[20000:20000 + BATCH])
        d.state = base
        fe = ShardFrontend(d, max_batch=BATCH, queue_depth=1 << 16,
                           verify_mode=tag)
        # settle: a duplicate-key insert (EXISTS — no state change) makes
        # the fresh frontend pay its one-time COW-baseline publish before
        # the clock starts; steady-state is what the gate is about
        assert fe.submit(Op(INSERT, int(warm[0]),
                            ycsb.expected_value(int(warm[0]))))
        fe.drain()
        # a single gen-2 GC pause (~0.5s against ~0.1s device ticks) would
        # own the p99 of whichever mode it lands in: collect now, then keep
        # the collector out of the measured window (both modes identically)
        import gc
        gc.collect()
        gc.disable()
        try:
            wall, served = drive(fe, fresh, 3)    # measured storm
        finally:
            gc.enable()
        stats = lat_stats([op.latency for op in served if op.kind == READ])
        stats["wall_s"] = wall
        stats["ops_per_s"] = len(served) / wall
        stats["host_plane_bytes"] = int(fe._host_plane_bytes.value)
        stats["retried_reads"] = fe.retried_reads
        stats["snapshot_reads"] = fe.snapshot_reads
        report[tag] = stats
        finals[tag] = d.state
        fes[tag] = fe

    # identical final state, bit-for-bit, before any gate is quoted: the
    # device retry loop + device splits must land exactly where the
    # host-sync baseline lands (same routing, same round structure)
    for name in type(finals["device"])._fields:
        a = np.asarray(getattr(finals["device"], name))
        b = np.asarray(getattr(finals["host"], name))
        assert np.array_equal(a, b), f"final state diverged on plane {name}"
    report["states_identical"] = True
    d = fes["device"].dht
    meta = np.asarray(d.state.meta)
    recount = int(((meta >> layout.COUNT_SHIFT) & 0xF).sum())
    assert d.n_items == recount == N_LOAD + N_FRESH + warm.size, \
        (d.n_items, recount)

    report["p99_ratio"] = (report["device"]["p99_us"]
                           / report["host"]["p99_us"])
    assert report["device"]["host_plane_bytes"] == 0, \
        "device read tick copied plane bytes to host"
    assert report["host"]["host_plane_bytes"] > 0, \
        "host baseline never exercised the mirror verify"

    # per-shard read-sojourn histograms (device mode): the aggregate of the
    # per-shard registries must agree with the exact samples within 10%
    # (log-bucket geometry bounds the error at ~2.2%)
    from repro.obs import Registry
    regs = fes["device"].shard_registries()
    agg = Registry.aggregate(regs).get("shard.read_sojourn_s").snapshot()
    exact = report["device"]
    assert agg["n"] == exact["n"], (agg["n"], exact["n"])
    hist_agree = {"n": agg["n"]}
    for q in ("p50", "p99"):
        err = abs(agg[q] * 1e6 - exact[f"{q}_us"]) / exact[f"{q}_us"]
        hist_agree[f"{q}_err"] = err
        assert err <= 0.10, \
            f"shard hist {q} {agg[q]*1e6:.1f}us vs {exact[f'{q}_us']:.1f}us"
    report["hist_agree"] = hist_agree
    report["shard_hist"] = {
        "aggregate": {k: (v * 1e6 if k.startswith(("p", "m", "s")) else v)
                      for k, v in agg.items()},
        "per_shard_n": [r.get("shard.read_sojourn_s").snapshot()["n"]
                        for r in regs]}

    # ---- bulk-split micro: one device dispatch vs the per-shard host loop
    d2 = DistributedDash(cfg, mesh, axes=("data", "model"), capacity=256)
    d2.insert(space[30000:36000],
              (np.arange(6000) % 1000 + 1).astype(np.uint32))
    base = jax.tree.map(jnp.copy, d2.state)

    # probe keys touching <= split_lanes distinct segments per shard so the
    # capped device plan and the host loop split the exact same set
    from repro.core import hashing
    from repro.distributed.dht import np_owner_of
    cand = space[36000:44000]
    hi, lo = hashing.np_split_keys(cand)
    h1 = hashing.np_hash1(hi, lo)
    owner = np_owner_of(cand, d2.n_shards)
    dirs = np.asarray(base.dir)
    seg_of = dirs[owner, (h1 >> np.uint32(32 - cfg.dir_depth_max)).astype(
        np.int64)]
    keep = np.zeros(cand.size, bool)
    for s in range(d2.n_shards):
        m = owner == s
        segs = np.unique(seg_of[m])[:6]       # <= split_lanes per shard
        keep |= m & np.isin(seg_of, segs)
    probe = cand[keep]
    n_split = int(sum(np.unique(seg_of[keep & (owner == s)]).size
                      for s in range(d2.n_shards)))

    d2.state = jax.tree.map(jnp.copy, base)
    d2.split_for(probe)
    st_dev = d2.state
    d2.state = jax.tree.map(jnp.copy, base)
    d2._split_for_host(probe)
    for name in type(st_dev)._fields:
        assert np.array_equal(np.asarray(getattr(st_dev, name)),
                              np.asarray(getattr(d2.state, name))), \
            f"split paths diverged on plane {name}"

    def time_split(fn, reps=5):
        ts = []
        for _ in range(reps):
            d2.state = jax.tree.map(jnp.copy, base)
            t0 = time.perf_counter()
            fn(probe)
            jax.block_until_ready(d2.state)
            ts.append(time.perf_counter() - t0)
        return float(np.median(ts))

    dev_s = time_split(d2.split_for)
    host_s = time_split(d2._split_for_host)
    report["splits"] = {"device_s": dev_s, "host_s": host_s,
                        "speedup": host_s / dev_s, "n_segments": n_split,
                        "identical_states": True}
    print("RESULT " + json.dumps(report))


# ---------------------------------------------------------------------------
# workers: durable reopen time-to-first-query
# ---------------------------------------------------------------------------

def _writer_main(dirpath: str, clean: bool):
    from repro import persist
    from repro.core import DashConfig
    from repro.distributed import DistributedDash
    from repro.launch.mesh import make_test_mesh
    cfg = DashConfig(**POOL_CFG_KW)
    d = DistributedDash(cfg, make_test_mesh(2, 4), axes=("data", "model"),
                        capacity=256)
    d.attach_pools(persist.create_shard_pools(dirpath, cfg, d.n_shards))
    rng = np.random.default_rng(5)
    keys = np.unique(rng.integers(1, 2**63, 8000, dtype=np.uint64))[:POOL_N]
    st = d.insert(keys, np.arange(POOL_N, dtype=np.uint32) % 1000 + 1)
    assert (st == 0).all()
    d.flush_pools()
    if clean:
        d.close_pools()
    print("RESULT " + json.dumps({"written": POOL_N}))
    sys.stdout.flush()
    os._exit(0)       # the kill: dirty dirs never see a clean close


def _reader_main(dirpath: str, eager: bool):
    import time
    from repro import persist
    from repro.core import DashConfig, layout, recovery
    from repro.distributed import DistributedDash
    from repro.launch.mesh import make_test_mesh
    cfg = DashConfig(**POOL_CFG_KW)
    mesh = make_test_mesh(2, 4)
    rng = np.random.default_rng(5)
    keys = np.unique(rng.integers(1, 2**63, 8000, dtype=np.uint64))[:POOL_N]
    # warm the recovery jit cache on a throwaway state with the same plane
    # shapes BEFORE the clock: only the lazy/eager readers run recovery, so
    # its one-time compile would otherwise masquerade as per-segment
    # recovery work in the ttfq ratio (the gated claim is about the
    # data-proportional part)
    recovery.recover_segment_host(cfg, "eh", layout.make_state(cfg, "eh"), 0)
    t0 = time.perf_counter()
    stacked, wbs, info = persist.reopen_shards(
        dirpath, eager_recover_dirty=eager)
    t_reopen = time.perf_counter() - t0
    d = DistributedDash(cfg, mesh, axes=("data", "model"), capacity=256,
                        state=stacked)
    d.attach_pools(wbs)
    f, v = d.search(keys[:FIRST_QUERY])
    ttfq = time.perf_counter() - t0
    assert f.all()
    print("RESULT " + json.dumps({
        "ttfq_s": ttfq, "reopen_s": t_reopen,
        "dirty_shards": info["dirty_shards"],
        "recovered_segments": d.recovered_segments}))


def run():
    storm = _sub("_storm_main")

    tmp = tempfile.mkdtemp(prefix="dash_dhtpar_")
    try:
        dirs = {k: os.path.join(tmp, k) for k in ("clean", "lazy", "eager")}
        _sub("_writer_main", dirs["clean"], True)
        _sub("_writer_main", dirs["lazy"], False)
        _sub("_writer_main", dirs["eager"], False)
        clean = _sub("_reader_main", dirs["clean"], False)
        lazy = _sub("_reader_main", dirs["lazy"], False)
        eager = _sub("_reader_main", dirs["eager"], True)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    assert clean["dirty_shards"] == 0 and clean["recovered_segments"] == 0
    assert lazy["dirty_shards"] == 8
    assert lazy["recovered_segments"] > 0, \
        "lazy recovery never fired on first access"
    assert eager["recovered_segments"] == 0    # all work done at reopen

    report = dict(storm)
    report["verify"] = {"p99_ratio": report.pop("p99_ratio"),
                        "host_plane_bytes":
                            report["device"]["host_plane_bytes"]}
    report["reopen"] = {
        "clean": clean, "lazy": lazy, "eager": eager,
        "ttfq_ratio": lazy["ttfq_s"] / clean["ttfq_s"],
        "eager_ttfq_ratio": eager["ttfq_s"] / clean["ttfq_s"],
        "first_query": FIRST_QUERY, "n_keys": POOL_N}

    # the ISSUE-9 acceptance gates, asserted before the artifact is written
    # (scripts/check_bench.py re-checks them from the JSON)
    assert report["verify"]["p99_ratio"] <= 0.5, \
        (report["verify"], report["device"], report["host"])
    assert report["verify"]["host_plane_bytes"] == 0
    assert report["splits"]["speedup"] >= 2.0, report["splits"]
    assert report["reopen"]["ttfq_ratio"] <= 1.5, report["reopen"]

    write_artifact(ARTIFACT, report)
    return [
        Row("dht_parallel/device_read", report["device"]["p50_us"],
            f"p99={report['device']['p99_us']:.0f}us "
            f"{report['device']['ops_per_s']:.0f} ops/s"),
        Row("dht_parallel/host_read", report["host"]["p50_us"],
            f"p99={report['host']['p99_us']:.0f}us "
            f"plane_bytes={report['host']['host_plane_bytes']}"),
        Row("dht_parallel/p99_ratio", report["verify"]["p99_ratio"],
            "device/host read p99; device plane bytes = 0"),
        Row("dht_parallel/split_speedup", report["splits"]["speedup"],
            f"{report['splits']['n_segments']} segs: "
            f"{report['splits']['device_s']*1e3:.0f}ms vs "
            f"{report['splits']['host_s']*1e3:.0f}ms host loop"),
        Row("dht_parallel/reopen_ttfq_ratio", report["reopen"]["ttfq_ratio"],
            f"lazy {lazy['ttfq_s']:.1f}s vs clean {clean['ttfq_s']:.1f}s "
            f"(eager {eager['ttfq_s']:.1f}s), "
            f"recovered={lazy['recovered_segments']}"),
    ]


if __name__ == "__main__":
    for r in run():
        print(r.csv())
