#!/usr/bin/env python3
"""Smoke run of the durable Dash serving path on a TPU.

    python chip_smoke.py              # one chip: the durable single table
    python chip_smoke.py --chips 4    # four chips: the sharded table only

One chip: build a durable table of 2**14 segments (about 15M record slots)
with ``persist.create``, load 10M unique 8-byte keys through
``table.insert``, serve inserts, updates, deletes and reads of present and
absent keys through ``DashFrontend`` (flush on every publish) with read
batches on both sides of ``fused_threshold``, so that both Pallas read
kernels run, then kill the process without closing the pool. A second
process ``persist.reopen``s the pool, serves again and reads every loaded
key back. Every answer is checked against a plain dict of acknowledged
writes.

Four chips: the same checks over ``DistributedDash``/``ShardFrontend``
on a four-device mesh with one pool per shard (``persist.create_shard_pools``,
``persist.reopen_shards``), plus a check that each shard lives on its own
device. Each shard has the one-chip table's 2**14 segment slots and starts
at 16 segments; 2**17 keys fill every starting segment past its capacity,
so every shard splits (the device bulk split of ``DistributedDash.insert``),
and the reopen reads every loaded key back. The load is small because each
shard inserts through the sequential scan engine, one lane at a time. The shard frontend serves inserts and reads; updates and deletes
have no shard frontend lane.

All data comes from ``--seed``. The parent process never imports JAX: each
phase runs in a child, one after the other, and the chip belongs to one
child at a time. A phase fails (and the script exits non-zero with no
result line) when JAX finds no TPU, when an answer disagrees with the
reference, or when a read program holds no Pallas kernel. The last line of
a good run is ``{"ok": true, "device": {...}}``.

JAX's persistent compilation cache is on: in ``$JAX_COMPILATION_CACHE_DIR``
when that is set, else in ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
BUDGET_S = 1150                    # every phase, compilation included


def log(msg: str):
    """Progress line of a phase (the last stdout line is the parent's)."""
    print(f"[{time.strftime('%H:%M:%S')}] {msg}", flush=True)


@dataclasses.dataclass(frozen=True)
class Plan:
    """Sizes of one run. The script always runs ``Plan()``; the tests run
    the same phases on the CPU with a small plan."""
    max_segments: int = 2**14      # ~15M record slots, ~216 MB of state
    dir_depth_max: int = 14
    init_depth: int = 13           # 8192 segments to start: the load splits
    n_keys: int = 10_000_000
    load_batch: int = 5_000_000    # two batches; the second splits segments
    check_batch: int = 2**18       # table.search batches: the routed kernel
    n_ops: int = 240               # served ops per wave
    small_batch: int = 256         # frontend batch under fused_threshold
    large_batch: int = 2048        # frontend batch over it
    shards: int = 4
    shard_init_depth: int = 4      # 16 segments per shard to start
    shard_keys: int = 2**17        # 2048 per starting segment: all split
    shard_batch: int = 2**12       # shard inserts scan 4x this many lanes

    def config(self, init_depth=None):
        from repro.core import DashConfig
        return DashConfig(max_segments=self.max_segments,
                          dir_depth_max=self.dir_depth_max,
                          init_depth=(self.init_depth if init_depth is None
                                      else init_depth))


class SmokeFailure(RuntimeError):
    pass


# ---------------------------------------------------------------------------
# data and the reference
# ---------------------------------------------------------------------------

def make_data(seed: int, n_keys: int):
    """(keys, values, spare): ``n_keys`` unique nonzero uint64 keys with
    uint32 values, and spare keys that are never loaded (absent reads and
    new inserts draw from them)."""
    rng = np.random.default_rng(seed)
    raw = rng.integers(1, 2**63, size=n_keys + n_keys // 16 + 4096,
                       dtype=np.uint64)
    pool = rng.permutation(np.unique(raw))
    if pool.size < n_keys + 2048:
        raise SmokeFailure("key generator produced too few unique keys")
    vals = rng.integers(1, 2**32, size=n_keys, dtype=np.uint64)
    return pool[:n_keys], vals.astype(np.uint32), pool[n_keys:]


class Reference:
    """The plain reference: the loaded keys and values with a dict of every
    acknowledged write applied on top (``None`` marks a delete)."""

    def __init__(self, keys: np.ndarray, vals: np.ndarray, acked: dict):
        written = np.fromiter(acked, np.uint64, len(acked))
        live = [(k, v) for k, v in acked.items() if v is not None]
        keep = ~np.isin(keys, written)
        k = np.concatenate([keys[keep],
                            np.asarray([k for k, _ in live], np.uint64)])
        v = np.concatenate([vals[keep],
                            np.asarray([v for _, v in live], np.uint32)])
        order = np.argsort(k)
        self.keys, self.vals = k[order], v[order]

    def lookup(self, keys: np.ndarray):
        keys = np.asarray(keys, np.uint64)
        i = np.clip(np.searchsorted(self.keys, keys), 0, self.keys.size - 1)
        found = self.keys[i] == keys
        return found, np.where(found, self.vals[i], 0).astype(np.uint32)

    def compare(self, keys, found, vals) -> int:
        """Mismatching answers: presence, and the value where present."""
        want_f, want_v = self.lookup(keys)
        found = np.asarray(found, bool)
        vals = np.asarray(vals, np.uint32)
        return int(np.sum((found != want_f) | (want_f & (vals != want_v))))


def _sample(rng, n: int, k: int) -> np.ndarray:
    """k distinct indices below n."""
    if k >= n:
        return rng.permutation(n)
    out = np.unique(rng.integers(0, n, size=2 * k + 64))
    return rng.permutation(out)[:k]


def write_wave(rng, keys, spare, acked: dict, n_ops: int):
    """(kind, key, value) write ops over disjoint keys, interleaved as a
    mixed client stream: inserts of new keys, updates and deletes of live
    loaded keys."""
    live = np.asarray([i for i in _sample(rng, keys.size, 3 * n_ops)
                       if int(keys[i]) not in acked], np.int64)
    fresh = spare[np.asarray([i for i in _sample(rng, spare.size, 2 * n_ops)
                              if int(spare[i]) not in acked], np.int64)]
    vals = rng.integers(1, 2**32, size=n_ops, dtype=np.uint64)
    ops = []
    for j in range(n_ops):
        kind = ("insert", "update", "delete")[j % 3]
        key = int(fresh[j]) if kind == "insert" else int(keys[live[j]])
        ops.append((kind, key, int(vals[j]) if kind != "delete" else 0))
    return ops


def read_wave(rng, keys, spare, acked: dict, n_ops: int):
    """Reads of loaded keys, of every key a write touched, and of keys that
    were never written."""
    touched = list(acked)
    rng.shuffle(touched)
    n_absent = n_ops // 4
    rest = max(n_ops - n_absent - len(touched), 0)
    picks = [int(keys[i]) for i in _sample(rng, keys.size, rest)]
    absent = [int(spare[i]) for i in _sample(rng, spare.size, 2 * n_absent)
              if int(spare[i]) not in acked][:n_absent]
    ks = touched[:n_ops - n_absent - len(picks)] + picks + absent
    return [("read", k, 0) for k in ks]


def serve(fe, ops, ref_acked: dict) -> dict:
    """Submit ``ops`` in order, drain, and record acknowledged writes into
    ``ref_acked``. Returns the per-kind counts and the ops' results."""
    from repro.serving.frontend import INSERTED, Op
    handles = []
    for kind, key, value in ops:
        op = Op(kind, key, value)
        if not fe.submit(op):
            raise SmokeFailure(f"admission rejected a {kind}")
        handles.append(op)
    fe.drain()
    bad_status = 0
    for op in handles:
        if op.kind == "read":
            continue
        if op.status != INSERTED:
            bad_status += 1
            continue
        ref_acked[op.key] = None if op.kind == "delete" else op.value
    return {"ops": handles, "bad_status": bad_status}


def check_reads(ref: Reference, handles) -> tuple:
    reads = [op for op in handles if op.kind == "read"]
    keys = np.asarray([op.key for op in reads], np.uint64)
    bad = ref.compare(keys, [op.found for op in reads],
                      [op.result for op in reads])
    return len(reads), bad


# ---------------------------------------------------------------------------
# device checks
# ---------------------------------------------------------------------------

def device_info(require_tpu: bool = True) -> dict:
    import jax
    dev = jax.devices()[0]
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}
    if require_tpu and info["platform"] != "tpu":
        raise SmokeFailure(f"no TPU: JAX found {info['platform']} devices")
    return info


def kernel_in_program(jitted, *args) -> bool:
    """True when the program ``jitted(*args)`` lowers to holds a Pallas
    TPU kernel (a ``tpu_custom_call``)."""
    return "tpu_custom_call" in jitted.lower(*args).as_text()


def read_programs_hold_kernels(table, small_batch: int, check_batch: int):
    """The two read programs — the fused probe (frontend batches under the
    threshold) and the routed fingerprint probe (large table.search
    batches): check that this process's reads dispatched each (its jit
    cache is not empty) and that each lowers to a program holding its
    kernel."""
    import jax.numpy as jnp
    from repro.core import engine
    from repro.kernels import fused
    cfg = table.cfg

    def q(n):
        z = jnp.zeros((n,), jnp.uint32)
        return z, z

    fused_fn, routed_fn = fused._fused_search_kernel, engine._search_batch_routed
    return {
        "fused": fused_fn._cache_size() > 0 and kernel_in_program(
            fused_fn, cfg, "eh", table.state, *q(small_batch), False),
        "routed": routed_fn._cache_size() > 0 and kernel_in_program(
            routed_fn, cfg, "eh", table.state, *q(check_batch),
            jnp.zeros((check_batch, cfg.key_heap_words), jnp.uint32), 128),
    }


def search_all(table, keys: np.ndarray, batch: int):
    """``table.search`` over ``keys`` in batches (each above
    ``fused_threshold``: the routed kernel)."""
    found = np.zeros(keys.size, bool)
    vals = np.zeros(keys.size, np.uint32)
    for s in range(0, keys.size, batch):
        f, v = table.search(keys[s:s + batch])
        found[s:s + batch], vals[s:s + batch] = f, v
    return found, vals


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------

def phase_load(plan: Plan, seed: int, workdir: str, require_tpu=True) -> dict:
    """Build, load, serve and flush. The caller exits without closing."""
    from repro import persist
    from repro.core import layout
    from repro.serving.frontend import DashFrontend

    dev = device_info(require_tpu)
    keys, vals, spare = make_data(seed, plan.n_keys)
    cfg = plan.config()
    table = persist.create(os.path.join(workdir, "table.pool"), cfg)

    log(f"load: created a pool of {cfg.max_segments} segments")
    t0 = time.perf_counter()
    for s in range(0, keys.size, plan.load_batch):
        st = table.insert(keys[s:s + plan.load_batch],
                          vals[s:s + plan.load_batch])
        if not (st == layout.INSERTED).all():
            raise SmokeFailure(f"load: {int((st != 0).sum())} keys refused")
        log(f"load: {s + st.size} keys in, "
            f"{int(table.state.watermark)} segments")
    table.flush()
    load_s = time.perf_counter() - t0

    rng = np.random.default_rng(seed + 1)
    acked: dict = {}
    ref = Reference(keys, vals, acked)
    sample = keys[_sample(rng, keys.size, plan.check_batch)]
    absent = spare[:plan.check_batch]
    f, v = search_all(table, np.concatenate([sample, absent]),
                      plan.check_batch)
    bad = ref.compare(np.concatenate([sample, absent]), f, v)
    checked = 2 * plan.check_batch
    log(f"load: checked {checked} reads, {bad} mismatches")
    bad_status = 0
    reads_small = reads_large = 0
    for batch in (plan.small_batch, plan.large_batch):
        fe = DashFrontend(table, max_batch=batch)
        r = serve(fe, write_wave(rng, keys, spare, acked, plan.n_ops), acked)
        bad_status += r["bad_status"]
        r = serve(fe, read_wave(rng, keys, spare, acked, plan.n_ops), acked)
        ref = Reference(keys, vals, acked)
        n, b = check_reads(ref, r["ops"])
        checked += plan.n_ops + n
        bad += b
        if batch == plan.small_batch:
            reads_small = n
        else:
            reads_large = n
        if fe.stats().get("flush_hint_misses", 0):
            raise SmokeFailure("a publish flushed less than it wrote")
        log(f"serve: frontend max_batch={batch} done, {bad} mismatches")
    st = table.state
    return {
        "device": dev,
        "segments": int(st.watermark), "max_segments": cfg.max_segments,
        "records": int(st.n_items), "splits": int(st.n_splits),
        "state_bytes": cfg.max_segments * cfg.bytes_per_segment(),
        "load_s": load_s, "loaded": int(keys.size),
        "checked": checked, "mismatches": bad, "bad_status": bad_status,
        "reads_fused_batches": reads_small, "reads_routed_batches": reads_large,
        "kernels": (read_programs_hold_kernels(table, plan.small_batch,
                                               plan.check_batch)
                    if dev["platform"] == "tpu" else {}),
        "acked": [[k, v] for k, v in acked.items()],
    }


def phase_reopen(plan: Plan, seed: int, workdir: str, acked: dict,
                 require_tpu=True) -> dict:
    """Reopen the killed pool, answer a first query, serve, and read every
    loaded key back."""
    from repro import persist
    from repro.serving.frontend import DashFrontend

    dev = device_info(require_tpu)
    keys, vals, spare = make_data(seed, plan.n_keys)
    rng = np.random.default_rng(seed + 2)
    t0 = time.perf_counter()
    table, info = persist.reopen(os.path.join(workdir, "table.pool"))
    if info["clean"]:
        raise SmokeFailure("the killed writer left a clean pool")
    fe = DashFrontend(table, max_batch=plan.small_batch)
    first = serve(fe, [("read", int(keys[0]), 0)], acked)["ops"]
    ttfq = time.perf_counter() - t0
    log(f"reopen: first query answered after {ttfq:.3f} s")
    ref = Reference(keys, vals, acked)
    _, bad = check_reads(ref, first)
    checked = 1
    r = serve(fe, write_wave(rng, keys, spare, acked, plan.n_ops), acked)
    bad_status = r["bad_status"]
    r = serve(fe, read_wave(rng, keys, spare, acked, plan.n_ops), acked)
    ref = Reference(keys, vals, acked)
    n, b = check_reads(ref, r["ops"])
    checked += plan.n_ops + n
    bad += b
    log(f"reopen: served, {bad} mismatches; reading every loaded key")
    f, v = search_all(table, keys, plan.check_batch)
    bad += ref.compare(keys, f, v)
    checked += keys.size
    return {"device": dev, "ttfq_s": ttfq, "reopen_s": info["seconds"],
            "checked": checked, "mismatches": bad, "bad_status": bad_status,
            "recovered_segments": table.recovered_segments}


# ---------------------------------------------------------------------------
# four chips: the sharded table
# ---------------------------------------------------------------------------

def shard_mesh(n: int):
    import jax
    from repro.launch.mesh import auto_mesh
    if len(jax.devices()) < n:
        raise SmokeFailure(f"--chips {n}: JAX sees {len(jax.devices())}")
    return auto_mesh((n,), ("data",), devices=jax.devices()[:n])


def shard_devices(dht) -> list:
    """Device id holding each shard's record planes."""
    shards = dht.state.key_hi.addressable_shards
    return [s.device.id for s in sorted(shards, key=lambda s: s.index[0].start)]


def phase_shard_load(plan: Plan, seed: int, workdir: str,
                     require_tpu=True) -> dict:
    from repro import persist
    from repro.distributed import DistributedDash, ShardFrontend

    dev = device_info(require_tpu)
    keys, vals, spare = make_data(seed, plan.shard_keys)
    cfg = plan.config(plan.shard_init_depth)
    dht = DistributedDash(cfg, shard_mesh(plan.shards),
                          q_local_hint=plan.shard_batch // plan.shards)
    dht.attach_pools(persist.create_shard_pools(
        os.path.join(workdir, "shards"), cfg, dht.n_shards))
    t0 = time.perf_counter()
    for s in range(0, keys.size, plan.shard_batch):
        st = dht.insert(keys[s:s + plan.shard_batch],
                        vals[s:s + plan.shard_batch], max_rounds=32)
        if not (st == 0).all():
            raise SmokeFailure(f"load: {int((st != 0).sum())} keys refused")
        if (s // plan.shard_batch) % 16 == 15:
            log(f"shard load: {s + st.size} keys in, "
                f"{int(np.sum(np.asarray(dht.state.n_splits)))} splits")
    dht.flush_pools()
    load_s = time.perf_counter() - t0
    log(f"shard load: {keys.size} keys in {load_s:.1f} s, "
        f"{int(np.sum(np.asarray(dht.state.n_splits)))} splits")

    rng = np.random.default_rng(seed + 1)
    acked: dict = {}
    fe = ShardFrontend(dht, max_batch=plan.small_batch)
    wave = [op for op in write_wave(rng, keys, spare, acked, plan.n_ops)
            if op[0] == "insert"]
    r = serve(fe, wave, acked)
    bad_status = r["bad_status"]
    r = serve(fe, read_wave(rng, keys, spare, acked, plan.n_ops), acked)
    ref = Reference(keys, vals, acked)
    n, bad = check_reads(ref, r["ops"])
    checked = len(wave) + n
    sample = keys[_sample(rng, keys.size, plan.shard_batch)]
    f, v = dht.search(sample)
    bad += ref.compare(sample, f, v)
    checked += sample.size
    devices = shard_devices(dht)
    return {"device": dev, "shards": dht.n_shards, "shard_devices": devices,
            "segments": [int(x) for x in np.asarray(dht.state.watermark)],
            "max_segments": cfg.max_segments, "records": dht.n_items,
            "splits": int(np.sum(np.asarray(dht.state.n_splits))),
            "state_bytes": dht.n_shards * cfg.max_segments
            * cfg.bytes_per_segment(),
            "load_s": load_s, "loaded": int(keys.size), "checked": checked,
            "mismatches": bad, "bad_status": bad_status,
            "acked": [[k, v] for k, v in acked.items()]}


def phase_shard_reopen(plan: Plan, seed: int, workdir: str, acked: dict,
                       require_tpu=True) -> dict:
    from repro import persist
    from repro.distributed import DistributedDash, ShardFrontend

    dev = device_info(require_tpu)
    keys, vals, spare = make_data(seed, plan.shard_keys)
    cfg = plan.config(plan.shard_init_depth)
    t0 = time.perf_counter()
    stacked, wbs, info = persist.reopen_shards(os.path.join(workdir,
                                                            "shards"))
    if info["dirty_shards"] != plan.shards:
        raise SmokeFailure(f"{info['dirty_shards']} dirty shards after kill")
    dht = DistributedDash(cfg, shard_mesh(plan.shards),
                          q_local_hint=plan.shard_batch // plan.shards,
                          state=stacked)
    dht.attach_pools(wbs)
    fe = ShardFrontend(dht, max_batch=plan.small_batch)
    first = serve(fe, [("read", int(keys[0]), 0)], acked)["ops"]
    ttfq = time.perf_counter() - t0
    rng = np.random.default_rng(seed + 2)
    r = serve(fe, read_wave(rng, keys, spare, acked, plan.n_ops), acked)
    ref = Reference(keys, vals, acked)
    n, bad = check_reads(ref, first + r["ops"])
    checked = n
    log(f"shard reopen: served, {bad} mismatches; reading every loaded key")
    for s in range(0, keys.size, plan.shard_batch):
        f, v = dht.search(keys[s:s + plan.shard_batch])
        bad += ref.compare(keys[s:s + plan.shard_batch], f, v)
    checked += keys.size
    return {"device": dev, "ttfq_s": ttfq, "checked": checked,
            "mismatches": bad, "shard_devices": shard_devices(dht),
            "recovered_segments": dht.recovered_segments}


# ---------------------------------------------------------------------------
# processes
# ---------------------------------------------------------------------------

PHASES = {"load": phase_load, "reopen": phase_reopen,
          "shard_load": phase_shard_load, "shard_reopen": phase_shard_reopen}


def _child(args) -> int:
    """One phase in this process; its result goes to ``<workdir>/<phase>.json``.
    A load phase exits with ``os._exit`` right after its last flush: the
    pool is never closed, as after a kill."""
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    from benchmarks.common import cache_stats, enable_compilation_cache
    enable_compilation_cache()
    plan = Plan()
    fn = PHASES[args.child]
    extra = ()
    if args.child.endswith("reopen"):
        prev = "load" if args.child == "reopen" else "shard_load"
        with open(os.path.join(args.workdir, prev + ".json")) as f:
            extra = ({k: v for k, v in json.load(f)["acked"]},)
    t0 = time.perf_counter()
    result = fn(plan, args.seed, args.workdir, *extra)
    result["phase_s"] = time.perf_counter() - t0
    result["cache"] = cache_stats()
    with open(os.path.join(args.workdir, args.child + ".json"), "w") as f:
        json.dump(result, f)
    sys.stdout.flush()
    os._exit(0)


def _run_phase(phase: str, seed: int, workdir: str, timeout: float) -> dict:
    cmd = [sys.executable, os.path.abspath(__file__), "--child", phase,
           "--seed", str(seed), "--workdir", workdir]
    t0 = time.perf_counter()
    proc = subprocess.run(cmd, cwd=ROOT, timeout=timeout)
    if proc.returncode != 0:
        raise SmokeFailure(f"phase {phase} exited {proc.returncode}")
    with open(os.path.join(workdir, phase + ".json")) as f:
        out = json.load(f)
    out["wall_s"] = time.perf_counter() - t0
    return out


def _report(phase: str, r: dict):
    keep = {k: v for k, v in r.items() if k not in ("acked", "device")}
    print(f"smoke {phase}: " + json.dumps(keep, sort_keys=True), flush=True)


def _verdict(results: dict) -> list:
    """The reasons a run failed (empty when it passed)."""
    why = []
    for phase, r in results.items():
        if r["mismatches"]:
            why.append(f"{phase}: {r['mismatches']} answers disagree")
        if r.get("splits") == 0:
            why.append(f"{phase}: the load split no segment")
        if r.get("bad_status"):
            why.append(f"{phase}: {r['bad_status']} writes not acknowledged")
        for name, ok in r.get("kernels", {}).items():
            if not ok:
                why.append(f"{phase}: the {name} read program did not run "
                           "or has no kernel")
        devs = r.get("shard_devices")
        if devs is not None and len(set(devs)) != len(devs):
            why.append(f"{phase}: shards share devices {devs}")
    return why


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=12)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--child", choices=sorted(PHASES), help=argparse.SUPPRESS)
    ap.add_argument("--workdir", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.child:
        return _child(args)
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print("chip_smoke.py: run it from a checkout of the repository "
              f"(no src/repro next to {ROOT})", file=sys.stderr)
        return 2
    phases = (["load", "reopen"] if args.chips == 1
              else ["shard_load", "shard_reopen"])
    workdir = tempfile.mkdtemp(prefix="dash_smoke_")
    deadline = time.monotonic() + BUDGET_S
    results = {}
    try:
        for phase in phases:
            results[phase] = _run_phase(phase, args.seed, workdir,
                                        deadline - time.monotonic())
            _report(phase, results[phase])
    except (SmokeFailure, subprocess.TimeoutExpired, OSError) as e:
        print(f"chip_smoke.py: FAILED: {e}", file=sys.stderr)
        return 1
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    why = _verdict(results)
    if why:
        print("chip_smoke.py: FAILED: " + "; ".join(why), file=sys.stderr)
        return 1
    first = results[phases[0]]
    print(f"smoke summary (one smoke run, not a benchmark): "
          f"device_kind={first['device']['kind']} "
          f"segments={first['segments']} records={first['records']} "
          f"state_bytes={first['state_bytes']} load_s={first['load_s']} "
          f"checked={sum(r['checked'] for r in results.values())} "
          f"mismatches=0 ttfq_s={results[phases[1]]['ttfq_s']}", flush=True)
    print(json.dumps({"ok": True, "device": results[phases[-1]]["device"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
