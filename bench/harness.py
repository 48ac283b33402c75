"""One run of one cell: set-up, the measured window, the check.

Set-up makes the records from the seed, creates a durable pool
(``persist.create``: every publish of the frontend is flushed before its
operations are acknowledged), loads the records through ``table.insert``
and warms every program shape the cell's traffic uses. The window then
drives ``DashFrontend.submit``/``step`` with a closed loop of clients
(``bench/loop.py``). Python's garbage collector runs as it does for any
user of the frontend.

After the window every acknowledged operation is replayed against the
plain reference (``bench/data.py``). Where the mix writes, the pool file
is read back (``bench/durable.py``) and the frontend's durability
counters are held to zero as well.
"""
from __future__ import annotations

import dataclasses
import gc
import os
import sys
import time
from typing import Dict, List, Optional

import numpy as np
from jax.profiler import TraceAnnotation

from . import compiles, data, durable, trace as trace_mod
from .data import READ, WRITES
from .loop import ClosedLoop
from .spec import BENCH_DIR, Cell
from .traffic import Generator, Mix

CACHE_DIR = os.path.join(BENCH_DIR, ".cache")
JAX_CACHE = os.path.join(CACHE_DIR, "jax")
WORK_DIR = os.path.join(CACHE_DIR, "work")
TRACE_DIR = os.path.join(CACHE_DIR, "trace")
DRAIN_S = 60.0


def log(msg: str):
    print(f"[bench {time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr,
          flush=True)


@dataclasses.dataclass
class Run:
    """What the metric readers see."""
    cell: Cell
    seed: int
    seconds: float
    setup_s: float = 0.0
    window_s: float = 0.0
    window_ops: List = dataclasses.field(default_factory=list)
    steps: int = 0
    steps_with_work: int = 0
    trace: Optional[trace_mod.Reduced] = None
    device_kind: str = ""
    notes: List[str] = dataclasses.field(default_factory=list)

    @property
    def table_cfg(self) -> dict:
        return self.cell.config["table"]

    def ops_of(self, kinds) -> list:
        return [op for op in self.window_ops if op.kind in kinds]

    def note(self, msg: str):
        self.notes.append(msg)


def dash_config(table: dict):
    from repro.core import DashConfig
    fields = {k: v for k, v in table.items() if k != "mode"}
    return DashConfig(**fields)


def load_table(path: str, table_cfg: dict, keys, vals, batch: int):
    """A durable table at ``path`` holding ``keys``/``vals``, loaded through
    ``table.insert`` in equal batches and flushed."""
    from repro import persist
    if os.path.exists(path):
        os.unlink(path)
    table = persist.create(path, dash_config(table_cfg),
                           mode=table_cfg.get("mode", "eh"))
    for s in range(0, keys.size, batch):
        st = table.insert(keys[s:s + batch], vals[s:s + batch])
        bad = int(np.sum(st != data.INSERTED))
        if bad:
            raise RuntimeError(f"load: {bad} of {st.size} inserts refused")
    table.flush()
    return table


def durability_counters(fe) -> Dict[str, int]:
    st = fe.stats()
    return {"unflushed_publishes": int(st.get("unflushed_publishes", 0)),
            "flush_hint_misses": int(st.get("flush_hint_misses", 0))}


def warm(loop: ClosedLoop, gen: Generator, mix: Mix):
    """A full read batch, then ticks of the cell's own mix: every program
    shape the window uses is built or loaded before it opens."""
    if READ in mix.mix:
        for op in gen.batch(READ, mix.max_batch):
            loop.submit(*op)
        while loop.outstanding[READ]:
            loop.tick(resubmit=False)
    loop.fill()
    for _ in range(mix.warmup_ticks):
        loop.tick()


def run_cell(cell: Cell, seed: int, seconds: float, traced: bool,
             t_start: float, fault: Optional[str] = None,
             work_dir: str = WORK_DIR) -> dict:
    """One run; returns the result object (see ``bench/run.py``). The pool
    files live in ``work_dir`` and are removed at the end."""
    import jax
    from repro.serving.frontend import DashFrontend, Op

    compiles.enable_cache(JAX_CACHE)
    clog = compiles.compile_log()
    dev = jax.devices()[0]
    run = Run(cell=cell, seed=seed, seconds=seconds,
              device_kind=dev.device_kind)
    cfg, mix = cell.config, Mix.from_json(cell.traffic)
    os.makedirs(work_dir, exist_ok=True)
    pool_path = os.path.join(work_dir, "table.pool")

    keys, vals, spare = data.make_data(seed, int(cfg["record_count"]),
                                       int(cfg.get("spare_keys", 0)),
                                       int(cfg.get("key_bytes", 8)))
    log(f"{cell.name}: {keys.size} records, {spare.size} spare keys")
    t = time.perf_counter()
    table = load_table(pool_path, cfg["table"], keys, vals,
                       int(cfg["load_batch"]))
    log(f"load: {keys.size} records in {time.perf_counter() - t:.2f} s, "
        f"{table.n_segments} segments")
    ref = data.Reference(keys, vals)
    undo = None
    if fault is not None:
        from . import faults
        undo = faults.apply(fault)

    gen = Generator(mix, seed, keys, spare)
    fe = DashFrontend(table, max_batch=mix.max_batch)
    loop = ClosedLoop(fe, gen, mix.clients, Op)
    t = time.perf_counter()
    # collect before the warm-up, not between it and the window, where the
    # pause would fall on the reads in flight
    gc.collect()
    warm(loop, gen, mix)
    log(f"warm-up: {loop.steps} steps in {time.perf_counter() - t:.2f} s")

    mark = clog.mark()
    if traced:
        trace_mod.start(TRACE_DIR)
    t0 = time.perf_counter()
    run.setup_s = t0 - t_start
    with TraceAnnotation("window"):
        h0, s0, w0 = loop.harvests, loop.steps, loop.steps_with_work
        loop.fill()
        t_end = loop.run_for(seconds, t0)
    if traced:
        trace_mod.stop()
    run.window_s = t_end - t0
    built = clog.since(mark)
    run.window_ops = loop.acked_ops(after=h0)
    run.steps = loop.steps - s0
    run.steps_with_work = loop.steps_with_work - w0
    log(f"window: {run.window_s:.3f} s, {len(run.window_ops)} ops "
        f"acknowledged in {run.steps} steps")
    log(f"programs built inside the window: {built['compiles']} "
        f"({built['compile_s']:.2f} s), traced: {built['traces']}"
        + (f": {', '.join(built['names'])}" if built['names'] else ""))

    loop.drain(DRAIN_S)
    unanswered = sum(len(q) for q in loop.outstanding.values())
    dur = durability_counters(fe)
    mem = (dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
    if undo is not None:
        undo()
    del fe, table
    loop.fe = None
    gc.collect()

    if traced:
        t = time.perf_counter()
        run.trace = trace_mod.reduce(trace_mod.load(TRACE_DIR))
        log(f"trace: {run.trace.window_s:.3f} s window, busy "
            f"{run.trace.busy_s:.4f} s, reduced in "
            f"{time.perf_counter() - t:.2f} s")

    t = time.perf_counter()
    writes = any(k in WRITES for k in mix.mix)
    with TraceAnnotation("check"):
        acked = loop.acked_ops()
        res = data.check_ops(ref, acked)
        lost = (durable.lost_writes(ref, pool_path,
                                    int(cfg["table"]["num_buckets"]))
                if writes else 0)
    log(f"check: {res['reads']} reads and {res['writes']} writes replayed"
        + (f", {len(ref.hist)} written keys held against the pool"
           if writes else "")
        + f", in {time.perf_counter() - t:.2f} s")
    checks = {"read_wrong": [res["read_wrong"], 0],
              "unanswered": [unanswered, 0]}
    if writes:
        checks.update(
            write_status_wrong=[res["write_status_wrong"], 0],
            durable_lost=[lost, 0],
            unflushed_publishes=[dur["unflushed_publishes"], 0],
            flush_hint_misses=[dur["flush_hint_misses"], 0])
    correct = all(v <= lim for v, lim in checks.values())

    metrics = cell.per_layer if traced else cell.end_to_end
    out_metrics = {}
    for m in metrics:
        v = m.read(run)
        if v is not None:
            out_metrics[m.name] = {"value": float(v), "unit": m.unit}
    for n in run.notes:
        log(n)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": int(mem)}
    result = {"correct": bool(correct),
              "attempted": len(acked) + unanswered,
              "failed": (res["read_wrong"] + res["write_status_wrong"]
                         + unanswered),
              "metrics": out_metrics, "device": device}
    if traced:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        result["breakdown"] = trace_mod.breakdown(run.trace)
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    for f in os.listdir(work_dir):
        os.unlink(os.path.join(work_dir, f))
    log(f"disk: this process wrote {_written_bytes() / 2**30:.3f} GiB")
    return result


def _written_bytes() -> int:
    """Bytes this process caused to be written to storage (Linux)."""
    try:
        with open("/proc/self/io") as f:
            for line in f:
                if line.startswith("write_bytes:"):
                    return int(line.split()[1])
    except OSError:
        pass
    return 0
