# One function per paper table/figure. Prints ``name,us_per_call,derived`` CSV.
"""Benchmark driver: ``PYTHONPATH=src python -m benchmarks.run [--only fig9]``.

Modules that emit a JSON artifact declare ``ARTIFACT``; the runner skips them
when the artifact is fresh (newer than the module source) unless ``--force``.

Modules map 1:1 to the paper's artifacts:
  fig7   single_op            per-op cost, 4 tables, fixed + var-len keys
  fig8   scalability          shard scaling + mixed workload + DHT
  fig9   fingerprint_effect   fingerprints on/off
  fig10  overflow_metadata    stash metadata on/off x stash count
  fig11  load_factor_stack    technique stack vs segment size
  fig12  load_factor_curve    load factor vs inserts, 5 schemes
  fig13  concurrency          optimistic vs pessimistic search
  table1 recovery_time        restart cost vs data size
  fig14  lazy_recovery        post-restart throughput timeline
  durable durable_restart     durable reopen ttfq + flush volume + torn crash
                              (+ JSON artifact)
  fig15  allocator            preallocated pool vs grow-on-demand
  extra  dht_roofline         256-chip DHT fabric-vs-HBM accounting
  extra  kernel_probe         Pallas probe path timing (interpret)
  extra  batch_parallel       segment-parallel vs scan engine + small-batch
                              fused-path p50/p99 latency rows — also under
                              the ``latency`` tag (+ JSON artifact)
  extra  smo                  bulk vs scalar split/merge SMOs (+ JSON artifact)
  extra  online_resize        frontend vs stop-the-world p50/p99 during a
                              split storm (+ JSON artifact)
  extra  chaos                >=200-seed fault matrix + scrub latency +
                              degraded-mode throughput (+ JSON artifact)
  extra  blackbox             flight-recorder flush overhead (<=1.05x) +
                              >=100-schedule forensics soundness matrix
                              (+ JSON artifact)
"""
from __future__ import annotations

import argparse
import importlib
import os
import sys
import time
import traceback

MODULES = [
    ("fig7", "benchmarks.single_op"),
    ("fig8", "benchmarks.scalability"),
    ("fig9", "benchmarks.fingerprint_effect"),
    ("fig10", "benchmarks.overflow_metadata"),
    ("fig11", "benchmarks.load_factor_stack"),
    ("fig12", "benchmarks.load_factor_curve"),
    ("fig13", "benchmarks.concurrency"),
    ("table1", "benchmarks.recovery_time"),
    ("fig14", "benchmarks.lazy_recovery"),
    ("durable", "benchmarks.durable_restart"),
    ("fig15", "benchmarks.allocator"),
    ("dht", "benchmarks.dht_roofline"),
    ("dhtpar", "benchmarks.dht_parallel"),
    ("kernel", "benchmarks.kernel_probe"),
    ("batchpar|latency", "benchmarks.batch_parallel"),
    ("smo", "benchmarks.smo"),
    ("resize", "benchmarks.online_resize"),
    ("chaos", "benchmarks.chaos"),
    ("blackbox", "benchmarks.blackbox"),
]


def _library_mtime() -> float:
    """Newest source mtime under the repro package — an artifact produced
    before a library change is stale even if the bench module is untouched
    (the acceptance asserts must re-run against the new code)."""
    import repro
    newest = 0.0
    for pkg_dir in repro.__path__:       # namespace package: no __file__
        for root, _, files in os.walk(pkg_dir):
            for f in files:
                if f.endswith(".py"):
                    newest = max(newest,
                                 os.path.getmtime(os.path.join(root, f)))
    return newest


def artifact_fresh(modname: str) -> bool:
    """True iff the module declares an ARTIFACT whose file is newer than
    both the module's own source and the library (re-running would just
    reproduce it)."""
    mod = importlib.import_module(modname)
    artifact = getattr(mod, "ARTIFACT", None)
    if artifact is None or not os.path.exists(artifact):
        return False
    src_mtime = max(os.path.getmtime(mod.__file__), _library_mtime())
    return os.path.getmtime(artifact) >= src_mtime


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated tags (fig7,fig9,...)")
    ap.add_argument("--force", action="store_true",
                    help="re-run benches even when their JSON artifact is fresh")
    ap.add_argument("--list", action="store_true",
                    help="list tags, modules and artifact freshness; run nothing")
    ap.add_argument("--trace", action="store_true",
                    help="capture op-lifecycle spans (obs/trace.py) in benches "
                         "that drive a frontend; writes TRACE_<bench>.json")
    args = ap.parse_args()
    if args.trace:
        os.environ["REPRO_TRACE"] = "1"
    only = set(args.only.split(",")) if args.only else None

    if args.list:
        print("tag,module,artifact,status")
        for tag, modname in MODULES:
            if only and not (set(tag.split("|")) & only):
                continue
            mod = importlib.import_module(modname)
            artifact = getattr(mod, "ARTIFACT", None)
            status = ("fresh" if artifact_fresh(modname) else "stale") \
                if artifact else "-"
            print(f"{tag},{modname},{artifact or '-'},{status}", flush=True)
        return

    from .common import enable_compilation_cache
    enable_compilation_cache()
    print("name,us_per_call,derived")
    failures = []
    for tag, modname in MODULES:
        if only and not (set(tag.split("|")) & only):
            continue
        t0 = time.time()
        try:
            if not args.force and artifact_fresh(modname):
                print(f"# {tag} skipped (artifact fresh; --force to re-run)",
                      flush=True)
                continue
            mod = __import__(modname, fromlist=["run"])
            for row in mod.run():
                print(row.csv(), flush=True)
            print(f"# {tag} done in {time.time()-t0:.0f}s", flush=True)
        except Exception as e:
            traceback.print_exc()
            failures.append((tag, repr(e)))
            print(f"{tag}/FAILED,0,{e!r}", flush=True)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
