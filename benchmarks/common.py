"""Shared benchmark harness: timing, key generation, CSV emission.

Every module exposes ``run() -> list[Row]``; benchmarks.run prints
``name,us_per_call,derived`` CSV (one row per measured configuration).
Sizes are tuned for the 1-core CPU container: the numbers demonstrate the
paper's RELATIVE effects (fingerprint speedups, load-factor stacks, O(1)
recovery); absolute Mops/s belongs to the TPU deployment.
"""
from __future__ import annotations

import dataclasses
import os
import time
from typing import Callable, Optional

import numpy as np

# ---------------------------------------------------------------------------
# persistent compilation cache (cold compiles count against every run; the
# cached executables amortize them across processes and runs)
#
# On by default. An older jaxlib (0.4.36, CPU) mishandled buffer donation in
# executables deserialized from this cache, so it used to be opt-in. On the
# installed JAX 0.9 the differential that caught it (tests/
# test_batch_parallel.py, scan vs segment engines) passes on a cold and on
# two warm runs of one cache directory.
# ---------------------------------------------------------------------------

_CACHE_STATS = {"hits": 0, "misses": 0}
_cache_enabled = False

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", ".jax_cache"))


def _cache_listener(event: str, **kwargs):
    if event == "/jax/compilation_cache/cache_hits":
        _CACHE_STATS["hits"] += 1
    elif event == "/jax/compilation_cache/cache_misses":
        _CACHE_STATS["misses"] += 1


def enable_compilation_cache() -> str:
    """Idempotent: turn JAX's persistent compilation cache on and start
    counting hits/misses. Call before the first jit dispatch; benches record
    ``cache_stats()`` in their JSON artifacts so a compile-dominated run is
    visible.

    The directory is ``$JAX_COMPILATION_CACHE_DIR`` when that is set (JAX
    reads it itself, and no other directory is set here), else the
    checkout's ``.jax_cache/`` (gitignored): a fixed path, since the path is
    part of what a cache entry is found by."""
    global _cache_enabled
    import jax
    if not _cache_enabled:
        if not os.environ.get(CACHE_DIR_ENV):
            jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
        # tiny kernels dominate this repo: cache everything, not just slow builds
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        jax.monitoring.register_event_listener(_cache_listener)
        _cache_enabled = True
    return jax.config.jax_compilation_cache_dir


def cache_stats() -> dict:
    """Persistent-cache state + hit/miss counters (artifact field)."""
    return {"enabled": _cache_enabled, **_CACHE_STATS}


def provenance() -> dict:
    """Run provenance stamped into every ``BENCH_*.json`` artifact: git SHA
    (+dirty marker), jax/jaxlib versions, device kind, and a timestamp — so
    the perf trajectory across PRs is attributable to a code state and a
    substrate."""
    import subprocess
    import jax
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    def _git(*args):
        try:
            return subprocess.run(("git",) + args, cwd=root, text=True,
                                  capture_output=True, timeout=10
                                  ).stdout.strip()
        except Exception:
            return ""
    try:
        import jaxlib
        jaxlib_v = jaxlib.__version__
    except Exception:          # pragma: no cover
        jaxlib_v = ""
    dev = jax.devices()[0]
    return {
        "git_sha": _git("rev-parse", "HEAD") or "unknown",
        "git_dirty": bool(_git("status", "--porcelain")),
        "jax": jax.__version__,
        "jaxlib": jaxlib_v,
        "backend": jax.default_backend(),
        "device_kind": getattr(dev, "device_kind", str(dev)),
        "device_count": jax.device_count(),
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S%z"),
    }


def write_artifact(path: str, report: dict):
    """One artifact writer for every bench: stamps ``provenance`` and the
    compilation-cache counters, then writes pretty JSON."""
    import json
    report.setdefault("provenance", provenance())
    report.setdefault("compilation_cache", cache_stats())
    with open(path, "w") as f:
        json.dump(report, f, indent=2)


@dataclasses.dataclass
class Row:
    name: str
    us_per_call: float
    derived: str = ""

    def csv(self) -> str:
        return f"{self.name},{self.us_per_call:.3f},{self.derived}"


def unique_keys(rng: np.random.Generator, n: int) -> np.ndarray:
    out = np.unique(rng.integers(1, 2**63, size=int(n * 2.2) + 16,
                                 dtype=np.uint64))
    assert out.size >= n
    return out[:n]


def time_op(fn: Callable[[], object], repeats: int = 3,
            warmup: int = 1) -> float:
    """Median wall seconds of fn() (fn must block on device results)."""
    for _ in range(warmup):
        fn()
    ts = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def ops_row(name: str, seconds: float, n_ops: int, extra: str = "") -> Row:
    us = seconds / n_ops * 1e6
    mops = n_ops / seconds / 1e6
    derived = f"{mops:.3f} Mops/s"
    if extra:
        derived += f"; {extra}"
    return Row(name, us, derived)


# ---------------------------------------------------------------------------
# observability hooks (obs/): every bench artifact carries histogram rows;
# `run.py --trace` (or REPRO_TRACE=1) additionally captures op-lifecycle
# spans and drops a TRACE_<bench>.json next to the artifact
# ---------------------------------------------------------------------------

def trace_enabled() -> bool:
    from repro.obs import trace_enabled_from_env
    return trace_enabled_from_env()


def histogram_rows(obs, prefix: str = "") -> dict:
    """The registry's histogram snapshots (n/p50/p90/p99/max per name) in
    artifact shape — stamp under a ``"histograms"`` key."""
    return obs.registry.histogram_rows(prefix)


def export_trace(obs, name: str) -> Optional[str]:
    """Write the tracer ring as ``TRACE_<name>.json`` next to the bench
    artifacts when tracing is on; returns the path (None when disabled or
    nothing was recorded)."""
    tracer = obs.tracer
    if not tracer.enabled or tracer.recorded == 0:
        return None
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..",
                        f"TRACE_{name}.json")
    path = os.path.abspath(path)
    tracer.export_chrome_trace(path)
    return path
