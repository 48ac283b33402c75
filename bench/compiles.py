"""Count the programs JAX builds (traces, and compiles or cache loads) and
name them, so a run can say how many were built inside its window."""
from __future__ import annotations

import jax

TRACE = "/jax/core/compile/jaxpr_trace_duration"
COMPILE = "/jax/core/compile/backend_compile_duration"


_LOG = None


def compile_log() -> "CompileLog":
    """The process's one listener."""
    global _LOG
    if _LOG is None:
        _LOG = CompileLog()
    return _LOG


class CompileLog:
    def __init__(self):
        self.events = []              # (event, fun_name, seconds)
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event in (TRACE, COMPILE):
            self.events.append((event, str(kw.get("fun_name", "?")),
                                float(duration)))

    def mark(self) -> int:
        return len(self.events)

    def since(self, mark: int) -> dict:
        ev = self.events[mark:]
        return {"traces": sum(e[0] == TRACE for e in ev),
                "compiles": sum(e[0] == COMPILE for e in ev),
                "compile_s": sum(e[2] for e in ev if e[0] == COMPILE),
                "names": sorted({e[1] for e in ev if e[0] == COMPILE})}


def enable_cache(path: str):
    """JAX's persistent compilation cache at a fixed directory, caching
    every program however quick to build."""
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
