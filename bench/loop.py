"""Closed-loop load generator for one ``DashFrontend``.

Each client keeps one operation outstanding; when the frontend
acknowledges it, the client at once submits its next draw of the mix. The latency of
an operation runs from the harness's submit to the frontend's
acknowledgement stamp (``Op.done_t``, the same ``perf_counter`` clock).
A harvest follows every ``step()``: it collects the acknowledged
operations, stamps each with the harvest index (``h_ack``) and submits
the replacements, which carry the index they were submitted after
(``h_sub``). The reference check replays operations by these indices.

Each acknowledged operation is kept as a plain tuple of numbers and
strings (``ACKED_FIELDS``), which Python's garbage collector stops
tracking, and the frontend's ``Op`` is dropped: the collector stays on
through the window and pays for the frontend's garbage alone, not for
the record the check needs.
"""
from __future__ import annotations

import time
from collections import defaultdict, deque, namedtuple
from typing import Callable, List

from jax.profiler import TraceAnnotation



ACKED_FIELDS = ("kind", "key", "value", "status", "found", "result",
                "h_sub", "h_ack", "t_sub", "done_t")
Acked = namedtuple("Acked", ACKED_FIELDS)


class LoopError(RuntimeError):
    pass


class ClosedLoop:
    def __init__(self, fe, gen, clients: int, op_factory: Callable):
        self.fe, self.gen, self.clients = fe, gen, clients
        self.Op = op_factory
        self.outstanding = defaultdict(deque)   # kind -> ops, in submit order
        self.harvests = 0
        self.acked: List[tuple] = []   # ACKED_FIELDS of every ack, in order
        self.steps_with_work = 0
        self.steps = 0

    # -- submission --------------------------------------------------------

    def submit(self, kind: str, key: int, value: int):
        op = self.Op(kind, key, value)
        op.h_sub = self.harvests
        op.t_sub = time.perf_counter()
        if not self.fe.submit(op):
            raise LoopError(f"admission refused a {kind}")
        self.outstanding[kind].append(op)
        return op

    def fill(self):
        """Give every idle client its next operation."""
        with TraceAnnotation("generate"):
            busy = sum(len(q) for q in self.outstanding.values())
            for _ in range(self.clients - busy):
                self.submit(*self.gen.draw())

    # -- one tick ----------------------------------------------------------

    def tick(self, resubmit: bool = True) -> int:
        """One ``fe.step()`` and its harvest; returns ops acknowledged."""
        with TraceAnnotation("fe.step"):
            did = self.fe.step()
        self.steps += 1
        self.steps_with_work += bool(did)
        self.harvests += 1
        h = self.harvests
        done = 0
        with TraceAnnotation("generate"):
            for q in list(self.outstanding.values()):
                while q and q[0].done_t:
                    op = q.popleft()
                    self.acked.append((
                        op.kind, op.key, op.value, op.status, op.found,
                        op.result, op.h_sub, h, op.t_sub, op.done_t))
                    done += 1
                    if resubmit:
                        self.submit(*self.gen.draw())
        return done

    def acked_ops(self, after: int = -1) -> List[Acked]:
        """The acknowledged operations of harvests after ``after``."""
        return [Acked._make(r) for r in self.acked if r[7] > after]

    def run_for(self, seconds: float, t0: float) -> float:
        """Tick until ``seconds`` have passed since ``t0``; returns the
        time of the last harvest."""
        t = time.perf_counter()
        while t - t0 < seconds:
            self.tick()
            t = time.perf_counter()
        return t

    def drain(self, timeout_s: float) -> int:
        """Tick without resubmitting until nothing is outstanding or the
        timeout passes; returns the ops still unanswered."""
        t_end = time.perf_counter() + timeout_s
        while (any(self.outstanding.values()) or self.fe.busy) \
                and time.perf_counter() < t_end:
            self.tick(resubmit=False)
        return sum(len(q) for q in self.outstanding.values())
