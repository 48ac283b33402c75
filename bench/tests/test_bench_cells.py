"""Each cell's harness end to end at a tiny configuration on the CPU:
set-up, warm-up, the closed-loop window, the reference check."""
import gc
import json
import os

import pytest

from bench import spec

from .conftest import run_tiny

CELLS = [w["name"] for w in json.load(open(os.path.join(
    spec.ROOT, "BENCHMARK.json")))["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_cell_runs_correct_at_tiny_size(name):
    r = run_tiny(name)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0
    cell = spec.load_cell(name)
    want = {m.name for m in cell.end_to_end}
    assert set(r["metrics"]) == want, (set(r["metrics"]), want)
    assert r["metrics"]["ops_per_s"]["value"] > 0
    assert list(r)[-1] == "checks"
    assert all(c["value"] == 0 and c["limit"] == 0
               for c in r["checks"].values())


def test_the_record_of_acknowledged_ops_is_not_tracked_by_the_collector():
    from bench.loop import ClosedLoop

    class Op:
        def __init__(self, kind, key, value):
            self.kind, self.key, self.value = kind, key, value
            self.status, self.found, self.result = 0, True, 7
            self.done_t = 0.0

    class Frontend:
        busy = False

        def __init__(self):
            self.q = []

        def submit(self, op):
            self.q.append(op)
            return True

        def step(self):
            for op in self.q:
                op.done_t = 1.0
            self.q = []
            return True

    class Gen:
        def draw(self):
            return ("read", 5, 0)

    loop = ClosedLoop(Frontend(), Gen(), 8, Op)
    loop.fill()
    for _ in range(3):
        loop.tick()
    gc.collect()
    assert len(loop.acked) == 24
    assert not any(gc.is_tracked(r) for r in loop.acked)
    assert [a.h_ack for a in loop.acked_ops(after=2)] == [3] * 8
