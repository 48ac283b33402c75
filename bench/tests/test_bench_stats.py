"""Rate and tail arithmetic of the end-to-end metrics."""
import types

import pytest

from bench import harness, spec
from bench.stats import percentile


def _op(kind, t_sub, done_t):
    return types.SimpleNamespace(kind=kind, t_sub=t_sub, done_t=done_t)


def _run(ops, window_s):
    cell = spec.load_cell("ycsb-c-zipf")
    run = harness.Run(cell=cell, seed=0, seconds=window_s)
    run.window_ops, run.window_s = ops, window_s
    return run


def _read(run, name):
    return spec.metric_module("end_to_end", name).read(run)


def test_percentile_is_nearest_rank():
    xs = list(range(1, 101))
    assert percentile(xs, 99) == 99
    assert percentile(xs, 100) == 100
    assert percentile(xs, 50) == 50
    assert percentile([7.0], 99) == 7.0
    assert percentile([], 99) is None


def test_rate_counts_every_acknowledged_op_over_the_window():
    ops = [_op("read", i * 0.01, i * 0.01 + 0.002) for i in range(300)]
    ops += [_op("update", i * 0.01, i * 0.01 + 0.004) for i in range(100)]
    run = _run(ops, 4.0)
    assert _read(run, "ops_per_s") == pytest.approx(100.0)
    assert _read(run, "read_p99_ms") == pytest.approx(2.0)


def test_a_stall_moves_the_tail_and_the_rate():
    steady = [_op("read", i * 0.01, i * 0.01 + 0.002) for i in range(1000)]
    base = _run(steady, 10.0)
    # the same window with a 0.5 s stall: the reads caught in it wait, and
    # fewer operations complete
    stalled = [_op("read", i * 0.01, i * 0.01 + 0.002) for i in range(950)]
    stalled[500:520] = [_op("read", 5.0 + i * 0.001, 5.5)
                        for i in range(20)]
    hit = _run(stalled, 10.0)
    assert _read(hit, "read_p99_ms") > 100 * _read(base, "read_p99_ms")
    assert _read(hit, "ops_per_s") < _read(base, "ops_per_s")


def test_no_reads_means_no_read_tail():
    run = _run([_op("insert", 0.0, 0.001)], 1.0)
    assert _read(run, "read_p99_ms") is None
    assert _read(run, "ops_per_s") == pytest.approx(1.0)
