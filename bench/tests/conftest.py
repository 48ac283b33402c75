"""Tiny cells of the benchmark for CPU tests: the configurations and mixes
of ``BENCHMARK.json`` with the table cut to 32 segments and the mix to 64
clients in batches of 16."""
import tempfile
import time

from bench import harness, spec


def tiny_cell(name: str) -> spec.Cell:
    cell = spec.load_cell(name)
    table = cell.config["table"]
    table.update(max_segments=32, dir_depth_max=5, init_depth=5)
    slots = 32 * (table["num_buckets"] + table["num_stash"]) \
        * table["num_slots"]
    cell.config["record_count"] = int(slots * 0.66)
    cell.config["load_batch"] = cell.config["record_count"] // 2 + 1
    cell.traffic.update(clients=64, max_batch=16, warmup_ticks=4)
    return cell


def run_tiny(name: str, seed: int = 2**31 + 11, seconds: float = 1.0,
             fault=None) -> dict:
    """One run of a tiny cell, with its pool files in a directory of its
    own (test workers run side by side)."""
    from bench import faults
    with tempfile.TemporaryDirectory(prefix="bench-tiny-") as work:
        try:
            return harness.run_cell(tiny_cell(name), seed, seconds, False,
                                    time.perf_counter(), fault=fault,
                                    work_dir=work)
        finally:
            faults.clear()
