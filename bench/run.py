#!/usr/bin/env python3
"""Run one benchmark cell of the durable Dash serving path on this
machine's TPU and print one JSON result line.

    python bench/run.py --workload ycsb-c-zipf --seed 7 --seconds 51 --trace 0

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` runs
the same window under the JAX profiler and reports its per-layer
metrics, the device's busy time and a breakdown. Every number the
correctness check compares is printed with its limit as the last lines
on standard error and under ``checks`` in the result line. Without a TPU,
or with fewer chips than the cell asks for, it exits non-zero and prints
no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    from bench import spec
    cell = spec.load_cell(args.workload)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench/run.py: no TPU (JAX found {devices[0].platform})",
              file=sys.stderr)
        return 2
    if len(devices) < cell.chips:
        print(f"bench/run.py: {cell.name} needs {cell.chips} chips, JAX "
              f"found {len(devices)}", file=sys.stderr)
        return 2
    from bench import harness
    result = harness.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), T_START)
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
