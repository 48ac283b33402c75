#!/usr/bin/env python3
"""Run one cell on several seeds in one process, with or without a planted
fault, and print each run's checks: the readings the limits of ``correct``
are set from.

    python bench/control.py --workload ycsb-c-zipf --seeds 1,2,3 \
        --seconds 51 --fault answer_altered

``--fault none`` runs the program as the benchmark does. Faults are listed
in ``bench/faults.py``. Without a TPU it exits non-zero.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--fault", default="none")
    args = ap.parse_args(argv)
    from bench import faults, harness, spec
    cell = spec.load_cell(args.workload)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("bench/control.py: no TPU", file=sys.stderr)
        return 2
    for seed in [int(s) for s in args.seeds.split(",")]:
        t = time.perf_counter()
        try:
            r = harness.run_cell(cell, seed, args.seconds, False, t,
                                 fault=None if args.fault == "none"
                                 else args.fault)
            line = {"seed": seed, "fault": args.fault,
                    "correct": r["correct"], "checks": r["checks"],
                    "metrics": r["metrics"]}
        except Exception as e:          # a crashed control has failed
            line = {"seed": seed, "fault": args.fault, "correct": False,
                    "error": f"{type(e).__name__}: {e}"}
        finally:
            faults.clear()
        print("control " + json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
