"""Chip benchmark of the durable Dash serving path.

``python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json``. Everything a cell needs is found by
name: its configuration in ``configs/<name>.json``, its traffic mix in
``traffic/<name>.json``, each end-to-end metric in
``end_to_end/<name>.py`` and each per-layer metric in ``metrics/<name>.py``.
"""
