"""The harness finds cells, configurations, mixes and metrics by name:
an added one needs no edit to an existing file."""
import hashlib
import json
import os
import shutil

import pytest

from bench import spec
from bench.traffic import Generator, Mix


def _digest(root):
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def test_added_cell_mix_and_metric_are_found(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(spec.ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), root)
    before = _digest(root / "bench")
    # new files only
    cfg = json.loads((root / "bench/configs/dash-eh-ycsb-10m.json")
                     .read_text())
    cfg["record_count"] = 123
    (root / "bench/configs/new-config.json").write_text(json.dumps(cfg))
    (root / "bench/traffic/new-mix.json").write_text(json.dumps(
        {"clients": 8, "max_batch": 4,
         "mix": {"read": 0.75, "delete": 0.25},
         "keys": {"distribution": "uniform"}}))
    (root / "bench/metrics/new_metric.py").write_text(
        "def read(run):\n    return 42.0\n")
    # new entries in BENCHMARK.json
    b = json.loads((root / "BENCHMARK.json").read_text())
    b["configs"].append({"name": "new-config", "source": "x",
                         "file": "bench/configs/new-config.json",
                         "reduced": [], "why": "test"})
    b["workloads"].append({"name": "new-cell", "config": "new-config",
                           "traffic": "new-mix", "chips": 1, "why": "t"})
    b["per_layer"].append({"name": "new_metric", "unit": "ops",
                           "better": "higher", "source": "program_counter",
                           "layer": "frontend", "moves": "ops_per_s",
                           "workloads": ["new-cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    cell = spec.load_cell("new-cell", root=str(root))
    assert cell.config["record_count"] == 123
    assert Mix.from_json(cell.traffic).mix == {"read": 0.75,
                                               "delete": 0.25}
    assert [m.name for m in cell.per_layer][-1] == "new_metric"
    assert cell.per_layer[-1].read(None) == 42.0
    # the existing cells still load, and no existing file changed
    assert spec.load_cell("ycsb-c-zipf", root=str(root)).per_layer
    after = _digest(root / "bench")
    assert {k: after[k] for k in before} == before


def test_unknown_names_are_errors():
    with pytest.raises(spec.SpecError):
        spec.load_cell("no-such-cell")
    with pytest.raises(spec.SpecError):
        spec.metric_module("per_layer", "no_such_metric")


def test_every_entry_has_its_files():
    b = json.load(open(os.path.join(spec.ROOT, "BENCHMARK.json")))
    for w in b["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.end_to_end and cell.per_layer
        assert "setup_s" in [m.name for m in cell.end_to_end]


def test_mix_shares_and_draws_are_deterministic():
    import numpy as np
    mix = Mix.from_json({"clients": 1024, "max_batch": 256,
                         "mix": {"read": 19, "update": 1},
                         "keys": {"distribution": "zipfian",
                                  "theta": 0.99}})
    assert mix.mix == {"read": 0.95, "update": 0.05}
    keys = np.arange(1, 10001, dtype=np.uint64)
    a = Generator(mix, 2**32 + 1, keys, keys[:0]).batch("update", 500)
    b = Generator(mix, 2**32 + 1, keys, keys[:0]).batch("update", 500)
    assert a == b
    hot = sum(1 for _, k, _ in a if k == 1)
    assert hot > 10                      # rank 0 draws about 9% of keys


def test_every_operation_draws_its_kind_from_the_mix():
    import numpy as np
    mix = Mix.from_json({"clients": 8, "max_batch": 4,
                         "mix": {"read": 0.5, "update": 0.5},
                         "keys": {"distribution": "uniform"}})
    keys = np.arange(1, 101, dtype=np.uint64)
    gen = Generator(mix, 2**40 + 3, keys, keys[:0])
    kinds = [gen.draw()[0] for _ in range(4000)]
    assert 1800 < kinds.count("update") < 2200
    # kinds alternate within a run of draws, as YCSB's clients' do
    switches = sum(a != b for a, b in zip(kinds, kinds[1:]))
    assert switches > 1500
