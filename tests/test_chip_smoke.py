"""chip_smoke.py on the CPU: it refuses to report without a TPU, and its
phases agree with their dict reference at a small size (the single table
in-process, the sharded table on four virtual devices in a subprocess)."""
import os
import shutil
import subprocess
import sys
import textwrap

import chip_smoke

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")
SMALL = chip_smoke.Plan(max_segments=64, dir_depth_max=8, init_depth=3,
                        n_keys=12_000, load_batch=4096, check_batch=2048,
                        n_ops=64, small_batch=64, large_batch=2048,
                        shard_init_depth=1, shard_keys=8192,
                        shard_batch=2048)


def _run(cmd, cwd, env_extra=None, timeout=600):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **(env_extra or {})}
    return subprocess.run(cmd, cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=timeout)


def test_smoke_fails_without_tpu():
    r = _run([sys.executable, SCRIPT], ROOT)
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout
    assert "no TPU" in r.stderr


def test_smoke_fails_outside_checkout(tmp_path):
    shutil.copy(SCRIPT, tmp_path / "chip_smoke.py")
    r = _run([sys.executable, "chip_smoke.py"], str(tmp_path))
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout


def test_smoke_phases_single_table(tmp_path):
    """Load + serve, then reopen the unclosed pool and serve again: every
    answer matches the reference and every write is acknowledged."""
    d = str(tmp_path)
    load = chip_smoke.phase_load(SMALL, 3, d, require_tpu=False)
    assert load["mismatches"] == 0 and load["bad_status"] == 0
    assert load["splits"] > 0 and load["records"] > SMALL.n_keys
    assert load["reads_fused_batches"] and load["reads_routed_batches"]
    acked = {k: v for k, v in load["acked"]}
    again = chip_smoke.phase_reopen(SMALL, 3, d, acked, require_tpu=False)
    assert again["mismatches"] == 0 and again["bad_status"] == 0
    assert again["checked"] > SMALL.n_keys
    assert not chip_smoke._verdict({"load": load, "reopen": again})


def test_smoke_phases_sharded(tmp_path):
    """The --chips 4 phases on four virtual CPU devices, load and reopen in
    two processes like on the chip: every shard splits, and the reopen reads
    every loaded key back."""
    common = f"""
        import json, chip_smoke
        from tests.test_chip_smoke import SMALL
        d = {str(tmp_path)!r}
    """
    env = {"XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "PYTHONPATH": os.pathsep.join([os.path.join(ROOT, "src"), ROOT])}
    r = _run([sys.executable, "-c", textwrap.dedent(common + """
        out = chip_smoke.phase_shard_load(SMALL, 5, d, require_tpu=False)
        json.dump(out, open(d + "/shard_load.json", "w"))
        print("LOAD", out["mismatches"], out["bad_status"],
              len(set(out["shard_devices"])), out["splits"] > 0)
    """)], ROOT, env)
    assert r.returncode == 0, r.stderr[-4000:]
    assert "LOAD 0 0 4 True" in r.stdout
    r = _run([sys.executable, "-c", textwrap.dedent(common + """
        acked = {k: v for k, v in json.load(open(d + "/shard_load.json"))["acked"]}
        out = chip_smoke.phase_shard_reopen(SMALL, 5, d, acked,
                                            require_tpu=False)
        print("REOPEN", out["mismatches"], len(set(out["shard_devices"])),
              out["checked"] > SMALL.shard_keys)
    """)], ROOT, env)
    assert r.returncode == 0, r.stderr[-4000:]
    assert "REOPEN 0 4 True" in r.stdout
