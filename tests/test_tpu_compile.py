"""Compile the read path's kernels for a described TPU v5e (no chip needed).

The TPU compiler is installed with jaxlib, and it compiles for a chip that
is described rather than attached. These tests lower every Pallas kernel of
the read path, and the two search programs at the table size
``chip_smoke.py`` serves, with ``interpret=False``: they catch block shapes
the Mosaic compiler refuses and programs that do not fit one chip's 16 GB,
which the interpret-mode tests cannot see. Nothing runs, so they say nothing
about results or times.

The topology is described inside a module-scoped fixture (never at import):
only one process at a time may load the TPU library, and every test worker
imports this file.
"""
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import engine, layout
from repro.kernels import fused, hashmix, probe

HBM_BYTES = 16 * 10**9          # one v5e chip
# the table chip_smoke.py loads (2**14 segments, ~15M record slots)
SMOKE_CFG = layout.DashConfig(max_segments=2**14, dir_depth_max=14,
                              init_depth=13)


@pytest.fixture(scope="module")
def chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # compiles for a described chip cannot be read back from the persistent
    # cache; keep it off here so the tests stay silent
    from jax.experimental.compilation_cache import compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


def _spec(chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)


def _check(compiled):
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert used < HBM_BYTES, used


def _state(chip, cfg):
    abstract = jax.eval_shape(lambda: layout.make_state(cfg, "eh"))
    return jax.tree.map(lambda x: _spec(chip, x.shape, x.dtype), abstract)


def _queries(chip, cfg, n):
    u32 = jnp.uint32
    return (_spec(chip, (n,), u32), _spec(chip, (n,), u32),
            _spec(chip, (n, cfg.key_heap_words), u32))


def test_fingerprint_probe_compiles(chip):
    S, C = 512, 4 * probe.BQ
    i32 = jnp.int32
    q = _spec(chip, (S, C), i32)
    _check(probe.fingerprint_probe.lower(
        _spec(chip, (S, probe.ROWS, probe.LANES), jnp.uint8),
        _spec(chip, (S, probe.ROWS), i32), q, q, q,
        interpret=False).compile())


def test_fused_probe_compiles(chip):
    cfg, n = SMOKE_CFG, 256
    i32, u32 = jnp.int32, jnp.uint32
    planes = jax.eval_shape(lambda st: fused.stored_views(st, True),
                            _state(chip, cfg))
    planes = jax.tree.map(lambda x: _spec(chip, x.shape, x.dtype), planes)
    q = _spec(chip, (n,), i32)
    _check(fused.fused_probe.lower(
        planes, q, q, _spec(chip, (n,), u32), _spec(chip, (n,), u32), q, q,
        nb=cfg.num_buckets, ns=cfg.num_stash, window=cfg.probe_window,
        interpret=False).compile())


def test_bulk_hash_compiles(chip):
    n = 64 * hashmix.BLOCK
    _check(hashmix.bulk_hash.lower(
        _spec(chip, (n,), jnp.uint32), _spec(chip, (n,), jnp.uint32),
        interpret=False).compile())


@pytest.mark.parametrize("n,capacity", [(65536, 128), (2048, 2048)])
def test_search_batch_routed_compiles(chip, n, capacity):
    """Large read batches: the routed fingerprint program, with the exact
    capacity the table passes and with the frontend's default."""
    cfg = SMOKE_CFG
    _check(engine._search_batch_routed.lower(
        cfg, "eh", _state(chip, cfg), *_queries(chip, cfg, n),
        capacity).compile())


@pytest.mark.parametrize("n", [1024, 256])
def test_fused_search_routed_compiles(chip, n):
    """Small read batches: the fused read program, one kernel step per
    query."""
    cfg = SMOKE_CFG
    _check(fused._fused_search_kernel.lower(
        cfg, "eh", _state(chip, cfg), *_queries(chip, cfg, n)[:2],
        False).compile())


#: an HLO instruction: ``%name = <result shape> <opcode>(<operands>)``
_INSTR = re.compile(
    r"^\s*(?:ROOT )?%(\S+) = (\S+?)(?:\{[^}]*\})? ([a-z][\w-]*)\(([^)]*)\)")


def test_fused_read_program_reads_planes_in_place(chip):
    """The fused read program at the benchmark cell's table (2**14
    segments of 64+2 buckets, 14 slots, batch 256) reads the planes where
    they lie: no instruction but a parameter or a bitcast has a shape with
    the 16,384-segment axis in a plane of two or more dimensions (no
    whole-plane relayout or staging copy), and no scatter takes more
    indices or updates than the batch holds (no scatter-back of routed
    lanes)."""
    cfg = SMOKE_CFG
    n, segs = 256, cfg.max_segments
    text = fused._fused_search_kernel.lower(
        cfg, "eh", _state(chip, cfg), *_queries(chip, cfg, n)[:2],
        False).compile().as_text()
    instrs = [m.groups() for m in map(_INSTR.match, text.splitlines()) if m]
    dims = {name: [int(d) for d in re.findall(r"\d+", shape.split("[")[-1])]
            for name, shape, _, _ in instrs}
    planes = [name for name, _, op, _ in instrs
              if op not in ("parameter", "bitcast")
              and len(dims[name]) >= 2 and segs in dims[name]]
    scatters = [name for name, _, op, args in instrs if op == "scatter"
                and max(math.prod(dims[a]) for a in
                        re.findall(r"%(\S+?)[,)]", args + ")")[1:]) > n]
    assert "tpu_custom_call" in text
    assert len(dims) > 50            # the instructions were read
    assert not planes, planes
    assert not scatters, scatters
