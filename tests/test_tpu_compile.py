"""Compile the read path's kernels for a described TPU v5e (no chip needed).

The TPU compiler is installed with jaxlib, and it compiles for a chip that
is described rather than attached. These tests lower every Pallas kernel of
the read path, and the two routed search programs at the table size
``chip_smoke.py`` serves, with ``interpret=False``: they catch block shapes
the Mosaic compiler refuses and programs that do not fit one chip's 16 GB,
which the interpret-mode tests cannot see. Nothing runs, so they say nothing
about results or times.

The topology is described inside a module-scoped fixture (never at import):
only one process at a time may load the TPU library, and every test worker
imports this file.
"""
import os

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
    U, C = 256, 2 * fused.BQ
    i32, u32 = jnp.int32, jnp.uint32
    q = _spec(chip, (U, C), i32)
    _check(fused.fused_probe.lower(
        _spec(chip, (U, fused.FEATS, fused.ROWS), i32), q, q, q,
        _spec(chip, (U, C), u32), _spec(chip, (U, C), u32),
        nb=64, ns=2, interpret=False).compile())


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


@pytest.mark.parametrize("n,capacity", [(1024, 128), (256, 256)])
def test_fused_search_routed_compiles(chip, n, capacity):
    """Small read batches: the fused mega-kernel program over the segments
    the batch touches."""
    cfg = SMOKE_CFG
    _check(fused._fused_search_routed.lower(
        cfg, "eh", _state(chip, cfg), *_queries(chip, cfg, n),
        capacity, False).compile())
