"""Pallas kernel sweeps vs pure-jnp oracles (exact integer equality)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import DashConfig, DashEH, INSERTED
from repro.core.hashing import np_split_keys
from repro.kernels import ops, ref
from repro.kernels.hashmix import BLOCK, bulk_hash
from repro.kernels.probe import BQ, fingerprint_probe, fingerprint_probe_jnp
from tests.conftest import unique_keys


@pytest.mark.parametrize("n", [BLOCK, 4 * BLOCK, 16 * BLOCK])
@pytest.mark.parametrize("seed", [0, 1])
def test_bulk_hash_sweep(n, seed):
    rng = np.random.default_rng(seed)
    hi = jnp.asarray(rng.integers(0, 2**32, n, dtype=np.uint64).astype(np.uint32))
    lo = jnp.asarray(rng.integers(0, 2**32, n, dtype=np.uint64).astype(np.uint32))
    got = bulk_hash(hi, lo, interpret=True)
    want = ref.bulk_hash_ref(hi, lo)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("segments,capacity", [(4, BQ), (8, 2 * BQ), (16, 4 * BQ)])
@pytest.mark.parametrize("fill", [200, 2000])
def test_probe_kernel_sweep(segments, capacity, fill, rng):
    cfg = DashConfig(max_segments=segments, dir_depth_max=8)
    t = DashEH(cfg)
    keys = unique_keys(rng, fill)
    t.insert(keys, np.arange(fill, dtype=np.uint32))
    hi, lo = np_split_keys(keys[:256])
    qf, qb, qpb, qsrc, keep, segs = ops.route_queries(
        cfg, t.state, jnp.asarray(hi), jnp.asarray(lo), capacity)
    fp_pad, alloc = ops.plane_views(cfg, t.state, segs)
    rb, rp, rfb, rfp = ref.fingerprint_probe_ref(fp_pad, alloc, qf, qb, qpb)
    # both lowerings — the Pallas kernel (interpreted) and the jnp CPU path —
    # must match the oracle bit-for-bit
    for probe_fn in (functools.partial(fingerprint_probe, interpret=True),
                     fingerprint_probe_jnp):
        kb, kp, kfb, kfp = probe_fn(fp_pad, alloc, qf, qb, qpb)
        np.testing.assert_array_equal(np.asarray(kb), np.asarray(rb))
        np.testing.assert_array_equal(np.asarray(kp), np.asarray(rp))
        np.testing.assert_array_equal(np.asarray(kfb), np.asarray(rfb))
        np.testing.assert_array_equal(np.asarray(kfp), np.asarray(rfp))
    # free-slot bitmaps disjoint from the alloc bitmap of the same bucket
    qb_np, fb_np = np.asarray(qb), np.asarray(kfb)
    al = np.asarray(alloc)
    for s in range(qb_np.shape[0]):
        live = qb_np[s] >= 0
        got_alloc = al[s][np.clip(qb_np[s], 0, al.shape[1] - 1)]
        assert ((fb_np[s][live] & got_alloc[live]) == 0).all()
        np.testing.assert_array_equal(          # free = ~alloc within 14 bits
            fb_np[s][live], (~got_alloc[live]) & 0x3FFF)


def test_probe_routed_end_to_end(rng):
    cfg = DashConfig(max_segments=16, dir_depth_max=8)
    t = DashEH(cfg)
    keys = unique_keys(rng, 4000)
    vals = np.arange(4000, dtype=np.uint32)
    assert (t.insert(keys, vals) == INSERTED).all()
    hi, lo = np_split_keys(keys[:512])
    f, v, keep = ops.probe_routed(cfg, t.state, jnp.asarray(hi),
                                  jnp.asarray(lo), 256, True)
    f, v, keep = map(np.asarray, (f, v, keep))
    assert f[keep].all()
    assert (v[keep] == vals[:512][keep]).all()
    neg = np.setdiff1d(unique_keys(rng, 2000), keys)[:512]
    nh, nl = np_split_keys(neg)
    nf, _, nkeep = ops.probe_routed(cfg, t.state, jnp.asarray(nh),
                                    jnp.asarray(nl), 256, True)
    assert np.asarray(nf)[np.asarray(nkeep)].sum() == 0


def test_route_writes_hints_match_planes(rng):
    """Insert-router hints (match bits + free-slot bitmaps) come from the
    same plane views as the search path and match the oracle."""
    cfg = DashConfig(max_segments=8, dir_depth_max=7)
    t = DashEH(cfg)
    keys = unique_keys(rng, 1200)
    t.insert(keys, np.arange(1200, dtype=np.uint32))
    hi, lo = np_split_keys(keys[:256])
    hi, lo = jnp.asarray(hi), jnp.asarray(lo)
    payload = (hi, lo, jnp.zeros(256, jnp.uint32),
               jnp.zeros((256, cfg.key_heap_words), jnp.uint32),
               jnp.ones(256, jnp.bool_))
    lanes, src, keep = ops.route_writes(cfg, "eh", t.state, payload, 128)
    hints = ops.write_hints(cfg, t.state, lanes, True)
    fp_pad, alloc = ops.plane_views(
        cfg, t.state, jnp.arange(cfg.max_segments, dtype=jnp.int32))
    q_fp = (lanes["h2"] & jnp.uint32(0xFF)).astype(jnp.int32)
    q_b = jnp.where(lanes["valid"], lanes["b"], -1)
    q_pb = jnp.where(lanes["valid"], (lanes["b"] + 1) & (cfg.num_buckets - 1),
                     -1)
    want = ref.fingerprint_probe_ref(fp_pad, alloc, q_fp, q_b, q_pb)
    for got, wnt in zip(hints, want):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(wnt))
    # the inserted keys are present: every valid lane's match bits must hit
    bits = np.asarray(hints[0]) | np.asarray(hints[1])
    assert (bits[np.asarray(lanes["valid"])] != 0).all()


def test_probe_kernel_agrees_with_engine_search(rng):
    """Kernel fast path == engine slow path on the same table."""
    from repro.core import engine
    cfg = DashConfig(max_segments=8, dir_depth_max=7)
    t = DashEH(cfg)
    keys = unique_keys(rng, 1500)
    t.insert(keys, np.arange(1500, dtype=np.uint32))
    probe = np.concatenate([keys[:300], np.setdiff1d(unique_keys(rng, 1000), keys)[:200]])
    hi, lo = np_split_keys(probe)
    f1, v1 = engine.search_batch(cfg, "eh", t.state, jnp.asarray(hi), jnp.asarray(lo))
    f2, v2, keep = ops.probe_routed(cfg, t.state, jnp.asarray(hi),
                                   jnp.asarray(lo), 512, True)
    keep = np.asarray(keep)
    np.testing.assert_array_equal(np.asarray(f1)[keep], np.asarray(f2)[keep])
    hit = np.asarray(f1) & keep
    np.testing.assert_array_equal(np.asarray(v1)[hit], np.asarray(v2)[hit])


@pytest.mark.parametrize("module", ["repro.kernels.probe", "repro.kernels"])
def test_kernels_import_before_core(module):
    """``repro.kernels`` imports without ``repro.core`` loaded first (core
    reaches the kernels only from inside functions)."""
    import os
    import subprocess
    import sys
    src = os.path.join(os.path.dirname(os.path.dirname(__file__)), "src")
    r = subprocess.run([sys.executable, "-c", f"import {module}"],
                       env={**os.environ, "PYTHONPATH": src,
                            "JAX_PLATFORMS": "cpu"},
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]


def test_touched_segments_compacts_a_batch():
    """Distinct ids ascending, padded with segment 0, and each item's row
    among them (-1 for an item with no segment)."""
    seg = jnp.asarray([7, 3, 7, -1, 12, 3], jnp.int32)
    segments, rows = ops.touched_segments(seg, 5)
    np.testing.assert_array_equal(np.asarray(segments), [3, 7, 12, 0, 0])
    np.testing.assert_array_equal(np.asarray(rows), [1, 0, 1, -1, 2, 0])
