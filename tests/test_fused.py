"""Differential tests for the fused small-batch latency path (kernels/fused.py).

The fused mega-dispatch (route -> probe -> commit in ONE jitted call) is what
the table planner selects for batches at or under ``DashTable.fused_threshold``,
so its correctness contract is bit-identity with the reference engines on any
fill: ``fused_insert`` == the scan engine (table state + statuses + stash
activation) and ``fused_search`` == the per-key vmap path (found + values),
across the feature-flag matrix (balanced / displacement / fingerprints /
overflow-metadata / stash ablations), LH addressing, pointer mode, padding
(valid) masks, in-batch duplicate keys, stash overflow and NEED_SPLIT
pressure. The Pallas mega-kernel and its jnp lowering are differentially
checked against each other and the vmap reference too.
"""
import dataclasses as dc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import DashConfig, DashEH, engine, hashing, layout
from repro.kernels import fused
from tests._hypothesis_compat import given, settings, st
from tests.conftest import unique_keys

B = 64            # one jit trace per (cfg, op) pair

#: feature-flag matrix — every ablation the fused commit mirrors branch-free
CONFIGS = {
    "default": DashConfig(max_segments=8, dir_depth_max=6, init_depth=1),
    "no_disp": DashConfig(max_segments=8, dir_depth_max=6, init_depth=1,
                          use_displacement=False),
    "no_fp": DashConfig(max_segments=8, dir_depth_max=6, init_depth=1,
                        use_fingerprints=False),
    "no_ometa": DashConfig(max_segments=8, dir_depth_max=6, init_depth=1,
                           use_overflow_meta=False),
    "no_stash": DashConfig(max_segments=8, dir_depth_max=6, init_depth=1,
                           num_stash=0),
    "no_ofp": DashConfig(max_segments=8, dir_depth_max=6, init_depth=1,
                         num_ofp=0),
    "small_buckets": DashConfig(max_segments=8, dir_depth_max=6,
                                init_depth=1, num_buckets=16, num_slots=8),
}


def _diverged(sa, sb):
    return [name for name, a, b in zip(sa._fields, jax.tree.leaves(sa),
                                       jax.tree.leaves(sb))
            if not (np.asarray(a) == np.asarray(b)).all()]


def _keys(rng, n):
    ks = unique_keys(rng, n)
    hi, lo = hashing.np_split_keys(ks)
    return jnp.asarray(hi), jnp.asarray(lo)


def _check_search(cfg, mode, state, hi, lo):
    f_v, v_v = engine.search_batch(cfg, mode, state, hi, lo, batching="vmap")
    f_f, v_f = engine.search_batch(cfg, mode, state, hi, lo, batching="fused")
    assert (np.asarray(f_v) == np.asarray(f_f)).all()
    assert (np.asarray(v_v) == np.asarray(v_f)).all()


def _drive(cfg, mode, rng, rounds=4, mask_round=2):
    """Fill a tiny table through both engines round by round; the small
    geometry reaches stash overflow and NEED_SPLIT within a few batches."""
    st_scan = layout.make_state(cfg, mode)
    st_fus = jax.tree.map(jnp.copy, st_scan)
    hi_all, lo_all = _keys(rng, rounds * B)
    saw_split = saw_stash = False
    for r in range(rounds):
        hi, lo = hi_all[r * B:(r + 1) * B], lo_all[r * B:(r + 1) * B]
        # in-batch duplicates: repeat a quarter of the lanes
        hi = hi.at[B // 2:B // 2 + B // 4].set(hi[:B // 4])
        lo = lo.at[B // 2:B // 2 + B // 4].set(lo[:B // 4])
        vals = jnp.asarray(rng.integers(1, 2**32, B).astype(np.uint32))
        valid = jnp.asarray(np.arange(B) < B // 2) if r == mask_round else None
        st_scan, s1, a1 = engine.insert_batch(
            cfg, mode, st_scan, hi, lo, vals, None, valid, batching="scan")
        st_fus, s2, a2 = engine.insert_batch(
            cfg, mode, st_fus, hi, lo, vals, None, valid, batching="fused")
        assert (np.asarray(s1) == np.asarray(s2)).all(), r
        assert bool(a1) == bool(a2), r
        bad = _diverged(st_scan, st_fus)
        assert not bad, (r, bad)
        saw_split |= bool((np.asarray(s1) == layout.NEED_SPLIT).any())
        if cfg.num_stash:             # records actually landed in stash rows
            stash_alloc = layout.meta_alloc(
                jnp.asarray(np.asarray(st_scan.meta)[:, cfg.num_buckets:]))
            saw_stash |= bool((np.asarray(stash_alloc) != 0).any())
        # read paths agree on the (identical) state, hits and misses both
        _check_search(cfg, mode, st_scan, hi, lo)
    miss_hi, miss_lo = _keys(np.random.default_rng(999), B)
    _check_search(cfg, mode, st_scan, miss_hi, miss_lo)
    return saw_split, saw_stash


def test_fused_matches_scan_across_feature_matrix():
    for name, cfg in CONFIGS.items():
        rng = np.random.default_rng(abs(hash(name)) % 2**32)
        _drive(cfg, "eh", rng)


def test_fused_matches_scan_under_pressure():
    """Drive the small geometry past capacity: stash activation and
    NEED_SPLIT pressure must actually occur AND stay bit-identical."""
    cfg = CONFIGS["small_buckets"]
    saw_split, saw_stash = _drive(cfg, "eh", np.random.default_rng(0xE0),
                                  rounds=8, mask_round=5)
    assert saw_split and saw_stash


def test_fused_matches_scan_under_lh_mode():
    cfg = DashConfig(max_segments=32, num_stash=4, lh_base_log2=2)
    _drive(cfg, "lh", np.random.default_rng(0x1A))


def test_fused_search_pointer_mode():
    """Pointer mode: query identity folds the full key words, and the probe
    dereferences heap handles — the fused gather must match vmap on both
    hit and miss lanes. (Fused INSERT is ineligible in pointer mode and
    falls back to the scan engine inside fused_insert — also checked.)"""
    cfg = DashConfig(max_segments=16, dir_depth_max=8, pointer_mode=True,
                     key_heap_size=4096, key_heap_words=3)
    rng = np.random.default_rng(0xF0)
    state = layout.make_state(cfg, "eh")
    words = jnp.asarray(
        rng.integers(1, 2**32, (2 * B, cfg.key_heap_words)).astype(np.uint32))
    vals = jnp.asarray(np.arange(2 * B, dtype=np.uint32) + 1)
    hi, lo = hashing.key_identity_from_words(words)
    state, s1, _ = engine.insert_batch(cfg, "eh", state, hi, lo, vals,
                                       words, batching="scan")
    st2 = jax.tree.map(jnp.copy, layout.make_state(cfg, "eh"))
    st2, s2, _ = engine.insert_batch(cfg, "eh", st2, hi, lo, vals,
                                     words, batching="fused")
    assert (np.asarray(s1) == np.asarray(s2)).all()
    assert not _diverged(state, st2)
    # hits: same words; misses: fresh words never inserted
    miss = jnp.asarray(
        rng.integers(1, 2**32, (B, cfg.key_heap_words)).astype(np.uint32))
    for w in (words[:B], miss):
        qh, ql = hashing.key_identity_from_words(w)
        f_v, v_v = engine.search_batch(cfg, "eh", state, qh, ql, words=w,
                                       batching="vmap")
        f_f, v_f = engine.search_batch(cfg, "eh", state, qh, ql, words=w,
                                       batching="fused")
        assert (np.asarray(f_v) == np.asarray(f_f)).all()
        assert (np.asarray(v_v) == np.asarray(v_f)).all()


def test_fused_kernel_matches_lowering_and_vmap():
    """The Pallas mega-kernel (interpret mode on CPU) and its jnp lowering
    must agree lane-for-lane, and both must agree with the per-key vmap
    reference on every kept (routed) lane."""
    cfg = DashConfig(max_segments=8, dir_depth_max=6, init_depth=1)
    rng = np.random.default_rng(0xCAFE)
    state = layout.make_state(cfg, "eh")
    hi, lo = _keys(rng, 256)
    vals = jnp.asarray(np.arange(256, dtype=np.uint32) + 1)
    state, _, _ = engine.insert_batch(cfg, "eh", state, hi, lo, vals,
                                      batching="scan")
    # queries: half hits, half misses
    mh, ml = _keys(np.random.default_rng(7), 128)
    qhi = jnp.concatenate([hi[:128], mh])
    qlo = jnp.concatenate([lo[:128], ml])

    from repro.kernels import ops
    h1 = hashing.hash1(qhi, qlo)
    h2 = hashing.hash2(qhi, qlo)
    fpv = (h2 & jnp.uint32(0xFF)).astype(jnp.int32)
    seg, b = ops.locate_batch(cfg, "eh", state, h1)
    NB = cfg.num_buckets
    capacity = 256                      # BQ-aligned
    lanes, src, keep = ops.route_lanes(
        seg, (fpv, b.astype(jnp.int32), qhi, qlo, seg >= 0),
        cfg.max_segments, capacity, (0, -1, 0, 0, False))
    q_fp, q_b, q_hi, q_lo, q_valid = lanes
    q_b = jnp.where(q_valid, q_b, -1)
    q_pb = jnp.where(q_valid, (q_b + 1) & (NB - 1), -1)
    q_fp = jnp.where(q_valid, q_fp, -1)
    planes = fused.fused_plane_views(
        cfg, state, jnp.arange(cfg.max_segments, dtype=jnp.int32))
    f_k, v_k = fused.fused_probe(planes, q_fp, q_b, q_pb, q_hi, q_lo,
                                 nb=NB, ns=cfg.num_stash, interpret=True)
    f_j, v_j = fused.fused_probe_jnp(planes, q_fp, q_b, q_pb, q_hi, q_lo,
                                     nb=NB, ns=cfg.num_stash)
    assert (np.asarray(f_k) == np.asarray(f_j)).all()
    assert (np.asarray(v_k) == np.asarray(v_j)).all()
    # scatter back and compare with vmap on kept lanes
    f_ref, v_ref = engine.search_batch(cfg, "eh", state, qhi, qlo,
                                       batching="vmap")
    flatf, flatv = np.asarray(f_j).reshape(-1), np.asarray(v_j).reshape(-1)
    srcf = np.asarray(src).reshape(-1)
    keep_np = np.asarray(keep)
    got_f = np.zeros(qhi.shape[0], bool)
    got_v = np.zeros(qhi.shape[0], np.uint32)
    m = srcf >= 0
    got_f[srcf[m]] = flatf[m] != 0
    got_v[srcf[m]] = flatv[m]
    assert (got_f[keep_np] == np.asarray(f_ref)[keep_np]).all()
    assert (got_v[keep_np] == np.asarray(v_ref)[keep_np]).all()


def _routed_case(case):
    """(cfg, state, hi, lo, capacity) for one ``_fused_search_routed`` case."""
    from repro.kernels import ops
    cfg = CONFIGS["no_fp" if case == "no_fp" else "default"]
    rng = np.random.default_rng(0x5EA)
    hi, lo = _keys(rng, 1024)
    state, st, _ = engine.insert_batch(
        cfg, "eh", layout.make_state(cfg, "eh"), hi[:768], lo[:768],
        jnp.arange(1, 769, dtype=jnp.uint32), batching="scan")
    # hits, then misses (keys 768.. were never inserted)
    q_hi, q_lo = hi[256:1024], lo[256:1024]
    seg, _ = ops.locate_batch(cfg, "eh", state,
                              hashing.hash1(q_hi, q_lo))
    seg = np.asarray(seg)
    if case == "padded":
        # few touched segments, each repeated, in a batch wider than the
        # segment count: touched_segments pads its rows with segment 0
        one = np.flatnonzero(seg == seg.max())
        pick = np.concatenate([one[one < 512][:16], one[one >= 512][:8]])
        pick = np.concatenate([pick, pick[:8], np.arange(8)])
        q_hi, q_lo = q_hi[pick], q_lo[pick]
        assert np.unique(seg[pick]).size < min(pick.size, cfg.max_segments)
        return cfg, state, q_hi, q_lo, 128
    if case == "overflow":
        capacity = 128
        assert np.bincount(seg).max() > capacity    # the cond fallback runs
        return cfg, state, q_hi, q_lo, capacity
    return cfg, state, q_hi, q_lo, 1024


@pytest.mark.parametrize("case", ["no_fp", "overflow", "padded"])
def test_fused_search_routed_matches_vmap(case):
    """The TPU read program (touched-segment routing, -1 padding lanes,
    capacity-overflow fallback) with the kernel in the Pallas interpreter
    returns exactly what the per-key vmap path returns."""
    cfg, state, q_hi, q_lo, capacity = _routed_case(case)
    words = jnp.zeros((q_hi.shape[0], cfg.key_heap_words), jnp.uint32)
    f_r, v_r = fused._fused_search_routed(cfg, "eh", state, q_hi, q_lo,
                                          words, capacity, True)
    f_v, v_v = engine.search_batch(cfg, "eh", state, q_hi, q_lo,
                                   batching="vmap")
    assert np.asarray(f_v).any() and not np.asarray(f_v).all()
    assert (np.asarray(f_r) == np.asarray(f_v)).all()
    assert (np.asarray(v_r) == np.asarray(v_v)).all()


OPS = st.lists(st.sampled_from(["ins", "mask", "dup"]), min_size=1,
               max_size=5)


@given(OPS)
@settings(max_examples=4, deadline=None)
def test_fused_randomized_fills(ops):
    """Hypothesis-style op mixes: fused vs scan stay bit-identical through
    arbitrary insert/mask/duplicate sequences, reads checked every step."""
    cfg = DashConfig(max_segments=8, dir_depth_max=6, init_depth=1)
    rng = np.random.default_rng(abs(hash(tuple(ops))) % 2**32)
    keyspace = np.unique(rng.integers(1, 2**63, 500, dtype=np.uint64))
    st_scan = layout.make_state(cfg, "eh")
    st_fus = jax.tree.map(jnp.copy, st_scan)
    for step, op in enumerate(ops):
        ks = keyspace[rng.integers(0, keyspace.size, B)]
        if op == "dup":               # heavy duplication inside one batch
            ks = np.repeat(ks[:B // 8], 8)[:B]
        hi, lo = hashing.np_split_keys(ks)
        hi, lo = jnp.asarray(hi), jnp.asarray(lo)
        vals = jnp.asarray(rng.integers(1, 2**32, B).astype(np.uint32))
        valid = jnp.asarray(rng.random(B) < 0.6) if op == "mask" else None
        st_scan, s1, a1 = engine.insert_batch(
            cfg, "eh", st_scan, hi, lo, vals, None, valid, batching="scan")
        st_fus, s2, a2 = engine.insert_batch(
            cfg, "eh", st_fus, hi, lo, vals, None, valid, batching="fused")
        assert (np.asarray(s1) == np.asarray(s2)).all(), (step, op)
        assert bool(a1) == bool(a2)
        bad = _diverged(st_scan, st_fus)
        assert not bad, (step, op, bad)
        _check_search(cfg, "eh", st_scan, hi, lo)


def test_table_planner_selects_fused():
    """The table routes small batches to the fused path and the threshold
    knob forces either side; end-to-end results are identical."""
    cfg = DashConfig(max_segments=32, dir_depth_max=8, init_depth=1)
    rng = np.random.default_rng(3)
    keys = unique_keys(rng, 2000)
    vals = np.arange(2000, dtype=np.uint32)
    t_fused = DashEH(cfg)                       # default threshold: fused
    t_off = DashEH(cfg, fused_threshold=0)      # forced routed/scan
    hi, lo = hashing.np_split_keys(keys[:256])
    seg = t_fused._segments_of(hi, lo)
    assert t_fused._write_plan(seg, 256)[0] == "fused"
    assert t_fused._search_plan(seg)[0] == "fused"
    assert t_off._write_plan(seg, 256)[0] != "fused"
    assert t_off._search_plan(seg)[0] != "fused"
    # delete/update never take the fused path (no fused engine for them)
    assert t_fused._write_plan(seg, 256, fused_ok=False)[0] != "fused"
    s1 = t_fused.insert(keys, vals)
    s2 = t_off.insert(keys, vals)
    assert (s1 == s2).all()
    assert not _diverged(t_fused.state, t_off.state)
    f1, v1 = t_fused.search(keys)
    f2, v2 = t_off.search(keys)
    assert f1.all() and (np.asarray(f1) == np.asarray(f2)).all()
    assert (np.asarray(v1) == np.asarray(v2)).all()
