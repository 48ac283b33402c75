"""Differential tests for the fused small-batch latency path (kernels/fused.py).

The fused mega-dispatch (route -> probe -> commit in ONE jitted call) is what
the table planner selects for batches at or under ``DashTable.fused_threshold``,
so its correctness contract is bit-identity with the reference engines on any
fill: ``fused_insert`` == the scan engine (table state + statuses + stash
activation) and ``fused_search`` == the per-key vmap path (found + values),
across the feature-flag matrix (balanced / displacement / fingerprints /
overflow-metadata / stash ablations), LH addressing, pointer mode, padding
(valid) masks, in-batch duplicate keys, stash overflow and NEED_SPLIT
pressure. The per-query Pallas read kernel and its jnp lowering are
differentially checked against each other and the vmap reference too.
"""
import dataclasses as dc
import functools

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


#: cases of the per-query read kernel: (config, mode, keys inserted)
KERNEL_CASES = {
    "eh": (CONFIGS["default"], "eh", 900),
    "no_fp": (CONFIGS["no_fp"], "eh", 900),
    "lh": (DashConfig(max_segments=32, num_stash=4, lh_base_log2=2), "lh",
           900),
    "stash": (CONFIGS["small_buckets"], "eh", 300),
    "hot": (CONFIGS["default"], "eh", 900),
}


@functools.lru_cache(maxsize=None)
def _kernel_case(case):
    """(cfg, mode, state, q_hi, q_lo) for one case of the read kernel."""
    cfg, mode, n = KERNEL_CASES[case]
    rng = np.random.default_rng(0x5EA)
    hi, lo = _keys(rng, n + 64)
    state, _, _ = engine.insert_batch(
        cfg, mode, layout.make_state(cfg, mode), hi[:n], lo[:n],
        jnp.arange(1, n + 1, dtype=jnp.uint32), batching="scan")
    zeros = jnp.zeros(8, jnp.uint32)
    if case == "stash":
        # every inserted key, so the records in stash rows are read too;
        # 300 queries: the last output row is partly padding
        stash_alloc = np.asarray(state.meta)[:, cfg.num_buckets:] & 0x3FFF
        assert stash_alloc.any()
        return cfg, mode, state, hi[:n], lo[:n]
    if case == "hot":
        # eight hot keys 24 times each, misses, zero-key padding lanes
        pick = np.concatenate([np.repeat(np.arange(8), 24),
                               np.arange(n, n + 56)])
        return (cfg, mode, state, jnp.concatenate([hi[pick], zeros]),
                jnp.concatenate([lo[pick], zeros]))
    # hits, misses (keys n.. were never inserted), zero-key padding lanes
    pick = np.arange(n - 192, n + 56)
    return (cfg, mode, state, jnp.concatenate([hi[pick], zeros]),
            jnp.concatenate([lo[pick], zeros]))


def _assert_matches_vmap(case, found, vals):
    cfg, mode, state, q_hi, q_lo = _kernel_case(case)
    f_v, v_v = engine.search_batch(cfg, mode, state, q_hi, q_lo,
                                   batching="vmap")
    assert np.asarray(f_v).any()
    assert case == "stash" or not np.asarray(f_v).all()
    assert (np.asarray(found) == np.asarray(f_v)).all()
    assert (np.asarray(vals) == np.asarray(v_v)).all()


@pytest.mark.parametrize("case", sorted(KERNEL_CASES))
def test_fused_kernel_matches_lowering_and_vmap(case):
    """The per-query Pallas kernel (interpret mode on CPU) and its jnp
    lowering agree lane for lane on the table's stored-layout views, and
    both agree with the per-key vmap reference."""
    cfg, mode, state, q_hi, q_lo = _kernel_case(case)
    seg, b = engine.locate(cfg, mode, state, hashing.hash1(q_hi, q_lo))
    args = (fused.stored_views(state, cfg.use_fingerprints), seg, b, q_hi,
            q_lo, hashing.fingerprint(hashing.hash2(q_hi, q_lo)).astype(
                jnp.int32), state.stash_active[seg])
    kw = dict(nb=cfg.num_buckets, ns=cfg.num_stash, window=cfg.probe_window)
    f_k, v_k = fused.fused_probe(*args, **kw, interpret=True)
    f_j, v_j = fused.fused_probe_jnp(*args, **kw)
    assert (np.asarray(f_k) == np.asarray(f_j)).all()
    assert (np.asarray(v_k) == np.asarray(v_j)).all()
    _assert_matches_vmap(case, np.asarray(f_j) != 0, v_j)


@pytest.mark.parametrize("case", sorted(KERNEL_CASES))
def test_fused_search_kernel_matches_vmap(case):
    """The TPU read program — hash, locate, the per-query kernel in the
    Pallas interpreter — returns exactly what the per-key vmap path
    returns, in query order."""
    cfg, mode, state, q_hi, q_lo = _kernel_case(case)
    assert fused.fused_kernel_eligible(cfg)
    f_r, v_r = fused._fused_search_kernel(cfg, mode, state, q_hi, q_lo, True)
    _assert_matches_vmap(case, f_r, v_r)


def test_fused_search_kernel_splits_large_batches(monkeypatch):
    """A batch over ``MAX_QUERIES`` runs as several kernel calls (their
    prefetched scalars must fit the scalar memory); answers stay in query
    order and equal the vmap path's."""
    monkeypatch.setattr(fused, "MAX_QUERIES", 128)
    cfg, mode, state, q_hi, q_lo = _kernel_case("stash")
    q_hi, q_lo = q_hi[:290], q_lo[:290]       # a shape no other test traced
    f_r, v_r = fused._fused_search_kernel(cfg, mode, state, q_hi, q_lo, True)
    f_v, v_v = engine.search_batch(cfg, mode, state, q_hi, q_lo,
                                   batching="vmap")
    assert np.asarray(f_v).any()
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
