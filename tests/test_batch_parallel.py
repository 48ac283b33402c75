"""Differential tests: segment-parallel engine == sequential scan engine.

The segment-parallel engine (vmap over segments, scan over intra-segment
lanes) must be *bit-identical* to the sequential reference on table state
and statuses for any op mix — that is the correctness contract that lets it
be the default write path. Randomized (hypothesis-style) op sequences cover
insert/delete/update/search mixes including duplicate keys inside one
batch, stash overflow, padding (valid) masks and NEED_SPLIT batches.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import DashConfig, DashEH, engine, hashing, layout
from repro.core import bucket as bk
from repro.core.layout import DROPPED, NEED_SPLIT
from tests._hypothesis_compat import given, settings, st
from tests.conftest import unique_keys

B = 64           # fixed batch size -> one jit trace per (op, engine) pair


def _states_equal(sa, sb):
    bad = [name for name, a, b in zip(sa._fields, jax.tree.leaves(sa),
                                      jax.tree.leaves(sb))
           if not (np.asarray(a) == np.asarray(b)).all()]
    return bad


def _batch(rng_np, keyspace):
    """Batch with duplicates (same-key ordering must be preserved)."""
    ks = keyspace[rng_np.integers(0, keyspace.size, B)]
    hi, lo = hashing.np_split_keys(ks)
    return jnp.asarray(hi), jnp.asarray(lo)


OPS = st.lists(st.sampled_from(["ins", "del", "upd", "mask"]),
               min_size=1, max_size=6)


@given(OPS)
@settings(max_examples=5, deadline=None)
def test_engines_bit_identical_random_ops(ops):
    # tiny table: stash overflow and NEED_SPLIT occur within a few batches
    cfg = DashConfig(max_segments=8, dir_depth_max=6, init_depth=1)
    rng_np = np.random.default_rng(hash(tuple(ops)) % 2**32)
    keyspace = np.unique(rng_np.integers(1, 2**63, 400, dtype=np.uint64))
    st_scan = layout.make_state(cfg, "eh")
    st_seg = jax.tree.map(jnp.copy, st_scan)
    saw_split = False

    for step, op in enumerate(ops):
        hi, lo = _batch(rng_np, keyspace)
        vals = jnp.asarray(rng_np.integers(1, 2**32, B).astype(np.uint32))
        valid = None
        if op == "mask":          # padded retry-batch shape: half the lanes
            valid = jnp.asarray(np.arange(B) < B // 2)
            op = "ins"
        if op == "ins":
            st_scan, s1, a1 = engine.insert_batch(
                cfg, "eh", st_scan, hi, lo, vals, None, valid, batching="scan")
            st_seg, s2, a2 = engine.insert_batch(
                cfg, "eh", st_seg, hi, lo, vals, None, valid,
                batching="segment", capacity=B)
            assert bool(a1) == bool(a2)
            saw_split |= (np.asarray(s1) == NEED_SPLIT).any()
        elif op == "del":
            st_scan, s1 = engine.delete_batch(cfg, "eh", st_scan, hi, lo,
                                              batching="scan")
            st_seg, s2 = engine.delete_batch(cfg, "eh", st_seg, hi, lo,
                                             batching="segment", capacity=B)
        else:
            st_scan, s1 = engine.update_batch(cfg, "eh", st_scan, hi, lo,
                                              vals, batching="scan")
            st_seg, s2 = engine.update_batch(cfg, "eh", st_seg, hi, lo, vals,
                                             batching="segment", capacity=B)
        assert (np.asarray(s1) == np.asarray(s2)).all(), (step, op)
        bad = _states_equal(st_scan, st_seg)
        assert not bad, (step, op, bad)

        # read paths agree on the (identical) state
        f1, v1 = engine.search_batch(cfg, "eh", st_scan, hi, lo,
                                     batching="vmap")
        f2, v2 = engine.search_batch(cfg, "eh", st_seg, hi, lo,
                                     batching="pallas", capacity=128)
        assert (np.asarray(f1) == np.asarray(f2)).all(), (step, op)
        assert (np.asarray(v1) == np.asarray(v2)).all(), (step, op)


def test_engines_identical_under_lh_mode(rng):
    """LH addressing (level/next word + stash chaining) through both engines."""
    cfg = DashConfig(max_segments=32, num_stash=4, lh_base_log2=2)
    keys = unique_keys(rng, 4 * B)
    st_scan = layout.make_state(cfg, "lh")
    st_seg = jax.tree.map(jnp.copy, st_scan)
    for i in range(4):
        hi, lo = hashing.np_split_keys(keys[i * B:(i + 1) * B])
        hi, lo = jnp.asarray(hi), jnp.asarray(lo)
        vals = jnp.asarray(np.arange(B, dtype=np.uint32))
        st_scan, s1, a1 = engine.insert_batch(cfg, "lh", st_scan, hi, lo,
                                              vals, batching="scan")
        st_seg, s2, a2 = engine.insert_batch(cfg, "lh", st_seg, hi, lo, vals,
                                             batching="segment", capacity=B)
        assert (np.asarray(s1) == np.asarray(s2)).all()
        assert bool(a1) == bool(a2)
        assert not _states_equal(st_scan, st_seg)


def test_table_end_to_end_equivalence(rng):
    """Full DashTable flows (splits + retries) with each engine forced."""
    cfg = DashConfig(max_segments=32, dir_depth_max=8, init_depth=1)
    keys = unique_keys(rng, 3000)
    vals = np.arange(3000, dtype=np.uint32)

    t_scan, t_seg = DashEH(cfg), DashEH(cfg)
    t_scan._write_plan = lambda seg, n, fused_ok=True: ("scan", None)
    seg_plan = type(t_seg)._write_plan

    def forced_segment(seg, n, fused_ok=True, _self=t_seg):
        _, cap = seg_plan(_self, seg, n, fused_ok=False)
        return "segment", cap or _self._lane_quantum(_self._max_per_segment(seg))
    t_seg._write_plan = forced_segment

    s1 = t_scan.insert(keys, vals)
    s2 = t_seg.insert(keys, vals)
    assert (s1 == s2).all()
    assert not _states_equal(t_scan.state, t_seg.state)

    d1 = t_scan.delete(keys[:1000])
    d2 = t_seg.delete(keys[:1000])
    assert (d1 == d2).all()
    u1 = t_scan.update(keys[1000:2000], vals[1000:2000] + 7)
    u2 = t_seg.update(keys[1000:2000], vals[1000:2000] + 7)
    assert (u1 == u2).all()
    assert not _states_equal(t_scan.state, t_seg.state)

    f1, v1 = t_scan.search(keys)
    f2, v2 = t_seg.search(keys)
    assert (f1 == f2).all() and (v1 == v2).all()


def test_update_batch_valid_mask():
    """update_batch takes the same padding mask as insert_batch: masked
    lanes come back DROPPED and write nothing (host retry subsets can pad
    to pow2 without recompiling on shape changes)."""
    cfg = DashConfig(max_segments=8, dir_depth_max=6)
    t = DashEH(cfg)
    keys = unique_keys(np.random.default_rng(3), B)
    t.insert(keys, np.arange(B, dtype=np.uint32))
    hi, lo = hashing.np_split_keys(keys)
    hi, lo = jnp.asarray(hi), jnp.asarray(lo)
    newv = jnp.asarray(np.full(B, 777, np.uint32))
    valid = jnp.asarray(np.arange(B) < B // 2)
    for batching in ("scan", "segment"):
        st2, statuses = engine.update_batch(
            cfg, "eh", jax.tree.map(jnp.copy, t.state), hi, lo, newv,
            None, valid, batching=batching)
        statuses = np.asarray(statuses)
        assert (statuses[B // 2:] == DROPPED).all(), batching
        f, v = engine.search_batch(cfg, "eh", st2, hi, lo)
        v = np.asarray(v)
        assert (v[:B // 2] == 777).all(), batching
        assert (v[B // 2:] == np.arange(B // 2, B)).all(), batching


# (b, slot) pairs on a 6 x 5 plane; the last two are out of range
SLOT_CASES = [(0, 0), (5, 4), (2, 3), (6, 1), (3, 5)]


@pytest.mark.parametrize("segments", [1, 3], ids=["view", "table"])
@pytest.mark.parametrize("dtype", [jnp.uint8, jnp.uint32])
def test_slot_access_matches_scatter(segments, dtype):
    """``bucket.set_slot``/``get_slot`` equal the element scatter and
    gather on a whole table (S > 1) and on the single-segment view the
    segment-parallel engines use (S == 1, there a masked select), vmapped
    as the engines run it; an out-of-range row or slot writes nothing."""
    rng = np.random.default_rng(segments)
    plane = jnp.asarray(rng.integers(0, 256, (segments, 6, 5)), dtype)
    x = jnp.asarray(7, dtype)
    seg = segments - 1
    for b, slot in SLOT_CASES:
        want = plane.at[seg, b, slot].set(x, mode="drop")
        assert np.array_equal(bk.set_slot(plane, seg, b, slot, x), want)
        if b < 6 and slot < 5:
            assert bk.get_slot(plane, seg, b, slot) == plane[seg, b, slot]
    bs = jnp.asarray([c[0] for c in SLOT_CASES], jnp.int32)
    ss = jnp.asarray([c[1] for c in SLOT_CASES], jnp.int32)
    views = jnp.broadcast_to(plane[seg], (len(SLOT_CASES),) + plane.shape[1:])
    got = jax.vmap(lambda v, b, s: bk.set_slot(v[None], 0, b, s, x)[0])(
        views, bs, ss)
    for i, (b, slot) in enumerate(SLOT_CASES):
        assert np.array_equal(got[i], plane[seg].at[b, slot].set(x, mode="drop"))
