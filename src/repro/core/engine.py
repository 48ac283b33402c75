"""Per-key Dash operations and the segment-parallel batched engine.

The paper's Algorithm 1 (insert with bucket load balancing), Algorithm 3
(search) and the delete procedure (Sec. 4.6), expressed as pure functions.

Batching & parallelism model
----------------------------
Dash's scalability claim rests on the *segment* being the unit of
concurrency: operations on different segments never contend (Sec. 4.4).
The batched engine mirrors that exactly:

  - **segment = unit of parallelism.** Mutating batches are routed by
    segment on device (the shared MoE-style dispatcher in
    ``kernels/ops.py``) and all segments run in parallel (``vmap`` over the
    segment axis); only the lanes *within* one segment are applied
    sequentially (``lax.scan``) — the same granularity as the paper's
    per-segment locks. Per-batch critical-path length drops from O(batch)
    to O(max lanes per segment).
  - **batch = unit of consistency.** The routing sort is stable, so lanes
    of one segment keep batch order; segments are disjoint state, so the
    resulting table is bit-identical to the sequential reference
    (``batching="scan"``, kept for differential testing).
  - **reads go through the Pallas fingerprint kernel by default.**
    ``search_batch`` routes queries per segment and scans fingerprints on
    the MXU/VPU (``kernels/probe.py``); only fingerprint hits load keys.
    Stash lanes are covered by a dense compare inside the routed path;
    capacity-overflow lanes and non-eligible configs (pointer mode,
    fingerprints disabled, probe windows > 2) fall back to the per-key
    ``vmap`` path. Lookups stay lock-free/optimistic (Sec. 4.4); version
    verification for concurrent composition lives in serving/engine.py.

Decision structure: every insert computes all candidate placements first
(counts, movable slots, stash occupancy — all cheap packed-word reads), then a
single ``lax.switch`` commits one branch. This is the TPU-native rendering of
Alg. 1's if/elif chain: uniform control flow, no divergence.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from . import bucket as bk
from . import hashing, layout
from .layout import (DROPPED, EXISTS, INSERTED, NEED_SPLIT, NOT_FOUND,
                     DashConfig, DashState, U32)

I32 = jnp.int32


# ---------------------------------------------------------------------------
# addressing
# ---------------------------------------------------------------------------

def locate(cfg: DashConfig, mode: str, state: DashState, h1):
    """(seg, b) for a hash under EH (MSB directory) or LH (level/next) rules."""
    if mode == "eh":
        seg = state.dir[layout.dir_index(cfg, h1)]
        b = layout.bucket_index(cfg, h1)
    else:
        seg = state.lh_dir[layout.lh_logical_segment(cfg, h1, state.lh_word)]
        b = layout.lh_bucket_index(cfg, h1)
    return seg, b


def _wrap(cfg: DashConfig, b):
    return b & (cfg.num_buckets - 1)


# ---------------------------------------------------------------------------
# segment-scope probe (search + uniqueness check)
# ---------------------------------------------------------------------------

def probe_in_segment(cfg: DashConfig, state: DashState, seg, b, h2,
                     q_hi, q_lo, q_words):
    """Full lookup inside one segment: window buckets, then stash via
    overflow metadata (Alg. 3). Returns (found, value)."""
    fpv = hashing.fingerprint(h2)
    window = cfg.probe_window

    found = jnp.asarray(False)
    value = U32(0)
    for w in range(window):
        bw = _wrap(cfg, b + w)
        f, _, v = bk.bucket_probe(cfg, state, seg, bw, fpv, q_hi, q_lo, q_words)
        value = jnp.where(f & ~found, v, value)
        found = found | f

    if cfg.num_stash == 0:
        return found, value

    # --- stash probing, gated by overflow metadata (Sec. 4.3 / Alg. 3) ---
    if not cfg.use_overflow_meta:
        # ablation (Fig. 10 baseline): no metadata => always scan the stash
        active = state.stash_active[seg]
        for s in range(cfg.num_stash):
            f, _, v2 = bk.bucket_probe(cfg, state, seg, cfg.num_buckets + s,
                                       fpv, q_hi, q_lo, q_words)
            hit = f & (s < active) & ~found
            value = jnp.where(hit, v2, value)
            found = found | hit
        return found, value

    pb = _wrap(cfg, b + 1)
    m_home = bk.ofp_matches(cfg, state, seg, b, fpv, want_member=False)   # (NOFP,)
    m_prob = bk.ofp_matches(cfg, state, seg, pb, fpv, want_member=True)
    scan_all = layout.ometa_ovf_count(state.ometa[seg, b]) > 0

    om_home = state.ometa[seg, b]
    om_prob = state.ometa[seg, pb]
    # which stash buckets are indicated by matching overflow fingerprints
    indicated = jnp.zeros((cfg.num_stash,), jnp.bool_)
    for j in range(cfg.num_ofp):
        sj_h = layout.ometa_stash_idx(om_home, jnp.uint32(j)).astype(I32)
        sj_p = layout.ometa_stash_idx(om_prob, jnp.uint32(j)).astype(I32)
        for s in range(cfg.num_stash):
            indicated = indicated.at[s].set(
                indicated[s] | (m_home[j] & (sj_h == s)) | (m_prob[j] & (sj_p == s)))

    active = state.stash_active[seg]
    for s in range(cfg.num_stash):
        sb = cfg.num_buckets + s
        probe_it = (indicated[s] | scan_all) & (s < active)
        f, _, v = bk.bucket_probe(cfg, state, seg, sb, fpv, q_hi, q_lo, q_words)
        hit = probe_it & f & ~found
        value = jnp.where(hit, v, value)
        found = found | hit
    return found, value


# ---------------------------------------------------------------------------
# insert (Algorithm 1 + Algorithm 2)
# ---------------------------------------------------------------------------

def _write_record(cfg: DashConfig, state: DashState, seg, b, slot,
                  q_hi, q_lo, q_words, v, fpv, member, heap_append=True):
    """bucket_write + pointer-mode key-heap append."""
    if cfg.pointer_mode and heap_append:
        handle = state.heap_top.astype(U32)
        state = state._replace(
            key_heap=jax.lax.dynamic_update_slice(
                state.key_heap, q_words[None, :], (state.heap_top, 0)),
            heap_top=state.heap_top + 1,
        )
        k_lo = handle
    else:
        k_lo = q_lo
    return bk.bucket_write(cfg, state, seg, b, slot, q_hi, k_lo, v, fpv, member)


def _insert_core(cfg: DashConfig, state: DashState, seg, b, h1, h2,
                 q_hi, q_lo, q_words, v, check_unique=True, heap_append=True):
    """Insert into a known segment (used both by the public insert and by
    split-rehash, which bypasses the directory exactly like the paper)."""
    fpv = hashing.fingerprint(h2)
    pb = _wrap(cfg, b + 1)
    NB, SL = cfg.num_buckets, cfg.num_slots

    if check_unique:
        exists, _ = probe_in_segment(cfg, state, seg, b, h2, q_hi, q_lo, q_words)
    else:
        exists = jnp.asarray(False)

    # ---- candidate computation (cheap packed-word reads) ----
    if cfg.use_balanced:
        cb, cp = bk.bucket_count(state, seg, b), bk.bucket_count(state, seg, pb)
        pick_pb = (cp < cb) & (cp < SL) | ((cb >= SL) & (cp < SL))
        can_plain = (cb < SL) | (cp < SL)
        ins_b = jnp.where(pick_pb, pb, b)
        ins_member = pick_pb
    else:
        # linear-probing window (CCEH style / Fig. 11 '+Probing'); member unused
        counts = jnp.stack([bk.bucket_count(state, seg, _wrap(cfg, b + w))
                            for w in range(max(cfg.probe_len, 1))])
        free = counts < SL
        can_plain = jnp.any(free)
        woff = jnp.argmax(free).astype(I32)
        ins_b = _wrap(cfg, b + woff)
        ins_member = jnp.asarray(False)

    # displacement candidates (Alg. 2) — only meaningful in balanced mode
    if cfg.use_balanced and cfg.use_displacement:
        pb2 = _wrap(cfg, b + 2)
        bm1 = _wrap(cfg, b - 1)
        okA_slot, slotA = bk.find_movable_slot(cfg, state, seg, pb, want_member_set=False)
        okA = okA_slot & (bk.bucket_count(state, seg, pb2) < SL)
        okB_slot, slotB = bk.find_movable_slot(cfg, state, seg, b, want_member_set=True)
        okB = okB_slot & (bk.bucket_count(state, seg, bm1) < SL)
    else:
        pb2 = bm1 = b
        slotA = slotB = I32(0)
        okA = okB = jnp.asarray(False)

    # stash candidate: first active stash bucket with a free slot
    active = state.stash_active[seg]
    if cfg.num_stash > 0:
        stash_free = jnp.stack([
            (bk.bucket_count(state, seg, NB + s) < SL) & (s < active)
            for s in range(cfg.num_stash)])
        ok_stash = jnp.any(stash_free)
        st_j = jnp.argmax(stash_free).astype(I32)
        # activation analog for LH chaining: can we open one more stash bucket?
        can_activate = active < cfg.num_stash
        ok_stash_or_new = ok_stash | can_activate
        st_j = jnp.where(ok_stash, st_j, active)          # newly activated index
        stash_activates = ~ok_stash & can_activate
    else:
        ok_stash_or_new = jnp.asarray(False)
        st_j = I32(0)
        stash_activates = jnp.asarray(False)

    # ---- decision (priority: exists > plain > dispA > dispB > stash > split) ----
    code = jnp.where(
        exists, 0,
        jnp.where(can_plain, 1,
                  jnp.where(okA, 2,
                            jnp.where(okB, 3,
                                      jnp.where(ok_stash_or_new, 4, 5)))))

    def br_exists(st):
        return st, I32(EXISTS)

    def br_plain(st):
        _, slot = bk.first_free_slot(cfg, st, seg, ins_b)
        st = _write_record(cfg, st, seg, ins_b, slot, q_hi, q_lo, q_words, v, fpv, ins_member, heap_append)
        return st, I32(INSERTED)

    def br_dispA(st):
        # move a target=pb record from pb to its probing bucket pb2
        mk_hi, mk_lo, mk_v, mk_fp = bk.read_slot(st, seg, pb, slotA)
        _, fs = bk.first_free_slot(cfg, st, seg, pb2)
        st = bk.bucket_write(cfg, st, seg, pb2, fs, mk_hi, mk_lo, mk_v, mk_fp, member=True)
        st = bk.bucket_clear_slot(cfg, st, seg, pb, slotA)
        st = _write_record(cfg, st, seg, pb, slotA, q_hi, q_lo, q_words, v, fpv, member=True, heap_append=heap_append)
        return st, I32(INSERTED)

    def br_dispB(st):
        # move a target=b-1 record (sitting in b with membership set) home to b-1
        mk_hi, mk_lo, mk_v, mk_fp = bk.read_slot(st, seg, b, slotB)
        _, fs = bk.first_free_slot(cfg, st, seg, bm1)
        st = bk.bucket_write(cfg, st, seg, bm1, fs, mk_hi, mk_lo, mk_v, mk_fp, member=False)
        st = bk.bucket_clear_slot(cfg, st, seg, b, slotB)
        st = _write_record(cfg, st, seg, b, slotB, q_hi, q_lo, q_words, v, fpv, member=False, heap_append=heap_append)
        return st, I32(INSERTED)

    def br_stash(st):
        sb = NB + st_j
        st = st._replace(stash_active=st.stash_active.at[seg].set(
            jnp.maximum(st.stash_active[seg], st_j + 1)))
        _, slot = bk.first_free_slot(cfg, st, seg, sb)
        st = _write_record(cfg, st, seg, sb, slot, q_hi, q_lo, q_words, v, fpv, member=False, heap_append=heap_append)
        if not cfg.use_overflow_meta:      # Fig. 10 ablation
            return st, I32(INSERTED)
        # overflow metadata: home bucket first, then probing bucket (Sec. 4.3)
        st1, ok1 = bk.ofp_try_set(cfg, st, seg, b, fpv, st_j, member=False)

        def try_prob(_):
            st2, ok2 = bk.ofp_try_set(cfg, st1, seg, pb, fpv, st_j, member=True)
            st3 = bk.ovf_count_add(st2, seg, b, 1)
            return jax.lax.cond(ok2, lambda s: s[0], lambda s: s[1], (st2, st3))

        st = jax.lax.cond(ok1, lambda _: st1, try_prob, None)
        return st, I32(INSERTED)

    def br_split(st):
        return st, I32(NEED_SPLIT)

    branches = [br_exists, br_plain, br_dispA, br_dispB,
                br_stash if cfg.num_stash > 0 else br_split, br_split]
    state, status = jax.lax.switch(code, branches, state)
    state = state._replace(n_items=state.n_items + (status == INSERTED).astype(I32))
    return state, status, stash_activates & (status == INSERTED) & (code == 4)


# ---------------------------------------------------------------------------
# delete (Sec. 4.6)
# ---------------------------------------------------------------------------

def delete_in_segment(cfg: DashConfig, state: DashState, seg, b, h2,
                      q_hi, q_lo, q_words):
    fpv = hashing.fingerprint(h2)
    window = cfg.probe_window

    # locate in window buckets
    found_w = jnp.asarray(False)
    w_b = I32(0)
    w_slot = I32(0)
    for w in range(window):
        bw = _wrap(cfg, b + w)
        f, slot, _ = bk.bucket_probe(cfg, state, seg, bw, fpv, q_hi, q_lo, q_words)
        take = f & ~found_w
        w_b = jnp.where(take, bw, w_b)
        w_slot = jnp.where(take, slot, w_slot)
        found_w = found_w | f

    # locate in stash
    found_s = jnp.asarray(False)
    s_j = I32(0)
    s_slot = I32(0)
    if cfg.num_stash > 0:
        active = state.stash_active[seg]
        for s in range(cfg.num_stash):
            f, slot, _ = bk.bucket_probe(cfg, state, seg, cfg.num_buckets + s, fpv,
                                         q_hi, q_lo, q_words)
            take = f & (s < active) & ~found_s
            s_j = jnp.where(take, s, s_j)
            s_slot = jnp.where(take, slot, s_slot)
            found_s = found_s | (f & (s < active))

    code = jnp.where(found_w, 0, jnp.where(found_s, 1, 2))

    def br_window(st):
        return bk.bucket_clear_slot(cfg, st, seg, w_b, w_slot), I32(INSERTED)

    def br_stash(st):
        st = bk.bucket_clear_slot(cfg, st, seg, cfg.num_buckets + s_j, s_slot)
        if not cfg.use_overflow_meta:      # Fig. 10 ablation
            return st, I32(INSERTED)
        # clear the matching overflow fingerprint (home first, then probing),
        # else decrement the overflow counter (Sec. 4.6 delete)
        pb = _wrap(cfg, b + 1)
        m_home = bk.ofp_matches(cfg, st, seg, b, fpv, want_member=False)
        m_prob = bk.ofp_matches(cfg, st, seg, pb, fpv, want_member=True)
        om_h, om_p = st.ometa[seg, b], st.ometa[seg, pb]
        idx_h = jnp.stack([layout.ometa_stash_idx(om_h, jnp.uint32(j)).astype(I32)
                           for j in range(cfg.num_ofp)])
        idx_p = jnp.stack([layout.ometa_stash_idx(om_p, jnp.uint32(j)).astype(I32)
                           for j in range(cfg.num_ofp)])
        cand_h = m_home & (idx_h == s_j)
        cand_p = m_prob & (idx_p == s_j)
        has_h, has_p = jnp.any(cand_h), jnp.any(cand_p)
        j_h = jnp.argmax(cand_h).astype(I32)
        j_p = jnp.argmax(cand_p).astype(I32)

        def clear_home(s):
            return bk.ofp_clear(cfg, s, seg, b, j_h)

        def clear_prob_or_count(s):
            return jax.lax.cond(
                has_p,
                lambda x: bk.ofp_clear(cfg, x, seg, pb, j_p),
                lambda x: bk.ovf_count_add(x, seg, b, -1),
                s)

        st = jax.lax.cond(has_h, clear_home, clear_prob_or_count, st)
        return st, I32(INSERTED)

    def br_missing(st):
        return st, I32(NOT_FOUND)

    state, status = jax.lax.switch(
        code, [br_window, br_stash if cfg.num_stash > 0 else br_missing,
               br_missing], state)
    state = state._replace(n_items=state.n_items - (status == INSERTED).astype(I32))
    return state, jnp.where(status == I32(INSERTED), I32(INSERTED), I32(NOT_FOUND))


# ---------------------------------------------------------------------------
# top-level per-key ops (directory lookup + segment op)
# ---------------------------------------------------------------------------

def _query_parts(cfg: DashConfig, q_hi, q_lo, q_words):
    """(h1, h2) for a query. Pointer mode folds the full key words."""
    if cfg.pointer_mode:
        q_hi, q_lo = hashing.key_identity_from_words(q_words)
    h1 = hashing.hash1(q_hi, q_lo)
    h2 = hashing.hash2(q_hi, q_lo)
    return q_hi, q_lo, h1, h2


def insert_one(cfg: DashConfig, mode: str, state: DashState,
               q_hi, q_lo, q_words, v):
    q_hi, q_lo, h1, h2 = _query_parts(cfg, q_hi, q_lo, q_words)
    seg, b = locate(cfg, mode, state, h1)
    return _insert_core(cfg, state, seg, b, h1, h2, q_hi, q_lo, q_words, v)


def search_one(cfg: DashConfig, mode: str, state: DashState, q_hi, q_lo, q_words):
    q_hi, q_lo, h1, h2 = _query_parts(cfg, q_hi, q_lo, q_words)
    seg, b = locate(cfg, mode, state, h1)
    return probe_in_segment(cfg, state, seg, b, h2, q_hi, q_lo, q_words)


def delete_one(cfg: DashConfig, mode: str, state: DashState, q_hi, q_lo, q_words):
    q_hi, q_lo, h1, h2 = _query_parts(cfg, q_hi, q_lo, q_words)
    seg, b = locate(cfg, mode, state, h1)
    return delete_in_segment(cfg, state, seg, b, h2, q_hi, q_lo, q_words)


# ---------------------------------------------------------------------------
# batched APIs
# ---------------------------------------------------------------------------

def _dummy_words(cfg: DashConfig, n: int):
    return jnp.zeros((n, cfg.key_heap_words), U32)


def _pow2_at_least(n: int, floor: int = 8) -> int:
    n = max(int(n), 1)
    return max(floor, 1 << (n - 1).bit_length())


def pallas_search_eligible(cfg: DashConfig) -> bool:
    """Configs the Pallas fingerprint read path covers exactly: inline keys,
    fingerprints on, and a probe window the 2-bucket kernel spans. Everything
    else (ablation baselines, pointer mode) uses the per-key vmap path."""
    from repro.kernels.probe import ROWS
    return (cfg.use_fingerprints and not cfg.pointer_mode
            and (cfg.use_balanced or cfg.probe_len <= 2)
            and cfg.buckets_total <= ROWS)


@functools.partial(jax.jit, static_argnums=(0, 1), donate_argnums=(2,))
def _insert_batch_scan(cfg: DashConfig, mode: str, state: DashState,
                       keys_hi, keys_lo, vals, words, valid):
    """Sequential reference engine (lax.scan carry = the table). Kept as the
    ``batching="scan"`` mode for differential testing; also serves pointer
    mode, whose global key heap is not segment-local."""
    def step(st, xs):
        hi, lo, w, v, ok = xs

        def do(s):
            return insert_one(cfg, mode, s, hi, lo, w, v)

        def skip(s):
            return s, I32(DROPPED), jnp.asarray(False)

        st, status, act = jax.lax.cond(ok, do, skip, st)
        return st, (status, act)

    state, (statuses, acts) = jax.lax.scan(
        step, state, (keys_hi, keys_lo, words, vals, valid))
    return state, statuses, jnp.any(acts)


# mutable per-segment planes carried through the vmapped intra-segment scan
_SEG_PLANES = ("fp", "ofp", "key_hi", "key_lo", "val", "meta", "ometa",
               "version", "stash_active")


def _segment_parallel(cfg: DashConfig, state: DashState, lanes, body):
    """Run ``body`` over routed lanes: vmap over the segment axis, scan over
    the intra-segment lanes — Dash's locking granularity as a compute
    schedule. ``lanes`` is a pytree of (S, C, ...) planes; ``body`` operates
    on a single-segment view of the table (seg index 0) and must only touch
    ``_SEG_PLANES`` + ``n_items``. Returns (state, outs) where outs are the
    stacked per-lane outputs, shape (S, C, ...)."""
    planes = {k: getattr(state, k) for k in _SEG_PLANES}

    def per_seg(pl, ln):
        st = state._replace(n_items=jnp.asarray(0, I32),
                            **{k: v[None] for k, v in pl.items()})
        st, outs = jax.lax.scan(body, st, ln)
        return {k: getattr(st, k)[0] for k in _SEG_PLANES}, outs, st.n_items

    new_planes, outs, d_items = jax.vmap(per_seg)(planes, lanes)
    state = state._replace(n_items=state.n_items + jnp.sum(d_items),
                           **new_planes)
    return state, outs


def _scatter_statuses(statuses, src, n: int):
    """(S, C) lane statuses -> (Q,) batch statuses; lanes that never got a
    slot (capacity overflow) come back DROPPED so the host retry loop can
    aggregate them with NEED_SPLIT subsets."""
    flat = statuses.reshape(-1)
    src = src.reshape(-1)
    out = jnp.full((n,), -1, I32).at[jnp.clip(src, 0)].max(
        jnp.where(src >= 0, flat, -1))
    return jnp.where(out < 0, I32(DROPPED), out)


@functools.partial(jax.jit, static_argnums=(0, 1, 8), donate_argnums=(2,))
def _insert_batch_segments(cfg: DashConfig, mode: str, state: DashState,
                           keys_hi, keys_lo, vals, words, valid,
                           capacity: int):
    from repro.kernels import ops
    lanes, src, keep = ops.route_writes(
        cfg, mode, state, (keys_hi, keys_lo, vals, words, valid), capacity)

    def body(st, ln):
        def do(s):
            return _insert_core(cfg, s, 0, ln["b"], ln["h1"], ln["h2"],
                                ln["hi"], ln["lo"], ln["words"], ln["val"])

        def skip(s):
            return s, I32(DROPPED), jnp.asarray(False)

        st, status, act = jax.lax.cond(ln["valid"], do, skip, st)
        return st, (status, act)

    state, (statuses, acts) = _segment_parallel(cfg, state, lanes, body)
    return (state, _scatter_statuses(statuses, src, keys_hi.shape[0]),
            jnp.any(acts))


def insert_batch(cfg: DashConfig, mode: str, state: DashState,
                 keys_hi, keys_lo, vals, words=None, valid=None,
                 batching: str = "segment", capacity: int | None = None):
    """Sequentially-consistent batch insert. Returns (state, statuses,
    any_stash_activation).

    ``batching="segment"`` (default) routes by segment and runs all segments
    in parallel; ``"scan"`` is the sequential reference; ``"fused"`` is the
    single-dispatch merged-commit path (kernels/fused.py) the table planner
    selects for small batches. All produce
    bit-identical table state and statuses when ``capacity`` covers the
    largest per-segment lane count (the host wrapper sizes it exactly;
    the default ``capacity=None`` -> next pow2 >= batch covers any skew).
    ``valid`` masks out padding lanes (host pads retry subsets to pow2 sizes
    to avoid shape recompiles).

    Donation discipline: every mutating dispatch donates (consumes) the live
    state's buffers, so a published snapshot must OWN its planes — it can
    alias a previous snapshot's pool-managed buffers (core/epoch.py) but
    never the live arrays passed here."""
    n = keys_hi.shape[0]
    if words is None:
        words = _dummy_words(cfg, n)
    if valid is None:
        valid = jnp.ones(n, jnp.bool_)
    if batching == "fused":
        from repro.kernels import fused
        return fused.fused_insert(cfg, mode, state, keys_hi, keys_lo, vals,
                                  words, valid, capacity)
    if batching == "scan" or cfg.pointer_mode:
        return _insert_batch_scan(cfg, mode, state, keys_hi, keys_lo, vals,
                                  words, valid)
    if capacity is None:
        capacity = _pow2_at_least(n)
    return _insert_batch_segments(cfg, mode, state, keys_hi, keys_lo, vals,
                                  words, valid, min(capacity, _pow2_at_least(n)))


@functools.partial(jax.jit, static_argnums=(0, 1))
def _search_batch_vmap(cfg: DashConfig, mode: str, state: DashState,
                       keys_hi, keys_lo, words):
    fn = lambda hi, lo, w: search_one(cfg, mode, state, hi, lo, w)
    return jax.vmap(fn)(keys_hi, keys_lo, words)


@functools.partial(jax.jit, static_argnums=(0, 1, 6))
def _search_batch_routed(cfg: DashConfig, mode: str, state: DashState,
                         keys_hi, keys_lo, words, capacity: int):
    from repro.kernels import ops
    # only reached on TPU (the dispatcher sends other hosts to probe_direct):
    # run the real Pallas kernel, not its interpreter/jnp stand-ins
    found, vals, keep = ops.probe_routed(cfg, state, keys_hi, keys_lo,
                                         capacity, False, mode)
    if capacity >= keys_hi.shape[0]:
        return found, vals          # no lane can overflow: keep is all-True

    # capacity-overflow lanes: per-key fallback, only traced into the branch
    # actually taken (scalar predicate -> real cond, not a vmap select)
    def fallback(_):
        return _search_batch_vmap(cfg, mode, state, keys_hi, keys_lo, words)

    def none(_):
        return jnp.zeros_like(found), jnp.zeros_like(vals)

    f2, v2 = jax.lax.cond(jnp.any(~keep), fallback, none, None)
    return jnp.where(keep, found, f2), jnp.where(keep, vals, v2)


def search_batch(cfg: DashConfig, mode: str, state: DashState,
                 keys_hi, keys_lo, words=None, batching: str = "auto",
                 capacity: int | None = None):
    """Lock-free batched lookup — pure reads, zero writes (optimistic path).

    Default read path is the Pallas fingerprint kernel over segment-routed
    lanes (``batching="pallas"``); ``"vmap"`` is the per-key path, used
    automatically for configs the kernel does not cover; ``"fused"`` is the
    single-dispatch latency path (kernels/fused.py) the table planner
    selects for small batches. On non-TPU hosts
    the pallas mode runs the kernel's direct-addressed jnp lowering
    (``kernels/ops.py:probe_direct``) — same fingerprint-first read
    discipline, no per-segment lane planes (those are the TPU VMEM
    blocking)."""
    if batching == "fused":
        from repro.kernels import fused
        return fused.fused_search(cfg, mode, state, keys_hi, keys_lo, words)
    if words is None:
        words = _dummy_words(cfg, keys_hi.shape[0])
    if batching == "pallas" and not pallas_search_eligible(cfg):
        batching = "vmap"      # fingerprint path would silently miss records
    if batching == "auto":
        batching = "pallas" if pallas_search_eligible(cfg) else "vmap"
    if batching == "vmap":
        return _search_batch_vmap(cfg, mode, state, keys_hi, keys_lo, words)
    if jax.default_backend() != "tpu":
        from repro.kernels import ops
        return ops.probe_direct(cfg, state, keys_hi, keys_lo, mode)
    if capacity is None:
        capacity = _pow2_at_least(keys_hi.shape[0], floor=128)
    return _search_batch_routed(cfg, mode, state, keys_hi, keys_lo, words,
                                capacity)


@functools.partial(jax.jit, static_argnums=(0, 1), donate_argnums=(2,))
def search_batch_pessimistic(cfg: DashConfig, mode: str, state: DashState,
                             keys_hi, keys_lo, words=None):
    """Fig. 13 baseline: read-locking searches. Every probe 'acquires/releases'
    a read lock = two version-word writes per touched bucket, which also
    serializes the batch (scan, not vmap). Models the PM-write cost the paper
    attributes to pessimistic locking."""
    if words is None:
        words = _dummy_words(cfg, keys_hi.shape[0])

    def step(st, xs):
        hi, lo, w = xs
        q_hi, q_lo, h1, h2 = _query_parts(cfg, hi, lo, w)
        seg, b = locate(cfg, mode, st, h1)
        pb = _wrap(cfg, b + 1)
        st = bk.bump_version(st, seg, b)      # acquire
        st = bk.bump_version(st, seg, pb)
        found, val = probe_in_segment(cfg, st, seg, b, h2, q_hi, q_lo, w)
        st = bk.bump_version(st, seg, b)      # release
        st = bk.bump_version(st, seg, pb)
        return st, (found, val)

    state, (found, vals) = jax.lax.scan(step, state, (keys_hi, keys_lo, words))
    return state, found, vals


@functools.partial(jax.jit, static_argnums=(0, 1), donate_argnums=(2,))
def _delete_batch_scan(cfg: DashConfig, mode: str, state: DashState,
                       keys_hi, keys_lo, words, valid):
    def step(st, xs):
        hi, lo, w, ok = xs

        def do(s):
            return delete_one(cfg, mode, s, hi, lo, w)

        def skip(s):
            return s, I32(DROPPED)

        st, status = jax.lax.cond(ok, do, skip, st)
        return st, status

    state, statuses = jax.lax.scan(step, state,
                                   (keys_hi, keys_lo, words, valid))
    return state, statuses


@functools.partial(jax.jit, static_argnums=(0, 1, 7), donate_argnums=(2,))
def _delete_batch_segments(cfg: DashConfig, mode: str, state: DashState,
                           keys_hi, keys_lo, words, valid, capacity: int):
    from repro.kernels import ops
    vals = jnp.zeros_like(keys_hi)     # deletes carry no payload
    lanes, src, _ = ops.route_writes(
        cfg, mode, state, (keys_hi, keys_lo, vals, words, valid), capacity)

    def body(st, ln):
        def do(s):
            return delete_in_segment(cfg, s, 0, ln["b"], ln["h2"],
                                     ln["hi"], ln["lo"], ln["words"])

        def skip(s):
            return s, I32(DROPPED)

        st, status = jax.lax.cond(ln["valid"], do, skip, st)
        return st, status

    state, statuses = _segment_parallel(cfg, state, lanes, body)
    return state, _scatter_statuses(statuses, src, keys_hi.shape[0])


def delete_batch(cfg: DashConfig, mode: str, state: DashState,
                 keys_hi, keys_lo, words=None, valid=None,
                 batching: str = "segment", capacity: int | None = None):
    n = keys_hi.shape[0]
    if words is None:
        words = _dummy_words(cfg, n)
    if valid is None:
        valid = jnp.ones(n, jnp.bool_)
    if batching == "scan" or cfg.pointer_mode:
        return _delete_batch_scan(cfg, mode, state, keys_hi, keys_lo, words,
                                  valid)
    if capacity is None:
        capacity = _pow2_at_least(n)
    return _delete_batch_segments(cfg, mode, state, keys_hi, keys_lo, words,
                                  valid, min(capacity, _pow2_at_least(n)))


def update_in_segment(cfg: DashConfig, state: DashState, seg, b, h2,
                      q_hi, q_lo, q_words, v):
    """Set the payload of an existing key within a known segment. The
    touched bucket's version word is bumped like every other write: the
    optimistic snapshot-verify path (Sec. 4.4, serving/) detects stale
    payloads only through version planes, so a silent in-place update would
    be invisible to concurrent readers."""
    fpv = hashing.fingerprint(h2)
    window = cfg.probe_window
    status = I32(NOT_FOUND)
    for wo in range(window):
        bw = _wrap(cfg, b + wo)
        f, slot, _ = bk.bucket_probe(cfg, state, seg, bw, fpv, q_hi, q_lo, q_words)
        do = f & (status == NOT_FOUND)
        state = state._replace(
            val=jnp.where(do, bk.set_slot(state.val, seg, bw, slot, v),
                          state.val),
            version=jnp.where(do, state.version.at[seg, bw].add(U32(2)),
                              state.version))
        status = jnp.where(do, I32(INSERTED), status)
    for s in range(cfg.num_stash):
        sb = cfg.num_buckets + s
        f, slot, _ = bk.bucket_probe(cfg, state, seg, sb, fpv, q_hi, q_lo, q_words)
        do = f & (s < state.stash_active[seg]) & (status == NOT_FOUND)
        state = state._replace(
            val=jnp.where(do, bk.set_slot(state.val, seg, sb, slot, v),
                          state.val),
            version=jnp.where(do, state.version.at[seg, sb].add(U32(2)),
                              state.version))
        status = jnp.where(do, I32(INSERTED), status)
    return state, status


@functools.partial(jax.jit, static_argnums=(0, 1), donate_argnums=(2,))
def _update_batch_scan(cfg: DashConfig, mode: str, state: DashState,
                       keys_hi, keys_lo, vals, words, valid):
    def step(st, xs):
        hi, lo, w, v, ok = xs

        def do(s):
            q_hi, q_lo, h1, h2 = _query_parts(cfg, hi, lo, w)
            seg, b = locate(cfg, mode, s, h1)
            return update_in_segment(cfg, s, seg, b, h2, q_hi, q_lo, w, v)

        def skip(s):
            return s, I32(DROPPED)

        st, status = jax.lax.cond(ok, do, skip, st)
        return st, status

    state, statuses = jax.lax.scan(
        step, state, (keys_hi, keys_lo, words, vals, valid))
    return state, statuses


@functools.partial(jax.jit, static_argnums=(0, 1, 8), donate_argnums=(2,))
def _update_batch_segments(cfg: DashConfig, mode: str, state: DashState,
                           keys_hi, keys_lo, vals, words, valid,
                           capacity: int):
    from repro.kernels import ops
    lanes, src, _ = ops.route_writes(
        cfg, mode, state, (keys_hi, keys_lo, vals, words, valid), capacity)

    def body(st, ln):
        def do(s):
            return update_in_segment(cfg, s, 0, ln["b"], ln["h2"],
                                     ln["hi"], ln["lo"], ln["words"],
                                     ln["val"])

        def skip(s):
            return s, I32(DROPPED)

        st, status = jax.lax.cond(ln["valid"], do, skip, st)
        return st, status

    state, statuses = _segment_parallel(cfg, state, lanes, body)
    return state, _scatter_statuses(statuses, src, keys_hi.shape[0])


def update_batch(cfg: DashConfig, mode: str, state: DashState,
                 keys_hi, keys_lo, vals, words=None, valid=None,
                 batching: str = "segment", capacity: int | None = None):
    """Set payload for existing keys (serving cache refresh path). ``valid``
    masks padding lanes exactly like ``insert_batch``, so host-side retry
    subsets can pad to pow2 sizes without recompiling on shape changes."""
    n = keys_hi.shape[0]
    if words is None:
        words = _dummy_words(cfg, n)
    if valid is None:
        valid = jnp.ones(n, jnp.bool_)
    if batching == "scan" or cfg.pointer_mode:
        return _update_batch_scan(cfg, mode, state, keys_hi, keys_lo, vals,
                                  words, valid)
    if capacity is None:
        capacity = _pow2_at_least(n)
    return _update_batch_segments(cfg, mode, state, keys_hi, keys_lo, vals,
                                  words, valid, min(capacity, _pow2_at_least(n)))


# ---------------------------------------------------------------------------
# segment record extraction (split rehash + recovery)
# ---------------------------------------------------------------------------

def segment_records(cfg: DashConfig, state: DashState, seg):
    """All records of a segment: (hi, lo, val, valid) with shape (BT*SLOTS,).
    Pointer-mode lo is the heap handle; rehashing recomputes identity by
    re-folding the heap row (the 'dereference on rehash' cost of Sec. 4.5)."""
    BT, SL = cfg.buckets_total, cfg.num_slots
    hi = jax.lax.dynamic_slice(state.key_hi, (seg, 0, 0), (1, BT, SL))[0].reshape(-1)
    lo = jax.lax.dynamic_slice(state.key_lo, (seg, 0, 0), (1, BT, SL))[0].reshape(-1)
    val = jax.lax.dynamic_slice(state.val, (seg, 0, 0), (1, BT, SL))[0].reshape(-1)
    meta = jax.lax.dynamic_slice(state.meta, (seg, 0), (1, BT))[0]
    alloc = layout.meta_alloc(meta)
    slot_ids = jnp.arange(SL, dtype=U32)[None, :]
    valid = (((alloc[:, None] >> slot_ids) & U32(1)) == 1).reshape(-1)
    return hi, lo, val, valid


def recount_items(state: DashState):
    """Exact global record count from the packed per-bucket counters.

    ``n_items`` is maintained incrementally everywhere (SMOs move records —
    net zero; crash-duplicated slots were never counted, so recovery's
    dedupe restores agreement without touching the total). This full
    recount is the *audit*: tests assert ``n_items == recount_items`` after
    split/merge/shrink/recovery workloads."""
    return jnp.sum(layout.meta_count(state.meta).astype(I32))


@jax.jit
def changed_rows(prev_version, live_version):
    """Flattened per-bucket-row dirty mask between two version planes.

    This is the ground truth the copy-on-write publish scatters by
    (core/epoch.py:SnapshotRegistry.publish_cow): every mutating path —
    insert/delete/update via the bucket helpers, SMO rebuilds via the
    whole-segment bump in ``smo._scatter_planes``, recovery via
    ``recover_segment`` — bumps the version word of every bucket row it
    touches (see core/bucket.py), so ``prev != live`` at the version plane
    is a complete O(dirty) change record with zero extra bookkeeping on the
    write path. The host-side dirty-segment hints (``table.DirtyTracker``,
    derived from the same routing that feeds ``route_lanes``) are audited
    against this mask but never replace it.

    Works for any leading shape: (S, BT) for one table, (n_shards, S, BT)
    for the sharded DHT."""
    return (prev_version != live_version).reshape(-1)


def record_hashes(cfg: DashConfig, state: DashState, hi, lo):
    """(h1, h2) for stored records (handles pointer mode re-fold)."""
    if cfg.pointer_mode:
        rows = state.key_heap[lo % U32(max(cfg.key_heap_size, 1))]
        f_hi = hashing.fold_words(rows, hashing.FOLD_SEED_HI)
        f_lo = hashing.fold_words(rows, hashing.FOLD_SEED_LO)
        return hashing.hash1(f_hi, f_lo), hashing.hash2(f_hi, f_lo)
    return hashing.hash1(hi, lo), hashing.hash2(hi, lo)
