"""Jit'd wrappers binding the Pallas kernels to Dash state.

``plane_views`` reshapes the table's fingerprint/metadata planes into the
hardware-aligned tiles the probe kernel wants (cheap, fusible pads).
``probe_routed`` is the end-to-end fast path: queries routed per segment ->
Pallas fingerprint scan -> key verification only on fingerprint hits
(gathers bounded by the match bitmap, the paper's 'amortized one key load').
It backs the default ``engine.search_batch`` read path on TPU;
``probe_direct`` is its direct-addressed jnp lowering for non-TPU hosts
(same fingerprint-first discipline, no per-segment lane blocking).

Routing is the shared MoE-style dispatcher of the whole repo: the same
``group_ranks``/``route_lanes`` pair groups queries by *segment* here, by
*owner shard* in distributed/dht.py, and carries full key/value lanes for
the segment-parallel write engine (core/engine.py) via ``route_writes``.
Ranking is sort-based (O(Q log Q)), not the dense one-hot+cumsum (O(Q*S))
it replaced, so routing cost scales with batch size, not directory size.

``interpret`` has no default: ``True`` (the CPU tests) swaps
pl.pallas_call for the bit-identical jnp lowerings — the Pallas
interpreter's per-program overhead is not the hot path's job — and the TPU
path passes ``False``. The routed planes cover only the segments a batch
touches (``touched_segments``), so their size follows the batch, not the
segment pool.

The fused small-batch latency path (kernels/fused.py) is re-exported here:
``fused_search`` / ``fused_insert`` collapse the route->probe->verify /
route->probe->hint->scatter pipelines into one dispatch — the path the
table planner picks when a batch is at or under its fused threshold.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import hashing, layout
from repro.core.layout import DashConfig, DashState
from . import probe as probe_kernel
from .fused import (fused_insert, fused_insert_eligible,  # noqa: F401
                    fused_kernel_eligible, fused_probe, fused_probe_jnp,
                    fused_search, fused_search_eligible)
from .probe import LANES, NSLOTS, ROWS, fingerprint_probe

I32 = jnp.int32


@functools.partial(jax.jit, static_argnums=(0,))
def plane_views(cfg: DashConfig, state: DashState, segments):
    """(fp_padded (U,128,128) u8, alloc (U,128) i32) of the given segments."""
    BT = cfg.buckets_total
    U = segments.shape[0]
    fp = jnp.zeros((U, ROWS, LANES), jnp.uint8)
    fp = fp.at[:, :BT, :16].set(state.fp[segments])
    alloc = jnp.zeros((U, ROWS), jnp.int32)
    alloc = alloc.at[:, :BT].set(
        layout.meta_alloc(state.meta[segments]).astype(jnp.int32))
    return fp, alloc


def touched_segments(seg, num: int):
    """The distinct segment ids of a batch, and each item's row among them.

    Returns ``(segments, rows)``: ``segments`` is (num,) int32, ascending,
    padded with segment 0 (no item maps to a padding row); ``rows[i]`` is
    the index of ``seg[i]`` in it, -1 where ``seg[i] < 0``. ``num`` must be
    at least the number of distinct ids (the batch size or the pool size
    bounds it), so the routed kernels build plane views and lanes for what
    a batch touches, not for the whole segment pool."""
    pad = jnp.iinfo(I32).max
    seg = seg.astype(I32)
    ids = jnp.unique(jnp.where(seg >= 0, seg, pad), size=num, fill_value=pad)
    rows = jnp.where(seg >= 0, jnp.searchsorted(ids, seg).astype(I32), -1)
    return jnp.where(ids == pad, 0, ids), rows


# ---------------------------------------------------------------------------
# shared MoE-style dispatcher (segments here, owner shards in the DHT)
# ---------------------------------------------------------------------------

def group_ranks(group_ids):
    """Rank of each item within its group, preserving input order.

    Sort-based (stable argsort + run-start cummax): O(Q log Q) regardless of
    the number of groups. The stable sort is what makes the segment-parallel
    write engine sequentially consistent: lanes of one segment keep batch
    order.
    """
    n = group_ids.shape[0]
    order = jnp.argsort(group_ids)                    # stable in jnp
    sorted_ids = group_ids[order]
    idx = jnp.arange(n, dtype=I32)
    is_start = jnp.concatenate(
        [jnp.ones((1,), jnp.bool_), sorted_ids[1:] != sorted_ids[:-1]])
    run_start = jax.lax.cummax(jnp.where(is_start, idx, 0))
    return jnp.zeros((n,), I32).at[order].set(idx - run_start)


def route_lanes(group_ids, payloads, num_groups: int, capacity: int, fills):
    """Scatter per-item payload arrays into (num_groups, capacity) lane planes.

    Items past ``capacity`` in their group go to a trash slot *past the end*
    of the flat buffer — they can never clobber a live lane (the old dense
    router scattered them onto lane (0, 0)). Returns (planes, src, keep):
    ``src`` maps lanes back to batch positions (-1 = empty), ``keep[i]``
    is True iff item i received a lane.
    """
    n = group_ids.shape[0]
    group_ids = group_ids.astype(I32)
    rank = group_ranks(group_ids)
    keep = (rank < capacity) & (group_ids >= 0) & (group_ids < num_groups)
    trash = num_groups * capacity
    dst = jnp.where(keep, group_ids * capacity + rank, trash)
    outs = []
    for p, fill in zip(payloads, fills):
        flat = jnp.full((trash + 1,) + p.shape[1:], fill, p.dtype).at[dst].set(p)
        outs.append(flat[:-1].reshape((num_groups, capacity) + p.shape[1:]))
    src = jnp.full((trash + 1,), -1, I32).at[dst].set(jnp.arange(n, dtype=I32))
    return outs, src[:-1].reshape(num_groups, capacity), keep


def locate_batch(cfg: DashConfig, mode: str, state: DashState, h1):
    """Vectorized (seg, bucket) addressing for a batch of h1 hashes —
    engine.locate is pure jnp indexing, so it batches as-is; one copy of
    the EH/LH addressing rules."""
    from repro.core import engine    # local: core imports kernels lazily too
    return engine.locate(cfg, mode, state, h1)


@functools.partial(jax.jit, static_argnums=(0, 4, 5))
def route_queries(cfg: DashConfig, state: DashState, keys_hi, keys_lo,
                  capacity: int, mode: str = "eh"):
    """Group a query batch by segment with fixed capacity (MoE-style dispatch;
    the intra-host analog of the DHT's all_to_all routing).

    Returns (q_fp, q_b, q_pb, q_src, keep, segments): (U, C) planes whose
    row u serves ``segments[u]``, one row per segment the batch touches
    (``touched_segments``); q_src maps back to the original batch position
    (-1 = empty lane); ``keep`` is False for capacity-dropped queries
    (resolved by the caller on the per-key path)."""
    h1 = hashing.hash1(keys_hi, keys_lo)
    h2 = hashing.hash2(keys_hi, keys_lo)
    seg, b = locate_batch(cfg, mode, state, h1)
    pb = (b + 1) & (cfg.num_buckets - 1)
    fp = (h2 & jnp.uint32(0xFF)).astype(jnp.int32)
    segments, rows = touched_segments(
        seg, min(keys_hi.shape[0], cfg.max_segments))
    (q_fp, q_b, q_pb), q_src, keep = route_lanes(
        rows, (fp, b, pb), segments.shape[0], capacity, (0, -1, -1))
    return q_fp, q_b, q_pb, q_src, keep, segments


@functools.partial(jax.jit, static_argnums=(0, 4, 5, 6))
def probe_routed(cfg: DashConfig, state: DashState, keys_hi, keys_lo,
                 capacity: int, interpret: bool, mode: str = "eh"):
    """End-to-end batched search through the Pallas fingerprint kernel.

    Covers target+probing buckets via the MXU gather and the (few) stash
    buckets via a dense VPU compare against the same routed lanes — stash
    rows are per-segment constants, so no gather is needed and the overflow
    metadata walk of the scalar path is unnecessary. Returns (found, values,
    keep) aligned with the input batch; ``keep=False`` lanes overflowed the
    routing capacity and are untouched (found=False) — the caller resolves
    them on the per-key path.

    Requires inline keys + fingerprints + a <=2 bucket probe window (the
    engine dispatcher gates on exactly that, falling back to the vmap path).

    ``interpret=True`` (the CPU tests) runs the kernel's bit-identical jnp
    lowering instead of the Pallas interpreter — same routed planes, same
    bitmaps, none of the per-program interpreter overhead.
    """
    Q = keys_hi.shape[0]
    NB, SL = cfg.num_buckets, cfg.num_slots
    q_fp, q_b, q_pb, q_src, keep, segments = route_queries(
        cfg, state, keys_hi, keys_lo, capacity, mode)
    S = segments.shape[0]
    fp_pad, alloc = plane_views(cfg, state, segments)
    if interpret:
        bits_b, bits_pb, _free_b, _free_pb = probe_kernel.fingerprint_probe_jnp(
            fp_pad, alloc, q_fp, q_b, q_pb)
    else:
        bits_b, bits_pb, _free_b, _free_pb = fingerprint_probe(
            fp_pad, alloc, q_fp, q_b, q_pb, interpret=False)

    # verify fingerprint hits with real key compares — one row gather per
    # plane (the paper's 'amortized one key load': only matched rows hit)
    seg_ids = jnp.broadcast_to(segments[:, None], q_b.shape).reshape(-1)
    flat_src = q_src.reshape(-1)
    hi_r = jnp.where(flat_src >= 0, keys_hi[jnp.clip(flat_src, 0)], 0)
    lo_r = jnp.where(flat_src >= 0, keys_lo[jnp.clip(flat_src, 0)], 0)
    slot_ids = jnp.arange(cfg.num_slots)

    def verify(bqs, bits):
        safe_b = jnp.clip(bqs.reshape(-1), 0, cfg.buckets_total - 1)
        cand = ((bits.reshape(-1)[:, None] >> slot_ids) & 1) == 1  # (N, SL)
        k_hi = state.key_hi[seg_ids, safe_b]                       # (N, SL)
        k_lo = state.key_lo[seg_ids, safe_b]
        m = cand & (k_hi == hi_r[:, None]) & (k_lo == lo_r[:, None])
        vals_row = state.val[seg_ids, safe_b]
        val = jnp.max(jnp.where(m, vals_row, jnp.uint32(0)), axis=-1)
        return jnp.any(m, axis=-1), val

    ok_b, val_b = verify(q_b, bits_b)
    ok_p, val_p = verify(q_pb, bits_pb)
    ok = ok_b | ok_p
    val = jnp.where(ok_b, val_b, val_p)

    # --- stash lanes: dense compare, no gather (stash rows are per-segment
    # constants). Alloc-bitmap gating subsumes the stash_active check: a
    # never-activated stash bucket has no allocated slots.
    if cfg.num_stash > 0:
        C = q_fp.shape[1]
        st = slice(NB, NB + cfg.num_stash)
        st_alloc = layout.meta_alloc(state.meta[segments, st])
        slot_ids = jnp.arange(SL, dtype=jnp.uint32)
        st_live = ((st_alloc[..., None] >> slot_ids) & 1) == 1   # (S, ns, SL)
        st_hi = state.key_hi[segments, st, :SL]
        st_lo = state.key_lo[segments, st, :SL]
        st_val = state.val[segments, st, :SL]
        hi_l = hi_r.reshape(S, C)[:, :, None, None]
        lo_l = lo_r.reshape(S, C)[:, :, None, None]
        m = (st_live[:, None] & (st_hi[:, None] == hi_l) &
             (st_lo[:, None] == lo_l) & (q_src >= 0)[..., None, None])
        if cfg.use_fingerprints:
            st_fp = state.fp[segments, st, :SL].astype(jnp.int32)
            m = m & (st_fp[:, None] == q_fp[:, :, None, None])
        ok_s = jnp.any(m, axis=(2, 3)).reshape(-1)               # (S*C,)
        val_s = jnp.max(jnp.where(m, jnp.broadcast_to(st_val[:, None], m.shape),
                                  jnp.uint32(0)), axis=(2, 3)).reshape(-1)
        val = jnp.where(ok, val, val_s)
        ok = ok | ok_s

    found = jnp.zeros((Q,), jnp.bool_)
    values = jnp.zeros((Q,), jnp.uint32)
    src_safe = jnp.clip(flat_src, 0)
    found = found.at[src_safe].max(ok & (flat_src >= 0))
    values = values.at[src_safe].max(jnp.where(ok & (flat_src >= 0), val, 0))
    return found, values, keep


@functools.partial(jax.jit, static_argnums=(0, 4))
def probe_direct(cfg: DashConfig, state: DashState, keys_hi, keys_lo,
                 mode: str = "eh"):
    """Direct-addressed jnp lowering of the fingerprint read path (CPU hosts).

    Same read discipline as ``probe_routed`` — fingerprint match first, key
    loads only on candidates, stash covered by a dense compare — but
    per-query gathers instead of (S, C) lane planes: the fixed-capacity
    routing exists for the Pallas kernel's per-segment VMEM blocking, which
    buys nothing on XLA:CPU and pays ~S*C/Q lane overcapacity. Returns
    (found, values); never drops lanes (no routing capacity).
    """
    SL, NB = cfg.num_slots, cfg.num_buckets
    h1 = hashing.hash1(keys_hi, keys_lo)
    h2 = hashing.hash2(keys_hi, keys_lo)
    fpv = (h2 & jnp.uint32(0xFF)).astype(jnp.uint8)
    seg, b = locate_batch(cfg, mode, state, h1)
    slot_bit = jnp.uint32(1) << jnp.arange(SL, dtype=jnp.uint32)

    def bucket_hits(bx):
        alloc = layout.meta_alloc(state.meta[seg, bx])            # (Q,)
        live = (alloc[:, None] & slot_bit) != 0                   # (Q, SL)
        cand = live & (state.fp[seg, bx, :SL] == fpv[:, None])
        m = (cand & (state.key_hi[seg, bx] == keys_hi[:, None]) &
             (state.key_lo[seg, bx] == keys_lo[:, None]))
        val = jnp.max(jnp.where(m, state.val[seg, bx], jnp.uint32(0)), axis=-1)
        return jnp.any(m, axis=-1), val

    ok_b, val_b = bucket_hits(b)
    ok_p, val_p = bucket_hits((b + 1) & (NB - 1))
    found = ok_b | ok_p
    values = jnp.where(ok_b, val_b, val_p)

    if cfg.num_stash > 0:
        st_alloc = layout.meta_alloc(state.meta[:, NB:NB + cfg.num_stash])[seg]
        live = (st_alloc[..., None] & slot_bit) != 0              # (Q, ns, SL)
        cand = live & (state.fp[:, NB:NB + cfg.num_stash, :SL][seg]
                       == fpv[:, None, None])
        m = (cand &
             (state.key_hi[:, NB:NB + cfg.num_stash][seg] == keys_hi[:, None, None]) &
             (state.key_lo[:, NB:NB + cfg.num_stash][seg] == keys_lo[:, None, None]))
        ok_s = jnp.any(m, axis=(1, 2))
        val_s = jnp.max(jnp.where(m, state.val[:, NB:NB + cfg.num_stash][seg],
                                  jnp.uint32(0)), axis=(1, 2))
        values = jnp.where(found, values, val_s)
        found = found | ok_s
    return found, values


@functools.partial(jax.jit, static_argnums=(0, 1, 4))
def route_writes(cfg: DashConfig, mode: str, state: DashState,
                 payload, capacity: int):
    """Route a *write* batch by segment, carrying full key/value lanes.

    ``payload`` is (keys_hi, keys_lo, vals, words, valid). Returns
    ``(lanes, src, keep)`` where lanes is the dict the segment-parallel
    engine scans: hi/lo/val/words/b/h1/h2/valid, each (S, C[, W]).
    """
    keys_hi, keys_lo, vals, words, valid = payload
    h1 = hashing.hash1(keys_hi, keys_lo)
    h2 = hashing.hash2(keys_hi, keys_lo)
    seg, b = locate_batch(cfg, mode, state, h1)
    planes, src, keep = route_lanes(
        seg, (keys_hi, keys_lo, vals, words, b, h1, h2,
              valid & (seg >= 0)),
        cfg.max_segments, capacity,
        (0, 0, 0, 0, 0, 0, 0, False))
    lanes = dict(zip(("hi", "lo", "val", "words", "b", "h1", "h2", "valid"),
                     planes))
    return lanes, src, keep


@functools.partial(jax.jit, static_argnums=(0, 3))
def write_hints(cfg: DashConfig, state: DashState, lanes, interpret: bool):
    """Push ``route_writes`` lanes through the fingerprint pass over the
    *same* plane views the search path uses: per-lane (match_bits_b,
    match_bits_pb, free_slots_b, free_slots_pb). The free-slot bitmaps are
    advisory (pre-batch state — intra-batch inserts invalidate them):
    available to host-side admission and capacity prechecks, never for the
    commit decision. ``interpret=True`` runs the jnp lowering."""
    fp_pad, alloc = plane_views(
        cfg, state, jnp.arange(cfg.max_segments, dtype=I32))
    q_fp = (lanes["h2"] & jnp.uint32(0xFF)).astype(jnp.int32)
    q_b = jnp.where(lanes["valid"], lanes["b"].astype(jnp.int32), -1)
    q_pb = jnp.where(lanes["valid"],
                     (lanes["b"].astype(jnp.int32) + 1) & (cfg.num_buckets - 1),
                     -1)
    if interpret:
        return probe_kernel.fingerprint_probe_jnp(fp_pad, alloc, q_fp, q_b,
                                                  q_pb)
    return fingerprint_probe(fp_pad, alloc, q_fp, q_b, q_pb, interpret=False)
