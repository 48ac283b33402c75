"""Fused small-batch latency path: route→probe→verify / route→probe→scatter
in ONE dispatch.

Why this exists (ISSUE 7 / ROADMAP "fused kernel" item): the routed batch
paths win at large batches by amortizing the route / fingerprint / verify /
scatter stages across thousands of lanes, but the serving tick forms *small*
batches (64-256), where each extra XLA program launch is pure latency. At
batch 256 the routed search path measured 0.77x the plain vmap path and the
segment-parallel insert only 1.12x the sequential scan — fixed dispatch
overhead, not compute. IcebergHT (PAPERS.md) makes the same point for PM
hashing at low concurrency: per-op overhead governs latency.

Two entry points, both single-dispatch:

``fused_search``
    Reads. On TPU: ``fused_probe`` — one Pallas kernel with a grid step per
    query. XLA hashes the batch and reads each query's directory word; the
    kernel gets every query's segment and bucket as prefetched scalars and
    DMAs only the (slots, 8 buckets, 128 segments) tiles of the planes that
    hold the query's target bucket, probing bucket and stash rows, in the
    planes' stored (segment-minor) layout, so nothing the size of the table
    is copied. It compares fingerprint byte, allocation bit and both key
    words and writes the first matching slot's value at the query's own
    position. On non-TPU hosts: a direct-addressed jnp lowering — a single
    gather of the (window + stash) candidate rows per query and one dense
    compare.

``fused_insert``
    Writes. One jitted program: segment routing (``ops.route_writes``), the
    dense uniqueness probe, free-slot/displacement/stash hints read straight
    from the packed metadata words, and a *merged commit* — the Alg. 1/2
    decision is computed as a code, then applied as one set of masked
    single-element scatters (out-of-bounds index + ``mode='drop'`` for the
    not-taken ops). This replaces the ``lax.switch`` insert body whose
    branches XLA merges into whole-plane selects under vmap — the actual
    cost driver at small batches, measured ~6x the useful work.

Differential contract: both paths are bit-identical to the reference
engines (``batching="vmap"`` reads, ``batching="scan"`` writes) for every
config they accept — asserted by tests/test_fused.py and re-asserted on
live state by the latency benchmark before timing. The one documented
caveat: the dense stash probe checks every *active* stash row instead of
walking overflow-fingerprint indications, so it relies on the metadata
invariant (every stash record is either ofp-indicated or covered by a
nonzero overflow count) that insert/delete maintain — the same invariant
``probe_in_segment``'s miss-path correctness already depends on.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import bucket as bk
from repro.core import hashing, layout
from repro.core.layout import (DROPPED, EXISTS, INSERTED, NEED_SPLIT,
                               DashConfig, DashState, U32)

I32 = jnp.int32

# the read kernel's tiles: GB bucket rows (a u32 sublane tile) by SEG_TILE
# segments (a lane tile); NO_MATCH is the priority of a non-candidate
GB = 8
SEG_TILE = 128
NO_MATCH = 1 << 30
# queries per kernel call: its prefetched scalars, six words a query, must
# fit the 1 MiB of scalar memory
MAX_QUERIES = 16384


# ---------------------------------------------------------------------------
# eligibility
# ---------------------------------------------------------------------------

def fused_search_eligible(cfg: DashConfig) -> bool:
    """The direct jnp read path covers every config: balanced pairs or
    linear-probe windows, fingerprints on/off, pointer mode (heap rows are
    gathered and compared like ``bucket.keys_equal``), stash on/off."""
    return True


def fused_kernel_eligible(cfg: DashConfig) -> bool:
    """Configs the per-query Pallas kernel spans: inline keys, a window of
    at most two buckets (balanced pairs, or probe_len <= 2), normal buckets
    that fill whole tiles of ``GB`` rows and stash rows that fit the next
    tile. Fingerprints may be off — the kernel then compares no fp byte."""
    return (not cfg.pointer_mode and cfg.probe_window <= 2
            and cfg.num_buckets % GB == 0 and cfg.num_stash <= GB)


def fused_insert_eligible(cfg: DashConfig) -> bool:
    """The merged-commit write path covers the paper's main configuration:
    balanced two-bucket inserts (with or without displacement / stash /
    overflow metadata / fingerprints). Pointer mode keeps the sequential
    scan (its key heap is a global append log), and tiny tables where the
    b-1/b+2 displacement neighbors alias are excluded."""
    return (cfg.use_balanced and not cfg.pointer_mode
            and cfg.num_buckets >= 4)


# ---------------------------------------------------------------------------
# fused read — direct-addressed jnp lowering (the non-TPU execution path)
# ---------------------------------------------------------------------------

def _candidate_columns(cfg: DashConfig, b):
    """(Q, W) bucket-row indices per query: the probe window in order, then
    every stash row — the same visit order as ``probe_in_segment``."""
    NB = cfg.num_buckets
    cols = [(b + w) & (NB - 1) for w in range(cfg.probe_window)]
    cols += [jnp.full_like(b, NB + s) for s in range(cfg.num_stash)]
    return jnp.stack(cols, axis=1)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _fused_search_direct(cfg: DashConfig, mode: str, state: DashState,
                         keys_hi, keys_lo, words):
    """One gather of all candidate rows per query + one dense compare.

    Bit-identical to ``_search_batch_vmap``: column order encodes the
    window-then-stash probe priority, argmax over slots encodes
    ``bucket_probe``'s first-matching-slot rule.
    """
    SL, NB, ns = cfg.num_slots, cfg.num_buckets, cfg.num_stash
    window = cfg.probe_window
    if cfg.pointer_mode:        # identity pair folds the full key words
        keys_hi, keys_lo = hashing.key_identity_from_words(words)
    h1 = hashing.hash1(keys_hi, keys_lo)
    h2 = hashing.hash2(keys_hi, keys_lo)
    fpv = hashing.fingerprint(h2)

    from repro.kernels import ops
    seg, b = ops.locate_batch(cfg, mode, state, h1)
    bx = _candidate_columns(cfg, b)                      # (Q, W)
    W = bx.shape[1]
    segb = seg[:, None]

    alloc = layout.meta_alloc(state.meta[segb, bx])      # (Q, W)
    slot_bit = U32(1) << jnp.arange(SL, dtype=U32)
    live = (alloc[..., None] & slot_bit) != 0            # (Q, W, SL)
    cand = live
    if cfg.use_fingerprints:
        cand = cand & (state.fp[segb, bx, :SL] == fpv[:, None, None])
    s_hi = state.key_hi[segb, bx]                        # (Q, W, SL)
    s_lo = state.key_lo[segb, bx]
    if cfg.pointer_mode:
        rows = state.key_heap[s_lo % U32(max(cfg.key_heap_size, 1))]
        keq = (s_hi == keys_hi[:, None, None]) & jnp.all(
            rows == words[:, None, None, :], axis=-1)
    else:
        keq = (s_hi == keys_hi[:, None, None]) & (s_lo == keys_lo[:, None, None])
    m = cand & keq
    if ns:
        active = state.stash_active[seg]                 # (Q,)
        col_ok = jnp.concatenate(
            [jnp.ones((keys_hi.shape[0], window), jnp.bool_),
             jnp.arange(ns)[None, :] < active[:, None]], axis=1)
        m = m & col_ok[..., None]

    slot = jnp.argmax(m, axis=-1)                        # first matching slot
    okw = jnp.any(m, axis=-1)                            # (Q, W)
    vw = jnp.take_along_axis(state.val[segb, bx], slot[..., None],
                             axis=-1)[..., 0]
    found = jnp.zeros(keys_hi.shape[0], jnp.bool_)
    value = jnp.zeros(keys_hi.shape[0], U32)
    for w in range(W):                                   # window/stash priority
        take = okw[:, w] & ~found
        value = jnp.where(take, vw[:, w], value)
        found = found | okw[:, w]
    return found, value


# ---------------------------------------------------------------------------
# fused read — the per-query Pallas kernel (TPU path; interpret mode in tests)
# ---------------------------------------------------------------------------

def stored_views(state: DashState, use_fingerprints: bool):
    """The record planes as the device stores them: segments minor.

    XLA keeps a large table's (S, BT, SL) planes segment-minor (the slot
    axis is far narrower than a lane tile), so these transposes are
    bitcasts there and the kernel reads the planes where they lie. Where
    the layout differs they cost a copy and stay correct. Returns
    (key_hi, key_lo, val) as (SL, BT, S), fp as (BT, 16, S) — None with
    fingerprints off — and meta as (BT, S), in the state's dtypes."""
    fp = state.fp.transpose(1, 2, 0) if use_fingerprints else None
    return (state.key_hi.transpose(2, 1, 0), state.key_lo.transpose(2, 1, 0),
            state.val.transpose(2, 1, 0), fp, state.meta.T)


def _tiles(nb: int, ns: int, window: int):
    """The bucket-row tiles a query reads: its target bucket's, its probing
    bucket's where that can lie in another tile, and the stash rows'."""
    return (["home"] + (["probe"] if window == 2 and nb > GB else [])
            + (["stash"] if ns else []))


def _tile_of(kind: str, b, nb: int):
    if kind == "home":
        return b // GB
    if kind == "probe":
        return ((b + 1) & (nb - 1)) // GB
    return nb // GB


def _probe_query(seg_ref, b_ref, hi_ref, lo_ref, qfp_ref, act_ref, *refs,
                 nb: int, ns: int, window: int, use_fp: bool):
    """One grid step = one query. Each of the step's tiles holds eight
    bucket rows of 128 segments; the query's candidates are single rows of
    them, and its segment one lane. Every candidate (row, slot) gets a
    priority — target bucket, probing bucket, then stash rows, then the
    slot: ``probe_in_segment``'s visit order and ``bucket_probe``'s
    first-slot rule — and the least wins. The result lands at the query's
    own position of the resident outputs."""
    found_ref, val_ref = refs[-2:]
    per = 5 if use_fp else 4
    tiles = {kind: refs[k * per:(k + 1) * per]
             for k, kind in enumerate(_tiles(nb, ns, window))}
    i = pl.program_id(0)
    s, b = seg_ref[i], b_ref[i]
    pb = (b + 1) & (nb - 1)
    cross = pb // GB != b // GB          # the probing bucket's own tile
    sl, _, sb = refs[0].shape
    slot = jax.lax.broadcasted_iota(I32, (sl, sb), 0)
    at_lane = jax.lax.broadcasted_iota(I32, (sl, sb), 1) == s % sb

    # (tile, row within it, rank, whether the row is a candidate)
    rows = [(tiles["home"], b % GB, 0, True)]
    if window == 2:
        rows.append((tiles["home"], pb % GB, 1, ~cross))
        if "probe" in tiles:
            rows.append((tiles["probe"], pb % GB, 1, cross))
    rows += [(tiles["stash"], k, 2 + k, k < act_ref[i]) for k in range(ns)]

    prios, vals = [], []
    for tile, r, rank, live in rows:
        khi, klo, val = (jax.lax.bitcast_convert_type(t[:, r, :], I32)
                         for t in tile[:3])                   # (SL, sb)
        meta = jax.lax.bitcast_convert_type(tile[3][pl.ds(r, 1), :], I32)
        hit = (at_lane & live & (((meta >> slot) & 1) == 1)
               & (khi == hi_ref[i]) & (klo == lo_ref[i]))
        if use_fp:
            hit &= tile[4][r].astype(I32)[:sl] == qfp_ref[i]
        prios.append(jnp.where(hit, rank * 16 + slot, NO_MATCH))
        vals.append(val)

    best = functools.reduce(jnp.minimum, [jnp.min(p) for p in prios])
    value = functools.reduce(jnp.add, [jnp.sum(jnp.where(p == best, v, 0))
                                       for p, v in zip(prios, vals)])
    shape = found_ref.shape
    pos = (jax.lax.broadcasted_iota(I32, shape, 0) * shape[1]
           + jax.lax.broadcasted_iota(I32, shape, 1)) == i

    @pl.when(i == 0)
    def _():
        found_ref[...] = jnp.zeros(shape, I32)
        val_ref[...] = jnp.zeros(shape, I32)

    found = best < NO_MATCH
    found_ref[...] = jnp.where(pos, found.astype(I32), found_ref[...])
    val_ref[...] = jnp.where(pos, jnp.where(found, value, 0), val_ref[...])


@functools.partial(jax.jit, static_argnames=("nb", "ns", "window",
                                             "interpret"))
def fused_probe(planes, seg, b, q_hi, q_lo, q_fp, active, *, nb: int,
                ns: int, window: int, interpret: bool):
    """The read kernel: one grid step per query, planes read in place.

    Args:
      planes: ``stored_views`` of the table.
      seg, b: (n,) int32 segment and target bucket of each query.
      q_hi, q_lo: (n,) uint32 key words; q_fp: (n,) int32 fingerprint
        bytes; active: (n,) int32 active stash rows of each query's segment.
      interpret: run the Pallas interpreter (CPU tests) instead of Mosaic.

    Returns (found, val): (n,) int32 / uint32, in query order. The queries'
    segments and buckets are prefetched scalars, and every step's block
    indices come from them, so each step DMAs only the (SL, 8, 128) tiles
    that hold its query's rows; Pallas skips the copy where a step's tile
    is the previous step's.
    """
    khi, klo, val, fp, meta = planes
    use_fp = fp is not None
    sl, _, S = khi.shape
    sb = SEG_TILE if S % SEG_TILE == 0 else S
    n = seg.shape[0]
    in_specs, operands = [], []
    for kind in _tiles(nb, ns, window):
        def tile(i, seg, b, *_, kind=kind):
            return _tile_of(kind, b[i], nb), seg[i] // sb

        in_specs += [pl.BlockSpec((sl, GB, sb),
                                  lambda *a, tile=tile: (0, *tile(*a)))] * 3
        in_specs.append(pl.BlockSpec((GB, sb), tile))
        operands += [khi, klo, val, meta]
        if use_fp:
            in_specs.append(pl.BlockSpec(
                (GB, fp.shape[1], sb),
                lambda *a, tile=tile: (tile(*a)[0], 0, tile(*a)[1])))
            operands.append(fp)
    out = pl.BlockSpec((pl.cdiv(n, 128), 128), lambda i, *_: (0, 0))
    out_i32 = jax.ShapeDtypeStruct((pl.cdiv(n, 128), 128), I32)
    scalars = [x.astype(I32) for x in (seg, b)]
    scalars += [jax.lax.bitcast_convert_type(x, I32) for x in (q_hi, q_lo)]
    scalars += [q_fp.astype(I32), active.astype(I32)]
    found, value = pl.pallas_call(
        functools.partial(_probe_query, nb=nb, ns=ns, window=window,
                          use_fp=use_fp),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars), grid=(n,), in_specs=in_specs,
            out_specs=[out, out]),
        out_shape=[out_i32, out_i32],
        interpret=interpret,
    )(*scalars, *operands)
    return (found.reshape(-1)[:n],
            jax.lax.bitcast_convert_type(value.reshape(-1)[:n], U32))


@functools.partial(jax.jit, static_argnames=("nb", "ns", "window"))
def fused_probe_jnp(planes, seg, b, q_hi, q_lo, q_fp, active, *, nb: int,
                    ns: int, window: int):
    """Bit-identical jnp lowering of ``fused_probe`` (the differential
    oracle the kernel is pinned against): the same candidate rows visited
    in order, the first matching slot of the first matching row."""
    khi, klo, val, fp, meta = planes
    slots = jnp.arange(khi.shape[0], dtype=U32)
    rows = [b] + ([(b + 1) & (nb - 1)] if window == 2 else [])
    rows += [jnp.full_like(b, nb + k) for k in range(ns)]
    found = jnp.zeros(seg.shape, jnp.bool_)
    value = jnp.zeros(seg.shape, U32)
    for w, r in enumerate(rows):
        hit = ((layout.meta_alloc(meta[r, seg])[:, None] >> slots)
               & U32(1)) == 1                                     # (n, SL)
        if fp is not None:
            hit &= (fp[r[:, None], slots, seg[:, None]].astype(I32)
                    == q_fp[:, None])
        hit &= ((khi[:, r, seg].T == q_hi[:, None])
                & (klo[:, r, seg].T == q_lo[:, None]))
        if w >= len(rows) - ns:                                   # stash row
            hit &= w - (len(rows) - ns) < active[:, None]
        ok = jnp.any(hit, axis=1)
        v = jnp.take_along_axis(val[:, r, seg].T,
                                jnp.argmax(hit, axis=1)[:, None], axis=1)[:, 0]
        value = jnp.where(ok & ~found, v, value)
        found = found | ok
    return found.astype(I32), value


# ---------------------------------------------------------------------------
# fused read — host-facing dispatch
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnums=(0, 1, 5))
def _fused_search_kernel(cfg: DashConfig, mode: str, state: DashState,
                         keys_hi, keys_lo, interpret: bool):
    """TPU path: hash and locate the batch's queries in XLA (a gather of
    one directory word each), then ``fused_probe`` reads each query's
    candidate rows where the planes lie and writes its result in query
    order. ``interpret=True`` runs the kernel in the Pallas interpreter
    (the CPU tests); the TPU dispatcher passes False."""
    from repro.kernels import ops
    h1 = hashing.hash1(keys_hi, keys_lo)
    h2 = hashing.hash2(keys_hi, keys_lo)
    seg, b = ops.locate_batch(cfg, mode, state, h1)
    queries = (seg, b, keys_hi, keys_lo, hashing.fingerprint(h2).astype(I32),
               state.stash_active[seg])
    views = stored_views(state, cfg.use_fingerprints)
    parts = [fused_probe(views, *(q[i:i + MAX_QUERIES] for q in queries),
                         nb=cfg.num_buckets, ns=cfg.num_stash,
                         window=cfg.probe_window, interpret=interpret)
             for i in range(0, keys_hi.shape[0], MAX_QUERIES)]
    return (jnp.concatenate([f for f, _ in parts]) != 0,
            jnp.concatenate([v for _, v in parts]))


def fused_read_path(cfg: DashConfig) -> str:
    """Which program ``fused_search`` runs for ``cfg`` on this host:
    ``"kernel"`` (the per-query Pallas kernel, TPU hosts and configs in
    its span) or ``"direct"`` (the jnp lowering)."""
    if jax.default_backend() == "tpu" and fused_kernel_eligible(cfg):
        return "kernel"
    return "direct"


def fused_search(cfg: DashConfig, mode: str, state: DashState,
                 keys_hi, keys_lo, words=None):
    """Single-dispatch batched lookup. Returns (found, values), bit-identical
    to ``engine.search_batch(batching="vmap")``.

    TPU hosts take the per-query kernel when the config is in its span;
    other hosts and configs take the direct-addressed lowering (one gather
    + one dense compare). Only pointer mode reads ``words``."""
    if fused_read_path(cfg) == "kernel":
        return _fused_search_kernel(cfg, mode, state, keys_hi, keys_lo, False)
    if words is None and cfg.pointer_mode:
        words = jnp.zeros((keys_hi.shape[0], cfg.key_heap_words), U32)
    return _fused_search_direct(cfg, mode, state, keys_hi, keys_lo, words)


# ---------------------------------------------------------------------------
# fused insert — merged-commit write path
# ---------------------------------------------------------------------------

def _ofp_set_word(cfg: DashConfig, om, stash_idx, member):
    """Word-level mirror of ``bucket.ofp_try_set`` (no state, no scatter):
    returns (ok, new_word, ofp_slot)."""
    oa = layout.ometa_ofp_alloc(om)
    ids = jnp.arange(cfg.num_ofp, dtype=U32)
    free = ((oa >> ids) & U32(1)) == 0
    ok = jnp.any(free)
    slot = jnp.argmax(free).astype(I32)
    new_oa = oa | (U32(1) << slot.astype(U32))
    omem = layout.ometa_ofp_member(om)
    new_omem = omem | jnp.where(member, U32(1) << slot.astype(U32), U32(0))
    om2 = om & ~((U32(0xF) << layout.OFPA_SHIFT) | (U32(0xF) << layout.OFPM_SHIFT))
    om2 = om2 | (new_oa << layout.OFPA_SHIFT) | (new_omem << layout.OFPM_SHIFT)
    om2 = layout.ometa_set_stash_idx(om2, slot, jnp.asarray(stash_idx).astype(U32))
    om2 = om2 | (U32(1) << layout.OVFB_SHIFT)
    return ok, jnp.where(ok, om2, om), slot


def _ovf_count_add_word(om):
    """Word-level mirror of ``bucket.ovf_count_add`` (+1)."""
    cnt = (layout.ometa_ovf_count(om).astype(I32) + 1).astype(U32)
    om = (om & ~(U32(0x7F) << layout.OVFC_SHIFT)) | ((cnt & U32(0x7F)) << layout.OVFC_SHIFT)
    return om | (U32(1) << layout.OVFB_SHIFT)


def _merged_insert_body(cfg: DashConfig, st: DashState, ln):
    """One routed lane against a single-segment view of the table — the
    ``lax.switch`` insert body re-expressed as straight-line code: compute
    the Alg. 1/2 decision code, then apply ONE masked set of single-element
    scatters (disabled ops get an out-of-bounds row index + ``mode='drop'``).

    Bit-identical to ``engine._insert_core`` (same candidate formulas, same
    priority, same packed-word and version-bump sequence) for every config
    ``fused_insert_eligible`` admits. The uniqueness probe is the dense
    window+stash compare — exact under the overflow-metadata invariant (see
    module docstring).
    """
    NB, SL, ns = cfg.num_buckets, cfg.num_slots, cfg.num_stash
    BT = cfg.buckets_total
    valid = ln["valid"]
    hi, lo, v = ln["hi"], ln["lo"], ln["val"]
    b = ln["b"]
    fpv = hashing.fingerprint(ln["h2"])
    pb = (b + 1) & (NB - 1)
    OOB = I32(BT)                                       # dropped scatter target

    meta = st.meta[0]                                   # (BT,)
    slot_ids = jnp.arange(SL, dtype=U32)

    def alloc_bits(w):
        return ((layout.meta_alloc(w) >> slot_ids) & U32(1)) == 1

    def count(w):
        return layout.meta_count(w).astype(I32)

    def ffs(w):
        free = ((layout.meta_alloc(w) >> slot_ids) & U32(1)) == 0
        return jnp.argmax(free).astype(I32)

    # ---- uniqueness probe (dense window + active stash rows) ----
    def probe_bucket(bx):
        cand = alloc_bits(meta[bx])
        if cfg.use_fingerprints:
            cand = cand & (st.fp[0, bx, :SL] == fpv)
        return jnp.any(cand & (st.key_hi[0, bx] == hi) & (st.key_lo[0, bx] == lo))

    exists = probe_bucket(b) | probe_bucket(pb)
    if ns > 0:
        active = st.stash_active[0]
        sl_live = ((layout.meta_alloc(meta[NB:NB + ns])[:, None]
                    >> slot_ids[None, :]) & U32(1)) == 1
        cand = sl_live
        if cfg.use_fingerprints:
            cand = cand & (st.fp[0, NB:NB + ns, :SL] == fpv)
        eq = (cand & (st.key_hi[0, NB:NB + ns] == hi)
              & (st.key_lo[0, NB:NB + ns] == lo)
              & (jnp.arange(ns) < active)[:, None])
        exists = exists | jnp.any(eq)
    else:
        active = I32(0)

    # ---- candidates (identical formulas to _insert_core) ----
    meta_b, meta_pb = meta[b], meta[pb]
    cb, cp = count(meta_b), count(meta_pb)
    pick_pb = (cp < cb) & (cp < SL) | ((cb >= SL) & (cp < SL))
    can_plain = (cb < SL) | (cp < SL)
    ins_b = jnp.where(pick_pb, pb, b)
    ins_member = pick_pb

    if cfg.use_displacement:
        pb2 = (b + 2) & (NB - 1)
        bm1 = (b - 1) & (NB - 1)

        def movable(w, want):
            a = alloc_bits(w)
            mset = ((layout.meta_member(w) >> slot_ids) & U32(1)) == 1
            ok = a & (mset == want)
            return jnp.any(ok), jnp.argmax(ok).astype(I32)

        okA_s, slotA = movable(meta_pb, False)
        okA = okA_s & (count(meta[pb2]) < SL)
        okB_s, slotB = movable(meta_b, True)
        okB = okB_s & (count(meta[bm1]) < SL)
    else:
        pb2 = bm1 = b
        slotA = slotB = I32(0)
        okA = okB = jnp.asarray(False)

    if ns > 0:
        st_counts = layout.meta_count(meta[NB:NB + ns]).astype(I32)
        stash_free = (st_counts < SL) & (jnp.arange(ns) < active)
        ok_stash = jnp.any(stash_free)
        st_j = jnp.argmax(stash_free).astype(I32)
        can_activate = active < ns
        ok_stash_or_new = ok_stash | can_activate
        st_j = jnp.where(ok_stash, st_j, active)
        stash_activates = ~ok_stash & can_activate
    else:
        ok_stash_or_new = jnp.asarray(False)
        st_j = I32(0)
        stash_activates = jnp.asarray(False)

    # ---- decision code (priority: exists > plain > dispA > dispB > stash) --
    code = jnp.where(
        exists, 0,
        jnp.where(can_plain, 1,
                  jnp.where(okA, 2,
                            jnp.where(okB, 3,
                                      jnp.where(ok_stash_or_new, 4, 5)))))
    committed = valid & (code >= 1) & (code <= 4)
    status = jnp.where(
        ~valid, I32(DROPPED),
        jnp.where(code == 0, I32(EXISTS),
                  jnp.where(code == 5, I32(NEED_SPLIT), I32(INSERTED))))

    # ---- merged commit: displacement move, clear, new record ----
    is_move = committed & ((code == 2) | (code == 3))
    mv_src_b = jnp.where(code == 2, pb, b)
    mv_src_slot = jnp.where(code == 2, slotA, slotB)
    mv_dst_b = jnp.where(code == 2, pb2, bm1)
    mv_dst_slot = ffs(meta[mv_dst_b])                   # pre-state; branch guarantees room
    mv_member = code == 2                               # dispA re-homes as member-set
    mk_hi, mk_lo, mk_v, mk_fp = bk.read_slot(st, 0, mv_src_b, mv_src_slot)

    sb = NB + st_j
    new_b = jnp.where(code == 1, ins_b,
                      jnp.where(code == 2, pb,
                                jnp.where(code == 3, b, sb)))
    new_slot = jnp.where(code == 1, ffs(meta[ins_b]),
                         jnp.where(code == 2, slotA,
                                   jnp.where(code == 3, slotB, ffs(meta[sb]))))
    new_member = jnp.where(code == 1, ins_member, code == 2)

    mv_row = jnp.where(is_move, mv_dst_b, OOB)
    new_row = jnp.where(committed, new_b, OOB)

    def write2(plane, x_mv, x_new):
        plane = bk.set_slot(plane, 0, mv_row, mv_dst_slot, x_mv)
        return bk.set_slot(plane, 0, new_row, new_slot, x_new)

    key_hi = write2(st.key_hi, mk_hi, hi)
    key_lo = write2(st.key_lo, mk_lo, lo)
    val = write2(st.val, mk_v, v)
    fp = write2(st.fp, mk_fp, fpv)

    # packed metadata words (publish points), in _insert_core's store order
    bit = lambda s: U32(1) << s.astype(U32)
    w_mv = meta[mv_dst_b]
    w1 = layout.meta_pack(layout.meta_alloc(w_mv) | bit(mv_dst_slot),
                          layout.meta_member(w_mv)
                          | jnp.where(mv_member, bit(mv_dst_slot), U32(0)),
                          layout.meta_count(w_mv) + U32(1))
    w_src = meta[mv_src_b]
    wc = layout.meta_pack(layout.meta_alloc(w_src) & ~bit(mv_src_slot),
                          layout.meta_member(w_src) & ~bit(mv_src_slot),
                          layout.meta_count(w_src) - U32(1))
    # the displaced branches overwrite the just-cleared word at src == new_b
    w2_base = jnp.where(is_move, wc, meta[new_b])
    w2 = layout.meta_pack(layout.meta_alloc(w2_base) | bit(new_slot),
                          layout.meta_member(w2_base)
                          | jnp.where(new_member, bit(new_slot), U32(0)),
                          layout.meta_count(w2_base) + U32(1))
    meta_pl = st.meta
    meta_pl = meta_pl.at[0, mv_row].set(w1, mode="drop")
    meta_pl = meta_pl.at[0, jnp.where(is_move, mv_src_b, OOB)].set(wc, mode="drop")
    meta_pl = meta_pl.at[0, new_row].set(w2, mode="drop")

    # version bumps: +2 per constituent bucket op, exactly as the branches
    ver = st.version
    ver = ver.at[0, mv_row].add(U32(2), mode="drop")                 # move write
    ver = ver.at[0, jnp.where(is_move, mv_src_b, OOB)].add(U32(2), mode="drop")  # clear
    ver = ver.at[0, new_row].add(U32(2), mode="drop")                # new write

    st = st._replace(key_hi=key_hi, key_lo=key_lo, val=val, fp=fp,
                     meta=meta_pl)

    # stash activation + overflow metadata chain (br_stash)
    is_st = committed & (code == 4)
    if ns > 0:
        st = st._replace(stash_active=st.stash_active.at[0].set(
            jnp.where(is_st, jnp.maximum(active, st_j + 1), active)))
        if cfg.use_overflow_meta:
            OOB_NB = I32(NB)
            om_b, om_pb = st.ometa[0, b], st.ometa[0, pb]
            if cfg.num_ofp > 0:
                ok1, om_b_set, ofs1 = _ofp_set_word(cfg, om_b, st_j, member=False)
                ok2, om_pb_set, ofs2 = _ofp_set_word(cfg, om_pb, st_j, member=True)
            else:
                ok1 = ok2 = jnp.asarray(False)
                om_b_set, om_pb_set = om_b, om_pb
                ofs1 = ofs2 = I32(0)
            need_count = ~ok1 & ~ok2
            om_b_new = jnp.where(ok1, om_b_set, _ovf_count_add_word(om_b))
            ometa = st.ometa
            ometa = ometa.at[0, jnp.where(is_st & (ok1 | need_count), b, OOB_NB)
                             ].set(om_b_new, mode="drop")
            ometa = ometa.at[0, jnp.where(is_st & ~ok1 & ok2, pb, OOB_NB)
                             ].set(om_pb_set, mode="drop")
            ofp = st.ofp
            ofp = bk.set_slot(ofp, 0, jnp.where(is_st & ok1, b, OOB_NB),
                              ofs1, fpv)
            ofp = bk.set_slot(ofp, 0, jnp.where(is_st & ~ok1 & ok2, pb,
                                                OOB_NB), ofs2, fpv)
            ver = ver.at[0, jnp.where(is_st, jnp.where(~ok1 & ok2, pb, b), OOB)
                         ].add(U32(2), mode="drop")
            st = st._replace(ometa=ometa, ofp=ofp)

    st = st._replace(version=ver,
                     n_items=st.n_items + (status == INSERTED).astype(I32))
    return st, (status, stash_activates & is_st)


@functools.partial(jax.jit, static_argnums=(0, 1, 8), donate_argnums=(2,))
def _fused_insert_jit(cfg: DashConfig, mode: str, state: DashState,
                      keys_hi, keys_lo, vals, words, valid, capacity: int):
    from repro.core import engine
    from repro.kernels import ops
    lanes, src, keep = ops.route_writes(
        cfg, mode, state, (keys_hi, keys_lo, vals, words, valid), capacity)

    def body(st, ln):
        return _merged_insert_body(cfg, st, ln)

    state, (statuses, acts) = engine._segment_parallel(cfg, state, lanes, body)
    return (state, engine._scatter_statuses(statuses, src, keys_hi.shape[0]),
            jnp.any(acts))


def fused_insert(cfg: DashConfig, mode: str, state: DashState,
                 keys_hi, keys_lo, vals, words=None, valid=None,
                 capacity: int | None = None):
    """Single-dispatch batch insert: route -> probe -> hint -> merged
    scatter commit, one jitted program. Returns (state, statuses,
    any_stash_activation) with the exact semantics (and bit pattern) of
    ``engine.insert_batch`` — falls back to the reference engines for
    configs outside ``fused_insert_eligible``."""
    from repro.core import engine
    n = keys_hi.shape[0]
    if words is None:
        words = jnp.zeros((n, cfg.key_heap_words), U32)
    if valid is None:
        valid = jnp.ones(n, jnp.bool_)
    if not fused_insert_eligible(cfg):
        return engine.insert_batch(cfg, mode, state, keys_hi, keys_lo, vals,
                                   words, valid, batching="scan")
    if capacity is None:
        capacity = engine._pow2_at_least(n)
    return _fused_insert_jit(cfg, mode, state, keys_hi, keys_lo, vals, words,
                             valid, min(capacity, engine._pow2_at_least(n)))
