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
    Reads. On TPU: ``fused_probe`` — one Pallas mega-kernel whose grid walks
    the segments the batch actually touches; each program fuses the one-hot
    MXU bucket gather (the route), the fingerprint compare (the probe), the
    16-bit-half key compare (the verify) and the value select, for the
    target bucket, the probing bucket and the stash rows. Pallas's grid
    pipeline double-buffers the next segment's plane block into VMEM while
    the current one computes. On non-TPU hosts: a direct-addressed jnp
    lowering — a single gather of the (window + stash) candidate rows per
    query and one dense compare, no lane planes at all (those only pay off
    as TPU VMEM blocking).

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

from repro.core import bucket as bk
from repro.core import hashing, layout
from repro.core.layout import (DROPPED, EXISTS, INSERTED, NEED_SPLIT,
                               DashConfig, DashState, U32)

I32 = jnp.int32

BQ = 128          # queries per kernel program (full VPU/MXU row block)
ROWS = 128        # padded bucket rows per segment plane


# ---------------------------------------------------------------------------
# eligibility
# ---------------------------------------------------------------------------

def fused_search_eligible(cfg: DashConfig) -> bool:
    """The direct jnp read path covers every config: balanced pairs or
    linear-probe windows, fingerprints on/off, pointer mode (heap rows are
    gathered and compared like ``bucket.keys_equal``), stash on/off."""
    return True


def fused_kernel_eligible(cfg: DashConfig) -> bool:
    """Configs the Pallas mega-kernel spans: inline keys and a 2-bucket
    window (balanced pairs, or probe_len <= 2), planes within the padded
    tile. Fingerprints may be off — the wrapper feeds zero fp planes and
    zero query bytes so the compare degenerates to the allocated mask."""
    return (not cfg.pointer_mode
            and (cfg.use_balanced or cfg.probe_len <= 2)
            and cfg.buckets_total <= ROWS)


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
# fused read — the Pallas mega-kernel (TPU path; interpret mode in tests)
# ---------------------------------------------------------------------------

# A touched segment's plane, slot-major: feature k of slot j sits in row
# k * SROWS + j, bucket row r in column r. The eight features are the
# fingerprint byte, the allocation bit, and the 16-bit halves of key_hi,
# key_lo and the value, so one (FEATS, ROWS) @ (ROWS, BQ) one-hot matmul
# gathers everything a query compares against in its bucket.
SROWS = 16                        # slot rows per feature (14 real -> 16)
F_FP, F_ALLOC, F_KHIA, F_KHIB, F_KLOA, F_KLOB, F_VA, F_VB = range(8)
FEATS = 8 * SROWS                 # = 128, one MXU tile


def _halves(x):
    """Split a uint32 plane into (lo16, hi16) int32 halves — exact in f32."""
    xi = x.astype(jnp.uint32)
    return ((xi & U32(0xFFFF)).astype(jnp.int32),
            (xi >> U32(16)).astype(jnp.int32))


def _fused_read_block(plane_ref, qfp_ref, qb_ref, qpb_ref, qhia_ref,
                      qhib_ref, qloa_ref, qlob_ref, found_ref, val_ref, *,
                      nb: int, ns: int):
    """One (touched-segment, query-block) program, queries on the lanes:
    one one-hot MXU gather per probed row (target bucket, probing bucket,
    then each stash row) pulls every feature of that row, the keys are
    verified in 16-bit halves, and the first matching slot's value is
    selected. The fp32-precision matmul keeps the 16-bit halves exact."""
    plane = plane_ref[0].astype(jnp.float32)               # (FEATS, ROWS)
    qfp = qfp_ref[0]                                       # (1, BQ) each
    q = [r[0] for r in (qhia_ref, qhib_ref, qloa_ref, qlob_ref)]
    qb = qb_ref[0]
    live = qb >= 0
    rows = jax.lax.broadcasted_iota(jnp.int32, (ROWS, BQ), 0)
    slot = jax.lax.broadcasted_iota(jnp.int32, (SROWS, BQ), 0)

    def row_hits(qrow):
        onehot = (rows == qrow).astype(jnp.float32)        # (ROWS, BQ)
        g = jnp.dot(plane, onehot, precision=jax.lax.Precision.HIGHEST,
                    preferred_element_type=jnp.float32).astype(jnp.int32)

        def f(k):
            return g[k * SROWS:(k + 1) * SROWS]            # (SROWS, BQ)

        eq = ((f(F_ALLOC) == 1) & (f(F_FP) == qfp)
              & (f(F_KHIA) == q[0]) & (f(F_KHIB) == q[1])
              & (f(F_KLOA) == q[2]) & (f(F_KLOB) == q[3]) & live)
        first = jnp.min(jnp.where(eq, slot, SROWS), axis=0, keepdims=True)
        val = f(F_VA) | (f(F_VB) << 16)
        return first < SROWS, jnp.sum(jnp.where(slot == first, val, 0),
                                      axis=0, keepdims=True)

    found, val = row_hits(qb)
    for qrow in [qpb_ref[0]] + [jnp.full_like(qb, nb + s) for s in range(ns)]:
        ok, v = row_hits(qrow)
        val = jnp.where(ok & ~found, v, val)
        found = found | ok
    found_ref[0] = found.astype(jnp.int32)
    val_ref[0] = val


def fused_plane_views(cfg: DashConfig, state: DashState, segments):
    """(U, FEATS, ROWS) int32 slot-major planes of the given segments only.

    ``segments``: (U,) int32 segment ids (may repeat for padding). Stash
    rows beyond each segment's ``stash_active`` get a zero alloc bit so the
    kernel needs no activation logic. With fingerprints disabled the fp
    feature is zero (queries feed zero bytes -> compare is a no-op)."""
    BT, ns, NB, SL = (cfg.buckets_total, cfg.num_stash, cfg.num_buckets,
                      cfg.num_slots)
    alloc = layout.meta_alloc(state.meta[segments])                  # (U, BT)
    if ns:
        srow = jnp.arange(BT) - NB                           # stash index or <0
        act = state.stash_active[segments][:, None]
        alloc = jnp.where((srow[None, :] >= 0) & (srow[None, :] >= act),
                          U32(0), alloc)
    abits = ((alloc[..., None] >> jnp.arange(SL, dtype=U32)) & U32(1))
    fp = state.fp[segments][..., :SL].astype(jnp.int32)
    if not cfg.use_fingerprints:
        fp = jnp.zeros_like(fp)
    feats = ((fp, abits.astype(jnp.int32)) + _halves(state.key_hi[segments])
             + _halves(state.key_lo[segments]) + _halves(state.val[segments]))
    x = jnp.stack(feats, axis=1)                             # (U, 8, BT, SL)
    x = jnp.pad(x, ((0, 0), (0, 0), (0, ROWS - BT), (0, SROWS - SL)))
    return x.transpose(0, 1, 3, 2).reshape(x.shape[0], FEATS, ROWS)


@functools.partial(jax.jit, static_argnames=("nb", "ns", "interpret"))
def fused_probe(planes, q_fp, q_b, q_pb, q_hi, q_lo, *, nb: int, ns: int,
                interpret: bool):
    """The mega-kernel: route+probe+verify over compact touched segments.

    Args:
      planes: output of ``fused_plane_views`` — (U, FEATS, ROWS).
      q_fp, q_b, q_pb: (U, C) int32 routed fingerprint bytes and bucket
        rows (-1 = padding lane).
      q_hi, q_lo: (U, C) uint32 routed key words.
      interpret: run the Pallas interpreter (CPU tests) instead of Mosaic.

    Returns (found, val): (U, C) int32 / uint32 per-lane results. The grid
    is (U, C // BQ) with per-segment plane blocks: Pallas's sequential grid
    pipeline prefetches segment u+1's block while u computes — the
    double-buffering this path is named for. Query rows travel as (U, 1, C)
    so every block satisfies the TPU's (8, 128) tiling rule.
    """
    U, C = q_fp.shape
    assert C % BQ == 0
    rows3 = [x.reshape(U, 1, C)
             for x in (q_fp, q_b, q_pb) + _halves(q_hi) + _halves(q_lo)]
    grid = (U, C // BQ)
    pspec = pl.BlockSpec((1, FEATS, ROWS), lambda s, c: (s, 0, 0))
    qspec = pl.BlockSpec((1, 1, BQ), lambda s, c: (s, 0, c))
    out_i32 = jax.ShapeDtypeStruct((U, 1, C), jnp.int32)
    found, val = pl.pallas_call(
        functools.partial(_fused_read_block, nb=nb, ns=ns),
        grid=grid,
        in_specs=[pspec] + [qspec] * 7,
        out_specs=[qspec, qspec],
        out_shape=[out_i32, out_i32],
        interpret=interpret,
    )(planes, *rows3)
    return found.reshape(U, C), val.reshape(U, C).astype(U32)


@functools.partial(jax.jit, static_argnames=("nb", "ns"))
def fused_probe_jnp(planes, q_fp, q_b, q_pb, q_hi, q_lo, *, nb: int, ns: int):
    """Bit-identical jnp lowering of ``fused_probe`` (the differential
    oracle the kernel is pinned against). Same visit order, same
    first-slot rule, same padded-lane masking."""
    qhia, qhib = _halves(q_hi)
    qloa, qlob = _halves(q_lo)
    live = q_b >= 0
    u = jnp.arange(planes.shape[0])[:, None]

    def row_hits(qrow):
        g = planes[u, :, jnp.clip(qrow, 0, ROWS - 1)]      # (U, C, FEATS)
        g = jnp.where((qrow >= 0)[..., None], g, 0)

        def f(k):
            return g[..., k * SROWS:(k + 1) * SROWS]       # (U, C, SROWS)

        eq = ((f(F_ALLOC) == 1) & (f(F_FP) == q_fp[..., None])
              & (f(F_KHIA) == qhia[..., None]) & (f(F_KHIB) == qhib[..., None])
              & (f(F_KLOA) == qloa[..., None]) & (f(F_KLOB) == qlob[..., None])
              & live[..., None])
        val = f(F_VA) | (f(F_VB) << 16)
        j = jnp.argmax(eq, axis=-1)
        return jnp.any(eq, axis=-1), jnp.where(
            jnp.any(eq, axis=-1),
            jnp.take_along_axis(val, j[..., None], axis=-1)[..., 0], 0)

    found, val = row_hits(q_b)
    for qrow in [q_pb] + [jnp.full_like(q_b, nb + s) for s in range(ns)]:
        ok, v = row_hits(qrow)
        val = jnp.where(ok & ~found, v, val)
        found = found | ok
    return found.astype(jnp.int32), val.astype(U32)


# ---------------------------------------------------------------------------
# fused read — host-facing dispatch
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnums=(0, 1, 6, 7))
def _fused_search_routed(cfg: DashConfig, mode: str, state: DashState,
                         keys_hi, keys_lo, words, capacity: int,
                         interpret: bool):
    """TPU path: route queries to the segments the batch touches, run the
    mega-kernel over those segments only, scatter results back.
    Capacity-overflow lanes fall back to the direct path, mirroring
    ``_search_batch_routed``. ``interpret=True`` runs the kernel in the
    Pallas interpreter (the CPU tests); the TPU dispatcher passes False."""
    from repro.kernels import ops
    h1 = hashing.hash1(keys_hi, keys_lo)
    h2 = hashing.hash2(keys_hi, keys_lo)
    fpv = (h2 & U32(0xFF)).astype(jnp.int32)
    if not cfg.use_fingerprints:
        fpv = jnp.zeros_like(fpv)
    seg, b = ops.locate_batch(cfg, mode, state, h1)
    NB = cfg.num_buckets
    n = keys_hi.shape[0]
    segments, cid = ops.touched_segments(seg, min(n, cfg.max_segments))
    lanes, src, keep = ops.route_lanes(
        cid, (fpv, b.astype(jnp.int32), keys_hi, keys_lo),
        segments.shape[0], capacity, (-1, -1, 0, 0))
    q_fp, q_b, q_hi, q_lo = lanes
    q_pb = jnp.where(q_b >= 0, (q_b + 1) & (NB - 1), -1)
    f, v = fused_probe(fused_plane_views(cfg, state, segments), q_fp, q_b,
                       q_pb, q_hi, q_lo, nb=NB, ns=cfg.num_stash,
                       interpret=interpret)
    flatf, flatv = f.reshape(-1) != 0, v.reshape(-1)
    srcf = src.reshape(-1)
    ok = jnp.clip(srcf, 0)
    found = jnp.zeros((n,), jnp.bool_).at[ok].max(jnp.where(srcf >= 0, flatf, False))
    val = jnp.zeros((n,), U32).at[ok].max(jnp.where(srcf >= 0, flatv, U32(0)))
    if capacity >= n:
        return found, val           # no lane can overflow: keep is all-True

    def fallback(_):
        return _fused_search_direct(cfg, mode, state, keys_hi, keys_lo, words)

    def none(_):
        return jnp.zeros_like(found), jnp.zeros_like(val)

    f2, v2 = jax.lax.cond(jnp.any(~keep), fallback, none, None)
    return jnp.where(keep, found, f2), jnp.where(keep, val, v2)


def fused_search(cfg: DashConfig, mode: str, state: DashState,
                 keys_hi, keys_lo, words=None, capacity: int | None = None):
    """Single-dispatch batched lookup. Returns (found, values), bit-identical
    to ``engine.search_batch(batching="vmap")``.

    Non-TPU hosts always take the direct-addressed lowering (one gather +
    one dense compare — no routing, which is the whole point at small
    batches). TPU hosts take the routed mega-kernel when the config is in
    its span, the direct lowering otherwise. ``capacity`` is the lanes per
    touched segment; the default (the padded batch) can never overflow."""
    n = keys_hi.shape[0]
    if words is None:
        words = jnp.zeros((n, cfg.key_heap_words), U32)
    if jax.default_backend() == "tpu" and fused_kernel_eligible(cfg):
        if capacity is None:
            capacity = max(BQ, 1 << (max(n - 1, 1)).bit_length())
        return _fused_search_routed(cfg, mode, state, keys_hi, keys_lo,
                                    words, capacity, False)
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
