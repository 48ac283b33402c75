"""Pallas TPU kernel: fingerprint probe (the paper's SIMD fingerprint scan).

The paper's probe hot-path scans 18 one-byte fingerprints per bucket with
SIMD before touching any key (Sec. 4.2). On TPU the analogous unit is the VPU
(8x128 lanes) with the MXU doing the bucket-row *gather* as a one-hot matmul
— the idiomatic TPU replacement for random row gathers.

Layout adaptation (DESIGN.md Sec. 2): a segment's fingerprint plane is padded
to a (128, 128) uint8 tile — 128 bucket rows (64 normal + stash + pad) by 128
lanes (first 16 = slot fingerprints). 128 is the MXU's native dimension, so
the one-hot gather `fp_plane^T @ one_hot(q_b)^T` is a single aligned MXU
pass, and the fingerprint compare runs with the queries on full VPU lanes. This mirrors the paper's
choice of a 256-byte bucket (the Optane block): size the probe unit to the
hardware's native transfer/compute block.

Grid: (segments, query_blocks). Each program probes a block of BQ queries,
already routed to their segment (the DHT dispatch of distributed/dht.py),
against that segment's resident fingerprint plane:

    out[s, q] = match bitmap of query q's fingerprint over the allocated
                slots of its target bucket (and probing bucket), 14 bits.

Queries with bucket id -1 are padding (bitmap 0). Key verification of the
(rare) matches happens outside — exactly the paper's "only access slots with
matching fingerprints".
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BQ = 128          # queries per program — one full VPU/MXU row block
ROWS = 128        # padded bucket rows per segment (64+stash -> 128)
LANES = 128       # padded fingerprint lanes (16 real -> 128)
NSLOTS = 14


SROWS = 16        # slot rows of a gathered bucket (14 real -> 16)


def _probe_block(fp_ref, alloc_ref, qfp_ref, qb_ref, qpb_ref,
                 out_b_ref, out_pb_ref, free_b_ref, free_pb_ref):
    """One (segment, query-block) program, queries on the lanes.

    The one-hot is built transposed, (ROWS, BQ), so every per-query value
    is a (1, BQ) row and every per-slot value a (SROWS, BQ) tile: the MXU
    gathers the query's bucket column out of the fingerprint plane and out
    of the unpacked allocation bits, and the slot fold is a sublane
    reduction. Values are below 256, so the gather is exact at any matmul
    precision."""
    fp = fp_ref[0].astype(jnp.int32).astype(jnp.float32)   # (ROWS, LANES)
    alloc = alloc_ref[0]                                   # (1, ROWS) 14-bit bitmaps
    qfp = qfp_ref[0]                                       # (1, BQ)
    abits = ((alloc >> jax.lax.broadcasted_iota(jnp.int32, (SROWS, ROWS), 0))
             & 1).astype(jnp.float32)                      # (SROWS, ROWS)
    rows = jax.lax.broadcasted_iota(jnp.int32, (ROWS, BQ), 0)
    slot = jax.lax.broadcasted_iota(jnp.int32, (SROWS, BQ), 0)
    real = slot < NSLOTS

    def gather_and_match(qb):
        onehot = (rows == qb).astype(jnp.float32)                    # (ROWS, BQ)
        gfp = jax.lax.dot_general(                                   # fp^T @ onehot
            fp, onehot, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)[:SROWS]              # (SROWS, BQ)
        galloc = jnp.dot(abits, onehot, preferred_element_type=jnp.float32)
        live = (galloc > 0.5) & real
        hit = live & (gfp.astype(jnp.int32) == qfp)
        bits = jnp.sum(hit.astype(jnp.int32) << slot, axis=0, keepdims=True)
        # free-slot bitmap of the same gathered bucket (reused by the insert
        # router — same plane view, no extra gather); 0 for padding lanes
        free = jnp.sum(((galloc < 0.5) & real).astype(jnp.int32) << slot,
                       axis=0, keepdims=True)
        return bits, jnp.where(qb < 0, 0, free)

    out_b_ref[0], free_b_ref[0] = gather_and_match(qb_ref[0])
    out_pb_ref[0], free_pb_ref[0] = gather_and_match(qpb_ref[0])


@functools.partial(jax.jit, static_argnames=("interpret",))
def fingerprint_probe(fp_padded, alloc, q_fp, q_b, q_pb, *, interpret: bool):
    """Batched fingerprint probe over routed queries.

    Args:
      fp_padded: (S, ROWS, LANES) uint8 — per-segment padded fp planes.
      alloc:     (S, ROWS) int32 — per-bucket allocation bitmaps (14 bits).
      q_fp:      (S, C) int32 — query fingerprint bytes, routed per segment.
      q_b, q_pb: (S, C) int32 — target/probing bucket rows (-1 = padding).
      interpret: run the Pallas interpreter (CPU tests) instead of Mosaic.

    Returns:
      (bits_b, bits_pb, free_b, free_pb): (S, C) int32 — per-query 14-bit
      match bitmaps for the target/probing bucket, plus the free-slot
      bitmaps of the same buckets (bit j set = slot j unallocated; 0 on
      padding lanes). The free bitmaps let the insert router reuse this
      single gather pass: ``ctz(free_b)`` is Alg. 1's first-free-slot.

    The per-segment rows are passed as (S, 1, ·) arrays so that every block's
    last two dimensions are (1, full) or (1, BQ): the TPU's (8, 128) tiling
    rule holds for any S.
    """
    S, C = q_fp.shape
    assert C % BQ == 0, "query capacity must be a multiple of BQ"
    grid = (S, C // BQ)
    qspec = pl.BlockSpec((1, 1, BQ), lambda s, c: (s, 0, c))
    out_i32 = jax.ShapeDtypeStruct((S, 1, C), jnp.int32)
    rows3 = [x.reshape(S, 1, C) for x in (q_fp, q_b, q_pb)]
    outs = pl.pallas_call(
        _probe_block,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, ROWS, LANES), lambda s, c: (s, 0, 0)),  # fp plane: VMEM-resident per segment
            pl.BlockSpec((1, 1, ROWS), lambda s, c: (s, 0, 0)),
            qspec, qspec, qspec,
        ],
        out_specs=[qspec, qspec, qspec, qspec],
        out_shape=[out_i32, out_i32, out_i32, out_i32],
        interpret=interpret,
    )(fp_padded, alloc.reshape(S, 1, ROWS), *rows3)
    return tuple(o.reshape(S, C) for o in outs)


def _match_jnp(fp_padded, alloc, q_fp, qb):
    safe = jnp.clip(qb, 0, fp_padded.shape[1] - 1)
    rows = jnp.take_along_axis(fp_padded.astype(jnp.int32),
                               safe[:, :, None], axis=1)[..., :NSLOTS]
    a = jnp.take_along_axis(alloc, safe, axis=1)                # (S, C)
    slot = jnp.arange(NSLOTS)
    eq = (rows == q_fp[:, :, None]) & (((a[:, :, None] >> slot) & 1) == 1)
    bits = jnp.sum(eq.astype(jnp.int32) << slot, axis=-1)
    free = (~a) & ((1 << NSLOTS) - 1)
    live = qb >= 0
    return jnp.where(live, bits, 0), jnp.where(live, free, 0)


@jax.jit
def fingerprint_probe_jnp(fp_padded, alloc, q_fp, q_b, q_pb):
    """Bit-identical jnp lowering of ``fingerprint_probe`` — the execution
    path on non-TPU hosts. ``pl.pallas_call(interpret=True)`` pays
    per-program interpreter overhead that defeats the kernel's purpose off
    TPU; this lowering expresses the same gather+compare as two
    ``take_along_axis`` passes that XLA:CPU fuses well. Tests pin it (and
    the interpreted Pallas kernel) against the same oracle."""
    bb, fb = _match_jnp(fp_padded, alloc, q_fp, q_b)
    bp, fp_ = _match_jnp(fp_padded, alloc, q_fp, q_pb)
    return bb, bp, fb, fp_
