"""Pallas TPU kernel: bulk key hashing (murmur-style mixers on the VPU).

Hashing is the other per-op fixed cost of the data path (Sec. 2.2 notes the
hash function is orthogonal but every op pays it). The mixer is pure
shift/xor/multiply — ideal VPU work. One program hashes a (BLOCK,) tile of
(hi, lo) key pairs into (h1, h2, fingerprint) with both seeds, fused so the
key words are read from VMEM once (the 'touch the bytes once' discipline the
paper applies to PM, applied to HBM).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import hashing

BLOCK = 1024


def _mix_block(hi_ref, lo_ref, h1_ref, h2_ref, fp_ref):
    hi = hi_ref[...]
    lo = lo_ref[...]
    h1 = hashing.hash_pair(hi, lo, hashing.SEED1)
    h2 = hashing.hash_pair(hi, lo, hashing.SEED2)
    h1_ref[...] = h1
    h2_ref[...] = h2
    fp_ref[...] = (h2 & jnp.uint32(0xFF)).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def bulk_hash(key_hi, key_lo, *, interpret: bool):
    """(h1, h2, fp) for a (N,) uint32-pair key batch. N % BLOCK == 0.
    ``interpret=True`` runs the Pallas interpreter (CPU tests)."""
    n = key_hi.shape[0]
    assert n % BLOCK == 0, "pad key batches to BLOCK"
    spec = pl.BlockSpec((BLOCK,), lambda i: (i,))
    return pl.pallas_call(
        _mix_block,
        grid=(n // BLOCK,),
        in_specs=[spec, spec],
        out_specs=[spec, spec, spec],
        out_shape=[jax.ShapeDtypeStruct((n,), jnp.uint32),
                   jax.ShapeDtypeStruct((n,), jnp.uint32),
                   jax.ShapeDtypeStruct((n,), jnp.int32)],
        interpret=interpret,
    )(key_hi, key_lo)
