"""Bucket-level primitives for Dash (probe / insert / displace / stash math).

All functions are pure and operate on the full table state with ``(seg, b)``
indices; mutations return a new state (XLA turns the ``.at[].set`` chains into
in-place updates under donation). Per the paper's persistence discipline
(Alg. 2): record slots are written first, then the *single packed metadata
word* (alloc | membership | count) is published last — the word is the commit
point, and our crash simulator (recovery.py) is allowed to keep slot writes
while dropping the word, never the converse.

Version discipline (the optimistic-concurrency analog, Sec. 4.4, and the
copy-on-write snapshot contract): EVERY mutation of a bucket row — record
slots, the packed metadata word, overflow fingerprints, the packed overflow
word — bumps that bucket's version word by 2 (bit 0 stays the lock bit).
The version plane is therefore a complete change record: the snapshot
verify pass (serving/engine.py) and the O(dirty) publish
(core/epoch.py:SnapshotRegistry.publish_cow) both rely on "content changed
implies version changed"; a silent write would corrupt published snapshots.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import layout
from .layout import DashConfig, DashState, U32

I32 = jnp.int32


def _slot_mask(plane, b, slot):
    """(1, rows, cols) bool: the one element ``[0, b, slot]`` of a
    single-segment plane (none when ``b`` or ``slot`` is out of range)."""
    rows = jnp.arange(plane.shape[1], dtype=I32)[:, None]
    cols = jnp.arange(plane.shape[2], dtype=I32)[None, :]
    return ((rows == b) & (cols == slot))[None]


def set_slot(plane, seg, b, slot, x):
    """``plane.at[seg, b, slot].set(x)`` for an (S, rows, cols) plane.

    On a single-segment view (S == 1: the body the segment-parallel engines
    vmap over every segment) the write is a masked select instead of an
    element scatter. Vmapped over 2**14 segments, XLA:TPU (v5e) put the
    three-index scatter's updates in the wrong elements, while the same
    program on the CPU matched bit for bit; the select has no index to get
    wrong. An out-of-range ``b`` or ``slot`` writes nothing in both forms.
    """
    if plane.shape[0] != 1:
        return plane.at[seg, b, slot].set(x)
    return jnp.where(_slot_mask(plane, b, slot),
                     jnp.asarray(x).astype(plane.dtype), plane)


def get_slot(plane, seg, b, slot):
    """``plane[seg, b, slot]``; a masked reduction on a single-segment view
    (the read twin of :func:`set_slot`)."""
    if plane.shape[0] != 1:
        return plane[seg, b, slot]
    return jnp.max(jnp.where(_slot_mask(plane, b, slot), plane,
                             jnp.zeros((), plane.dtype)))


def slot_fp_matches(cfg: DashConfig, state: DashState, seg, b, fpv):
    """(SLOTS,) bool — allocated slots whose fingerprint matches.

    With fingerprinting disabled (ablation / CCEH baseline) every allocated
    slot is a candidate — modeling the extra key loads the paper avoids.
    """
    meta = state.meta[seg, b]
    alloc = layout.meta_alloc(meta)
    slot_ids = jnp.arange(cfg.num_slots, dtype=U32)
    allocated = ((alloc >> slot_ids) & U32(1)).astype(jnp.bool_)
    if not cfg.use_fingerprints:
        return allocated
    fps = jax.lax.dynamic_slice(state.fp, (seg, b, 0), (1, 1, 16))[0, 0, :cfg.num_slots]
    return allocated & (fps == fpv)


def keys_equal(cfg: DashConfig, state: DashState, seg, b, q_hi, q_lo, q_words):
    """(SLOTS,) bool — full key comparison for every slot (caller masks).

    Inline mode compares the (hi, lo) pair in the slot. Pointer mode treats
    ``key_lo`` as a key-heap handle and compares the heap row against
    ``q_words`` — the 'dereference the 8-byte pointer' path of Sec. 4.5.
    """
    s_hi = state.key_hi[seg, b]
    s_lo = state.key_lo[seg, b]
    if not cfg.pointer_mode:
        return (s_hi == q_hi) & (s_lo == q_lo)
    rows = state.key_heap[s_lo % U32(max(cfg.key_heap_size, 1))]   # (SLOTS, W)
    return (s_hi == q_hi) & jnp.all(rows == q_words[None, :], axis=-1)


def bucket_probe(cfg: DashConfig, state: DashState, seg, b, fpv, q_hi, q_lo, q_words):
    """Search one bucket. Returns (found, slot, value)."""
    cand = slot_fp_matches(cfg, state, seg, b, fpv)
    eq = cand & keys_equal(cfg, state, seg, b, q_hi, q_lo, q_words)
    found = jnp.any(eq)
    slot = jnp.argmax(eq).astype(I32)
    return found, slot, get_slot(state.val, seg, b, slot)


def first_free_slot(cfg: DashConfig, state: DashState, seg, b):
    """(has_free, slot) — lowest clear bit of the alloc bitmap."""
    alloc = layout.meta_alloc(state.meta[seg, b])
    slot_ids = jnp.arange(cfg.num_slots, dtype=U32)
    free = ((alloc >> slot_ids) & U32(1)) == 0
    return jnp.any(free), jnp.argmax(free).astype(I32)


def bucket_count(state: DashState, seg, b):
    return layout.meta_count(state.meta[seg, b]).astype(I32)


def bump_version(state: DashState, seg, b):
    """+2 keeps the lock bit (bit 0) clear — release+version-increment analog."""
    return state._replace(version=state.version.at[seg, b].add(U32(2)))


def bucket_write(cfg: DashConfig, state: DashState, seg, b, slot,
                 k_hi, k_lo, v, fpv, member):
    """Write a record into a known-free slot and publish the metadata word.

    Mirrors Alg. 2 bucket::insert: (1) slot payload, (2) fingerprint,
    (3) one atomic store of alloc|membership|count, (4) version bump.
    """
    state = state._replace(
        key_hi=set_slot(state.key_hi, seg, b, slot, k_hi),
        key_lo=set_slot(state.key_lo, seg, b, slot, k_lo),
        val=set_slot(state.val, seg, b, slot, v),
        fp=set_slot(state.fp, seg, b, slot, fpv),
    )
    meta = state.meta[seg, b]
    alloc = layout.meta_alloc(meta) | (U32(1) << slot.astype(U32))
    memb = layout.meta_member(meta) | jnp.where(member, U32(1) << slot.astype(U32), U32(0))
    count = layout.meta_count(meta) + U32(1)
    state = state._replace(meta=state.meta.at[seg, b].set(layout.meta_pack(alloc, memb, count)))
    return bump_version(state, seg, b)


def bucket_clear_slot(cfg: DashConfig, state: DashState, seg, b, slot, clear_member=True):
    """Delete = clear alloc bit + decrement count in one packed-word store."""
    meta = state.meta[seg, b]
    bit = U32(1) << slot.astype(U32)
    alloc = layout.meta_alloc(meta) & ~bit
    memb = layout.meta_member(meta)
    memb = jnp.where(clear_member, memb & ~bit, memb)
    count = layout.meta_count(meta) - U32(1)
    state = state._replace(meta=state.meta.at[seg, b].set(layout.meta_pack(alloc, memb, count)))
    return bump_version(state, seg, b)


def find_movable_slot(cfg: DashConfig, state: DashState, seg, b, want_member_set):
    """Displacement helper (Alg. 2): pick an allocated slot whose membership
    bit equals ``want_member_set``. Scanning the bitmap only — no key loads
    (the paper's point: the membership bitmap avoids PM reads)."""
    meta = state.meta[seg, b]
    alloc = layout.meta_alloc(meta)
    memb = layout.meta_member(meta)
    slot_ids = jnp.arange(cfg.num_slots, dtype=U32)
    allocated = ((alloc >> slot_ids) & U32(1)) == 1
    mset = ((memb >> slot_ids) & U32(1)) == 1
    ok = allocated & (mset == want_member_set)
    return jnp.any(ok), jnp.argmax(ok).astype(I32)


def read_slot(state: DashState, seg, b, slot):
    return tuple(get_slot(p, seg, b, slot) for p in
                 (state.key_hi, state.key_lo, state.val, state.fp))


# ---- overflow (stash) metadata on the home bucket --------------------------

def ofp_try_set(cfg: DashConfig, state: DashState, seg, b, fpv, stash_idx, member):
    """Try to record an overflow fingerprint on bucket ``b``.
    Returns (state, ok).

    A successful set bumps the bucket's version word: overflow metadata
    changes what a probe of ``b`` observes, so it must be visible to the
    version-plane verify pass and to the copy-on-write publish (which
    scatters exactly the version-changed bucket rows)."""
    if cfg.num_ofp == 0:
        return state, jnp.asarray(False)
    om = state.ometa[seg, b]
    oa = layout.ometa_ofp_alloc(om)
    ids = jnp.arange(cfg.num_ofp, dtype=U32)
    free = ((oa >> ids) & U32(1)) == 0
    ok = jnp.any(free)
    slot = jnp.argmax(free).astype(I32)
    new_oa = oa | (U32(1) << slot.astype(U32))
    omem = layout.ometa_ofp_member(om)
    new_omem = omem | jnp.where(member, U32(1) << slot.astype(U32), U32(0))
    om2 = (om & ~((U32(0xF) << layout.OFPA_SHIFT) | (U32(0xF) << layout.OFPM_SHIFT)))
    om2 = om2 | (new_oa << layout.OFPA_SHIFT) | (new_omem << layout.OFPM_SHIFT)
    om2 = layout.ometa_set_stash_idx(om2, slot, stash_idx.astype(U32))
    om2 = om2 | (U32(1) << layout.OVFB_SHIFT)
    om_out = jnp.where(ok, om2, om)
    st = state._replace(
        ometa=state.ometa.at[seg, b].set(om_out),
        ofp=jnp.where(ok, set_slot(state.ofp, seg, b, slot, fpv), state.ofp),
        version=jnp.where(ok, state.version.at[seg, b].add(U32(2)),
                          state.version),
    )
    return st, ok


def ovf_count_add(state: DashState, seg, b, delta):
    """Adjust the overflow counter (records in stash with no ofp slot).
    Version-bumped like every metadata write (COW dirtiness contract)."""
    om = state.ometa[seg, b]
    cnt = (layout.ometa_ovf_count(om).astype(jnp.int32) + delta).astype(U32)
    om = (om & ~(U32(0x7F) << layout.OVFC_SHIFT)) | ((cnt & U32(0x7F)) << layout.OVFC_SHIFT)
    om = om | (U32(1) << layout.OVFB_SHIFT)
    return bump_version(state._replace(ometa=state.ometa.at[seg, b].set(om)),
                        seg, b)


def ofp_matches(cfg: DashConfig, state: DashState, seg, b, fpv, want_member):
    """(NOFP,) bool — overflow fingerprints on bucket ``b`` that match ``fpv``
    and whose membership equals ``want_member`` (Sec. 4.3 overflow probing)."""
    if cfg.num_ofp == 0:
        return jnp.zeros((0,), jnp.bool_)
    om = state.ometa[seg, b]
    oa = layout.ometa_ofp_alloc(om)
    omem = layout.ometa_ofp_member(om)
    ids = jnp.arange(cfg.num_ofp, dtype=U32)
    allocated = ((oa >> ids) & U32(1)) == 1
    mset = ((omem >> ids) & U32(1)) == 1
    fps = jax.lax.dynamic_slice(state.ofp, (seg, b, 0), (1, 1, 4))[0, 0, :cfg.num_ofp]
    return allocated & (mset == want_member) & (fps == fpv)


def ofp_clear(cfg: DashConfig, state: DashState, seg, b, slot):
    om = state.ometa[seg, b]
    bit = U32(1) << slot.astype(U32)
    oa = layout.ometa_ofp_alloc(om) & ~bit
    omem = layout.ometa_ofp_member(om) & ~bit
    om2 = (om & ~((U32(0xF) << layout.OFPA_SHIFT) | (U32(0xF) << layout.OFPM_SHIFT)))
    om2 = om2 | (oa << layout.OFPA_SHIFT) | (omem << layout.OFPM_SHIFT)
    return bump_version(state._replace(ometa=state.ometa.at[seg, b].set(om2)),
                        seg, b)
