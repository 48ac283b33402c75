"""Read the durable image: every record the pool file holds.

After the window, every acknowledged write has to be in the pool. This
reads the pool's plane regions (the program's format reader,
``PmPool.plane``) and decodes the records with numpy alone: a slot holds
a record when its bucket's allocation bit is set (stash buckets only up
to the segment's active stash count). ``lost_writes`` compares the final
value of every written key with the reference.
"""
from __future__ import annotations

import numpy as np

SLOT_MASK = (1 << 14) - 1             # allocation bits of a bucket's meta word


def image_records(path: str, num_buckets: int):
    """(keys, vals) of every record in the pool at ``path``, sorted by key."""
    from repro.persist.pool import PmPool
    pool = PmPool.open(path)
    try:
        meta = np.asarray(pool.plane("meta"))                  # (S, BT)
        S, BT = meta.shape
        alloc = meta & np.uint32(SLOT_MASK)
        stash = np.asarray(pool.plane("stash_active")).reshape(S, 1)
        row = np.arange(BT).reshape(1, BT)
        alloc = np.where((row >= num_buckets) & (row - num_buckets >= stash),
                         np.uint32(0), alloc)
        SL = pool.plane("key_hi").shape[-1]
        bits = ((alloc[..., None] >> np.arange(SL, dtype=np.uint32)) & 1) == 1
        hi = np.asarray(pool.plane("key_hi"))[bits].astype(np.uint64)
        lo = np.asarray(pool.plane("key_lo"))[bits].astype(np.uint64)
        vals = np.asarray(pool.plane("val"))[bits]
    finally:
        pool.close()
    keys = (hi << np.uint64(32)) | lo
    order = np.argsort(keys)
    return keys[order], vals[order]


def lost_writes(ref, path: str, num_buckets: int) -> int:
    """Written keys whose durable state differs from the reference's final
    state (a deleted key must be absent), plus keys held twice."""
    keys, vals = image_records(path, num_buckets)
    dup = int(np.sum(keys[1:] == keys[:-1])) if keys.size else 0
    written = list(ref.hist)
    if not written:
        return dup
    wk = np.asarray(written, np.uint64)
    i = np.clip(np.searchsorted(keys, wk), 0, max(keys.size - 1, 0))
    present = (keys[i] == wk) if keys.size else np.zeros(wk.size, bool)
    lost = 0
    for k, p, j in zip(written, present, i):
        want = ref.hist[k][1][-1]
        if want is None:
            lost += bool(p)
        elif not p or int(vals[j]) != want:
            lost += 1
    return lost + dup
