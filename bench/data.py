"""Data from the seed, and the plain reference the served answers are
checked against.

``make_data`` draws the loaded records and the spare keys (never loaded:
fresh inserts take them). ``Reference`` is a sorted array of the loaded
records with a per-key history of every acknowledged write on top; it
imports nothing of the program. A read is correct when its answer
(presence and value) is a state the key held at some point between the
read's submission and its acknowledgement: the state after every write
acknowledged before the read was submitted, or the effect of a write
acknowledged while the read was outstanding. A write is correct when its
status is what the writes acknowledged before it imply.
"""
from __future__ import annotations

import bisect
from typing import Optional

import numpy as np

# statuses of the program's write interface (core/layout.py), restated
INSERTED, EXISTS, NOT_FOUND = 0, 1, 4

READ, UPDATE, INSERT, DELETE, RMW = "read", "update", "insert", "delete", "rmw"
WRITES = (UPDATE, INSERT, DELETE, RMW)

_ABSENT = None


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """Independent generator per use of the seed (any integer, 64 bits)."""
    return np.random.default_rng([int(seed) & 0xFFFFFFFFFFFFFFFF, stream])


def make_data(seed: int, n_load: int, n_spare: int, key_bytes: int = 8):
    """(keys, vals, spare): ``n_load`` distinct nonzero keys of ``key_bytes``
    bytes (held as uint64) in random order with nonzero uint32 values, and
    ``n_spare`` further distinct keys that are not loaded."""
    rng = rng_for(seed, 0)
    want = n_load + n_spare
    top = 2**63 if key_bytes >= 8 else 2**(8 * key_bytes)
    raw = rng.integers(1, top, size=want + want // 32 + 4096,
                       dtype=np.uint64)
    pool = np.unique(raw)
    if pool.size < want:
        raise RuntimeError("key generator produced too few distinct keys")
    pool = rng.permutation(pool)[:want]
    vals = rng.integers(1, 2**32, size=n_load, dtype=np.uint64)
    return pool[:n_load], vals.astype(np.uint32), pool[n_load:]


class Reference:
    """Loaded records plus the history of acknowledged writes."""

    def __init__(self, keys: np.ndarray, vals: np.ndarray):
        order = np.argsort(keys)
        self.keys = np.asarray(keys, np.uint64)[order]
        self.vals = np.asarray(vals, np.uint32)[order]
        # key -> ([harvest, ...], [value or None, ...]) in acknowledgement
        # order; a harvest's last entry is the key's state after it
        self.hist: dict = {}

    def loaded(self, key: int):
        i = int(np.searchsorted(self.keys, np.uint64(key)))
        if i < self.keys.size and int(self.keys[i]) == key:
            return int(self.vals[i])
        return _ABSENT

    def state_at(self, key: int, harvest: int):
        """The key's value (None: absent) after every write acknowledged in
        harvests ``<= harvest``."""
        h = self.hist.get(key)
        if h is None:
            return self.loaded(key)
        i = bisect.bisect_right(h[0], harvest)
        return h[1][i - 1] if i else self.loaded(key)

    def apply_write(self, kind: str, key: int, value: int, status: int,
                    harvest: int) -> int:
        """Apply one acknowledged write; returns the status the reference
        expects for it (taken against the state before the write)."""
        h = self.hist.get(key)
        before = (h[1][-1] if h else self.loaded(key))
        if kind == INSERT:
            want = INSERTED if before is _ABSENT else EXISTS
            after = value if before is _ABSENT else before
        elif kind == DELETE:
            want = NOT_FOUND if before is _ABSENT else INSERTED
            after = _ABSENT
        else:                                   # update, rmw
            want = NOT_FOUND if before is _ABSENT else INSERTED
            after = before if before is _ABSENT else value
        if h is None:
            h = self.hist[key] = ([], [])
        h[0].append(harvest)
        h[1].append(after)
        return want

    def read_ok(self, key: int, found: bool, result: int, sub: int,
                ack: int) -> bool:
        """True when (found, result) is a state the key held between the
        read's submission (after harvest ``sub``) and its acknowledgement
        (harvest ``ack``)."""
        got = int(result) if found else _ABSENT
        h = self.hist.get(key)
        if h is None:
            return got == self.loaded(key)
        if got == self.state_at(key, sub):
            return True
        lo = bisect.bisect_right(h[0], sub)
        hi = bisect.bisect_right(h[0], ack)
        return got in h[1][lo:hi]


def check_ops(ref: Reference, ops) -> dict:
    """Replay acknowledged ops (each with ``kind, key, value, status, found,
    result, h_sub, h_ack``) against the reference: writes first, in
    acknowledgement order, then every read. Returns counts."""
    ops = sorted(ops, key=lambda o: o.h_ack)      # stable: batch order kept
    bad_status = reads = writes = 0
    for op in ops:
        if op.kind in WRITES:
            writes += 1
            before = None
            if op.kind == RMW:
                before = ref.state_at(op.key, op.h_ack - 1)
            want = ref.apply_write(op.kind, op.key, op.value, op.status,
                                   op.h_ack)
            if op.status != want:
                bad_status += 1
            if op.kind == RMW and not _rmw_read_ok(op, before):
                bad_status += 1
    bad_reads = 0
    plain = [op for op in ops if op.kind == READ and op.key not in ref.hist]
    if plain:
        keys = np.fromiter((op.key for op in plain), np.uint64, len(plain))
        i = np.clip(np.searchsorted(ref.keys, keys), 0, ref.keys.size - 1)
        present = ref.keys[i] == keys
        found = np.fromiter((op.found for op in plain), bool, len(plain))
        got = np.fromiter((op.result for op in plain), np.uint64, len(plain))
        bad_reads += int(np.sum((found != present)
                                | (present & (got != ref.vals[i]))))
    for op in ops:
        if op.kind == READ and op.key in ref.hist:
            if not ref.read_ok(op.key, op.found, op.result, op.h_sub,
                               op.h_ack):
                bad_reads += 1
    reads = sum(1 for op in ops if op.kind == READ)
    return {"reads": reads, "writes": writes, "read_wrong": bad_reads,
            "write_status_wrong": bad_status}


def _rmw_read_ok(op, before: Optional[int]) -> bool:
    got = int(op.result) if op.found else _ABSENT
    return got == before
