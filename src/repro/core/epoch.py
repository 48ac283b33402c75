"""Epoch-based reclamation + copy-on-write versioned snapshot registry.

Dash readers hold no locks, so a snapshot being read must not be reclaimed
until every reader that could see it has exited (paper Sec. 4.4). In our
batched adaptation the unit of protection is a STATE SNAPSHOT (the
functional table version a search batch runs against): writers publish new
versions; old versions are retired into the epoch's limbo list and freed two
epochs later — the classic 3-epoch scheme.

Three layers live here:

``EpochManager``
    The grace-period core: readers ``pin()`` an epoch around a read critical
    section; writers ``retire()`` superseded payloads; a payload is reclaimed
    once no pinned reader can still reference it (2 epochs later).

``PlanePool``
    Refcounts on published plane buffers. Copy-on-write versions SHARE
    planes: an untouched plane of version v_n is aliased (the same device
    array object) into v_n+1, v_n+2, ... Reclamation is therefore
    plane-level, not snapshot-level: retiring v_n releases one reference on
    each of its planes, and a plane's device buffer is deleted only when no
    newer snapshot still aliases it. (The pre-PR-4 whole-snapshot
    ``leaf.delete()`` would free planes still aliased by newer versions.)
    The live table state never enters the pool — the engine's mutating
    dispatches donate (consume) the live buffers, so snapshots always own
    or pool-share their planes, never the live arrays.

``SnapshotRegistry``
    The serving-frontend contract on top. ``publish_cow(cfg, live)`` installs
    the live state as the next version in O(dirty) bytes:

      * the per-bucket-row dirty mask is the version-plane diff against the
        previous version (``engine.changed_rows`` — every plane mutation
        bumps its bucket's version word, see core/bucket.py), so an insert
        batch republises a few hundred rows, an SMO republises exactly the
        rebuilt segments, and everything else is shared;
      * dirty rows of the record planes are scattered into the previous
        version's buffers IN PLACE when that version is unpinned and its
        planes are unshared (buffer donation — the common frontend cadence),
        otherwise into fresh copies (the pinned-reader slow path);
      * the directory and per-segment metadata planes carry no version
        words, so one bundled device compare decides alias-vs-copy for
        them; scalars are tiny and copied every publish.

    ``acquire()`` returns the current Snapshot under an epoch pin AND a
    per-snapshot pin count — the pin count is what makes in-place donation
    safe (a pinned version's planes are never donated). ``publish(state)``
    is the legacy whole-payload path (still used for arbitrary payloads).

Publish lifecycle (one write batch)::

    v_n (snapshot) ──alias──────────────► v_n+1   clean planes: refcount++
         │                                  ▲
         │ dirty rows (version-plane diff)  │
         └─────────scatter (donated)────────┘     O(dirty) bytes moved
    v_n retired ─► limbo ─► release planes (refcount--; delete at zero)
"""
from __future__ import annotations

import functools
import math
import threading
from collections import defaultdict
from typing import Any, Callable, Optional

import numpy as np

from . import layout


class EpochManager:
    def __init__(self, reclaim: Optional[Callable[[Any], None]] = None):
        self._lock = threading.Lock()
        self.global_epoch = 0
        self._active = defaultdict(int)        # epoch -> active readers
        self._limbo = defaultdict(list)        # retire epoch -> payloads
        self._reclaim = reclaim or (lambda obj: None)
        self.reclaimed = 0

    # -- readers -----------------------------------------------------------

    def enter(self) -> int:
        with self._lock:
            e = self.global_epoch
            self._active[e] += 1
            return e

    def exit(self, epoch: int):
        with self._lock:
            self._active[epoch] -= 1
            if self._active[epoch] == 0:
                del self._active[epoch]
            self._try_advance_locked()

    class _Guard:
        def __init__(self, mgr):
            self.mgr = mgr

        def __enter__(self):
            self.epoch = self.mgr.enter()
            return self.epoch

        def __exit__(self, *exc):
            self.mgr.exit(self.epoch)

    def pin(self) -> "_Guard":
        """with epochs.pin(): ... — lock-free read critical section."""
        return self._Guard(self)

    @property
    def active_readers(self) -> int:
        with self._lock:
            return sum(self._active.values())

    @property
    def limbo_size(self) -> int:
        with self._lock:
            return sum(len(v) for v in self._limbo.values())

    # -- writers -----------------------------------------------------------

    def retire(self, obj: Any):
        """Queue an old snapshot/segment for reclamation once safe."""
        with self._lock:
            self._limbo[self.global_epoch].append(obj)
            self._try_advance_locked()

    def _try_advance_locked(self):
        # advance when no reader is pinned at or before the current epoch;
        # reclaim limbo entries 2 epochs old (nobody can reference them)
        if not self._active or min(self._active) >= self.global_epoch:
            self.global_epoch += 1
        safe = self.global_epoch - 2
        for e in [e for e in self._limbo if e <= safe]:
            for obj in self._limbo.pop(e):
                self._reclaim(obj)
                self.reclaimed += 1

    def flush(self):
        """Reclaim everything (quiescent point: e.g. engine shutdown)."""
        with self._lock:
            assert not self._active, "readers still pinned"
            self.global_epoch += 3
            for e in list(self._limbo):
                for obj in self._limbo.pop(e):
                    self._reclaim(obj)
                    self.reclaimed += 1


def _try_delete(leaf):
    """Free one device buffer; safe on already-deleted (e.g. donated) arrays
    and on non-array leaves."""
    try:
        leaf.delete()
    except Exception:
        pass


class PlanePool:
    """Refcounts on published plane buffers, keyed by array identity.

    A plane enters the pool when a snapshot referencing it is published
    (``incref``); each snapshot that aliases the same array object adds a
    reference. ``decref`` releases one reference and deletes the device
    buffer only at zero — a plane shared by a newer snapshot survives the
    older snapshot's reclamation. Donated-away planes (their buffer was
    reused in place by a COW scatter) are already dead handles; deleting
    them at refcount zero is a no-op.
    """

    def __init__(self):
        self._refs: dict = {}          # id(arr) -> [arr, refcount]

    def incref(self, leaf):
        e = self._refs.get(id(leaf))
        if e is None:
            self._refs[id(leaf)] = [leaf, 1]
        else:
            e[1] += 1

    def decref(self, leaf) -> bool:
        """Release one reference; True iff the plane was freed."""
        e = self._refs.get(id(leaf))
        if e is None:               # never pooled (defensive): free directly
            _try_delete(leaf)
            return True
        e[1] -= 1
        if e[1] == 0:
            del self._refs[id(leaf)]
            _try_delete(leaf)
            return True
        return False

    def refcount(self, leaf) -> int:
        e = self._refs.get(id(leaf))
        return 0 if e is None else e[1]

    @property
    def live_planes(self) -> int:
        return len(self._refs)


class Snapshot:
    """One published table version: an immutable state pytree + the version
    id it was published under + a pin count. Readers hold it only inside an
    epoch pin (or for as long as the frontend batch that acquired it is in
    flight); ``pins`` > 0 blocks in-place buffer donation by the next
    publish."""

    __slots__ = ("version", "state", "pins")

    def __init__(self, version: int, state: Any):
        self.version = version
        self.state = state
        self.pins = 0

    def __repr__(self):  # pragma: no cover
        return f"Snapshot(v{self.version})"


def delete_buffers(snap: "Snapshot"):
    """Whole-snapshot reclaimer: free every device buffer of the snapshot.
    Correct ONLY for never-aliased snapshots (the legacy ``publish`` path
    with standalone payloads); pooled registries release plane-level
    references instead — see ``PlanePool``."""
    import jax
    for leaf in jax.tree.leaves(snap.state):
        _try_delete(leaf)


class DirtyHint:
    """Host-side dirty report drained from a table's ``DirtyTracker`` at
    publish: the segments the mutating paths routed writes to (plus whether
    the directory / the whole state changed). The version-plane diff is the
    publish's ground truth; the hint is audited against it
    (``SnapshotRegistry.hint_misses``) and drives the force-full escape for
    paths outside the version discipline (crash simulation, restart)."""

    __slots__ = ("segments", "dir", "full")

    def __init__(self, segments=frozenset(), dir=False, full=False):
        self.segments = frozenset(int(s) for s in segments)
        self.dir = bool(dir)
        self.full = bool(full)


# -- jitted COW helpers ------------------------------------------------------

def _scatter_body(bases, lives, ids, nlead):
    import jax.numpy as jnp
    out = []
    for base, live in zip(bases, lives):
        shape = base.shape
        rows = math.prod(shape[:nlead])
        br = base.reshape((rows,) + shape[nlead:])
        lr = live.reshape((rows,) + shape[nlead:])
        # padding lanes carry the sentinel id == rows: in-bounds for the
        # clipped gather, out-of-bounds (dropped) for the scatter — a
        # negative sentinel would WRAP to the last row and corrupt it
        picked = lr[jnp.clip(ids, 0, rows - 1)]
        out.append(br.at[ids].set(picked, mode="drop").reshape(shape))
    return tuple(out)


@functools.lru_cache(maxsize=None)
def _scatter_fns():
    import jax
    donate = jax.jit(_scatter_body, static_argnums=(3,), donate_argnums=(0,))
    copy = jax.jit(_scatter_body, static_argnums=(3,))
    return donate, copy


@functools.lru_cache(maxsize=None)
def _neq_many():
    """One bundled device compare: per-leaf 'did this plane change' bools
    for the version-word-free planes (directory + per-segment metadata)."""
    import jax
    import jax.numpy as jnp
    return jax.jit(lambda xs, ys: tuple(
        jnp.any(x != y) for x, y in zip(xs, ys)))


def _pad_ids(ids: np.ndarray, rows: int):
    """Pad dirty-row ids to quantized pow4 levels (floor 128, capped at the
    row count) so the scatter reuses a handful of jit traces; padding lanes
    carry the out-of-bounds sentinel ``rows`` (dropped by the scatter)."""
    import jax.numpy as jnp
    n = max(int(ids.size), 1)
    cap = 128
    while cap < n:
        cap *= 4
    cap = min(cap, rows)
    out = np.full(cap, rows, np.int32)
    out[:ids.size] = ids
    return jnp.asarray(out)


class SnapshotRegistry:
    """Monotonic published-version chain guarded by an EpochManager, with
    plane-pooled copy-on-write publishing.

    ``publish_cow(cfg, live)`` installs the live table state as the newest
    version copying only dirty planes (see module docstring); ``publish``
    is the legacy whole-payload path. ``acquire()`` returns the current
    Snapshot under an epoch pin; use as a context manager:

        with registry.acquire() as snap:
            found, vals = search_batch(cfg, mode, snap.state, ...)

    Superseded versions retire into the EpochManager's limbo; reclamation
    releases plane-level references (``PlanePool``) — a plane aliased by a
    newer snapshot survives. Passing a custom ``reclaim`` (or a caller-owned
    ``epochs``) keeps the legacy snapshot-level behavior for standalone
    payloads.

    Observability: ``publish_bytes`` / ``last_publish_bytes`` (bytes
    actually copied), ``planes_copied`` / ``planes_aliased`` (plane counts),
    ``hint_misses`` (dirty segments the host tracker failed to report —
    should stay 0), ``published`` / ``reclaimed``. The frontend's
    ``publish`` span times a publish where it runs.
    """

    def __init__(self, epochs: Optional[EpochManager] = None,
                 reclaim: Optional[Callable[[Snapshot], None]] = None):
        self.pool = PlanePool()
        self._pooled = epochs is None and reclaim is None
        if self._pooled:
            self.epochs = EpochManager(reclaim=self._release)
        else:
            self.epochs = epochs or EpochManager(reclaim=reclaim
                                                 or delete_buffers)
        self._lock = threading.Lock()
        self._current: Optional[Snapshot] = None
        self._next_version = 0
        self.published = 0
        self.publish_bytes = 0
        self.last_publish_bytes = 0
        self.planes_copied = 0
        self.planes_aliased = 0
        self.hint_misses = 0

    # -- plane-level reclamation ------------------------------------------

    def _release(self, snap: Snapshot):
        """Pooled reclaimer: drop one reference per plane; buffers are
        deleted only when the last aliasing snapshot releases them."""
        import jax
        for leaf in jax.tree.leaves(snap.state):
            self.pool.decref(leaf)

    @property
    def current(self) -> Optional[Snapshot]:
        with self._lock:
            return self._current

    @property
    def version(self) -> int:
        with self._lock:
            return -1 if self._current is None else self._current.version

    # -- publishing --------------------------------------------------------

    def _install(self, state: Any):
        """Register a fully-assembled state as the newest version (caller
        holds ``_lock``). Returns (snapshot, superseded-or-None)."""
        import jax
        snap = Snapshot(self._next_version, state)
        self._next_version += 1
        if self._pooled:
            for leaf in jax.tree.leaves(state):
                self.pool.incref(leaf)
        old, self._current = self._current, snap
        self.published += 1
        return snap, old

    def publish(self, state: Any) -> Snapshot:
        """Install ``state`` as the newest version; retire the old one.
        The caller passes a state whose buffers it will not donate
        afterwards (no copy is made here)."""
        with self._lock:
            snap, old = self._install(state)
        if old is not None:
            self.epochs.retire(old)
        return snap

    def publish_cow(self, cfg: layout.DashConfig, live: layout.DashState,
                    dirty_hint: Optional[DirtyHint] = None) -> Snapshot:
        """O(dirty) publish of the live table state (see module docstring).

        ``live`` is only read (gathered) — its buffers stay owned by the
        engine's donation chain. The first publish (and any ``dirty_hint``
        with ``full`` set, e.g. after a crash simulation that bypasses the
        version discipline, or pointer-mode tables whose key heap carries
        no version words) falls back to a whole-state copy.

        One publisher at a time (the frontends' write side is sequential);
        concurrent readers are supported. The device diff — which blocks on
        the write batch's pending dispatches — runs OUTSIDE the registry
        lock so readers acquiring mid-publish stall only for the assembly
        (the donated scatter must exclude new pins, so it stays inside).
        """
        import jax
        import jax.numpy as jnp
        assert self._pooled, "publish_cow needs the pool-managed registry"
        force_full = (dirty_hint is not None and dirty_hint.full) \
            or cfg.pointer_mode
        prev = self.current                # stable: single publisher

        if prev is None or force_full \
                or not isinstance(prev.state, layout.DashState):
            state = jax.tree.map(jnp.copy, live)
            nbytes = layout.state_nbytes(state)
            with self._lock:
                self.planes_copied += len(jax.tree.leaves(state))
                snap, old = self._install(state)
        else:
            diff = self._cow_diff(cfg, prev, live, dirty_hint)
            with self._lock:
                snap, old, nbytes = self._assemble_cow_locked(
                    cfg, prev, live, *diff)
        with self._lock:
            self.publish_bytes += nbytes
            self.last_publish_bytes = nbytes
        if old is not None:
            self.epochs.retire(old)
        return snap

    def _cow_diff(self, cfg, prev: Snapshot, live: layout.DashState,
                  dirty_hint: Optional[DirtyHint]):
        """Device diff + host id extraction (syncs on pending device work —
        called outside the registry lock)."""
        from . import engine

        NB, BT = cfg.num_buckets, cfg.buckets_total
        mask = np.asarray(engine.changed_rows(prev.state.version,
                                              live.version))
        # dir + per-segment metadata carry no version words: alias-vs-copy
        # is decided by one bundled content compare (tiny planes)
        meta_names = layout.DIR_PLANES + layout.SEG_META_PLANES
        meta_neq = [bool(x) for x in _neq_many()(
            tuple(getattr(prev.state, n) for n in meta_names),
            tuple(getattr(live, n) for n in meta_names))]
        lead_shape = live.version.shape[:-1]       # (S,) or (n_shards, S)
        m = mask.reshape(lead_shape + (BT,))
        ids_bt = np.flatnonzero(mask).astype(np.int32)
        ids_nb = np.flatnonzero(m[..., :NB]).astype(np.int32)

        # audit the host dirty hint against the device ground truth: every
        # device-dirty segment (and a changed directory) must have been
        # reported by some mutating path
        if dirty_hint is not None and len(lead_shape) == 1:
            if ids_bt.size:
                seen = set(np.unique(ids_bt // BT).tolist())
                self.hint_misses += len(seen - dirty_hint.segments)
            if meta_neq[0] and not dirty_hint.dir:   # DIR_PLANES lead
                self.hint_misses += 1
        return ids_bt, ids_nb, meta_neq

    def _assemble_cow_locked(self, cfg, prev: Snapshot,
                             live: layout.DashState,
                             ids_bt, ids_nb, meta_neq):
        import jax.numpy as jnp

        meta_names = layout.DIR_PLANES + layout.SEG_META_PLANES
        lead_shape = live.version.shape[:-1]
        new = {}
        copied_bytes = 0
        scatter_donate, scatter_copy = _scatter_fns()
        nlead = len(lead_shape) + 1
        for names, ids in ((layout.BT_PLANES, ids_bt),
                           (layout.NB_PLANES, ids_nb)):
            prev_leaves = tuple(getattr(prev.state, n) for n in names)
            if ids.size == 0:
                # nothing in this group changed: alias the previous
                # version's planes (refcounted by _install)
                for n, leaf in zip(names, prev_leaves):
                    new[n] = leaf
                self.planes_aliased += len(names)
                continue
            live_leaves = tuple(getattr(live, n) for n in names)
            rows = math.prod(live_leaves[0].shape[:nlead])
            pad = _pad_ids(ids, rows)
            donate = prev.pins == 0 and all(
                self.pool.refcount(l) == 1 for l in prev_leaves)
            if donate:
                # in-place: the previous version's buffers are exclusively
                # ours — reuse them, moving only the dirty rows
                outs = scatter_donate(prev_leaves, live_leaves, pad, nlead)
                copied_bytes += ids.size * sum(
                    l.nbytes // rows for l in live_leaves)
            else:
                # pinned / shared planes: scatter into fresh copies (XLA
                # copies the base — the honest whole-plane cost)
                outs = scatter_copy(prev_leaves, live_leaves, pad, nlead)
                copied_bytes += sum(l.nbytes for l in live_leaves)
            for n, out in zip(names, outs):
                new[n] = out
            self.planes_copied += len(names)

        for n, changed in zip(meta_names, meta_neq):
            if bool(changed):
                leaf = jnp.copy(getattr(live, n))
                new[n] = leaf
                copied_bytes += leaf.nbytes
                self.planes_copied += 1
            else:
                new[n] = getattr(prev.state, n)     # aliased, refcounted
                self.planes_aliased += 1

        # scalars + key heap: tiny, copied every publish — a snapshot must
        # never alias the live arrays (the engine donates those on the next
        # dispatch), and scalar counters change with almost every batch
        for n in live._fields:
            if n in new:
                continue
            leaf = jnp.copy(getattr(live, n))
            new[n] = leaf
            copied_bytes += leaf.nbytes
            self.planes_copied += 1

        snap, old = self._install(type(live)(**new))
        return snap, old, copied_bytes

    # -- readers -----------------------------------------------------------

    class _Acquired:
        def __init__(self, registry: "SnapshotRegistry"):
            self.registry = registry

        def __enter__(self) -> Snapshot:
            # epoch FIRST: from this point no retired version this reader
            # could still see is reclaimed. Pinning before entering would
            # leave a window where the pinned version's planes are freed
            # (reclamation consults epochs, pins only gate donation).
            self.epoch = self.registry.epochs.enter()
            try:
                with self.registry._lock:
                    snap = self.registry._current
                    assert snap is not None, "acquire() before first publish()"
                    snap.pins += 1
                    self.snap = snap
            except BaseException:
                self.registry.epochs.exit(self.epoch)   # don't leak the pin
                raise
            return snap

        def __exit__(self, *exc):
            with self.registry._lock:
                self.snap.pins -= 1
            self.registry.epochs.exit(self.epoch)

    def acquire(self) -> "_Acquired":
        """Pin an epoch (and the snapshot's pin count) and yield the newest
        published Snapshot."""
        return self._Acquired(self)

    @property
    def reclaimed(self) -> int:
        return self.epochs.reclaimed

    def flush(self):
        self.epochs.flush()

    def stats(self) -> dict:
        """One observability surface for benches and tests."""
        return {
            "published": self.published,
            "publish_bytes": self.publish_bytes,
            "last_publish_bytes": self.last_publish_bytes,
            "planes_copied": self.planes_copied,
            "planes_aliased": self.planes_aliased,
            "reclaimed": self.reclaimed,
            "hint_misses": self.hint_misses,
            "live_planes": self.pool.live_planes,
        }
