"""Host-facing Dash tables: batch orchestration + split retry + lazy recovery.

The device does the data-plane work (batched probes/inserts, SMOs); the host
plays the role of the paper's "goto retry" loops (Alg. 1 line 31): when a
batch reports NEED_SPLIT, the host runs the SMO and retries the failed subset.
Per-segment lazy recovery (Sec. 4.8) also hooks in here: before touching a
segment whose version mismatches the global V, the accessing *batch* recovers
it — amortizing recovery over runtime exactly as the paper does over accesses.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np
import jax.numpy as jnp

from . import dash_eh, dash_lh, engine, hashing, layout, recovery, smo
from .epoch import DirtyHint
from .layout import (EXISTS, INSERTED, NEED_SPLIT, NOT_FOUND, DashConfig,
                     DashState)


class TableFullError(RuntimeError):
    pass


class DirtyTracker:
    """Host-side dirty-plane accounting for the copy-on-write publish.

    Every mutating path notes the segments it routed writes to (the same
    per-key segment ids that feed ``route_lanes``) plus whether the
    directory changed; the serving frontend drains this at publish time.
    The version-plane diff is the publish's ground truth — the tracker is
    the O(1) host mirror used for observability and audited against the
    device mask (``SnapshotRegistry.hint_misses``). ``note_full`` marks
    mutations outside the version discipline (crash simulation, restart),
    forcing the next publish to copy the whole state."""

    def __init__(self):
        self.segments: set = set()
        self.dir = False
        self.full = False

    def note_segments(self, ids):
        # one vectorized pass: per-key segment arrays arrive on every write
        # batch, but distinct values are bounded by the pool size
        ids = np.asarray(ids).reshape(-1)
        self.segments.update(np.unique(ids[ids >= 0]).tolist())

    def note_dir(self):
        self.dir = True

    def note_full(self):
        self.full = True

    @property
    def any(self) -> bool:
        return self.full or self.dir or bool(self.segments)

    def drain(self) -> DirtyHint:
        hint = DirtyHint(self.segments, self.dir, self.full)
        self.segments = set()
        self.dir = False
        self.full = False
        return hint


@dataclasses.dataclass
class InsertJob:
    """Resumable insert batch: the host state of one ``insert`` retry loop,
    factored out so callers can interleave other work between rounds.

    ``DashTable.insert`` pumps a job to completion inline (stop-the-world
    splits); the online-resize frontend (serving/frontend.py) runs one
    ``insert_round`` per scheduler tick and defers the pressured-segment SMO
    to a staged background task, serving reads from a pinned snapshot in
    between."""
    hi: np.ndarray
    lo: np.ndarray
    w: Optional[np.ndarray]
    vals: np.ndarray
    out: np.ndarray                  # per-input statuses (NEED_SPLIT until done)
    pending: np.ndarray              # input indices still unplaced
    first: bool = True               # first round: full batch, lazy recovery
    cap_used: Optional[int] = None   # sticky lane capacity across retry rounds
    rounds: int = 0

    @property
    def done(self) -> bool:
        return self.pending.size == 0


# Largest batch that takes the fused single-dispatch latency path by
# default. Calibrated on the batch_parallel latency rows: at 256 the fused
# insert ran ~6x the scan engine and the fused read ~1.3x vmap on CPU; by
# 4096 the routed/segment engines win on throughput. 1024 is the crossover
# region's conservative edge.
FUSED_THRESHOLD_DEFAULT = 1024


class DashTable:
    """Shared host logic; subclasses define addressing + pressure handling.

    ``smo_mode="bulk"`` (default) routes structural modifications through the
    device-parallel SMO engine (core/smo.py): all segments pressured in one
    batch round split in a single dispatch with one directory publish.
    ``smo_mode="scalar"`` keeps the per-segment reference path (one scan-rehash
    dispatch per SMO) — the differential baseline."""

    mode: str = "eh"

    def __init__(self, cfg: DashConfig, lazy_recovery: bool = True,
                 smo_mode: str = "bulk",
                 state: Optional[DashState] = None,
                 fused_threshold: Optional[int] = None):
        self.cfg = cfg
        # batches at or under this size take the fused single-dispatch
        # latency path (kernels/fused.py); 0 forces the routed/vmap paths,
        # a huge value forces fused everywhere. Default calibrated by
        # benchmarks/batch_parallel.py's latency rows (see README
        # "Latency path").
        self.fused_threshold = (FUSED_THRESHOLD_DEFAULT
                                if fused_threshold is None
                                else int(fused_threshold))
        # `state` restores a persisted table (persist.reopen) without
        # paying a throwaway full-pool allocation
        self.state: DashState = state if state is not None \
            else layout.make_state(cfg, self.mode)
        self.lazy_recovery = lazy_recovery
        self.smo_mode = smo_mode
        self.recovered_segments = 0   # stat: lazy recoveries performed
        self.free_segments: list = []  # merged-away ids, recycled by splits
        self.dirty = DirtyTracker()   # dirty planes since the last publish
        self.writeback = None         # durable PM-pool engine (persist/)
        self.lost_report: list = []   # quarantined rows from a verified reopen
        self.obs = None               # observability bundle (obs/), optional

    # -- key plumbing --------------------------------------------------------

    def _split_keys(self, keys):
        keys = np.asarray(keys, dtype=np.uint64)
        hi, lo = hashing.np_split_keys(keys)
        return jnp.asarray(hi), jnp.asarray(lo), None

    def _key_words(self, words):
        """Pointer mode: keys come as (n, W) uint32 padded word rows."""
        words = np.asarray(words, dtype=np.uint32)
        assert words.shape[1] == self.cfg.key_heap_words
        hi = hashing.np_fold_words(words, hashing.FOLD_SEED_HI)
        lo = hashing.np_fold_words(words, hashing.FOLD_SEED_LO)
        return jnp.asarray(hi), jnp.asarray(lo), jnp.asarray(words)

    def _prep(self, keys=None, words=None):
        if self.cfg.pointer_mode:
            assert words is not None, "pointer mode takes `words` (n, W) uint32"
            return self._key_words(words)
        return self._split_keys(keys)

    # -- host-visible routing (lazy recovery + batch planning) ----------------

    def _segments_of(self, hi, lo) -> np.ndarray:
        """Physical segment of every key (host mirror of engine.locate)."""
        h1 = hashing.np_hash1(np.asarray(hi), np.asarray(lo))
        if self.mode == "eh":
            dirv = np.asarray(self.state.dir)
            return dirv[h1 >> np.uint32(32 - self.cfg.dir_depth_max)]
        word = int(np.asarray(self.state.lh_word))
        level, nxt = word >> 24, word & 0xFFFFFF
        mask_lo = (1 << (self.cfg.lh_base_log2 + level)) - 1
        seg = (h1 & np.uint32(mask_lo)).astype(np.int64)
        mask_hi = (mask_lo << 1) | 1
        seg2 = (h1 & np.uint32(mask_hi)).astype(np.int64)
        logical = np.where(seg < nxt, seg2, seg)
        return np.asarray(self.state.lh_dir)[logical]

    def _touched_segments(self, hi, lo) -> np.ndarray:
        return np.unique(self._segments_of(hi, lo))

    _pow2 = staticmethod(engine._pow2_at_least)

    @staticmethod
    def _lane_quantum(n: int, floor: int = 8) -> int:
        """Round lane capacity up to a pow2 or 1.5*pow2 level: capacity is
        the intra-segment critical path, so pure pow2 rounding wastes up to
        2x sequential steps; the extra half-steps keep jit recompiles to
        ~2 levels per octave."""
        n = max(int(n), 1)
        p = max(floor, 1 << (n - 1).bit_length())
        mid = p // 2 + p // 4          # the 1.5*pow2 level below p
        return mid if n <= mid and mid >= floor else p

    @staticmethod
    def _max_per_segment(seg: np.ndarray) -> int:
        live = seg[seg >= 0]
        return int(np.bincount(live).max()) if live.size else 1

    def _write_plan(self, seg: np.ndarray, n_total: int, fused_ok: bool = True):
        """(batching, capacity) for a mutating batch, from the per-key
        segment ids (computed once per op, shared with lazy recovery).

        The host sees the directory, so it can size the per-segment lane
        capacity exactly (max keys routed to one segment — padding lanes sit
        after real keys in batch order, so they can only overflow, never
        displace). Small batches (<= ``fused_threshold``) take the fused
        merged-commit path — one dispatch, no per-lane branch merging —
        sized with the same exact lane capacity. Segment-parallel wins when
        the critical path (capacity) is meaningfully shorter than the batch;
        a freshly-created table with 2 segments has no parallelism to
        exploit, so it stays on the scan engine until splits spread the
        directory. ``fused_ok=False`` (delete/update, which have no fused
        engine) skips the latency path."""
        from repro.kernels import fused    # local: kernels import core
        capacity = self._lane_quantum(self._max_per_segment(seg))
        if (fused_ok and n_total <= self.fused_threshold
                and fused.fused_insert_eligible(self.cfg)):
            return "fused", capacity
        if capacity * 4 <= self._pow2(n_total):
            return "segment", capacity
        return "scan", None

    def _search_plan(self, seg: np.ndarray):
        """(batching, capacity) for a read batch: the fused single-dispatch
        path for small batches (its whole point is killing per-stage launch
        overhead), the Pallas fingerprint path for large batches on eligible
        configs, per-key vmap otherwise. The routed fingerprint kernel gets
        the exact per-segment lane capacity, so no lane overflows to the
        per-key path; the fused path has no lanes to size."""
        from repro.kernels import fused    # local: kernels import core
        if (seg.size <= self.fused_threshold
                and fused.fused_search_eligible(self.cfg)):
            return "fused", None
        if seg.size >= 256 and engine.pallas_search_eligible(self.cfg):
            return "pallas", self._pow2(self._max_per_segment(seg), floor=128)
        return "vmap", None

    def _ensure_recovered(self, touched: np.ndarray):
        """Lazy per-segment recovery over precomputed touched segment ids."""
        if not self.lazy_recovery:
            return

        def note(seg, affected):
            # recovery may continue an in-flight SMO: the side-linked
            # neighbor (either direction) and the directory are fair game
            self.dirty.note_segments(affected)
            self.dirty.note_dir()

        self.state, recovered = recovery.lazy_recover_touched(
            self.cfg, self.mode, self.state, touched, note=note)
        self.recovered_segments += len(recovered)
        if self.obs is not None:
            for seg in recovered:
                self.obs.registry.counter("table.lazy_recoveries").inc()
                self.obs.tracer.instant("lazy_recovery", "recovery",
                                        segment=seg)

    # -- public ops -----------------------------------------------------------

    def insert_begin(self, keys=None, values=None, words=None) -> InsertJob:
        """Start a resumable insert batch (see InsertJob)."""
        hi_j, lo_j, w_j = self._prep(keys, words)
        hi, lo = np.asarray(hi_j), np.asarray(lo_j)
        w = None if w_j is None else np.asarray(w_j)
        vals = np.asarray(values, dtype=np.uint32)
        return InsertJob(hi=hi, lo=lo, w=w, vals=vals,
                         out=np.full(hi.shape[0], NEED_SPLIT, dtype=np.int32),
                         pending=np.arange(hi.shape[0]))

    def insert_round(self, job: InsertJob) -> bool:
        """One insert dispatch over the job's pending subset. Updates
        ``job.out``/``job.pending``; does NOT run SMOs — the caller decides
        whether to split inline (``insert``) or defer to a background task
        (the frontend). Returns the LH stash-activation signal."""
        hi, lo, w, vals, pending = job.hi, job.lo, job.w, job.vals, job.pending
        # per-key segments: recomputed each round (splits remap keys),
        # shared by recovery, the batch plan, and the failure hints
        seg = self._segments_of(hi[pending], lo[pending])
        self.dirty.note_segments(seg)            # the dispatch writes there
        if job.first:
            self._ensure_recovered(seg)
            idx, valid = pending, None           # full batch, no padding
        else:
            # pad retry subsets to pow2 so jit shapes are reused
            n = self._pow2(pending.size)
            idx = np.concatenate([pending, np.zeros(n - pending.size, np.int64)])
            valid = jnp.asarray(np.arange(n) < pending.size)
        batching, capacity = self._write_plan(seg, idx.size)
        if batching in ("segment", "fused"):
            # sticky lane capacity: splits shrink the per-segment max
            # every retry round, and each fresh capacity is a fresh jit
            # trace — reusing the first round's (clamped to the padded
            # batch) keeps the retry loop on already-compiled code
            if job.cap_used is not None and capacity < job.cap_used:
                capacity = min(job.cap_used, self._pow2(idx.size))
            job.cap_used = capacity
        self.state, statuses, activated = engine.insert_batch(
            self.cfg, self.mode, self.state,
            jnp.asarray(hi[idx]), jnp.asarray(lo[idx]),
            jnp.asarray(vals[idx]),
            None if w is None else jnp.asarray(w[idx]), valid,
            batching=batching, capacity=capacity)
        statuses = np.asarray(statuses)[:pending.size]
        job.out[pending] = statuses
        job.pending = pending[statuses == NEED_SPLIT]
        job.first = False
        job.rounds += 1
        return bool(activated)

    def pressure_hints(self, job: InsertJob) -> np.ndarray:
        """Touched segments of the job's pending keys, computed from the
        CURRENT directory: lazy recovery (or an LH activation split) may
        have republished it since the round was routed — stale hints would
        split the wrong segment."""
        return self._touched_segments(job.hi[job.pending], job.lo[job.pending])

    def insert(self, keys=None, values=None, words=None, max_retries: int = 256):
        """Stop-the-world insert: pump the resumable job, splitting inline
        whenever a round reports pressure (the paper's 'goto retry' loop)."""
        job = self.insert_begin(keys, values, words)
        for _ in range(max_retries):
            activated = self.insert_round(job)
            if activated:
                self._on_pressure(None)   # LH: stash-allocation split trigger
            if job.done:
                return job.out
            self._on_pressure(self.pressure_hints(job))
        raise TableFullError("insert retry budget exhausted")

    def search(self, keys=None, words=None):
        hi, lo, w = self._prep(keys, words)
        seg = self._segments_of(hi, lo)
        self._ensure_recovered(seg)
        batching, capacity = self._search_plan(seg)
        found, vals = engine.search_batch(self.cfg, self.mode, self.state,
                                          hi, lo, w, batching=batching,
                                          capacity=capacity)
        self.count_read_batch(batching)
        return np.asarray(found), np.asarray(vals)

    def count_read_batch(self, batching: str):
        """Count a fused read batch in the obs registry by the program it
        ran: ``reads.fused_kernel_batches`` (the per-query Pallas kernel)
        or ``reads.fused_direct_batches`` (the direct jnp lowering)."""
        if self.obs is not None and batching == "fused":
            from repro.kernels import fused    # local: kernels import core
            path = fused.fused_read_path(self.cfg)
            self.obs.registry.counter(f"reads.fused_{path}_batches").inc()

    def delete(self, keys=None, words=None):
        hi, lo, w = self._prep(keys, words)
        seg = self._segments_of(hi, lo)
        self._ensure_recovered(seg)
        self.dirty.note_segments(seg)
        batching, capacity = self._write_plan(seg, seg.size, fused_ok=False)
        self.state, statuses = engine.delete_batch(
            self.cfg, self.mode, self.state, hi, lo, w,
            batching=batching, capacity=capacity)
        return np.asarray(statuses)

    def update(self, keys=None, values=None, words=None):
        hi, lo, w = self._prep(keys, words)
        seg = self._segments_of(hi, lo)
        self._ensure_recovered(seg)
        self.dirty.note_segments(seg)
        vals = jnp.asarray(np.asarray(values, dtype=np.uint32))
        batching, capacity = self._write_plan(seg, seg.size, fused_ok=False)
        self.state, statuses = engine.update_batch(
            self.cfg, self.mode, self.state, hi, lo, vals, w,
            batching=batching, capacity=capacity)
        return np.asarray(statuses)

    # -- lifecycle / stats ----------------------------------------------------

    def attach_writeback(self, wb):
        """Bind a durable PM-pool writeback engine (persist/writeback.py);
        ``flush()`` (and the serving frontend's publish) then mirror every
        acknowledged batch into the pool in O(dirty) bytes."""
        self.writeback = wb
        if self.obs is not None:
            wb.attach_obs(self.obs)

    def attach_obs(self, obs):
        """Bind an observability bundle (obs/): the table counts lazy
        recoveries and staged SMOs into its registry and propagates the
        bundle to an attached writeback (flush spans, scrub counters)."""
        self.obs = obs
        if self.writeback is not None:
            self.writeback.attach_obs(obs)

    def flush(self) -> int:
        """Make the live state durable: drain the dirty tracker and write
        only the dirty planes to the attached pool (ordered flush+fence —
        the acknowledgment point of the durable contract). Returns bytes
        written."""
        assert self.writeback is not None, "no pool attached (persist.create)"
        return self.writeback.flush(self.state, self.dirty.drain())

    def close(self):
        """Durable clean shutdown: set the clean marker and flush, so the
        next ``persist.reopen`` skips recovery entirely (paper Sec. 4.8's
        graceful path)."""
        self.graceful_shutdown()
        if self.writeback is not None:
            self.flush()
            self.writeback.pool.close()

    def graceful_shutdown(self):
        self.state = self.state._replace(clean=jnp.asarray(True))

    def restart(self):
        """Instant recovery (Sec. 4.8): O(1) work, constant in data size.
        (Volatile restart of the in-memory state; the durable equivalent —
        map the pool, read the superblock, same constant work — is
        ``persist.reopen``.)"""
        self.state, work = recovery.instant_restart(self.state)
        self.dirty.note_full()   # lazy recovery will rewrite at first touch
        return work

    def crash(self, rng: Optional[np.random.Generator] = None, **kw):
        # crash surgery rewrites planes WITHOUT version bumps — the next
        # COW publish (and durable flush) must not trust the version diff.
        # With a pool attached, `crash(); flush()` emulates the paper's
        # crash-with-artifacts-IN-PM: the artifacts land durably and the
        # reopened pool must lazily recover them (tests/test_persist.py).
        self.dirty.note_full()
        self.state = recovery.simulate_crash(self.cfg, self.mode, self.state,
                                             rng or np.random.default_rng(0), **kw)

    @property
    def load_factor(self) -> float:
        return float(np.asarray(layout.load_factor(self.cfg, self.state)))

    @property
    def n_items(self) -> int:
        return int(np.asarray(self.state.n_items))

    @property
    def n_segments(self) -> int:
        return int(np.asarray(self.state.watermark))

    def _on_pressure(self, seg_hint):
        raise NotImplementedError

    def smo_task_eligible(self) -> bool:
        """True iff pressure SMOs run through the staged bulk pipeline (the
        path the online-resize frontend can defer/interleave)."""
        return self.smo_mode == "bulk" and smo.rebuild_eligible(self.cfg)

    def make_smo_task(self, seg_hint):
        """Plan a deferred SMO for the pressured segments and return a staged
        task (``pump(state) -> (state, done)``; see core/smo.py). Returns
        None when the signal needs no SMO (e.g. EH stash activation).
        Raises TableFullError exactly like the inline path."""
        raise NotImplementedError

    def _pump_smo(self, task):
        """Stop-the-world rendering of a staged SMO task: run every stage
        inline, then surface a planning shortfall as pool exhaustion (the
        feasible splits still landed first, same as the old inline path)."""
        self.note_smo(task)
        done = False
        while not done:
            self.state, done = task.pump(self.state)
        if task.shortfall:
            raise TableFullError("segment pool exhausted")

    def note_smo(self, task):
        """Record a staged SMO's dirty footprint (rebuilt + directory
        planes) — callers pumping a task themselves (the online-resize
        frontend) invoke this once per task."""
        self.dirty.note_segments(task.touched)
        self.dirty.note_dir()
        if self.obs is not None:
            self.obs.registry.counter("table.smo_tasks").inc()
            self.obs.registry.counter("table.smo_segments").inc(
                int(np.asarray(task.touched).size))


class DashEH(DashTable):
    """Dash extendible hashing (paper Sec. 4)."""

    mode = "eh"

    def _check_depth(self, segs):
        """Shared depth-exhaustion guard of the inline and staged paths."""
        depths = np.asarray(self.state.local_depth)
        for seg in segs:
            if depths[seg] >= self.cfg.dir_depth_max:
                raise TableFullError("directory depth exhausted")

    def make_smo_task(self, seg_hint):
        """Bulk EH pressure plan: allocate every new id up front (recycled
        merge victims first, then the pool watermark) so all pressured
        segments split in one staged pipeline with one directory publish."""
        if seg_hint is None:
            return None                 # EH ignores stash-activation signals
        segs = [int(s) for s in np.asarray(seg_hint).reshape(-1)]
        self._check_depth(segs)
        wm = int(np.asarray(self.state.watermark))
        new_ids = []
        for _ in segs:
            if self.free_segments:
                new_ids.append(self.free_segments.pop())
            elif wm < self.cfg.max_segments:
                new_ids.append(wm)
                wm += 1
            else:
                break
        if not new_ids:
            raise TableFullError("segment pool exhausted")
        return smo.BulkSplitTask(self.cfg, segs[:len(new_ids)], new_ids,
                                 shortfall=len(segs) - len(new_ids))

    def _on_pressure(self, seg_hint):
        if seg_hint is None:
            return                      # EH ignores stash-activation signals
        if not self.smo_task_eligible():
            segs = [int(s) for s in np.asarray(seg_hint).reshape(-1)]
            self._check_depth(segs)
            return self._on_pressure_scalar(segs)
        task = self.make_smo_task(seg_hint)
        if task is not None:
            self._pump_smo(task)

    def _on_pressure_scalar(self, segs):
        """Reference path: one scan-rehash SMO dispatch per segment."""
        wm = int(np.asarray(self.state.watermark))
        for seg in segs:
            new_id = self.free_segments.pop() if self.free_segments else None
            if new_id is None and wm >= self.cfg.max_segments:
                raise TableFullError("segment pool exhausted")
            self.dirty.note_segments([seg, wm if new_id is None else new_id])
            self.dirty.note_dir()
            self.state, ok = dash_eh.split_segment(self.cfg, self.state, seg,
                                                   new_id, impl="scan")
            if not bool(ok):
                raise AssertionError("split rehash failed to refit records")
            wm += 1

    @property
    def global_depth(self) -> int:
        return int(np.asarray(self.state.global_depth))

    def shrink(self, target_fill: float = 0.8, max_merges: int = 10**6) -> int:
        """Merge buddy segment pairs while their combined records fit under
        ``target_fill`` of one segment (paper Sec. 4.7: merge on low load
        factor). Freed ids are recycled by future splits. Returns merges.

        Planning is one vectorized buddy-pair scan + one counts pass per
        round (not per merge), and the bulk path merges every fitting pair
        of a round in a single device dispatch; cascading merges (pairs that
        only become buddies after their neighbors merged) land in the next
        round."""
        cap = int(self.cfg.seg_capacity * target_fill)
        use_bulk = self.smo_mode == "bulk" and smo.rebuild_eligible(self.cfg)
        merges = 0
        while merges < max_merges:
            counts = self._segment_counts()
            dirv = np.asarray(self.state.dir)
            depths = np.asarray(self.state.local_depth)
            pairs = smo.find_buddy_pairs(self.cfg, dirv, depths)
            if pairs.size:
                pairs = pairs[counts[pairs[:, 0]] + counts[pairs[:, 1]] <= cap]
            if pairs.size == 0:
                return merges
            pairs = pairs[:max_merges - merges]
            c0, c1 = counts[pairs[:, 0]], counts[pairs[:, 1]]
            victim = np.where(c0 <= c1, pairs[:, 0], pairs[:, 1])
            keep = np.where(c0 <= c1, pairs[:, 1], pairs[:, 0])
            self.dirty.note_segments(pairs)
            self.dirty.note_dir()
            if use_bulk:
                # fixed-size chunks: every dispatch shares ONE jit trace
                # (per-round K values would each compile their own)
                C = 8
                for j in range(0, pairs.shape[0], C):
                    kc, vc = keep[j:j + C], victim[j:j + C]
                    K = kc.size
                    kj = jnp.asarray(np.concatenate(
                        [kc, np.full(C - K, -1)]).astype(np.int32))
                    vj = jnp.asarray(np.concatenate(
                        [vc, np.full(C - K, -1)]).astype(np.int32))
                    ok_mask = jnp.asarray(np.arange(C) < K)
                    self.state, ok = smo.bulk_merge(self.cfg, self.state,
                                                    kj, vj, ok_mask)
                    for i in np.nonzero(~np.asarray(ok)[:K])[0]:
                        self.state, ok1 = dash_eh.merge_segments_scan(
                            self.cfg, self.state, int(kc[i]), int(vc[i]))
                        assert bool(ok1)
            else:
                for k, v in zip(keep, victim):
                    self.state, ok1 = dash_eh.merge_segments_scan(
                        self.cfg, self.state, int(k), int(v))
                    assert bool(ok1)
            self.free_segments.extend(int(v) for v in victim)
            merges += pairs.shape[0]
        return merges

    def _segment_counts(self) -> np.ndarray:
        meta = np.asarray(self.state.meta)
        return ((meta >> layout.COUNT_SHIFT) & 0xF).sum(axis=1)


class DashLH(DashTable):
    """Dash linear hashing (paper Sec. 5)."""

    mode = "lh"

    #: bulk expansion stride (paper Sec. 5.2 hybrid expansion: grow by a
    #: segment-array stride, not one segment — dash_lh.
    #: hybrid_expansion_directory derives the stride-8 directory accounting)
    expansion_stride = 8

    def _check_headroom(self):
        """(level, nxt, round_size) after the pool/round bound checks the
        inline and deferred paths share."""
        cfg = self.cfg
        wm = int(np.asarray(self.state.watermark))
        if wm >= cfg.max_segments:
            raise TableFullError("segment pool exhausted")
        word = int(np.asarray(self.state.lh_word))
        level, nxt = word >> 24, word & 0xFFFFFF
        round_size = (1 << cfg.lh_base_log2) << level
        if round_size + nxt >= cfg.max_segments:
            raise TableFullError("lh directory exhausted")
        return wm, nxt, round_size

    def make_smo_task(self, seg_hint=None):
        """Bulk stride expansion plan: split Next..Next+R-1 in one staged
        dispatch, capped at the round boundary and the pool/directory
        headroom. LH pressure ignores the segment hint (it always splits at
        Next, Sec. 5.3)."""
        cfg = self.cfg
        wm, nxt, round_size = self._check_headroom()
        R = max(1, min(self.expansion_stride, round_size - nxt,
                       cfg.max_segments - wm,
                       cfg.max_segments - (round_size + nxt)))
        old_phys = np.asarray(self.state.lh_dir)[nxt:nxt + R]
        return smo.BulkSplitNextTask(
            cfg, R, touched=np.concatenate([old_phys, wm + np.arange(R)]))

    def _on_pressure(self, seg_hint):
        if not self.smo_task_eligible():
            wm, nxt, _ = self._check_headroom()
            self.dirty.note_segments(
                [int(np.asarray(self.state.lh_dir)[nxt]), wm])
            self.state, ok = dash_lh.split_next_scan(self.cfg, self.state)
            if not bool(ok):
                raise AssertionError("LH split rehash failed to refit records")
            return
        self._pump_smo(self.make_smo_task(seg_hint))

    @property
    def active_segments(self) -> int:
        return dash_lh.lh_active_segments(self.cfg, self.state)
