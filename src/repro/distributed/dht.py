"""Distributed Dash: the paper's "scalable hashing" scaled out to a TPU pod.

Every device owns an independent Dash-EH table (a shard). The top
log2(n_shards) bits of the addressing hash pick the owner — the distributed
extension of the MSB directory. Query batches start *sharded over devices*;
each device routes its local queries to owners with a fixed-capacity
``all_to_all`` (MoE-style dispatch), owners probe shard-locally (the Pallas
fingerprint path applies verbatim — shards are ordinary Dash tables), and a
second ``all_to_all`` routes results back.

Scalability argument mirrors the paper's: probes are bandwidth-bound and
shards touch disjoint memory; the only cross-chip cost is ~24 bytes/query
each way vs. the ~256-byte bucket traffic it replaces, so the fabric term
stays well under the local-HBM term (benchmarks/dht_roofline.py derives both
from the dry-run artifact).

SMOs stay shard-local: a segment split never moves keys across shards (the
owner bits are disjoint from the shard-local directory bits), so there is no
cross-shard coordination — this is what makes the design elastic: growing
from 1 to 2 pods adds one owner bit and moves only metadata.

**Device-resident hot path.** The steady-state serving loop runs INSIDE the
shard_map program — one dispatch per tick, zero host plane transfers:

* ``snap_search_fn`` probes an epoch-pinned snapshot AND verifies it against
  the live version planes in the same program (``serving.engine.
  buckets_changed_local`` inlined per shard), returning a device-resident
  retry mask instead of the old host-mirrored plane diff.
* ``insert_round_fn`` keeps per-key statuses and the pending mask on device
  across retry rounds; the host syncs a (n_shards, 3) flags array per round
  (any-retry / any-need-split / any-stale), not O(batch) statuses.
* ``split_fn`` plans AND commits every pressured shard's bulk splits in one
  dispatch (``core/smo.plan_local_splits`` + ``split_segments_local``) — no
  host ``np.asarray`` sub-state rebuild.
* Every owner-side probe carries a per-access lazy-recovery hook: lanes
  whose segment's ``seg_version`` lags the recovery generation are flagged
  (reads) or bounced (writes), and the host recovers exactly the touched
  segments — so ``persist.reopen_shards`` defaults to
  ``eager_recover_dirty=False`` and a dirty-shard reopen is O(1) in stored
  data, like the single-table path.

The host-mirror verify and the host split loop are retained (``ShardFrontend
(verify_mode="host")``, ``DistributedDash._split_for_host``) as the
differential references and the bench baseline.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import DashConfig, engine, hashing, layout, recovery, smo
from repro.core.layout import DashState
from repro.kernels import ops as kops
from repro.parallel import sharding
from repro.serving import engine as serving_engine
from repro.serving import frontend

I32 = jnp.int32
U32 = jnp.uint32


def make_sharded_state(cfg: DashConfig, n_shards: int) -> DashState:
    one = layout.make_state(cfg, "eh")
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x, (n_shards,) + x.shape).copy(), one)


def make_abstract(cfg: DashConfig, n_shards: int):
    one = jax.eval_shape(lambda: layout.make_state(cfg, "eh"))
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct((n_shards,) + x.shape, x.dtype), one)


def owner_of(keys_hi, keys_lo, n_shards: int):
    """Owner shard from the TOP bits of h1 — the distributed MSB directory.
    Shard-local directories consume the next dir_depth_max bits, so probing
    inside the owner uses the unchanged 32-bit hash."""
    h1 = hashing.hash1(keys_hi, keys_lo)
    return (h1 >> U32(32 - int(np.log2(n_shards)))).astype(I32)


def np_owner_of(keys: np.ndarray, n_shards: int) -> np.ndarray:
    """Host mirror of ``owner_of`` over raw uint64 keys (routing is pure
    hashing — the host can attribute keys to shards without touching any
    device plane)."""
    hi, lo = hashing.np_split_keys(np.asarray(keys, np.uint64))
    h1 = hashing.np_hash1(hi, lo)
    return (h1 >> np.uint32(32 - int(np.log2(n_shards)))).astype(np.int64)


def _local_dispatch(hi, lo, v, n_shards: int, capacity: int,
                    owner_mask=None):
    """Route this device's queries into (n_shards, capacity) buffers via the
    shared MoE-style dispatcher (kernels/ops.py) — the same sort-based
    router the engine uses to group by segment, here grouping by owner
    shard. ``owner_mask=False`` lanes route to owner -1 (dropped). Returns
    buffers + src map (-1 = empty lane) + kept mask."""
    owner = owner_of(hi, lo, n_shards)
    if owner_mask is not None:
        owner = jnp.where(owner_mask, owner, -1)
    (b_hi, b_lo, b_v), b_src, keep = kops.route_lanes(
        owner, (hi, lo, v), n_shards, capacity, (0, 0, 0))
    return b_hi, b_lo, b_v, b_src, keep


def auto_capacity(q_local: int, n_shards: int, slack: float = 4.0) -> int:
    """Routing lanes per (src, dst): expected q_local/n_shards with slack.
    Oversized lanes are pure wasted wire — right-sizing them was a 16x
    fabric-bytes win at 256 chips (EXPERIMENTS.md SSPerf, DHT cell)."""
    want = int(np.ceil(q_local / n_shards * slack))
    return max(8, 1 << int(np.ceil(np.log2(want))))


def build_dht_programs(cfg: DashConfig, mesh: Mesh, axes=("data",),
                       capacity: int | None = None, q_local_hint: int = 1024,
                       search_batching: str = "vmap", split_lanes: int = 8):
    """All jitted shard_map programs over a device-sharded table.

    Inputs: keys reshaped (n_shards, q_local), sharded on dim 0.
    Payloads are PACKED into one (n_shards, cap, W) word tensor so each
    direction is a single all_to_all (one launch on the ICI, not four).

    ``search_batching`` selects the shard-local read path; shards are
    ordinary Dash tables, so the Pallas fingerprint path applies verbatim
    (pass "pallas"/"auto" on TPU) and so does the fused single-dispatch
    probe (pass "fused" — the natural fit for the small shard-local
    sub-batch, and its direct gather is indifferent to the all_to_all
    padding lanes piling onto key 0's segment). The CPU default stays on
    the per-key path: interpret-mode MXU gathers lose on emulated
    devices, and routed paths would re-bucket the padding lanes.

    ``split_lanes`` bounds the distinct segments one shard splits per
    ``split_fn`` dispatch; surplus pressured segments stay NEED_SPLIT and
    are planned the next round (the retry loop converges regardless).
    """
    axes = tuple(axes)
    n_shards = int(np.prod([mesh.shape[a] for a in axes]))
    if capacity is None:
        capacity = auto_capacity(q_local_hint, n_shards)
    st_spec = sharding.shard_specs(axes, make_abstract(cfg, n_shards))
    q_spec = P(axes)
    a2a = lambda x: jax.lax.all_to_all(x, axes, 0, 0, tiled=True)

    def _local(st):
        return jax.tree.map(lambda x: x[0], st)

    def _stale_lanes(local, h1, valid):
        """Per-access lazy-recovery hook: a lane whose segment's
        seg_version lags the recovery generation (gver) may observe a
        crash-wiped probe structure — flag it; the host recovers exactly
        the touched segments and the lane retries."""
        seg = local.dir[layout.dir_index(cfg, h1)]
        return valid & (local.seg_version[seg] != local.gver)

    def _scatter_back(b_src, cols, n_local):
        """Undo the routing: scatter (n_shards*capacity,) response columns
        back to this device's query lanes (-1 src = padding, dropped)."""
        src = b_src.reshape(-1)
        safe = jnp.clip(src, 0)
        live = src >= 0
        outs = []
        for col, dtype in cols:
            col = col.reshape(-1)
            if dtype is jnp.bool_:
                outs.append(jnp.zeros(n_local, jnp.bool_)
                            .at[safe].max((col > 0) & live))
            else:
                outs.append(jnp.zeros(n_local, dtype)
                            .at[safe].max(jnp.where(live, col, 0)))
        return outs

    def search_inner(st, hi, lo):
        hi, lo = hi[0], lo[0]                     # (q_local,)
        b_hi, b_lo, _, b_src, keep = _local_dispatch(
            hi, lo, jnp.zeros_like(hi), n_shards, capacity)
        req = a2a(jnp.stack([b_hi, b_lo], axis=-1))       # one payload out
        local = _local(st)
        rhi = req[..., 0].reshape(-1)
        rlo = req[..., 1].reshape(-1)
        found, vals = engine.search_batch(cfg, "eh", local, rhi, rlo,
                                          batching=search_batching)
        stale = _stale_lanes(local, hashing.hash1(rhi, rlo),
                             jnp.ones_like(found))
        resp = a2a(jnp.stack([found.astype(U32), vals, stale.astype(U32)],
                             axis=-1).reshape(n_shards, capacity, 3))
        out_f, out_v, out_s = _scatter_back(
            b_src, [(resp[..., 0], jnp.bool_), (resp[..., 1], U32),
                    (resp[..., 2], jnp.bool_)], hi.shape[0])
        return out_f[None], out_v[None], out_s[None], keep[None]

    def snap_search_inner(old_st, new_st, hi, lo):
        """ONE dispatch for the whole optimistic read tick: route once,
        probe the pinned snapshot, verify each routed query against the
        live version planes (buckets_changed inlined per shard), check the
        live recovery generation, and route the packed response back. The
        retry mask never leaves the device as plane bytes — the host pulls
        O(batch) result words only."""
        hi, lo = hi[0], lo[0]
        b_hi, b_lo, _, b_src, keep = _local_dispatch(
            hi, lo, jnp.zeros_like(hi), n_shards, capacity)
        req = a2a(jnp.stack([b_hi, b_lo], axis=-1))
        old_local, new_local = _local(old_st), _local(new_st)
        rhi = req[..., 0].reshape(-1)
        rlo = req[..., 1].reshape(-1)
        found, vals = engine.search_batch(cfg, "eh", old_local, rhi, rlo,
                                          batching=search_batching)
        changed = serving_engine.buckets_changed_local(
            cfg, "eh", old_local, new_local, rhi, rlo)
        stale = _stale_lanes(new_local, hashing.hash1(rhi, rlo),
                             jnp.ones_like(changed))
        resp = a2a(jnp.stack([found.astype(U32), vals, changed.astype(U32),
                              stale.astype(U32)], axis=-1)
                   .reshape(n_shards, capacity, 4))
        out_f, out_v, out_c, out_s = _scatter_back(
            b_src, [(resp[..., 0], jnp.bool_), (resp[..., 1], U32),
                    (resp[..., 2], jnp.bool_), (resp[..., 3], jnp.bool_)],
            hi.shape[0])
        return out_f[None], out_v[None], out_c[None], out_s[None], keep[None]

    def insert_inner(st, hi, lo, v, valid):
        hi, lo, v, valid = hi[0], lo[0], v[0], valid[0]
        # padded lanes (host pads the batch to n_shards*q_local) route to
        # owner -1: the dispatcher never grants them a lane, so padding can
        # never insert the zero key (statuses come back DROPPED, trimmed by
        # the host)
        b_hi, b_lo, b_v, b_src, keep = _local_dispatch(
            hi, lo, v, n_shards, capacity,
            owner_mask=valid)
        valid_lane = (b_src >= 0).astype(U32)
        req = a2a(jnp.stack([b_hi, b_lo, b_v, valid_lane], axis=-1))
        local = _local(st)
        # shard-level parallelism is already this function's dispatch axis;
        # the shard-local sub-batch is small and mostly padding lanes, so the
        # sequential engine is the right inner mode (the segment-parallel
        # engine pays off for large host batches where the host sizes lane
        # capacity from the directory — see DashTable._write_plan)
        local, statuses, _ = engine.insert_batch(
            cfg, "eh", local, req[..., 0].reshape(-1), req[..., 1].reshape(-1),
            req[..., 2].reshape(-1), None, req[..., 3].reshape(-1) > 0,
            batching="scan")
        s_back = a2a(statuses.reshape(n_shards, capacity))
        out = jnp.full(hi.shape[0], -1, I32)
        src = b_src.reshape(-1)
        out = out.at[jnp.clip(src, 0)].max(
            jnp.where(src >= 0, s_back.reshape(-1), -1))
        out = jnp.where(out < 0, layout.DROPPED, out)   # capacity-overflow lanes
        return jax.tree.map(lambda x: x[None], local), out[None], keep[None]

    def insert_round_inner(st, hi, lo, v, pending, out):
        """One insert retry round, statuses resident on device: only the
        pending lanes route (the shrinking retry subset resolves capacity
        overflows, same as the host loop), owners bounce lanes that land on
        an unrecovered segment, and the host syncs a (3,)-flag word per
        shard instead of O(batch) statuses."""
        hi, lo, v = hi[0], lo[0], v[0]
        pending, out = pending[0], out[0]
        b_hi, b_lo, b_v, b_src, _ = _local_dispatch(
            hi, lo, v, n_shards, capacity, owner_mask=pending)
        valid_lane = (b_src >= 0).astype(U32)
        req = a2a(jnp.stack([b_hi, b_lo, b_v, valid_lane], axis=-1))
        local = _local(st)
        rhi = req[..., 0].reshape(-1)
        rlo = req[..., 1].reshape(-1)
        rv = req[..., 2].reshape(-1)
        rvalid = req[..., 3].reshape(-1) > 0
        # a write must NOT land in a crash-dirty segment (the wiped overflow
        # metadata could hide its duplicate in the stash): bounce it DROPPED
        # and flag the shard — the lane stays pending and retries after the
        # host's per-access recovery
        lane_stale = _stale_lanes(local, hashing.hash1(rhi, rlo), rvalid)
        local, statuses, _ = engine.insert_batch(
            cfg, "eh", local, rhi, rlo, rv, None, rvalid & ~lane_stale,
            batching="scan")
        statuses = jnp.where(lane_stale, I32(layout.DROPPED), statuses)
        s_back = a2a(statuses.reshape(n_shards, capacity))
        res = jnp.full(hi.shape[0], -1, I32)
        src = b_src.reshape(-1)
        res = res.at[jnp.clip(src, 0)].max(
            jnp.where(src >= 0, s_back.reshape(-1), -1))
        res = jnp.where(res < 0, layout.DROPPED, res)
        out = jnp.where(pending, res, out)
        need = pending & (out == layout.NEED_SPLIT)
        pending = need | (pending & (out == layout.DROPPED))
        flags = jnp.stack([jnp.any(pending).astype(I32),
                           jnp.any(need).astype(I32),
                           jnp.any(lane_stale).astype(I32)])
        return (jax.tree.map(lambda x: x[None], local), out[None],
                pending[None], need[None], flags[None])

    def split_inner(st, hi, lo, want):
        """Shard-local bulk SMOs in one dispatch: route the pressured keys
        to their owners, plan the distinct segments to split on device
        (``smo.plan_local_splits``), and run phase1+phase2 on the local
        sub-state (``smo.split_segments_local``). A resource-exhausted
        shard commits NOTHING and raises through its flag word — same
        semantics as the host loop's raise-before-mutate."""
        hi, lo, want = hi[0], lo[0], want[0]
        b_hi, b_lo, _, b_src, _ = _local_dispatch(
            hi, lo, jnp.zeros_like(hi), n_shards, capacity, owner_mask=want)
        valid_lane = (b_src >= 0).astype(U32)
        req = a2a(jnp.stack([b_hi, b_lo, valid_lane], axis=-1))
        local = _local(st)
        rhi = req[..., 0].reshape(-1)
        rlo = req[..., 1].reshape(-1)
        rwant = req[..., 2].reshape(-1) > 0
        old, new, valid, depth_bad, pool_bad = smo.plan_local_splits(
            cfg, local, hashing.hash1(rhi, rlo), rwant, split_lanes)
        stuck = depth_bad | pool_bad
        commit = valid & ~stuck
        local, ok = smo.split_segments_local(cfg, local, old, new, commit)
        flags = jnp.stack([depth_bad.astype(I32), pool_bad.astype(I32),
                           jnp.any(commit & ~ok).astype(I32)])
        return jax.tree.map(lambda x: x[None], local), flags[None]

    def _wrap(fn, in_specs, out_specs, donate=()):
        return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                                     out_specs=out_specs, check_vma=False),
                       donate_argnums=donate)

    q = q_spec
    return dict(
        n_shards=n_shards, capacity=capacity,
        search_fn=_wrap(search_inner, (st_spec, q, q), (q, q, q, q)),
        snap_search_fn=_wrap(snap_search_inner, (st_spec, st_spec, q, q),
                             (q, q, q, q, q)),
        insert_fn=_wrap(insert_inner, (st_spec, q, q, q, q),
                        (st_spec, q, q), donate=(0,)),
        insert_round_fn=_wrap(insert_round_inner, (st_spec, q, q, q, q, q),
                              (st_spec, q, q, q, q), donate=(0,)),
        split_fn=_wrap(split_inner, (st_spec, q, q, q), (st_spec, q),
                       donate=(0,)),
    )


def build_dht_ops(cfg: DashConfig, mesh: Mesh, axes=("data",),
                  capacity: int | None = None, q_local_hint: int = 1024,
                  search_batching: str = "vmap"):
    """Back-compat surface: jitted (search_fn, insert_fn, n_shards) over a
    device-sharded table (see ``build_dht_programs`` for the full set)."""
    progs = build_dht_programs(cfg, mesh, axes, capacity, q_local_hint,
                               search_batching)
    return progs["search_fn"], progs["insert_fn"], progs["n_shards"]


class DistributedDash:
    """Host wrapper: device-sharded Dash with shard-local SMO handling.

    ``state`` lets a caller restore a previously persisted sharded state
    (``persist.reopen_shards`` stacks one host pytree from the per-shard
    pools); ``attach_pools`` binds one durable pool per shard — flushed
    independently, so a dirty shard restart recovers shard-locally and
    never touches its neighbors' pools.

    A restored state may be crash-dirty: construction detects lagging
    shards from the SMALL planes only (seg_version / watermark / gver — a
    few KB), and every access lazily recovers exactly the segments it
    touches (``ensure_recovered``), with the shard_map programs' stale
    mask as the in-dispatch audit. ``lazy_recovery=False`` keeps the
    detection but expects the caller to recover eagerly."""

    def __init__(self, cfg: DashConfig, mesh: Mesh, axes=("data",),
                 capacity: int | None = None, q_local_hint: int = 1024,
                 search_batching: str = "vmap", state: DashState | None = None,
                 lazy_recovery: bool = True, split_lanes: int = 8):
        self.cfg = cfg
        self.mesh = mesh
        self.axes = tuple(axes)
        progs = build_dht_programs(cfg, mesh, self.axes, capacity,
                                   q_local_hint, search_batching, split_lanes)
        self.n_shards = progs["n_shards"]
        self.search_fn = progs["search_fn"]
        self.snap_search_fn = progs["snap_search_fn"]
        self.insert_fn = progs["insert_fn"]
        self.insert_round_fn = progs["insert_round_fn"]
        self.split_fn = progs["split_fn"]
        self._device_smo = smo.rebuild_eligible(cfg)
        sh = NamedSharding(mesh, P(self.axes))
        restored = state is not None
        if state is None:
            state = make_sharded_state(cfg, self.n_shards)
        else:
            assert state.version.shape[0] == self.n_shards, \
                "restored state shard count != mesh shard count"
        self.state = jax.device_put(state, sh)
        self.writebacks = None        # per-shard durable pools (persist/)
        self.lazy_recovery = lazy_recovery
        self.recovered_segments = 0
        self._dirty_shards: set = (
            self._detect_dirty_shards() if restored else set())

    # -- durable pools ------------------------------------------------------

    def attach_pools(self, writebacks):
        """Bind one durable pool per shard and mark the serving period
        dirty (the clean markers go durable only via ``close_pools``).
        Fresh pools get the current state flushed immediately, so a crash
        before the first ``flush_pools`` reopens to a valid table instead
        of an all-zeros plane region (mirrors ``persist.create``)."""
        assert len(writebacks) == self.n_shards
        self.writebacks = list(writebacks)
        self.state = self.state._replace(
            clean=jnp.zeros_like(self.state.clean))
        if any(wb.pool.sb.flush_seq == 0 for wb in self.writebacks):
            self.flush_pools()

    def flush_pools(self) -> int:
        """Flush every shard into its own pool (O(dirty) per shard: each
        shard's version-plane diff runs against its own pool mirror).
        Fault isolation: a shard whose pool degrades (I/O retry budget
        exhausted) is skipped — the OTHER shards still flush — and the
        degraded shard keeps serving from device state until
        ``recover_pools`` brings its pool back."""
        from repro import persist
        assert self.writebacks is not None, "no pools attached"
        return persist.flush_shards(self.state, self.writebacks)

    def recover_pools(self) -> int:
        """Probe every degraded shard pool and force-resync the ones that
        answer (``persist.recover_shards``). Returns shards recovered."""
        from repro import persist
        assert self.writebacks is not None, "no pools attached"
        return persist.recover_shards(self.state, self.writebacks)

    def degraded_shards(self) -> list:
        """Indices of shards whose pools are currently degraded."""
        if self.writebacks is None:
            return []
        return [i for i, wb in enumerate(self.writebacks) if wb.degraded]

    def close_pools(self):
        """Durable clean shutdown of every shard pool."""
        assert self.writebacks is not None, "no pools attached"
        self.state = self.state._replace(
            clean=jnp.ones_like(self.state.clean))
        self.flush_pools()
        for wb in self.writebacks:
            wb.pool.close()

    # -- lazy crash recovery ------------------------------------------------

    def _detect_dirty_shards(self) -> set:
        """Shards whose recovery generation lags — a host scan of the SMALL
        planes only (seg_version (S,), watermark, gver per shard; never the
        record planes). Runs once at restore; afterwards the set shrinks as
        accesses recover and the device stale mask audits it."""
        sv = np.asarray(self.state.seg_version)
        wm = np.asarray(self.state.watermark)
        gv = np.asarray(self.state.gver)
        return {i for i in range(self.n_shards)
                if (sv[i, :int(wm[i])] != gv[i]).any()}

    def ensure_recovered(self, keys=None) -> int:
        """Per-access lazy recovery (the host half of the device hook): for
        the dirty shards the keys route to, recover exactly the touched
        segments through the shared SMO-continuation orchestration
        (``core/recovery.lazy_recover_touched``) and re-stack the shard.
        ``keys=None`` recovers every dirty shard fully. Returns segments
        recovered."""
        if not self._dirty_shards:
            return 0
        if keys is None:
            owners, h1 = None, None
            shards = sorted(self._dirty_shards)
        else:
            keys = np.asarray(keys, np.uint64)
            khi, klo = hashing.np_split_keys(keys)
            h1 = hashing.np_hash1(khi, klo)
            owners = (h1 >> np.uint32(32 - int(np.log2(self.n_shards)))
                      ).astype(np.int64)
            shards = sorted(set(np.unique(owners).tolist())
                            & self._dirty_shards)
        total = 0
        for shard in shards:
            sub = jax.tree.map(lambda x: jnp.asarray(np.asarray(x)[shard]),
                               self.state)
            if owners is None:
                touched = np.arange(int(np.asarray(sub.watermark)))
            else:
                touched = np.asarray(sub.dir)[
                    h1[owners == shard]
                    >> np.uint32(32 - self.cfg.dir_depth_max)]
            sub, recovered = recovery.lazy_recover_touched(
                self.cfg, "eh", sub, touched)
            if recovered:
                self.state = jax.tree.map(
                    lambda full, s: full.at[shard].set(s), self.state, sub)
                total += len(recovered)
                self.recovered_segments += len(recovered)
            sv = np.asarray(sub.seg_version)
            wm = int(np.asarray(sub.watermark))
            if not (sv[:wm] != np.asarray(sub.gver)).any():
                self._dirty_shards.discard(shard)
        return total

    # -- batch API ----------------------------------------------------------

    def _shape_queries(self, keys):
        keys = np.asarray(keys, np.uint64)
        q_local = -(-keys.size // self.n_shards)
        pad = q_local * self.n_shards - keys.size
        keys_p = np.concatenate([keys, np.zeros(pad, np.uint64)])
        hi, lo = hashing.np_split_keys(keys_p)
        shape = (self.n_shards, q_local)
        return (jnp.asarray(hi).reshape(shape), jnp.asarray(lo).reshape(shape),
                keys.size, pad)

    def insert_once(self, keys, vals):
        """ONE sharded insert dispatch — no SMOs, no retries. Returns the
        per-key statuses; NEED_SPLIT/DROPPED lanes are the caller's to
        retry. This is the HOST-SYNC reference round (O(batch) statuses
        pulled per call) — the device-resident loop (``insert``) keeps
        statuses on device and syncs a flags word instead."""
        keys = np.asarray(keys, np.uint64)
        vals = np.asarray(vals, np.uint32)
        hi, lo, n, pad = self._shape_queries(keys)
        v = jnp.asarray(np.concatenate(
            [vals, np.zeros(pad, np.uint32)])).reshape(hi.shape)
        valid = jnp.asarray(np.arange(n + pad) < n).reshape(hi.shape)
        self.state, statuses, keep = self.insert_fn(self.state, hi, lo, v,
                                                    valid)
        return np.asarray(statuses).reshape(-1)[:n]

    def insert(self, keys, vals, max_rounds: int = 8):
        """Batch insert with shard-local SMO retries, statuses resident on
        device across rounds: each round syncs only the (n_shards, 3) flag
        word (any-retry / any-need-split / any-stale); the per-key statuses
        are pulled ONCE when the batch completes. Statuses are aligned with
        the *input* batch; capacity-DROPPED lanes retry too (the smaller
        retry subset routes without overflow)."""
        keys = np.asarray(keys, np.uint64)
        vals = np.asarray(vals, np.uint32)
        if self.lazy_recovery and self._dirty_shards:
            self.ensure_recovered(keys)
        hi, lo, n, pad = self._shape_queries(keys)
        v = jnp.asarray(np.concatenate(
            [vals, np.zeros(pad, np.uint32)])).reshape(hi.shape)
        pending = jnp.asarray(np.arange(n + pad) < n).reshape(hi.shape)
        out = jnp.full(hi.shape, layout.DROPPED, I32)
        for _ in range(max_rounds):
            self.state, out, pending, need, flags = self.insert_round_fn(
                self.state, hi, lo, v, pending, out)
            fl = np.asarray(flags)    # (n_shards, 3): the per-round sync
            if fl[:, 2].any():
                # owner saw a crash-dirty segment: recover it, lane retries
                self._dirty_shards |= self._detect_dirty_shards()
                self.ensure_recovered(keys)
            if fl[:, 1].any():
                self._dispatch_splits(hi, lo, need, keys)
            if not fl[:, 0].any():
                return np.asarray(out).reshape(-1)[:n]
        raise RuntimeError("dht insert retry budget exhausted")

    # -- shard-local SMOs ----------------------------------------------------

    def _check_split_flags(self, fl: np.ndarray):
        if fl[:, 0].any():
            raise RuntimeError("shard directory depth exhausted")
        if fl[:, 1].any():
            raise RuntimeError("shard segment pool exhausted")
        if fl[:, 2].any():
            self._repair_splits()

    def _dispatch_splits(self, hi, lo, want, keys):
        """Device bulk splits for the wanted lanes; ablation configs the
        one-pass rebuild doesn't cover take the retained host loop (the
        want mask is pulled once — O(batch) bools — only on that path)."""
        if not self._device_smo:
            need_np = np.asarray(want).reshape(-1)[:keys.size] > 0
            return self._split_for_host(keys[need_np])
        self.state, sflags = self.split_fn(self.state, hi, lo, want)
        self._check_split_flags(np.asarray(sflags))

    def split_for(self, keys):
        """Shard-local splits on the owners of failed keys. All pressured
        segments of every pressured shard split in ONE device dispatch:
        planning (directory dedupe + id assignment) and both split phases
        run inside the shard program — no host sub-state rebuild."""
        keys = np.asarray(keys, np.uint64)
        if not self._device_smo:
            return self._split_for_host(keys)
        hi, lo, n, pad = self._shape_queries(keys)
        want = jnp.asarray(np.arange(n + pad) < n).reshape(hi.shape)
        self.state, sflags = self.split_fn(self.state, hi, lo, want)
        self._check_split_flags(np.asarray(sflags))

    _split_for = split_for            # back-compat alias

    def _split_for_host(self, keys):
        """Retained host-driven split loop (differential reference + bench
        baseline + ablation fallback): rebuilds each pressured shard's
        sub-state through host copies and bulk-splits it."""
        from repro.core import dash_eh
        keys = np.asarray(keys, np.uint64)
        owners = np_owner_of(keys, self.n_shards)
        hi, lo = hashing.np_split_keys(keys)
        h1 = hashing.np_hash1(hi, lo)
        for shard in np.unique(owners):
            sub = jax.tree.map(lambda x: jnp.asarray(np.asarray(x)[shard]),
                               self.state)
            mask = owners == shard
            segs = np.unique(np.asarray(sub.dir)[
                h1[mask] >> np.uint32(32 - self.cfg.dir_depth_max)])
            depths = np.asarray(sub.local_depth)
            if (depths[segs] >= self.cfg.dir_depth_max).any():
                raise RuntimeError("shard directory depth exhausted")
            wm = int(np.asarray(sub.watermark))
            if wm + segs.size > self.cfg.max_segments:
                raise RuntimeError("shard segment pool exhausted")
            if self._device_smo:
                sub, _ = smo.bulk_split(self.cfg, sub, segs,
                                        wm + np.arange(segs.size))
            else:
                for seg in segs:
                    sub, ok = dash_eh.split_segment(self.cfg, sub, int(seg))
                    assert bool(ok)
            self.state = jax.tree.map(
                lambda full, s: full.at[shard].set(s), self.state, sub)

    def _repair_splits(self):
        """Scan-rehash fallback for shards whose one-pass rebuild could not
        fit a segment (rare pathological packings): finish each in-flight
        split exactly as BulkSplitTask's commit stage does — the source is
        still SPLITTING with its SEG_NEW neighbor side-linked."""
        from repro.core import dash_eh
        ss = np.asarray(self.state.seg_state)
        side = np.asarray(self.state.side_link)
        for shard in range(self.n_shards):
            srcs = np.nonzero(ss[shard] == layout.SEG_SPLITTING)[0]
            if not srcs.size:
                continue
            sub = jax.tree.map(lambda x: jnp.asarray(np.asarray(x)[shard]),
                               self.state)
            for seg in srcs:
                nbr = int(side[shard, seg])
                assert nbr >= 0 and ss[shard, nbr] == layout.SEG_NEW, \
                    "un-repairable split leftover"
                sub, fit = dash_eh.split_phase2_scan(
                    self.cfg, sub, jnp.asarray(int(seg), I32),
                    jnp.asarray(nbr, I32), False)
                if not bool(fit):
                    raise AssertionError(
                        "split rehash failed to refit records")
            self.state = jax.tree.map(
                lambda full, s: full.at[shard].set(s), self.state, sub)

    # -- reads ---------------------------------------------------------------

    def search_on(self, state, keys):
        """Search against a caller-supplied sharded state (e.g. an
        epoch-pinned snapshot); ``search`` is the live-state shorthand.
        The shard_map'd probe takes any state of the right shapes and
        never donates it, so snapshots survive the call."""
        hi, lo, n, _ = self._shape_queries(keys)
        f, v, _stale, _keep = self.search_fn(state, hi, lo)
        return (np.asarray(f).reshape(-1)[:n], np.asarray(v).reshape(-1)[:n])

    def search(self, keys):
        """Live-state search with the per-access recovery hook closed on
        host: dirty shards the keys route to are recovered BEFORE the
        dispatch; the in-program stale mask is the audit (it re-probes iff
        something re-dirtied behind the host's back)."""
        if self.lazy_recovery and self._dirty_shards:
            self.ensure_recovered(keys)
        hi, lo, n, _ = self._shape_queries(keys)
        f, v, stale, _ = self.search_fn(self.state, hi, lo)
        if bool(np.asarray(stale).reshape(-1)[:n].any()):
            self._dirty_shards |= self._detect_dirty_shards()
            self.ensure_recovered(keys)
            f, v, stale, _ = self.search_fn(self.state, hi, lo)
        return (np.asarray(f).reshape(-1)[:n], np.asarray(v).reshape(-1)[:n])

    def snap_search_on(self, snap_state, keys):
        """One-dispatch snapshot probe + in-program verify + recovery
        audit: (found, vals, changed, stale) host bool/word arrays —
        O(batch) result words, zero plane bytes."""
        hi, lo, n, _ = self._shape_queries(keys)
        f, v, c, s, _ = self.snap_search_fn(snap_state, self.state, hi, lo)
        cut = lambda x: np.asarray(x).reshape(-1)[:n]
        return cut(f), cut(v), cut(c), cut(s)

    @property
    def n_items(self) -> int:
        return int(np.sum(np.asarray(self.state.n_items)))


class ShardFrontend(frontend.FrontendBase):
    """The online-resize frontend (serving/frontend.py) adopted for the
    device-sharded table: epoch-guarded snapshot reads + deferred shard
    SMOs over ``DistributedDash``. Admission lanes, batch forming, the
    read-priority scheduler, and latency/retry accounting come from the
    shared ``FrontendBase``.

    ``verify_mode`` selects the read-tick machinery:

    * ``"device"`` (default) — ONE shard_map dispatch per read batch:
      snapshot probe, version-plane verify, and the lazy-recovery check all
      run inside the program (``snap_search_fn``); only O(batch) result
      words reach the host, never a plane. Write ticks run the
      device-resident retry round (statuses stay on device, flags-word
      sync) with shard-local bulk splits deferred to their own ticks.
    * ``"host"`` — the retained host-mirror baseline: probe dispatch, then
      a host copy of the dir/version planes diffed per query
      (``_changed_mask``, the host mirror of ``serving.engine.
      buckets_changed`` — the differential test keeps the two in
      lockstep), then a retry dispatch; insert rounds pull O(batch)
      statuses per round (``insert_once``). Every plane pull is metered
      into the ``frontend.host_plane_bytes`` counter — the device path
      never increments it, which is the bench's zero-copy gate.

    Insert + read lanes (the DHT serving surface); updates/deletes stay on
    the table API. Reads also attribute their sojourn to the owner shard's
    registry (``shard_registries`` / ``Registry.aggregate`` fleet view).
    """

    def __init__(self, dht: DistributedDash, *, max_batch: int = 256,
                 queue_depth: int = 4096, obs=None,
                 verify_mode: str = "device"):
        from repro.obs import Registry
        assert verify_mode in ("device", "host")
        super().__init__(max_batch=max_batch, queue_depth=queue_depth,
                         obs=obs)
        self.dht = dht
        self.verify_mode = verify_mode
        self._dirty = True
        # host-plane-transfer meter: every byte of dir/version plane the
        # verify path copies to host (the device path transfers none)
        self._host_plane_bytes = self.obs.registry.scope(
            "frontend").counter("host_plane_bytes")
        # per-shard registries: read-sojourn histograms recorded by owner
        # (host-visible routing), wb counters mirrored in on export
        self._shard_regs = [Registry() for _ in range(dht.n_shards)]
        self._shard_read_hists = [
            r.scope("shard").histogram("read_sojourn_s")
            for r in self._shard_regs]
        # per-shard degraded transitions (satellite of the quarantine/
        # transition surfacing): counts every shard that ENTERS degraded,
        # not just the frontend-level health flip
        self.shard_degraded_transitions = 0
        self._degraded_prev: set = set()
        self._publish()
        self._pending = None          # in-flight insert batch host state
        self._split_keys = None       # host mode: keys owing a bulk split
        self._split_want = None       # device mode: want mask owing splits

    def _publish(self):
        """Per-shard copy-on-write publish: the sharded state's planes have
        a (n_shards, S, ...) leading shape, and the same version-plane diff
        drives the O(dirty) scatter — an insert burst republises only the
        bucket rows its owners wrote, a shard split storm only the rebuilt
        segments (plus each shard's directory when it changed). With pools
        attached, every publish also flushes each shard into its own pool
        (flush-on-publish: acknowledged DHT ops are durable)."""
        tr = self.obs.tracer
        with tr.span("publish", "epoch") as psp:
            self.registry.publish_cow(self.dht.cfg, self.dht.state)
            self._publishes.inc()
            self._publish_bytes.inc(self.registry.last_publish_bytes)
            if self.dht.writebacks is not None:
                for i, wb in enumerate(self.dht.writebacks):
                    if wb.obs is None:
                        # per-shard flush spans nest under this publish
                        wb.attach_obs(self.obs)
                    if wb.recorder is None:
                        # one durable flight recorder per shard pool
                        # (obs/blackbox.py): each shard's windows carry its
                        # own metric deltas (the per-shard registry) and
                        # intent/ack stamps; the fleet-level tracer can
                        # only mirror into ONE sink, so the span story
                        # rides shard 0's windows. Wired by hand rather
                        # than attach_recorder to keep that choice local.
                        from repro.obs import FlightRecorder
                        rec = FlightRecorder()
                        rec.attach(
                            tracer=self.obs.tracer if i == 0 else None,
                            registry=self._shard_regs[i])
                        wb.recorder = rec
                        wb.pool.recorder = rec
                before = sum(w.flushed_bytes for w in self.dht.writebacks)
                self.dht.flush_pools()
                self._flush_bytes.inc(
                    sum(w.flushed_bytes for w in self.dht.writebacks)
                    - before)
                degraded = set(self.dht.degraded_shards())
                self.shard_degraded_transitions += len(
                    degraded - self._degraded_prev)
                self._degraded_prev = degraded
                if degraded:
                    if self.health == frontend.HEALTHY:
                        self._set_health(frontend.DEGRADED)
                    self.unflushed_publishes += 1
                elif self.health == frontend.DEGRADED:
                    self._set_health(frontend.HEALTHY)
            if psp is not None:
                psp.args["bytes"] = self.registry.last_publish_bytes
        self._dirty = False

    def submit(self, op) -> bool:
        """Reject kinds outside the DHT serving surface at admission time
        (an admitted op must never strand mid-drain)."""
        if op.kind not in (frontend.READ, frontend.INSERT):
            self.writes.rejected += 1
            return False
        return super().submit(op)

    def stats(self) -> dict:
        out = super().stats()
        out["shard_degraded_transitions"] = self.shard_degraded_transitions
        out["host_plane_bytes"] = self._host_plane_bytes.value
        out["recovered_segments"] = self.dht.recovered_segments
        if self.dht.writebacks is not None:
            out["flushes"] = sum(w.flushes for w in self.dht.writebacks)
            out["flushed_bytes"] = sum(w.flushed_bytes
                                       for w in self.dht.writebacks)
            out["pool_bytes"] = sum(w.pool.plane_bytes
                                    for w in self.dht.writebacks)
            degraded = self.dht.degraded_shards()
            out["shards_degraded"] = degraded
            out["health"] = (frontend.DEGRADED if degraded
                             else frontend.HEALTHY)
            out["flush_io_errors"] = sum(w.flush_io_errors
                                         for w in self.dht.writebacks)
            out["degraded_flushes"] = sum(w.degraded_flushes
                                          for w in self.dht.writebacks)
            # durable quarantine evidence, fleet-wide (satellite: chaos
            # runs assert on the aggregate without reaching into pools)
            out["lost_records"] = sum(w.pool.sb.lost_records
                                      for w in self.dht.writebacks)
            out["quarantined_bt"] = sum(len(w.pool.sb.lost_bt)
                                        for w in self.dht.writebacks)
            out["quarantined_nb"] = sum(len(w.pool.sb.lost_nb)
                                        for w in self.dht.writebacks)
        return out

    def shard_registries(self) -> list:
        """One ``Registry`` per shard — the persistent per-shard
        read-sojourn histograms plus (with pools attached) the writeback's
        cumulative counters mirrored in — so ``Registry.aggregate`` sums a
        fleet view, histograms included."""
        if self.dht.writebacks is not None:
            for r, wb in zip(self._shard_regs, self.dht.writebacks):
                r.ingest(wb.stats(), prefix="wb.", counters=True)
        return list(self._shard_regs)

    def obs_snapshot(self) -> dict:
        from repro.obs import Registry
        self.obs.registry.ingest(self.stats(), prefix="stats.")
        out = self.obs.snapshot()
        regs = self.shard_registries()
        if regs:
            out["shards"] = Registry.aggregate(regs).snapshot()
            out["per_shard"] = [r.snapshot() for r in regs]
        return out

    def _set_health(self, new: str):
        # fleet health transitions stamp EVERY shard's durable recorder —
        # forensics on any one pool shows when the frontend flipped
        if new != self.health and self.dht.writebacks is not None:
            for wb in self.dht.writebacks:
                if wb.recorder is not None:
                    wb.recorder.record_health(self.health, new)
        super()._set_health(new)

    # -- durable intent/ack stamps, per owner shard -----------------------

    def _record_shard_intents(self, ops):
        """Split the batch by owner and stamp each shard's recorder with
        its slice BEFORE flush-on-publish (see DashFrontend._record_intent
        — same protocol, one await_seq per shard pool)."""
        wbs = self.dht.writebacks
        if wbs is None or all(wb.recorder is None for wb in wbs):
            return None
        keys = np.asarray([op.key for op in ops], np.uint64)
        owner = np_owner_of(keys, self.dht.n_shards)
        stamps = []
        for shard in np.unique(owner):
            wb = wbs[int(shard)]
            if wb.recorder is None:
                continue
            mine = keys[owner == shard]
            aw = wb.pool.sb.flush_seq + 1
            wb.recorder.record_ops(frontend.INSERT, mine, await_seq=aw,
                                   shard=int(shard))
            stamps.append((int(shard), aw, len(mine)))
        return stamps

    def _record_shard_acks(self, stamps):
        for shard, aw, n in stamps or ():
            wb = self.dht.writebacks[shard]
            seq = wb.pool.sb.flush_seq
            wb.recorder.record_ack(seq, frontend.INSERT, n,
                                   durable=seq >= aw)

    def forensics_report(self) -> dict:
        """Fleet-merged crash forensics over every shard pool
        (obs/forensics.py): per-shard reports plus the summary an
        operator reads first — lowest/highest committed ``flush_seq``,
        shards with a torn flush, every in-flight op tagged with its
        shard. Raw file reads only; safe on a live or crashed fleet."""
        from repro.obs import forensics
        assert self.dht.writebacks is not None, "no pools attached"
        return forensics.merge_reports(
            [forensics.analyze_pool(wb.pool.path)
             for wb in self.dht.writebacks])

    def try_recover(self) -> bool:
        """Re-probe degraded shard pools; True when every shard is back
        HEALTHY. Healthy shards were never interrupted — recovery is
        strictly per-shard (fault isolation)."""
        if self.dht.writebacks is None:
            return True
        if self.dht.degraded_shards():
            self.dht.recover_pools()
        ok = not self.dht.degraded_shards()
        if ok:
            self._degraded_prev = set()
            self._set_health(frontend.HEALTHY)
        return ok

    def _write_pending(self) -> bool:
        return (self._pending is not None or self._split_keys is not None
                or self._split_want is not None)

    def _finish_reads(self, ops, found, vals, n_changed: int):
        super()._finish_reads(ops, found, vals, n_changed)
        # attribute each read's sojourn to its owner shard (pure host
        # hashing — no device traffic) for the per-shard fleet view
        keys = np.asarray([op.key for op in ops], np.uint64)
        owner = np_owner_of(keys, self.dht.n_shards)
        lats = np.asarray([op.latency for op in ops], np.float64)
        for shard in np.unique(owner):
            self._shard_read_hists[int(shard)].observe_many(
                lats[owner == shard])

    # -- read path -----------------------------------------------------------

    def _changed_mask(self, snap_state, keys) -> np.ndarray:
        """HOST-MIRROR verify (the ``verify_mode="host"`` baseline and the
        differential reference for the device mask): a host copy of the
        owner shards' dir + version planes, diffed per query — the same
        contract as serving.engine.buckets_changed (a contract change
        there MUST land here too; the shard consistency test guards it).
        Every plane byte copied is metered into ``host_plane_bytes``."""
        cfg = self.dht.cfg
        keys = np.asarray(keys, np.uint64)
        hi, lo = hashing.np_split_keys(keys)
        h1 = hashing.np_hash1(hi, lo)
        owner = np_owner_of(keys, self.dht.n_shards)
        d = (h1 >> np.uint32(32 - cfg.dir_depth_max)).astype(np.int64)
        old_dir, new_dir = np.asarray(snap_state.dir), np.asarray(
            self.dht.state.dir)
        seg = old_dir[owner, d].astype(np.int64)
        changed = seg != new_dir[owner, d]
        oldv = np.asarray(snap_state.version)
        newv = np.asarray(self.dht.state.version)
        self._host_plane_bytes.inc(old_dir.nbytes + new_dir.nbytes
                                   + oldv.nbytes + newv.nbytes)
        NB = cfg.num_buckets
        b = (h1 & np.uint32(NB - 1)).astype(np.int64)
        for w in range(cfg.probe_window):
            bw = (b + w) & (NB - 1)
            changed |= oldv[owner, seg, bw] != newv[owner, seg, bw]
        for s in range(cfg.num_stash):
            changed |= oldv[owner, seg, NB + s] != newv[owner, seg, NB + s]
        return changed

    def _serve_reads(self, ops):
        keys = np.asarray([op.key for op in ops], np.uint64)
        if self.dht.lazy_recovery and self.dht._dirty_shards:
            # per-access recovery BEFORE pinning: recovered segments bump
            # their version words, so the verify pass below redirects any
            # query that probes them to the (recovered) live state
            if self.dht.ensure_recovered(keys):
                self._dirty = True
        if self.verify_mode == "host":
            with self.registry.acquire() as snap:
                found, vals = self.dht.search_on(snap.state, keys)
                n_changed = 0
                if self._dirty:
                    changed = self._changed_mask(snap.state, keys)
                    n_changed = int(changed.sum())
                if n_changed:
                    f2, v2 = self.dht.search(keys)
                    found = np.where(changed, f2, found)
                    vals = np.where(changed, v2, vals)
            self._finish_reads(ops, found, vals, n_changed)
            return
        # device path: ONE dispatch probes the snapshot, verifies it
        # against the live planes, and checks the recovery generation —
        # the masks come back as O(batch) bools, never as plane bytes
        with self.registry.acquire() as snap:
            found, vals, changed, stale = self.dht.snap_search_on(
                snap.state, keys)
            changed = changed | stale
            n_changed = int(changed.sum())
            if n_changed:
                f2, v2 = self.dht.search(keys)
                found = np.where(changed, f2, found)
                vals = np.where(changed, v2, vals)
        self._finish_reads(ops, found, vals, n_changed)

    # -- write path ----------------------------------------------------------

    def _pump_write(self) -> bool:
        if self.verify_mode == "host":
            return self._pump_write_host()
        if self._split_want is not None and self._pending is not None:
            # the deferred storm: every pressured owner splits all its
            # pressured segments in one bulk dispatch (device-planned)
            ops, keys, vals, hi, lo, v, pend, out, rounds = self._pending
            self.dht._dispatch_splits(hi, lo, self._split_want, keys)
            self._split_want = None
            self._dirty = True
            self._publish()
            return True
        if self._pending is not None:
            ops, keys, vals, hi, lo, v, pend, out, rounds = self._pending
            if rounds > 32:
                raise RuntimeError("dht insert retry budget exhausted")
            self.dht.state, out, pend, need, flags = self.dht.insert_round_fn(
                self.dht.state, hi, lo, v, pend, out)
            self._dirty = True
            fl = np.asarray(flags)
            if fl[:, 2].any():
                self.dht._dirty_shards |= self.dht._detect_dirty_shards()
                self.dht.ensure_recovered(keys)
            if fl[:, 1].any():
                self._split_want = need
            if not fl[:, 0].any():
                aw = self._record_shard_intents(ops)
                self._finish_writes(ops,
                                    np.asarray(out).reshape(-1)[:keys.size])
                self._pending = None
                self._split_want = None
                self._publish()
                self._record_shard_acks(aw)
            else:
                self._pending = (ops, keys, vals, hi, lo, v, pend, out,
                                 rounds + 1)
            return True
        ops = self.former.form(self.writes)
        if not ops:
            return False
        assert ops[0].kind == frontend.INSERT, \
            "shard frontend lanes cover read + insert"
        keys = np.asarray([op.key for op in ops], np.uint64)
        vals = np.asarray([op.value for op in ops], np.uint32)
        if self.dht.lazy_recovery and self.dht._dirty_shards:
            if self.dht.ensure_recovered(keys):
                self._dirty = True
        hi, lo, n, pad = self.dht._shape_queries(keys)
        v = jnp.asarray(np.concatenate(
            [vals, np.zeros(pad, np.uint32)])).reshape(hi.shape)
        pend = jnp.asarray(np.arange(n + pad) < n).reshape(hi.shape)
        out = jnp.full(hi.shape, layout.DROPPED, I32)
        self._pending = (ops, keys, vals, hi, lo, v, pend, out, 0)
        return self._pump_write()

    def _pump_write_host(self) -> bool:
        """Retained host-sync write tick (``verify_mode="host"``): one
        ``insert_once`` per round with O(batch) statuses pulled to host,
        and pressured shards split through the host sub-state loop — the
        full pre-device-resident baseline the bench gates against. (The
        split PLAN is identical to the device path's, so the two modes
        still land bit-identical states.)"""
        if self._split_keys is not None:
            self.dht._split_for_host(self._split_keys)
            self._split_keys = None
            self._dirty = True
            self._publish()
            return True
        if self._pending is not None:
            keys, vals, out, pending, ops, rounds = self._pending
            if rounds > 32:
                raise RuntimeError("dht insert retry budget exhausted")
            statuses = self.dht.insert_once(keys[pending], vals[pending])
            self._dirty = True
            out[pending] = statuses
            need = statuses == layout.NEED_SPLIT
            retry = need | (statuses == layout.DROPPED)
            if not retry.any():
                aw = self._record_shard_intents(ops)
                self._finish_writes(ops, out)
                self._pending = None
                self._publish()
                self._record_shard_acks(aw)
            else:
                if need.any():
                    self._split_keys = keys[pending[need]]
                self._pending = (keys, vals, out, pending[retry], ops,
                                 rounds + 1)
            return True
        ops = self.former.form(self.writes)
        if not ops:
            return False
        assert ops[0].kind == frontend.INSERT, \
            "shard frontend lanes cover read + insert"
        keys = np.asarray([op.key for op in ops], np.uint64)
        vals = np.asarray([op.value for op in ops], np.uint32)
        if self.dht.lazy_recovery and self.dht._dirty_shards:
            if self.dht.ensure_recovered(keys):
                self._dirty = True
        self._pending = (keys, vals,
                         np.full(keys.size, layout.DROPPED, np.int32),
                         np.arange(keys.size), ops, 0)
        return self._pump_write_host()
