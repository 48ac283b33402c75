"""Online-resize serving frontend: epoch/grace-period manager, admission
pipeline, snapshot-verify-retry reads, and the no-torn-reads interleaving
property (ISSUE 3 acceptance: >= 200 randomized query/SMO schedules)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import DashConfig, engine as dash_engine, smo
from repro.core.epoch import EpochManager, Snapshot, SnapshotRegistry
from repro.core.hashing import np_split_keys
from repro.core.layout import INSERTED, NOT_FOUND
from repro.core.table import DashEH, DashLH
from repro.serving import buckets_changed, snapshot_search
from repro.serving.frontend import (INSERT, READ, RMW, UPDATE, AdmissionQueue,
                                    BatchFormer, DashFrontend, Op,
                                    StopTheWorldFrontend)
from repro.workloads import ycsb
from tests.conftest import unique_keys

CFG = DashConfig(max_segments=32, dir_depth_max=7, num_buckets=16,
                 num_slots=8)


# ---------------------------------------------------------------------------
# epoch manager + snapshot registry
# ---------------------------------------------------------------------------

def test_epoch_pin_blocks_reclamation():
    freed = []
    mgr = EpochManager(reclaim=freed.append)
    with mgr.pin():
        mgr.retire("v0")
        mgr.retire("v1")
        assert freed == []            # a pinned reader may still see them
        assert mgr.limbo_size == 2
    # after the reader exits, retire/advance cycles reclaim the limbo
    for _ in range(4):
        mgr.retire(object())
    assert "v0" in freed and "v1" in freed
    assert mgr.reclaimed >= 2


def test_snapshot_registry_versions_and_reclaim():
    freed = []
    reg = SnapshotRegistry(reclaim=lambda s: freed.append(s.version))
    reg.publish("s0")
    assert reg.version == 0
    with reg.acquire() as snap:
        assert snap.version == 0 and snap.state == "s0"
        reg.publish("s1")             # supersede while a reader is pinned
        assert reg.version == 1
        assert freed == []            # v0 protected by the pin
    for i in range(4):
        reg.publish(f"s{i + 2}")
    assert 0 in freed                 # reclaimed once the grace period passed
    assert reg.published == 6
    reg.flush()
    assert sorted(freed) == [0, 1, 2, 3, 4]   # all but current reclaimed


def test_snapshot_reclaim_deletes_buffers():
    reg = SnapshotRegistry()          # default reclaimer frees device buffers
    reg.publish(jnp.arange(4))
    old = reg.current
    for _ in range(5):
        reg.publish(jnp.arange(4))
    assert reg.reclaimed >= 1
    assert old.state.is_deleted()


# ---------------------------------------------------------------------------
# admission pipeline
# ---------------------------------------------------------------------------

def test_admission_queue_backpressure():
    q = AdmissionQueue(depth=2)
    assert q.offer(Op(READ, 1)) and q.offer(Op(READ, 2))
    assert not q.offer(Op(READ, 3))   # bounded: reject, don't grow
    assert q.rejected == 1 and q.admitted == 2
    q.pop()
    assert q.offer(Op(READ, 3))


def test_batch_former_homogeneous_runs():
    q = AdmissionQueue()
    for op in [Op(INSERT, 1, 1), Op(INSERT, 2, 2), Op(UPDATE, 1, 3),
               Op(INSERT, 3, 3)]:
        q.offer(op)
    f = BatchFormer(max_batch=8)
    b1 = f.form(q)
    assert [op.kind for op in b1] == [INSERT, INSERT]   # stops at kind change
    b2 = f.form(q)
    assert [op.kind for op in b2] == [UPDATE]
    assert [op.kind for op in f.form(q)] == [INSERT]
    assert f.form(q) == []


# ---------------------------------------------------------------------------
# frontend correctness vs the stop-the-world path
# ---------------------------------------------------------------------------

def _mixed_stream(rng, n_load=1200, n_fresh=600):
    keys = ycsb.load_keys(rng, n_load + n_fresh)
    loaded, fresh = keys[:n_load], keys[n_load:]
    ops = [Op(INSERT, int(k), ycsb.expected_value(int(k))) for k in loaded]
    # fill-driven storm: fresh inserts interleaved with reads + updates
    ridx = rng.integers(0, n_load, n_fresh)
    for i, k in enumerate(fresh):
        ops.append(Op(INSERT, int(k), ycsb.expected_value(int(k))))
        ops.append(Op(READ, int(loaded[ridx[i]])))
        if i % 3 == 0:
            kk = int(loaded[ridx[i]])
            ops.append(Op(UPDATE, kk, ycsb.updated_value(kk)))
    return keys, ops


def test_frontend_matches_stop_the_world(rng):
    keys, ops = _mixed_stream(np.random.default_rng(7))
    import copy
    ops_fe = copy.deepcopy(ops)

    t_stw = DashEH(CFG)
    stw = StopTheWorldFrontend(t_stw, max_batch=128, queue_depth=1 << 14)
    for op in ops:
        assert stw.submit(op)
    stw.drain()

    t_fe = DashEH(CFG)
    fe = DashFrontend(t_fe, max_batch=128, queue_depth=1 << 14)
    for op in ops_fe:
        assert fe.submit(op)
    fe.drain()

    # same acknowledged write outcomes, same final logical table (batch
    # formation differs across the lanes, so split *timing* may differ —
    # the record multiset is the contract, not the physical layout)
    assert t_fe.n_items == t_stw.n_items
    assert int(np.asarray(dash_engine.recount_items(t_fe.state))) == t_fe.n_items

    def all_records(t):
        recs = []
        for seg in range(t.n_segments):
            recs += smo.segment_record_set(CFG, t.state, seg)
        return sorted(recs)

    assert all_records(t_fe) == all_records(t_stw)
    st_fe = {(o.kind, o.key): o.status for o in ops_fe if o.kind != READ}
    st_stw = {(o.kind, o.key): o.status for o in ops if o.kind != READ}
    assert st_fe == st_stw
    # reads went through the snapshot path; some overlapped the storm
    assert fe.snapshot_reads > 0
    assert fe.smo_dispatches > 0      # splits actually ran deferred
    # every frontend read observed a pre- or post-write-consistent value
    for op in ops_fe:
        if op.kind != READ:
            continue
        pre, post = ycsb.expected_value(op.key), ycsb.updated_value(op.key)
        assert (not op.found) or op.result in (pre, post), op
    # acknowledged-write visibility: a drained frontend read sees the key
    f, v = t_fe.search(keys)
    assert f.all()


def test_frontend_ticks_identical_fused_on_off(rng):
    """Tick-for-tick equivalence of the fused read path: the same op
    stream through a fused-reads frontend and a routed-reads frontend
    produces identical per-op outcomes (found/value/status) AND the same
    final table — writes ride the planner's fused path in both, so this
    pins the serving-layer read selection specifically. The mixed stream
    drives splits mid-stream, so snapshot + verify-retry reads cross SMO
    boundaries under both paths."""
    import copy
    _, ops = _mixed_stream(np.random.default_rng(23))
    ops_on, ops_off = copy.deepcopy(ops), copy.deepcopy(ops)

    t_on = DashEH(CFG)
    fe_on = DashFrontend(t_on, max_batch=128, queue_depth=1 << 14,
                         fused_reads=True)
    t_off = DashEH(CFG)
    fe_off = DashFrontend(t_off, max_batch=128, queue_depth=1 << 14,
                          fused_reads=False)
    assert fe_on.read_batching == "fused"
    assert fe_off.read_batching == "auto"
    # interleave tick-for-tick so the two frontends see identical schedules
    for op_a, op_b in zip(ops_on, ops_off):
        assert fe_on.submit(op_a)
        assert fe_off.submit(op_b)
    while fe_on.step() | fe_off.step():
        pass
    for a, b in zip(ops_on, ops_off):
        assert (a.kind, a.key, a.status, a.found, a.result) == \
               (b.kind, b.key, b.status, b.found, b.result)
    from tests.test_fused import _diverged
    assert not _diverged(t_on.state, t_off.state)
    assert fe_on.snapshot_reads == fe_off.snapshot_reads
    assert fe_on.retried_reads == fe_off.retried_reads


def test_frontend_counts_fused_read_batches(monkeypatch):
    """Every fused read dispatch of the frontend — the tick's read and the
    verify pass's retry — is counted once in the table's obs registry by
    the program it ran (the direct lowering on a host without a TPU), and
    ``stats()`` mirrors both counters."""
    calls = []
    search = dash_engine.search_batch

    def spy(*args, **kw):
        calls.append(kw.get("batching"))
        return search(*args, **kw)

    monkeypatch.setattr(dash_engine, "search_batch", spy)
    _, ops = _mixed_stream(np.random.default_rng(29))
    t = DashEH(CFG)
    fe = DashFrontend(t, max_batch=128, queue_depth=1 << 14,
                      fused_reads=True)
    for op in ops:
        assert fe.submit(op)
    fe.drain()
    st = fe.stats()
    n = calls.count("fused")
    assert st["fused_direct_batches"] == n > 0
    assert st["fused_kernel_batches"] == 0
    assert fe.retried_reads > 0          # the retry dispatches were counted
    t.search(np.asarray([op.key for op in ops[:64]], np.uint64))
    assert fe.stats()["fused_direct_batches"] == calls.count("fused") == n + 1


def test_frontend_rmw_and_delete(rng):
    t = DashEH(CFG)
    fe = DashFrontend(t, max_batch=64, queue_depth=4096)
    keys = unique_keys(np.random.default_rng(11), 300)
    for k in keys:
        fe.submit(Op(INSERT, int(k), ycsb.expected_value(int(k))))
    fe.drain()
    for k in keys[:64]:
        fe.submit(Op(RMW, int(k), ycsb.updated_value(int(k))))
    fe.drain()
    # RMW observed the pre-image and installed the new value
    f, v = t.search(keys[:64])
    want = np.array([ycsb.updated_value(int(k)) for k in keys[:64]], np.uint32)
    assert f.all() and (v == want).all()


def test_frontend_lh_stride_expansion():
    cfg = DashConfig(max_segments=32, dir_depth_max=7, num_buckets=16,
                     num_slots=8, lh_base_log2=2)
    t = DashLH(cfg)
    fe = DashFrontend(t, max_batch=128, queue_depth=1 << 14)
    keys = ycsb.load_keys(np.random.default_rng(3), 1500)
    for k in keys:
        fe.submit(Op(INSERT, int(k), ycsb.expected_value(int(k))))
        fe.submit(Op(READ, int(k)))
    fe.drain()
    assert t.n_items == 1500
    assert t.active_segments > (1 << cfg.lh_base_log2)   # rounds expanded
    f, _ = t.search(keys)
    assert f.all()


# ---------------------------------------------------------------------------
# the interleaving property: no torn reads across randomized schedules
# ---------------------------------------------------------------------------

N_SCHEDULES = 200


def test_snapshot_search_no_torn_reads_under_smo_interleaving(rng):
    """>= N_SCHEDULES randomized schedules interleave ``snapshot_search``
    with a concurrent staged ``bulk_split`` (and concurrent inserts): every
    query must return either the pre-split-consistent or the
    post-split-consistent result — never a torn read (present key lost,
    value from nowhere, or phantom key).

    Shapes are pinned (fixed query batch, fixed split fan-out, fixed insert
    batch) so all schedules share one set of jit traces."""
    local = np.random.default_rng(0xE90C)
    base_keys = unique_keys(local, 1400)
    t = DashEH(CFG)
    t.insert(base_keys[:1000], np.arange(1000, dtype=np.uint32))
    base = t.state
    fresh_pool = base_keys[1000:]

    Q = 256                               # fixed probe batch (one jit trace)
    K = 2                                 # fixed split fan-out per schedule
    IN = 64                               # fixed concurrent-insert batch
    torn = 0
    for sched in range(N_SCHEDULES):
        state = jax.tree.map(jnp.copy, base)
        snapshot = jax.tree.map(jnp.copy, state)

        # --- concurrent writer: random interleave of SMO stages + inserts
        depths = np.asarray(state.local_depth)
        cand = [int(s) for s in np.unique(np.asarray(state.dir))
                if depths[s] < CFG.dir_depth_max]
        segs = list(local.choice(cand, size=K, replace=False))
        wm = int(np.asarray(state.watermark))
        task = smo.BulkSplitTask(CFG, segs, list(range(wm, wm + K)))
        n_stages = int(local.integers(0, 4))      # 0..3 of phase1/2/commit
        done = False
        for _ in range(n_stages):
            if not done:
                state, done = task.pump(state)
        ins_sel = local.integers(0, fresh_pool.size, IN)
        new_keys = fresh_pool[ins_sel]
        do_insert = bool(local.integers(0, 2))
        if do_insert:
            hi_n, lo_n = np_split_keys(new_keys)
            state, st_ins, _ = dash_engine.insert_batch(
                CFG, "eh", state, jnp.asarray(hi_n), jnp.asarray(lo_n),
                jnp.arange(IN, dtype=jnp.uint32) + 5000, batching="scan")

        # --- reader: base keys + the maybe-inserted keys + absent keys
        qsel = local.integers(0, 1000, Q - 2 * IN)
        q_keys = np.concatenate([base_keys[qsel], new_keys,
                                 fresh_pool[local.integers(0, fresh_pool.size,
                                                           IN)]])
        hi, lo = np_split_keys(q_keys)
        found, vals, retried = snapshot_search(
            CFG, snapshot, state, jnp.asarray(hi), jnp.asarray(lo))
        found, vals = np.asarray(found), np.asarray(vals)

        # --- consistency oracle -------------------------------------------
        # keys present at snapshot time must be found with their one value
        # (splits move records, they never change the mapping)
        base_mask = np.isin(q_keys, base_keys[:1000])
        val_of = {int(k): i for i, k in enumerate(base_keys[:1000])}
        for i in np.nonzero(base_mask)[0]:
            if not found[i] or int(vals[i]) != val_of[int(q_keys[i])]:
                torn += 1
        # concurrently-inserted keys: pre-consistent (absent) or
        # post-consistent (their new value) — never garbage
        ins_set = {int(k) for k in new_keys} if do_insert else set()
        for i in np.nonzero(~base_mask)[0]:
            k = int(q_keys[i])
            if k in ins_set:
                if found[i] and int(vals[i]) < 5000:
                    torn += 1
            elif found[i]:
                torn += 1              # phantom: never-inserted key found
    assert torn == 0, f"{torn} torn reads across {N_SCHEDULES} schedules"


def test_buckets_changed_flags_update_writes(rng):
    """An in-place update must be visible to the verify pass (version bump
    regression: silent payload rewrites would let snapshot readers serve
    stale values forever)."""
    t = DashEH(CFG)
    keys = unique_keys(np.random.default_rng(21), 500)
    t.insert(keys, np.arange(500, dtype=np.uint32))
    snap = jax.tree.map(jnp.copy, t.state)
    t.update(keys[:100], np.arange(100, dtype=np.uint32) + 7000)
    hi, lo = np_split_keys(keys[:100])
    changed = np.asarray(buckets_changed(
        CFG, "eh", snap, t.state, jnp.asarray(hi), jnp.asarray(lo)))
    assert changed.all()
    f, v, _ = snapshot_search(CFG, snap, t.state, jnp.asarray(hi),
                              jnp.asarray(lo))
    assert (np.asarray(v) == np.arange(100) + 7000).all()


# ---------------------------------------------------------------------------
# YCSB generator
# ---------------------------------------------------------------------------

def test_ycsb_mixes_and_distributions():
    rng = np.random.default_rng(1)
    loaded = ycsb.load_keys(rng, 512)
    fresh = ycsb.load_keys(np.random.default_rng(2), 2200)
    for mix, ratios in ycsb.MIXES.items():
        cfg = ycsb.YCSBConfig(mix=mix, n_ops=2000, seed=3)
        ops = ycsb.generate(cfg, loaded, insert_keys=fresh)
        kinds = {k: sum(op.kind == k for op in ops) / len(ops)
                 for k in set(op.kind for op in ops)}
        for k, r in ratios.items():
            if mix == "E":
                continue               # scan bursts reshape the ratio
            assert abs(kinds.get(k, 0.0) - r) < 0.08, (mix, kinds)
    # zipfian skews: the hottest key dominates a uniform draw
    z = ycsb.zipfian_ranks(np.random.default_rng(4), 512, 20000)
    counts = np.bincount(z, minlength=512)
    assert counts[0] > 4 * counts[256]
    # determinism
    a = ycsb.generate(ycsb.YCSBConfig(mix="A", n_ops=100, seed=9), loaded)
    b = ycsb.generate(ycsb.YCSBConfig(mix="A", n_ops=100, seed=9), loaded)
    assert [(o.kind, o.key, o.value) for o in a] == \
           [(o.kind, o.key, o.value) for o in b]
    # E's scan bursts count toward the op budget (size-comparable streams)
    e_ops = ycsb.generate(ycsb.YCSBConfig(mix="E", n_ops=100, seed=5),
                          loaded, insert_keys=fresh)
    assert len(e_ops) == 100
    # the pure-insert load mix works against an empty loaded space
    l_ops = ycsb.generate(ycsb.YCSBConfig(mix="load", n_ops=50, seed=5),
                          np.array([], np.uint64), insert_keys=fresh)
    assert len(l_ops) == 50 and all(o.kind == INSERT for o in l_ops)
    # distribution="latest" is honored: post-insert reads chase the front
    lat = ycsb.generate(ycsb.YCSBConfig(mix="D", n_ops=600, seed=5,
                                        distribution="latest"),
                        loaded, insert_keys=fresh)
    seen_ins = set()
    checked = 0
    for op in lat:
        if op.kind == INSERT:
            seen_ins.add(op.key)
        elif seen_ins:
            assert op.key in seen_ins, "latest read outside insert window"
            checked += 1
    assert checked > 50


def test_ycsb_e_scan_bursts():
    loaded = ycsb.load_keys(np.random.default_rng(5), 256)
    ops = ycsb.generate(ycsb.YCSBConfig(mix="E", n_ops=400, seed=6), loaded,
                        insert_keys=ycsb.load_keys(np.random.default_rng(7),
                                                   64))
    # consecutive-key runs of SCAN_LEN appear (the scan analog)
    runs = 0
    i = 0
    keyset = {int(k): i for i, k in enumerate(loaded)}
    while i < len(ops) - ycsb.SCAN_LEN:
        if all(ops[i + j].kind == READ for j in range(ycsb.SCAN_LEN)):
            idx = [keyset.get(ops[i + j].key, -1)
                   for j in range(ycsb.SCAN_LEN)]
            if -1 not in idx and all(
                    idx[j + 1] == (idx[j] + 1) % 256
                    for j in range(ycsb.SCAN_LEN - 1)):
                runs += 1
                i += ycsb.SCAN_LEN
                continue
        i += 1
    assert runs > 5


# ---------------------------------------------------------------------------
# full workload suite through the frontend (slow lane)
# ---------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.parametrize("mix", ["A", "B", "C", "D", "E", "F"])
def test_ycsb_suite_through_frontend(mix):
    """Every YCSB mix end-to-end through the concurrent frontend: all ops
    acknowledged, reads always pre- or post-consistent, table audit clean."""
    rng = np.random.default_rng(0x5C + ord(mix))
    loaded = ycsb.load_keys(rng, 1500)
    fresh = ycsb.load_keys(np.random.default_rng(ord(mix)), 800)
    t = DashEH(CFG)
    t.insert(loaded, np.asarray([ycsb.expected_value(int(k)) for k in loaded],
                                dtype=np.uint32))
    fe = DashFrontend(t, max_batch=128, queue_depth=1 << 15)
    ops = ycsb.generate(ycsb.YCSBConfig(mix=mix, n_ops=4000, seed=13),
                        loaded, insert_keys=fresh)
    for op in ops:
        assert fe.submit(op)
    fe.drain()
    assert t.n_items == int(np.asarray(dash_engine.recount_items(t.state)))
    for op in ops:
        if op.kind == READ and op.found:
            k = op.key
            assert op.result in (ycsb.expected_value(k),
                                 ycsb.updated_value(k)), op
        if op.kind in (INSERT, UPDATE, RMW):
            assert op.status in (INSERTED, NOT_FOUND, 1), op   # 1 = EXISTS
