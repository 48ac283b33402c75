"""Multi-device tests (subprocess with fake devices): DHT + shard_map +
elastic resize + compression psum."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

ENV = {**os.environ, "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
       "PYTHONPATH": "src", "JAX_PLATFORMS": "cpu"}


def run_sub(code: str, timeout=900):
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=ENV,
                       cwd=os.path.dirname(os.path.dirname(__file__)) or ".",
                       timeout=timeout)
    assert r.returncode == 0, r.stdout + "\n" + r.stderr
    return r.stdout


def test_dht_8_shards():
    out = run_sub("""
        import numpy as np
        from repro.core import DashConfig, INSERTED, EXISTS
        from repro.distributed import DistributedDash
        from repro.launch.mesh import make_test_mesh
        mesh = make_test_mesh(2, 4)
        d = DistributedDash(DashConfig(max_segments=32, dir_depth_max=8),
                            mesh, axes=("data", "model"), capacity=256)
        rng = np.random.default_rng(5)
        keys = np.unique(rng.integers(1, 2**63, 8000, dtype=np.uint64))[:4000]
        vals = np.arange(4000, dtype=np.uint32) % 1000 + 1
        st = d.insert(keys, vals)
        assert (st == INSERTED).all()
        assert (d.insert(keys[:64], vals[:64]) == EXISTS).all()
        f, v = d.search(keys)
        assert f.all() and (v == vals).all()
        neg = np.setdiff1d(np.unique(rng.integers(1, 2**63, 2000, dtype=np.uint64)), keys)[:500]
        f2, _ = d.search(neg); assert f2.sum() == 0
        print("OK items", d.n_items)
    """)
    assert "OK items 4000" in out


def test_dht_shard_splits_bulk():
    """Split-heavy DHT workload: small segments force NEED_SPLIT retry
    rounds, so owners run the bulk shard-local SMO dispatch and the retry
    batches are padded (regression: padded lanes must never insert the zero
    key — n_items has to agree with a meta recount)."""
    out = run_sub("""
        import numpy as np
        from repro.core import DashConfig, INSERTED, layout
        from repro.distributed import DistributedDash
        from repro.launch.mesh import make_test_mesh
        mesh = make_test_mesh(2, 4)
        cfg = DashConfig(max_segments=32, dir_depth_max=8, init_depth=1,
                         num_buckets=16, num_slots=8)
        d = DistributedDash(cfg, mesh, axes=("data", "model"), capacity=256)
        rng = np.random.default_rng(9)
        keys = np.unique(rng.integers(1, 2**63, 8000, dtype=np.uint64))[:3001]
        vals = np.arange(3001, dtype=np.uint32) % 1000 + 1
        st = d.insert(keys, vals)
        assert (st == INSERTED).all()
        wm = np.asarray(d.state.watermark)
        assert wm.max() > 2, wm          # splits actually happened
        f, v = d.search(keys)
        assert f.all() and (v == vals).all()
        meta = np.asarray(d.state.meta)
        recount = int(((meta >> layout.COUNT_SHIFT) & 0xF).sum())
        assert d.n_items == 3001 == recount, (d.n_items, recount)
        print("OK items", d.n_items, "max wm", int(wm.max()))
    """)
    assert "OK items 3001" in out


def test_dht_shard_frontend():
    """Epoch-guarded shard frontend: reads pin a published snapshot of the
    sharded state and verify owner-shard version planes; pressured owners'
    bulk splits run deferred between read batches. Reads must stay pre- or
    post-split-consistent and every insert must land."""
    out = run_sub("""
        import numpy as np
        from repro.core import DashConfig, INSERTED, layout
        from repro.distributed import DistributedDash
        from repro.distributed.dht import ShardFrontend
        from repro.launch.mesh import make_test_mesh
        from repro.serving.frontend import Op, READ, INSERT
        from repro.workloads import ycsb
        mesh = make_test_mesh(2, 4)
        cfg = DashConfig(max_segments=32, dir_depth_max=8, init_depth=1,
                         num_buckets=16, num_slots=8)
        d = DistributedDash(cfg, mesh, axes=("data", "model"), capacity=256)
        rng = np.random.default_rng(77)
        keys = np.unique(rng.integers(1, 2**63, 9000, dtype=np.uint64))[:3600]
        loaded, fresh = keys[:1800], keys[1800:]
        d.insert(loaded, np.asarray(
            [ycsb.expected_value(int(k)) for k in loaded], np.uint32))
        fe = ShardFrontend(d, max_batch=256, queue_depth=1 << 14)
        ridx = rng.integers(0, loaded.size, fresh.size)
        ops = []
        for i, k in enumerate(fresh):          # storm: inserts + racing reads
            ops.append(Op(INSERT, int(k), ycsb.expected_value(int(k))))
            ops.append(Op(READ, int(loaded[ridx[i]])))
        for op in ops:
            assert fe.submit(op)
        fe.drain()
        for op in ops:
            if op.kind == INSERT:
                assert op.status == INSERTED, op
            else:
                assert op.found and op.result == ycsb.expected_value(op.key), op
        wm = np.asarray(d.state.watermark)
        assert wm.max() > 2                    # splits ran during serving
        f, _ = d.search(keys)
        assert f.all()
        meta = np.asarray(d.state.meta)
        recount = int(((meta >> layout.COUNT_SHIFT) & 0xF).sum())
        assert d.n_items == 3600 == recount, (d.n_items, recount)
        print("SHARD FRONTEND OK", fe.snapshot_reads, fe.retried_reads,
              fe.registry.published)
    """)
    assert "SHARD FRONTEND OK" in out


def test_elastic_shrink_and_reshard():
    out = run_sub("""
        import jax, numpy as np
        import jax.numpy as jnp
        from repro.configs import get_config
        from repro.launch.mesh import make_test_mesh
        from repro.launch import elastic
        from repro.models import init_params, param_specs
        from repro.parallel import sharding
        from repro.train.steps import train_state_init

        cfg = get_config("yi-6b", reduced=True)
        mesh = make_test_mesh(2, 4)
        params, specs = init_params(jax.random.PRNGKey(0), cfg)
        with sharding.use(mesh, "train"):
            sh = sharding.tree_shardings(specs, mesh, shape_tree=params)
            params = jax.device_put(params, sh)
        # host failure: drop one data column -> (1, 4) mesh
        small = elastic.shrink_mesh(mesh, "data", 1)
        params2 = elastic.reshard_tree(params, small, specs)
        step = elastic.relower_for_mesh(cfg, small)
        state = train_state_init(params2)
        batch = {"tokens": jnp.zeros((2, 64), jnp.int32),
                 "labels": jnp.zeros((2, 64), jnp.int32)}
        with small:
            state2, metrics = step(state, batch)
        assert np.isfinite(float(metrics["loss"]))
        plan = elastic.rescale_batch_plan(256, 16, 15)
        assert plan["global_batch"] in (255, 256)
        print("ELASTIC OK", float(metrics["loss"]))
    """)
    assert "ELASTIC OK" in out


def test_compressed_psum_over_pod_axis():
    out = run_sub("""
        import jax, numpy as np
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.launch.mesh import make_test_mesh
        from repro.parallel import compression

        mesh = make_test_mesh(8, 1)
        g = jnp.asarray(np.random.default_rng(0).normal(0, 1, (8, 512)).astype(np.float32))

        def sync(gs):
            grads = {"w": gs[0]}
            res = compression.init_residuals(grads)
            out, res = compression.compressed_psum(grads, res, "data")
            return out["w"][None], res["w"][None]

        f = jax.shard_map(sync, mesh=mesh, in_specs=(P("data"),),
                          out_specs=(P("data"), P("data")), check_vma=False)
        mean_c, residual = f(g)
        true_mean = np.asarray(g).mean(axis=0)
        got = np.asarray(mean_c)[0]
        err = np.abs(got - true_mean).max()
        scale = np.abs(np.asarray(g)).max() / 127
        assert err < 3 * scale, (err, scale)
        print("COMPRESS OK", err)
    """)
    assert "COMPRESS OK" in out


def test_dht_durable_shard_pools(tmp_path):
    """One durable pool per shard under the real 8-device shard_map path:
    insert through the DHT, flush every shard's pool, 'kill' the process
    (subprocess exits), then a SECOND subprocess reopens the pools into a
    fresh DistributedDash and every acknowledged key is found."""
    d = str(tmp_path / "shards")
    common = f"""
        import numpy as np
        from repro.core import DashConfig
        from repro.distributed import DistributedDash
        from repro.launch.mesh import make_test_mesh
        from repro import persist
        cfg = DashConfig(max_segments=32, dir_depth_max=8)
        mesh = make_test_mesh(2, 4)
        rng = np.random.default_rng(5)
        keys = np.unique(rng.integers(1, 2**63, 8000, dtype=np.uint64))[:3000]
        vals = np.arange(3000, dtype=np.uint32) % 1000 + 1
    """
    run_sub(common + f"""
        d = DistributedDash(cfg, mesh, axes=("data", "model"), capacity=256)
        d.attach_pools(persist.create_shard_pools({d!r}, cfg, d.n_shards))
        st = d.insert(keys, vals)
        assert (st == 0).all()
        n = d.flush_pools()
        print("WRITER OK", d.n_items, "flushed", n)
    """)
    out = run_sub(common + f"""
        stacked, wbs, info = persist.reopen_shards({d!r})
        assert info["n_shards"] == 8 and info["dirty_shards"] == 8
        d = DistributedDash(cfg, mesh, axes=("data", "model"), capacity=256,
                            state=stacked)
        d.attach_pools(wbs)
        f, v = d.search(keys)
        assert f.all() and (v == vals).all()
        assert d.n_items == 3000
        d.close_pools()
        # clean reopen after close: no shard recovers
        stacked2, wbs2, info2 = persist.reopen_shards({d!r})
        assert info2["dirty_shards"] == 0
        print("REOPEN OK", int(f.sum()))
    """)
    assert "REOPEN OK 3000" in out


def test_dht_device_retry_never_inserts_zero_key():
    """Satellite regression for the shard_map *device* retry path: the
    all_to_all routing pads empty lanes with key 0, and the batch shaper
    pads the tail when the batch doesn't divide the shard count. Under
    forced split retries (tiny segments) those padded lanes loop through
    ``insert_round_fn`` many times — none may ever land key 0."""
    out = run_sub("""
        import numpy as np
        import jax.numpy as jnp
        from repro.core import DashConfig, INSERTED, layout
        from repro.distributed import DistributedDash
        from repro.launch.mesh import make_test_mesh
        mesh = make_test_mesh(2, 4)
        cfg = DashConfig(max_segments=32, dir_depth_max=8, init_depth=1,
                         num_buckets=16, num_slots=8)
        d = DistributedDash(cfg, mesh, axes=("data", "model"), capacity=256)
        rng = np.random.default_rng(23)
        # 2777 % 8 != 0 -> tail padding on top of routing padding
        keys = np.unique(rng.integers(1, 2**63, 8000, dtype=np.uint64))[:2777]
        vals = np.arange(2777, dtype=np.uint32) % 1000 + 1
        # device loop (insert_round_fn + split_fn), NOT the host-sync path
        st = d.insert(keys, vals)
        assert (st == INSERTED).all()
        assert np.asarray(d.state.watermark).max() > 2   # splits forced
        f0, _ = d.search(np.zeros(8, np.uint64))
        assert f0.sum() == 0, "padded lane inserted key 0"
        meta = np.asarray(d.state.meta)
        recount = int(((meta >> layout.COUNT_SHIFT) & 0xF).sum())
        assert d.n_items == 2777 == recount, (d.n_items, recount)
        # a phantom zero-key would also surface as a stored fp for key 0:
        f, v = d.search(keys)
        assert f.all() and (v == vals).all()
        print("ZERO KEY OK", d.n_items)
    """)
    assert "ZERO KEY OK 2777" in out


def test_dht_device_verify_matches_host_mirror():
    """Satellite differential: the device-resident retry mask produced
    inside the shard_map program (``snap_search_fn``'s changed word) must
    equal the host-mirror plane diff (``ShardFrontend._changed_mask``)
    across randomized SMO/read interleavings on 8 shards."""
    out = run_sub("""
        import jax, numpy as np
        import jax.numpy as jnp
        from repro.core import DashConfig
        from repro.distributed import DistributedDash, ShardFrontend
        from repro.launch.mesh import make_test_mesh
        mesh = make_test_mesh(2, 4)
        cfg = DashConfig(max_segments=32, dir_depth_max=8, init_depth=1,
                         num_buckets=16, num_slots=8)
        d = DistributedDash(cfg, mesh, axes=("data", "model"), capacity=256)
        fe = ShardFrontend(d, max_batch=256, verify_mode="host")
        rng = np.random.default_rng(41)
        keys = np.unique(rng.integers(1, 2**63, 24000, dtype=np.uint64))[:9000]
        vals = (np.arange(9000) % 1000 + 1).astype(np.uint32)
        d.insert(keys[:1500], vals[:1500])
        cursor, total = 1500, 0
        for step in range(50):
            old = jax.tree.map(jnp.copy, d.state)
            n = int(rng.integers(0, 140))   # 0 => read-only interleaving
            if n:
                d.insert(keys[cursor:cursor + n], vals[cursor:cursor + n])
                cursor += n
            probe = keys[rng.integers(0, cursor, 512)]
            _, _, dev, stale = d.snap_search_on(old, probe)
            assert not stale.any()
            host = fe._changed_mask(old, probe)
            assert (dev.astype(bool) == host).all(), step
            total += int(host.sum())
        assert total > 0              # the interleavings actually raced
        print("VERIFY DIFF OK", cursor, total)
    """)
    assert "VERIFY DIFF OK" in out


def test_buckets_changed_lh_device_matches_host_mirror():
    """The LH half of the differential satellite: DHT shards are EH tables,
    so LH is exercised at the per-shard level — the traceable
    ``buckets_changed_local`` (what the shard program inlines) against an
    independent numpy mirror of the LH addressing + version-plane diff."""
    out = run_sub("""
        import jax, numpy as np
        import jax.numpy as jnp
        from repro.core import DashConfig, DashLH, hashing, layout
        from repro.serving.engine import buckets_changed
        cfg = DashConfig(max_segments=64, num_stash=4, num_buckets=16,
                         num_slots=8, lh_base_log2=2)
        t = DashLH(cfg)
        rng = np.random.default_rng(57)
        keys = np.unique(rng.integers(1, 2**63, 16000, dtype=np.uint64))[:6000]

        def host_mask(old, new, probe):
            hi, lo = hashing.np_split_keys(probe)
            h1 = hashing.np_hash1(hi, lo)
            def seg_of(st):
                w = int(np.asarray(st.lh_word))
                level, nxt = w >> 24, w & 0xFFFFFF
                mask_lo = (np.uint32(1) << np.uint32(cfg.lh_base_log2 + level)) - 1
                seg = (h1 & mask_lo).astype(np.int64)
                mask_hi = (mask_lo << np.uint32(1)) | np.uint32(1)
                logical = np.where(seg < nxt, (h1 & mask_hi).astype(np.int64), seg)
                return np.asarray(st.lh_dir)[logical]   # logical -> physical
            so, sn = seg_of(old), seg_of(new)
            changed = so != sn
            b = ((h1 >> np.uint32(24)) & np.uint32(cfg.num_buckets - 1)).astype(np.int64)
            ov, nv = np.asarray(old.version), np.asarray(new.version)
            for w in range(cfg.probe_window):
                bw = (b + w) & (cfg.num_buckets - 1)
                changed |= ov[so, bw] != nv[so, bw]
            for s in range(cfg.num_stash):
                changed |= ov[so, cfg.num_buckets + s] != nv[so, cfg.num_buckets + s]
            return changed

        cursor, total = 0, 0
        for step in range(50):
            old = jax.tree.map(jnp.copy, t.state)
            n = min(int(rng.integers(0, 220)),   # big batches drive
                    keys.size - cursor)          # lh_split_next
            if n:
                t.insert(keys[cursor:cursor + n],
                         np.arange(n, dtype=np.uint32) + 1)
                cursor += n
            probe = keys[rng.integers(0, max(cursor, 1), 512)]
            hi, lo = hashing.np_split_keys(probe)
            dev = np.asarray(buckets_changed(cfg, "lh", old, t.state,
                                             jnp.asarray(hi), jnp.asarray(lo)))
            host = host_mask(old, t.state, probe)
            assert (dev.astype(bool) == host).all(), step
            total += int(host.sum())
        assert total > 0
        print("LH DIFF OK", cursor, total)
    """)
    assert "LH DIFF OK" in out
