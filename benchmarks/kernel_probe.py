"""Pallas probe-kernel microbench (interpret mode on CPU — correctness-path
timing; the MXU/VPU design targets TPU, see kernels/probe.py)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import DashConfig, DashEH
from repro.core.hashing import np_split_keys
from repro.core import engine
from repro.kernels import ops
from .common import Row, ops_row, time_op, unique_keys


def run():
    cfg = DashConfig(max_segments=32, dir_depth_max=9)
    t = DashEH(cfg)
    keys = unique_keys(np.random.default_rng(81), 8000)
    t.insert(keys, np.arange(8000, dtype=np.uint32))
    hi, lo = np_split_keys(keys[:1024])
    hi, lo = jnp.asarray(hi), jnp.asarray(lo)

    # result-equivalence gate before any timing: the Pallas-routed path must
    # agree with the engine's per-key path on every kept lane (keep=False
    # lanes overflowed routing capacity and are untouched by design)
    f_eng, v_eng = engine.search_batch(cfg, "eh", t.state, hi, lo,
                                       batching="vmap")
    f_krn, v_krn, keep = ops.probe_routed(cfg, t.state, hi, lo, capacity=512,
                                          interpret=True)
    keep = np.asarray(keep)
    assert (np.asarray(f_eng)[keep] == np.asarray(f_krn)[keep]).all()
    hit = np.asarray(f_eng) & keep
    assert (np.asarray(v_eng)[hit] == np.asarray(v_krn)[hit]).all()
    assert not np.asarray(f_krn)[~keep].any()   # dropped lanes stay untouched

    s_eng = time_op(lambda: jax.block_until_ready(
        engine.search_batch(cfg, "eh", t.state, hi, lo, batching="vmap")))
    s_krn = time_op(lambda: jax.block_until_ready(
        ops.probe_routed(cfg, t.state, hi, lo, capacity=512,
                         interpret=True)))
    return [ops_row("kernel/engine_search(vmap)", s_eng, 1024),
            ops_row("kernel/pallas_probe_routed(interpret)", s_krn, 1024)]
