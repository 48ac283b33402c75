"""Mixture-of-Experts layer: top-k routing with capacity-based dispatch.

Dispatch avoids the (tokens, experts, capacity) one-hot cube: position-in-
expert is a cumsum over the router assignment (the same trick Dash's
kernels/ops.py uses to route hash queries), then tokens scatter into a dense
(E, capacity, d) block that runs as one batched einsum — expert-parallel
friendly (EXPERT is a sharded logical axis; with EP the scatter becomes an
all_to_all, handled by the partitioner from the sharding annotations).
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from repro.parallel.sharding import logical_constraint
from .layers import EMBED, EXPERT, MLP, truncated_normal


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int = 2
    capacity_factor: float = 1.25


def moe_init(key, d, d_ff, cfg: MoEConfig):
    ks = jax.random.split(key, 4)
    E = cfg.n_experts
    s = 1.0 / math.sqrt(d)
    params = {
        "router": truncated_normal(ks[0], (d, E), s),
        "w_gate": truncated_normal(ks[1], (E, d, d_ff), s),
        "w_up": truncated_normal(ks[2], (E, d, d_ff), s),
        "w_down": truncated_normal(ks[3], (E, d_ff, d), 1.0 / math.sqrt(d_ff)),
    }
    specs = {
        "router": (EMBED, None),
        "w_gate": (EXPERT, EMBED, MLP),
        "w_up": (EXPERT, EMBED, MLP),
        "w_down": (EXPERT, MLP, EMBED),
    }
    return params, specs


def moe_capacity(cfg: MoEConfig, n_tokens: int) -> int:
    cap = int(cfg.capacity_factor * n_tokens * cfg.top_k / cfg.n_experts)
    return max(128, ((cap + 127) // 128) * 128)   # MXU-aligned


def _moe_math(cfg: MoEConfig, x, router_w, wg, wu, wd, cap):
    """Device-local MoE math: router -> row-local dispatch -> expert FFN ->
    weighted collect. Callers provide use-ready (bf16, gathered) weights."""
    B, S, d = x.shape
    E, K = cfg.n_experts, cfg.top_k

    logits = (x @ router_w).astype(jnp.float32)                           # (B,S,E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, experts = jax.lax.top_k(probs, K)                          # (B,S,K)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    me = jnp.mean(probs, axis=(0, 1))
    ce = jnp.mean(jax.nn.one_hot(experts[..., 0], E, dtype=jnp.float32),
                  axis=(0, 1))
    aux_loss = E * jnp.sum(me * ce)

    flat_exp = experts.reshape(B, S * K)
    onehot = jax.nn.one_hot(flat_exp, E, dtype=jnp.int32)
    pos = jnp.cumsum(onehot, axis=1) - 1
    slot = jnp.sum(pos * onehot, axis=-1)
    keep = slot < cap
    dst = jnp.where(keep, flat_exp * cap + slot, E * cap)
    tok_flat = jnp.repeat(jnp.arange(S), K)

    def dispatch_row(xr, dstr):
        return jnp.zeros((E * cap + 1, d), x.dtype).at[dstr].set(xr[tok_flat])

    buf = jax.vmap(dispatch_row)(x, dst)
    eb = buf[:, :E * cap].reshape(B, E, cap, d)

    g = jnp.einsum("becd,edf->becf", eb, wg)
    u = jnp.einsum("becd,edf->becf", eb, wu)
    yb = jnp.einsum("becf,efd->becd", jax.nn.silu(g) * u, wd)

    ysrc = yb.reshape(B, E * cap, d)
    w = gate_vals.reshape(B, S * K)[..., None].astype(x.dtype)

    def collect_row(ysr, dstr, keepr, wr):
        vals = jnp.where(keepr[:, None],
                         ysr[jnp.clip(dstr, 0, E * cap - 1)], 0.0) * wr
        return jnp.zeros((S, d), x.dtype).at[tok_flat].add(vals)

    y = jax.vmap(collect_row)(ysrc, dst, keep, w)
    return y, aux_loss


def moe_apply_shardmap(params, cfg: MoEConfig, x, mesh, batch_axes,
                       weight_axes=None):
    """Explicit data-parallel MoE under shard_map (production path for the
    'train_dp' layout; EXPERIMENTS.md SSPerf records why).

    Each device owns its batch rows and an FSDP shard of the expert weights.
    The block all-gathers the bf16-cast weights (the transpose of all_gather
    is psum_scatter, so weight gradients reduce-scatter in bf16 for free —
    half the wire of fp32 grad sync), runs the dispatch/FFN entirely locally,
    and touches the fabric for nothing else. SPMD partitioner guessing is out
    of the loop — the collective schedule is exactly what is written here."""
    from jax.sharding import PartitionSpec as P

    B, S, d = x.shape
    cap = moe_capacity(cfg, S)
    dt = x.dtype
    bx = tuple(batch_axes)                 # x rows sharded over these
    wx = tuple(weight_axes or batch_axes)  # FSDP weight shards over these

    def inner(xl, router, wg, wu, wd):
        from repro.parallel.compression import fsdp_gather_int8
        router = jax.lax.all_gather(router.astype(dt), wx, axis=0, tiled=True)
        wg = fsdp_gather_int8(wg, wx, 1, dt)    # int8 wire, bf16 use,
        wu = fsdp_gather_int8(wu, wx, 1, dt)    # bwd = bf16 reduce-scatter
        wd = fsdp_gather_int8(wd, wx, 2, dt)
        y, aux = _moe_math(cfg, xl, router, wg, wu, wd, cap)
        return y, jax.lax.pmean(aux, bx)

    y, aux = jax.shard_map(
        inner, mesh=mesh,
        in_specs=(P(bx), P(wx, None), P(None, wx, None),
                  P(None, wx, None), P(None, None, wx)),
        out_specs=(P(bx), P()),
        check_vma=False,
    )(x, params["router"], params["w_gate"], params["w_up"],
      params["w_down"])
    return y, aux


def moe_apply_ep_shardmap(params, cfg: MoEConfig, x, mesh, bx, ep_axis,
                          fsdp_axes):
    """True expert parallelism under shard_map: each rank of ``ep_axis`` owns
    E/n experts (FSDP-sharded over ``fsdp_axes`` on the embed dim); tokens
    travel to their experts with one all_to_all each way — activations move
    (~2*S*K*d bf16/device/layer) instead of expert weights, which wins when
    expert weights >> routed activations (phi3.5: 16 experts of 6400-ff vs
    4k tokens). Requires n_experts % size(ep_axis) == 0."""
    from jax.sharding import PartitionSpec as P

    B, S, d = x.shape
    E, K = cfg.n_experts, cfg.top_k
    n_ep = mesh.shape[ep_axis]
    assert E % n_ep == 0
    E_local = E // n_ep
    cap = moe_capacity(cfg, S)
    dt = x.dtype
    bx = tuple(bx)
    fx = tuple(fsdp_axes)

    def inner(xl, router, wg, wu, wd):
        from repro.parallel.compression import fsdp_gather_int8
        router = jax.lax.all_gather(router.astype(dt), fx, axis=0, tiled=True)
        wg = fsdp_gather_int8(wg, fx, 1, dt)      # (E_local, d, ff)
        wu = fsdp_gather_int8(wu, fx, 1, dt)
        wd = fsdp_gather_int8(wd, fx, 2, dt)

        Bl = xl.shape[0]
        logits = (xl @ router).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, experts = jax.lax.top_k(probs, K)
        gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True),
                                            1e-9)
        me = jnp.mean(probs, axis=(0, 1))
        ce = jnp.mean(jax.nn.one_hot(experts[..., 0], E, dtype=jnp.float32),
                      axis=(0, 1))
        aux = E * jnp.sum(me * ce)

        flat_exp = experts.reshape(Bl, S * K)
        onehot = jax.nn.one_hot(flat_exp, E, dtype=jnp.int32)
        pos = jnp.cumsum(onehot, axis=1) - 1
        slot = jnp.sum(pos * onehot, axis=-1)
        keep = slot < cap
        dst = jnp.where(keep, flat_exp * cap + slot, E * cap)
        tok_flat = jnp.repeat(jnp.arange(S), K)

        def dispatch_row(xr, dstr):
            return jnp.zeros((E * cap + 1, d), dt).at[dstr].set(xr[tok_flat])

        buf = jax.vmap(dispatch_row)(xl, dst)[:, :E * cap]
        # -> experts to their owners: one a2a out (activations, not weights)
        buf = buf.reshape(Bl, n_ep, E_local * cap, d)
        recv = jax.lax.all_to_all(buf, ep_axis, split_axis=1, concat_axis=0,
                                  tiled=True)              # (Bl*n_ep, 1, ...)
        eb = recv.reshape(Bl * n_ep, E_local, cap, d)

        g = jnp.einsum("becd,edf->becf", eb, wg)
        u = jnp.einsum("becd,edf->becf", eb, wu)
        yb = jnp.einsum("becf,efd->becd", jax.nn.silu(g) * u, wd)

        # route results home: inverse a2a
        yb = yb.reshape(Bl * n_ep, 1, E_local * cap, d)
        back = jax.lax.all_to_all(yb, ep_axis, split_axis=0, concat_axis=1,
                                  tiled=True)               # (Bl, n_ep, ...)
        ysrc = back.reshape(Bl, E * cap, d)

        w = gate_vals.reshape(Bl, S * K)[..., None].astype(dt)

        def collect_row(ysr, dstr, keepr, wr):
            vals = jnp.where(keepr[:, None],
                             ysr[jnp.clip(dstr, 0, E * cap - 1)], 0.0) * wr
            return jnp.zeros((S, d), dt).at[tok_flat].add(vals)

        y = jax.vmap(collect_row)(ysrc, dst, keep, w)
        return y, jax.lax.pmean(aux, bx)

    y, aux = jax.shard_map(
        inner, mesh=mesh,
        in_specs=(P(bx), P(fx, None), P(ep_axis, fx, None),
                  P(ep_axis, fx, None), P(ep_axis, None, fx)),
        out_specs=(P(bx), P()),
        check_vma=False,
    )(x, params["router"], params["w_gate"], params["w_up"],
      params["w_down"])
    return y, aux


def moe_apply_dense(params, cfg: MoEConfig, x):
    """Dispatch-free MoE for the serving path: compute EVERY expert and
    gate-weight the results. Costs E/k more expert FLOPs but removes all
    scatter/gather — the collective schedule equals a dense TP MLP (the
    vmap-dispatch form inflated MoE prefill to 80 s/step of collectives under
    TP rules; dense-MoE restores dense-level traffic at bounded extra
    compute, the standard trade for inference). No tokens are dropped."""
    from .layers import wuse
    B, S, d = x.shape
    E, K = cfg.n_experts, cfg.top_k
    x = logical_constraint(x, ("batch", None, "act_embed"))
    router = wuse(params["router"], x.dtype, (None, None))
    wg = wuse(params["w_gate"], x.dtype, ("expert", None, "mlp"))
    wu = wuse(params["w_up"], x.dtype, ("expert", None, "mlp"))
    wd = wuse(params["w_down"], x.dtype, ("expert", "mlp", None))

    logits = (x @ router).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    topv, _ = jax.lax.top_k(probs, K)
    thresh = topv[..., -1:]
    gates = jnp.where(probs >= thresh, probs, 0.0)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)  # (B,S,E)

    g = jnp.einsum("bsd,edf->bsef", x, wg)
    u = jnp.einsum("bsd,edf->bsef", x, wu)
    h = (jax.nn.silu(g) * u) * gates.astype(x.dtype)[..., None]
    y = jnp.einsum("bsef,efd->bsd", h, wd)
    y = logical_constraint(y, ("batch", "seq", "act_embed"))
    return y, jnp.zeros((), jnp.float32)


def moe_apply(params, cfg: MoEConfig, x):
    """x: (B, S, d) -> (B, S, d), plus aux load-balancing loss.

    SPMD-partitioned path: row-local dispatch + gathered-at-use weights.
    Perf history on mixtral x train_4k (EXPERIMENTS.md SSPerf): a flat
    (T, E*cap) scatter replicated the dispatch cube (4.5 TB/dev all-reduce);
    constraint pinning made it worse; only true batch-dim scatters (vmap)
    plus gathered-at-use weights tame it — and the fully explicit
    ``moe_apply_shardmap`` below is the production choice for the pure-DP
    layout (selected by the '_moe_shardmap' rules flag)."""
    from repro.parallel import sharding as shd
    mesh = shd.active_mesh()
    if mesh is not None and shd.flag("_moe_dense"):
        return moe_apply_dense(params, cfg, x)
    if (mesh is not None and shd.flag("_moe_ep")
            and "model" in mesh.axis_names
            and cfg.n_experts % mesh.shape["model"] == 0):
        bx = shd.batch_axes(x.shape[0])
        fx = shd.axes_for("embed", params["w_gate"].shape[1])
        if bx and fx:
            return moe_apply_ep_shardmap(params, cfg, x, mesh, bx, "model", fx)
    if mesh is not None and shd.flag("_moe_shardmap"):
        bx = shd.batch_axes(x.shape[0])
        wx = shd.axes_for("embed", params["w_gate"].shape[1])
        if bx and wx:
            return moe_apply_shardmap(params, cfg, x, mesh, bx, wx)

    B, S, d = x.shape
    cap = moe_capacity(cfg, S)
    # Megatron-SP discipline: gather the sequence-sharded residual once at
    # layer entry so the row-local dispatch stays device-local.
    x = logical_constraint(x, ("batch", None, "act_embed"))
    from .layers import wuse
    router = wuse(params["router"], x.dtype, (None, None))
    wg = wuse(params["w_gate"], x.dtype, ("expert", None, "mlp"))
    wu = wuse(params["w_up"], x.dtype, ("expert", None, "mlp"))
    wd = wuse(params["w_down"], x.dtype, ("expert", "mlp", None))
    y, aux_loss = _moe_math(cfg, x, router, wg, wu, wd, cap)
    y = logical_constraint(y, ("batch", "seq", "act_embed"))   # back to SP
    return y, aux_loss
