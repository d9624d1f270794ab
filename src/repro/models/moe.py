"""Mixture-of-Experts FFN: sort-based capacity dispatch (GShard-style, but
scatter/gather instead of one-hot einsums so no [B,S,E,C] tensor is ever
materialized — the TPU-memory-native form).

Experts shard over TP ('expert' -> model axis); the capacity axis shards
over data. Token->expert routing becomes gather/scatter across both axes,
which the SPMD partitioner lowers to all-to-all-like collectives.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import normal
from repro.sharding.ctx import shard


def init_moe(key, d, moe_cfg, layers):
    e, ff = moe_cfg.num_experts, moe_cfg.d_ff_expert
    ks = jax.random.split(key, 4)
    return {
        "gate": normal(ks[0], (layers, d, e), d ** -0.5),
        "w1": normal(ks[1], (layers, e, d, ff), d ** -0.5),
        "w3": normal(ks[2], (layers, e, d, ff), d ** -0.5),
        "w2": normal(ks[3], (layers, e, ff, d), ff ** -0.5),
    }


def moe_ffn(p, x, moe_cfg):
    d = getattr(moe_cfg, "dispatch", "global")
    if d == "sharded":
        return moe_ffn_sharded(p, x, moe_cfg)
    if d == "shardmap":
        return moe_ffn_shardmap(p, x, moe_cfg)
    return moe_ffn_global(p, x, moe_cfg)


def moe_ffn_global(p, x, moe_cfg):
    """x [B,S,d] -> [B,S,d]. Top-k routing with capacity dropping."""
    B, S, d = x.shape
    E, k = moe_cfg.num_experts, moe_cfg.top_k
    nt = B * S
    cap = max(int(moe_cfg.capacity_factor * nt * k / E), 1)
    # round capacity to a data-shardable multiple
    cap = -(-cap // 8) * 8

    xt = x.reshape(nt, d)
    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), p["gate"])
    topv, topi = jax.lax.top_k(logits, k)                   # [nt, k]
    gates = jax.nn.softmax(topv, axis=-1)                   # normalize top-k

    e_flat = topi.reshape(-1)                               # [nt*k]
    t_flat = jnp.repeat(jnp.arange(nt), k)
    g_flat = gates.reshape(-1)

    # sort pairs by expert; rank within expert = position - segment offset
    order = jnp.argsort(e_flat)
    se, st, sg = e_flat[order], t_flat[order], g_flat[order]
    counts = jnp.bincount(se, length=E)
    seg_off = jnp.cumsum(counts) - counts
    rank = jnp.arange(nt * k) - seg_off[se]
    keep = rank < cap
    slot = jnp.where(keep, rank, 0)
    sg = jnp.where(keep, sg, 0.0)

    # dispatch: [E, cap, d] buffer (expert axis -> TP, capacity -> data)
    buf = jnp.zeros((E, cap, d), dtype=x.dtype)
    gathered = jnp.where(keep[:, None], xt[st], 0)
    buf = buf.at[se, slot].add(gathered)
    buf = shard(buf, "expert", "cap", None)

    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, p["w1"].astype(x.dtype)))
    h = h * jnp.einsum("ecd,edf->ecf", buf, p["w3"].astype(x.dtype))
    y_buf = jnp.einsum("ecf,efd->ecd", h, p["w2"].astype(x.dtype))
    y_buf = shard(y_buf, "expert", "cap", None)

    # combine: weighted scatter back to tokens
    y_pairs = y_buf[se, slot] * sg[:, None].astype(x.dtype)
    out = jnp.zeros_like(xt).at[st].add(y_pairs)
    return out.reshape(B, S, d)


def moe_ffn_sharded(p, x, moe_cfg):
    """Hierarchical dispatch: sort/rank/scatter stay LOCAL to each data
    shard; only the [shards, E, cap_local, d] buffer crosses the mesh
    (data->expert all-to-all), the GShard pattern. Removes the global
    argsort/scatter that forces per-layer token all-gathers in
    :func:`moe_ffn_global` (the §Perf granite-moe hillclimb).
    """
    from repro.sharding.ctx import axis_size

    B, S, d = x.shape
    E, k = moe_cfg.num_experts, moe_cfg.top_k
    nt = B * S
    ds = axis_size("batch")
    while nt % ds:
        ds //= 2
    ntl = nt // ds
    cap_l = max(int(moe_cfg.capacity_factor * ntl * k / E), 1)
    cap_l = -(-cap_l // 8) * 8
    pairs = ntl * k

    xs = shard(x.reshape(ds, ntl, d), "batch", None, None)
    logits = jnp.einsum("ptd,de->pte", xs.astype(jnp.float32), p["gate"])
    topv, topi = jax.lax.top_k(logits, k)                   # [ds,ntl,k]
    gates = jax.nn.softmax(topv, axis=-1)

    e_flat = topi.reshape(ds, pairs)
    t_flat = jnp.broadcast_to(
        jnp.repeat(jnp.arange(ntl), k)[None], (ds, pairs))
    g_flat = gates.reshape(ds, pairs)

    order = jnp.argsort(e_flat, axis=1)
    se = jnp.take_along_axis(e_flat, order, axis=1)
    st = jnp.take_along_axis(t_flat, order, axis=1)
    sg = jnp.take_along_axis(g_flat, order, axis=1)
    counts = jax.vmap(lambda e: jnp.bincount(e, length=E))(se)
    seg_off = jnp.cumsum(counts, axis=1) - counts           # [ds,E]
    rank = jnp.arange(pairs)[None] - jnp.take_along_axis(seg_off, se, axis=1)
    keep = rank < cap_l
    slot = jnp.where(keep, rank, 0)
    sg = jnp.where(keep, sg, 0.0)

    pidx = jnp.broadcast_to(jnp.arange(ds)[:, None], (ds, pairs))
    gathered = jnp.where(keep[..., None],
                         jnp.take_along_axis(xs, st[..., None], axis=1), 0)
    # build the buffer DATA-LOCAL (E replicated over the model axis): the
    # scatter stays on-chip; the explicit respec to (data, expert) below is
    # then a free slice. Without this, XLA lowers the expert-crossing
    # gather/scatter as ~10 GB masked all-reduces per layer.
    buf = jnp.zeros((ds, E, cap_l, d), dtype=x.dtype)
    buf = buf.at[pidx, se, slot].add(gathered)
    buf = shard(buf, "batch", None, None, None)
    buf = shard(buf, "batch", "expert", None, None)

    h = jax.nn.silu(jnp.einsum("pecd,edf->pecf", buf, p["w1"].astype(x.dtype)))
    h = h * jnp.einsum("pecd,edf->pecf", buf, p["w3"].astype(x.dtype))
    y_buf = jnp.einsum("pecf,efd->pecd", h, p["w2"].astype(x.dtype))
    y_buf = shard(y_buf, "batch", "expert", None, None)
    # bring each data shard's slice home (all-gather over experts), then the
    # un-dispatch gather/scatter is local again
    y_buf = shard(y_buf, "batch", None, None, None)

    y_pairs = y_buf[pidx, se, slot] * sg[..., None].astype(x.dtype)
    out = jnp.zeros_like(xs).at[pidx, st].add(y_pairs)
    out = shard(out, "batch", None, None)
    return out.reshape(B, S, d)


def moe_ffn_shardmap(p, x, moe_cfg):
    """shard_map dispatch: routing, sort and scatter are *provably local*.

    Each device holds its data shard's tokens (replicated over the model
    axis) and builds the full [E, cap_l, d] buffer redundantly; it computes
    only its model-rank's E/tp experts and all-gathers the expert outputs
    over 'model' (transpose: reduce-scatter in backward). Per layer the only
    mesh traffic is that gather — no data-dependent cross-shard gathers, so
    XLA cannot fall back to halo permutes / masked all-reduces (the failure
    modes of the pjit formulations, see EXPERIMENTS §Perf).
    """
    from repro.sharding.ctx import _CTX

    if _CTX is None:                      # single-device tests: pure local
        return _moe_shardmap_local(p, x, moe_cfg, tp=1, my_experts=None)

    mesh = _CTX["mesh"]
    batch_axes = _CTX["rules"]["batch"]
    tp = mesh.shape["model"]
    B, S, d = x.shape
    nt = B * S
    import math
    ds = math.prod(mesh.shape[a] for a in batch_axes)
    assert nt % ds == 0
    from jax.sharding import PartitionSpec as P

    def inner(xs, gate, w1, w3, w2):
        # xs [ntl_local, d]; w* lead with E/tp local experts
        return _moe_shardmap_body(xs, gate, w1, w3, w2, moe_cfg, tp)

    fn = jax.shard_map(
        inner, mesh=mesh,
        in_specs=(P(batch_axes, None), P(None, None),
                  P("model", None, None), P("model", None, None),
                  P("model", None, None)),
        out_specs=P(batch_axes, None),
        check_vma=False)
    out = fn(x.reshape(nt, d), p["gate"].astype(jnp.float32),
             p["w1"].astype(x.dtype), p["w3"].astype(x.dtype),
             p["w2"].astype(x.dtype))
    return out.reshape(B, S, d)


def _moe_shardmap_body(xs, gate, w1, w3, w2, moe_cfg, tp):
    """Per-device body. xs [ntl, d] local tokens; w* [E/tp, d, ff] local."""
    from jax import lax

    E, k = moe_cfg.num_experts, moe_cfg.top_k
    ntl, d = xs.shape
    cap_l = max(int(moe_cfg.capacity_factor * ntl * k / E), 1)
    cap_l = -(-cap_l // 8) * 8
    pairs = ntl * k

    logits = jnp.einsum("td,de->te", xs.astype(jnp.float32), gate)
    topv, topi = lax.top_k(logits, k)
    gates = jax.nn.softmax(topv, axis=-1)

    e_flat = topi.reshape(pairs)
    t_flat = jnp.repeat(jnp.arange(ntl), k)
    g_flat = gates.reshape(pairs)
    order = jnp.argsort(e_flat)
    se, st, sg = e_flat[order], t_flat[order], g_flat[order]
    counts = jnp.bincount(se, length=E)
    seg_off = jnp.cumsum(counts) - counts
    rank = jnp.arange(pairs) - seg_off[se]
    keep = rank < cap_l
    slot = jnp.where(keep, rank, 0)
    sg = jnp.where(keep, sg, 0.0)

    buf = jnp.zeros((E, cap_l, d), dtype=xs.dtype)
    buf = buf.at[se, slot].add(jnp.where(keep[:, None], xs[st], 0))

    if tp > 1:
        mp = lax.axis_index("model")
        e_loc = E // tp
        buf_loc = lax.dynamic_slice_in_dim(buf, mp * e_loc, e_loc, axis=0)
    else:
        buf_loc = buf
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf_loc, w1))
    h = h * jnp.einsum("ecd,edf->ecf", buf_loc, w3)
    y_loc = jnp.einsum("ecf,efd->ecd", h, w2)
    if tp > 1:
        y_all = lax.all_gather(y_loc, "model", axis=0, tiled=True)
    else:
        y_all = y_loc

    y_pairs = y_all[se, slot] * sg[:, None].astype(xs.dtype)
    return jnp.zeros_like(xs).at[st].add(y_pairs)


def _moe_shardmap_local(p, x, moe_cfg, tp, my_experts):
    B, S, d = x.shape
    out = _moe_shardmap_body(
        x.reshape(B * S, d), p["gate"].astype(jnp.float32),
        p["w1"].astype(x.dtype), p["w3"].astype(x.dtype),
        p["w2"].astype(x.dtype), moe_cfg, tp=1)
    return out.reshape(B, S, d)
