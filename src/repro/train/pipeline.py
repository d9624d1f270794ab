"""Pipeline parallelism over the pod axis: GPipe schedule with shard_map.

The layer stack is split into ``n_stages`` contiguous stages (one per pod);
microbatches stream through with ``lax.ppermute`` boundary transfers. Used
by the granite-34b multi-pod §Perf exploration — the default plan keeps the
pod axis as pure DP, this module provides the alternative.

Bubble fraction = (S-1)/(M+S-1) for S stages and M microbatches, so the
driver should pick M >> S (the helper asserts M >= 4*S).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P


def pipeline_apply(body_fn, stage_params, x_mb, *, axis_name: str = "pod"):
    """Run a GPipe pipeline inside ``shard_map`` over ``axis_name``.

    body_fn(params, x) -> x            one stage's computation
    stage_params: per-stage params (leading stage axis sharded over pods)
    x_mb: [M, mb, ...] microbatched activations (replicated over pods)

    Returns [M, mb, ...] outputs of the LAST stage (other pods produce
    zeros; caller reduces/selects).
    """
    n_stages = lax.psum(1, axis_name)
    stage = lax.axis_index(axis_name)
    # shard_map keeps the (now size-1) stage axis on the params block
    stage_params = jax.tree.map(lambda a: a[0], stage_params)
    M = x_mb.shape[0]
    assert M >= 4 * n_stages, "use >=4x microbatches per stage (bubble)"
    n_ticks = M + n_stages - 1

    def tick(carry, t):
        buf_in, outputs = carry
        # stage 0 injects microbatch t (if any); others take the permuted in
        inject = jnp.where(t < M, t, M - 1)
        x0 = x_mb[inject]
        x_in = jnp.where(stage == 0, x0, buf_in)
        y = body_fn(stage_params, x_in)
        # pass to the next stage
        buf_next = lax.ppermute(
            y, axis_name,
            perm=[(i, i + 1) for i in range(n_stages - 1)])
        # last stage writes its completed microbatch (t - (S-1))
        out_idx = t - (n_stages - 1)
        ok = (stage == n_stages - 1) & (out_idx >= 0)
        outputs = lax.cond(
            ok,
            lambda o: o.at[jnp.maximum(out_idx, 0)].set(y),
            lambda o: o,
            outputs)
        return (buf_next, outputs), None

    buf0 = jnp.zeros_like(x_mb[0])
    out0 = jnp.zeros_like(x_mb)
    (_, outputs), _ = lax.scan(tick, (buf0, out0), jnp.arange(n_ticks))
    # broadcast the last stage's outputs to all pods
    outputs = lax.psum(
        jnp.where(stage == n_stages - 1, outputs, jnp.zeros_like(outputs)),
        axis_name)
    return outputs


def make_pipelined_forward(body_fn, mesh, axis_name: str = "pod"):
    """Wrap pipeline_apply in shard_map for the given mesh."""
    return jax.shard_map(
        functools.partial(pipeline_apply, body_fn, axis_name=axis_name),
        mesh=mesh,
        in_specs=(P(axis_name), P()),
        out_specs=P(),
        check_vma=False,
    )
