"""Pallas TPU kernel: per-unit carbon-deficit timeline from task arrays.

Computes, for every time unit t, ``max(sum_i w_i * active_i(t) - g_eff(t), 0)``
— the paper's carbon cost integrand (§3) — by tiling time into VMEM-resident
tiles and streaming task chunks through VMEM. The (task x time) activity
outer-comparison maps onto the VPU's (sublane x lane) grid; the task-chunk
grid axis accumulates into a VMEM scratch, the final chunk applies the
budget subtraction + relu.

Grid: (time_tiles, task_chunks)   — task_chunks is the reduction axis.
Blocks:
  starts/ends/works: (1, TASK_CHUNK)   f32, revisited per time tile;
  g_eff:             (1, TIME_TILE)    f32, per time tile;
  out:               (1, TIME_TILE)    f32, revisited across task chunks.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import resolve_interpret

TIME_TILE = 512
TASK_CHUNK = 512


def _kernel(starts_ref, ends_ref, works_ref, g_ref, t0_ref, out_ref, acc_ref):
    tile = pl.program_id(0)
    chunk = pl.program_id(1)
    n_chunks = pl.num_programs(1)

    @pl.when(chunk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # time coordinates of this tile: t0 + tile*TIME_TILE + [0..TIME_TILE)
    # Mosaic only lowers integer iotas: build the offsets in int32, cast
    t = (t0_ref[0] + tile * TIME_TILE
         + jax.lax.broadcasted_iota(jnp.int32, (1, TIME_TILE), 1)
         .astype(jnp.float32))
    s = starts_ref[...]            # (1, TASK_CHUNK)
    e = ends_ref[...]
    w = works_ref[...]
    # (TASK_CHUNK, TIME_TILE) activity matrix on the VPU
    active = ((s.T <= t) & (t < e.T)).astype(jnp.float32)
    acc_ref[...] += jnp.sum(w.T * active, axis=0, keepdims=True)

    @pl.when(chunk == n_chunks - 1)
    def _finish():
        out_ref[...] = jnp.maximum(acc_ref[...] - g_ref[...], 0.0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def deficit_timeline(starts, ends, works, g_eff, *,
                     interpret: bool | None = None):
    """Per-unit deficit (cost) timeline.

    Args:
      starts, ends, works: f32[N] task windows and work powers. Pad tasks
        with zero-length windows (start == end) — they contribute nothing.
      g_eff: f32[T] effective green budget per unit; T padded to TIME_TILE
        (pad with +inf so padding units cost 0).
      interpret: None = auto (interpret iff the backend is CPU).
    Returns:
      f32[T] with ``max(power(t) - g_eff(t), 0)``.
    """
    interpret = resolve_interpret(interpret)
    (n,) = starts.shape
    (T,) = g_eff.shape
    n_pad = -n % TASK_CHUNK
    t_pad = -T % TIME_TILE
    starts = jnp.pad(starts, (0, n_pad)).reshape(1, -1)
    ends = jnp.pad(ends, (0, n_pad)).reshape(1, -1)
    works = jnp.pad(works, (0, n_pad)).reshape(1, -1)
    g = jnp.pad(g_eff, (0, t_pad), constant_values=jnp.inf).reshape(1, -1)
    n_tiles = g.shape[1] // TIME_TILE
    n_chunks = starts.shape[1] // TASK_CHUNK
    t0 = jnp.zeros((1,), dtype=jnp.float32)

    out = pl.pallas_call(
        _kernel,
        grid=(n_tiles, n_chunks),
        in_specs=[
            pl.BlockSpec((1, TASK_CHUNK), lambda i, j: (0, j)),
            pl.BlockSpec((1, TASK_CHUNK), lambda i, j: (0, j)),
            pl.BlockSpec((1, TASK_CHUNK), lambda i, j: (0, j)),
            pl.BlockSpec((1, TIME_TILE), lambda i, j: (0, i)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((1, TIME_TILE), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, g.shape[1]), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, TIME_TILE), jnp.float32)],
        interpret=interpret,
        name="carbon_cost",
    )(starts, ends, works, g, t0)
    return out.reshape(-1)[:T]
