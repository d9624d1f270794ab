"""Level-synchronous max-plus sweep of the blocked longest-path form: a
Pallas kernel that keeps the sweep state in VMEM, and an exact jnp twin
that serves CPU.

The state ``d`` [Np, S, L] int32 is task-major: ``d[t]`` holds task t's
distances from one chunk's sources, one lane each (S * L sources). The
tables ``dst``, ``src``, ``w`` [levels, E] hold each level's edges; the
sweep relaxes the levels in order, each as

    d[dst] = max(d[dst], d[src] + w)        over all the level's edges,

and no level holds both ends of an edge, so every ``d[src]`` read is
final and the result does not depend on the order within a level.
Padding edges are the self-loop (0, 0, 0), which relaxes nothing.
``repro.kernels.backend.resolve_mode`` picks the executor:

* :func:`_sweep_kernel` — one ``pallas_call`` per sweep: the grid walks
  the levels, each level's three table rows arrive in SMEM, and the state
  sits in one VMEM scratch buffer for the whole sweep, copied in from HBM
  before the first level and out after the last. Each edge is one row
  load, add, max and row store on VMEM; with S * L = 1024 sources a task's
  row is one (8, 128) tile.
* :func:`_relax_jnp` — the twin: a ``fori_loop`` over the levels of one
  scatter-max each. XLA's TPU scatter updates the rows one at a time
  (about 170 ns a 4 KB row on a TPU v5e, ten times the kernel's time a
  sweep at the 4000-task class), so the kernel serves the chip wherever
  the state fits :data:`VMEM_STATE_MAX`.

Max-plus over int32 is exact, so the two are bit-identical (tested).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from repro.kernels.backend import resolve_mode

# the largest sweep state the kernel holds in VMEM (128 MiB on a v5e);
# the default lp budget keeps it at half that (two state-sized lp
# vectors per chunk fit LP_MAX_BYTES)
VMEM_STATE_MAX = 64 * 2**20


def _sweep_kernel(dst_ref, src_ref, w_ref, d0_hbm, out_hbm, d, sem):
    from jax.experimental.pallas import tpu as pltpu

    level = pl.program_id(0)

    @pl.when(level == 0)
    def _():
        copy = pltpu.make_async_copy(d0_hbm, d, sem)
        copy.start()
        copy.wait()

    def edge(e, carry):
        t = dst_ref[0, e]
        d[t] = jnp.maximum(d[t], d[src_ref[0, e]] + w_ref[0, e])
        return carry

    lax.fori_loop(0, dst_ref.shape[1], edge, 0)

    @pl.when(level == pl.num_programs(0) - 1)
    def _():
        copy = pltpu.make_async_copy(d, out_hbm, sem)
        copy.start()
        copy.wait()


def _kernel_call(d0, dst, src, w, *, mode: str):
    """Launch :func:`_sweep_kernel` over the levels (``mode`` = "pallas"
    compiled / "interpret")."""
    from jax.experimental.pallas import tpu as pltpu

    levels, E = dst.shape
    row = pl.BlockSpec((None, 1, E), lambda lv: (lv, 0, 0),
                       memory_space=pltpu.SMEM)
    nbytes = d0.size * d0.dtype.itemsize
    return pl.pallas_call(
        _sweep_kernel,
        grid=(levels,),
        in_specs=[row, row, row, pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct(d0.shape, d0.dtype),
        scratch_shapes=[pltpu.VMEM(d0.shape, d0.dtype),
                        pltpu.SemaphoreType.DMA(())],
        input_output_aliases={3: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=nbytes + 16 * 2**20),
        interpret=(mode == "interpret"),
        name="maxplus_sweep",
    )(*(t.reshape(levels, 1, E) for t in (dst, src, w)), d0)


def _relax_jnp(d0, dst, src, w):
    """The jnp twin: one scatter-max per level."""
    def level(lv, d):
        return d.at[dst[lv]].max(d[src[lv]] + w[lv][:, None, None],
                                 indices_are_sorted=True,
                                 mode="promise_in_bounds")

    return lax.fori_loop(0, dst.shape[0], level, d0)


def relax_levels(d0, dst, src, w, *, interpret: bool | None = None):
    """Relax the task-major state ``d0`` [Np, S, L] int32 over the level
    tables ``dst``, ``src``, ``w`` [levels, E] int32, level by level.

    ``interpret``: None = auto (the jnp twin on CPU; the compiled kernel
    on TPU when the state fits :data:`VMEM_STATE_MAX`, else the twin);
    True = Pallas interpreter; False = compiled kernel.
    """
    mode = resolve_mode(interpret)
    if interpret is None and d0.size * 4 > VMEM_STATE_MAX:
        mode = "jnp"
    if mode == "jnp":
        return _relax_jnp(d0, dst, src, w)
    return _kernel_call(d0, dst, src, w, mode=mode)
