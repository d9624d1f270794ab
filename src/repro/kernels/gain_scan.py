"""Local-search gain sweep (paper §5.3, batched): a tiled Pallas kernel +
an exact jnp twin that serves CPU.

For every task i and every shift delta in [-mu, mu], computes the exact
carbon-cost gain of moving task i by delta, given the current remaining-
budget timeline. Only the symmetric difference of the old/new execution
windows contributes, and both difference regions lie within ``mu`` units of
the task's start (s) or end (e). The wrapper therefore gathers two
lane-aligned windows of the timeline per task,

    win_s[i, j] = rem[s_i - PAD + j],   win_e[i, j] = rem[e_i - PAD + j],

and evaluates all 2*mu+1 shifts for every task at once. Two executors over
the same windows (``repro.kernels.backend.resolve_mode`` picks one):

* :func:`_gain_kernel` — the tiled Pallas kernel, blocked over the
  candidate(-segment) axis: the grid walks ``TASK_TILE``-row tiles of the
  flattened candidate axis (a "parallel" grid dimension — tiles are
  independent), every tile holds its two (TASK_TILE, W) windows in VMEM,
  and the 2*mu+1 shift columns are written as ONE lane-aligned
  (TASK_TILE, W) store built by select-accumulation over a lane iota.
  Every op is a 2-D VPU op (masked reductions over the 128-lane window
  axis) — no concatenate/pad inside the kernel — so the same body lowers
  through Mosaic on TPU and runs under the interpreter on CPU.
* :func:`gains_from_windows` — the jnp twin: every delta's masked window
  sum is a contiguous range, so all 2*mu+1 gains fall out of four prefix
  sums (O(N*mu) instead of O(N*W*mu)). All summands are integers below
  2^24, so f32 accumulation is exact in any order and the two paths are
  bit-identical (tested). This is the CPU fast path and stays the gain
  oracle of the device-resident climb on CPU; on TPU the climb routes
  through the compiled kernel (:func:`gains_windows_auto`).

The device climb (:mod:`repro.core.local_search_jax`) runs
:func:`gather_windows` then :func:`gains_windows_auto` inline in its jitted
round loop, vmapped over its rows; :func:`gain_scan` is the same
composition jitted for one row, the kernel tests' entry point.

Gain identities (rem includes the task at its old position; the newly
occupied region never overlaps the old window, so rem == rem-without-task
there):
  released(t) = min(max(-rem[t], 0), w)          on vacated units
  incurred(t) = min(max(w - max(rem[t], 0), 0), w)  on newly occupied units
  gain(delta) = sum released - sum incurred ;  illegal shifts -> -BIG.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.backend import resolve_mode

TASK_TILE = 256
W = 128          # lane-aligned window length; supports mu <= 42
NEG = -1e30


def _gain_kernel(mu: int, win_s_ref, win_e_ref, w_ref, dur_ref, lo_ref,
                 hi_ref, out_ref):
    """One candidate tile of the gain sweep; all ops 2-D, Mosaic-lowerable.

    Refs (one grid step = one TASK_TILE tile of the candidate axis):
      win_s/win_e: f32 (TASK_TILE, W) timeline windows around start/end.
      w/dur/lo/hi: f32 (TASK_TILE, 1) work, duration, RELATIVE legal
        shift bounds (lo > hi marks a row with no legal move).
      out: f32 (TASK_TILE, W) — lane d holds the gain of shift d - mu for
        d < 2*mu+1, NEG beyond (the caller slices the real columns).

    The shift loop is a static unroll (mu is a compile-time constant):
    per delta, the vacated/occupied sums are two masked reductions over
    the W lanes, and the resulting column is merged into the lane-aligned
    accumulator with a select against the lane iota — the whole tile is
    written back as one aligned store, so the kernel compiles on TPU
    instead of living interpreter-only.
    """
    pad = mu
    win_s = win_s_ref[...]                      # (TASK_TILE, W)
    win_e = win_e_ref[...]
    w = w_ref[...]                              # (TASK_TILE, 1)
    dur = dur_ref[...]
    lo = lo_ref[...]
    hi = hi_ref[...]
    # Mosaic only lowers integer iotas; the window offsets are compared
    # against f32 shift bounds, so cast once
    j = jax.lax.broadcasted_iota(jnp.int32, (1, W), 1).astype(jnp.float32)
    lane = jax.lax.broadcasted_iota(jnp.int32, out_ref.shape, 1)

    released_s = jnp.minimum(jnp.maximum(-win_s, 0.0), w)
    released_e = jnp.minimum(jnp.maximum(-win_e, 0.0), w)
    incurred_s = jnp.minimum(jnp.maximum(w - jnp.maximum(win_s, 0.0), 0.0), w)
    incurred_e = jnp.minimum(jnp.maximum(w - jnp.maximum(win_e, 0.0), 0.0), w)

    acc = jnp.full(out_ref.shape, NEG, jnp.float32)
    for d in range(2 * mu + 1):
        delta = d - mu
        ln = jnp.minimum(jnp.float32(abs(delta)), dur)   # (TASK_TILE, 1)
        if delta > 0:
            # vacated: times [s, s+ln)         -> win_s j in [pad, pad+ln)
            vac = (j >= pad) & (j < pad + ln)
            rel = jnp.sum(jnp.where(vac, released_s, 0.0), axis=1,
                          keepdims=True)
            # occupied: times [e+delta-ln, e+delta) -> win_e j
            occ = (j >= pad + delta - ln) & (j < pad + delta)
            inc = jnp.sum(jnp.where(occ, incurred_e, 0.0), axis=1,
                          keepdims=True)
        elif delta < 0:
            # vacated: times [e-ln, e)         -> win_e j in [pad-ln, pad)
            vac = (j >= pad - ln) & (j < pad)
            rel = jnp.sum(jnp.where(vac, released_e, 0.0), axis=1,
                          keepdims=True)
            # occupied: times [s+delta, s+delta+ln) -> win_s j
            occ = (j >= pad + delta) & (j < pad + delta + ln)
            inc = jnp.sum(jnp.where(occ, incurred_s, 0.0), axis=1,
                          keepdims=True)
        else:
            rel = jnp.zeros_like(w)
            inc = jnp.zeros_like(w)
        gain = rel - inc
        legal = (lo <= delta) & (delta <= hi) & (delta != 0) & (w > 0)
        col = jnp.where(legal, gain, NEG)                # (TASK_TILE, 1)
        acc = jnp.where(lane == d, col, acc)
    out_ref[...] = acc


def gather_windows(rem, start, dur, *, mu: int):
    """(win_s, win_e) f32[N, W] timeline windows around start and end:
    ``win_s[i, j] = rem[start_i - mu + j]`` (0 outside the timeline), for
    starts and ends within ``[0, T]``.

    Row ``r`` of the sliding-window matrix holds ``rem_pad[r:r + W]``, so
    each window is one whole-row gather: an element-wise gather of
    ``N * W`` scalars runs at about 43M elements/s on a TPU v5e, slow
    enough to dominate the climb.

    The matrix is built by doubling its width, ``log2(W)`` lane-dense
    concatenations of two operands each: after the step with offset k,
    ``m[r, j] = rem_pad[r + j]`` for ``j < 2k``. Stacking W shifted
    copies instead lowers on TPU to W single-lane copies, each padded out
    to 128 lanes in the tiled layout: on a v5e that build took longer
    than the gain kernel itself. Both builds move the same f32 values, so the
    windows are bit-identical.
    """
    t_total = rem.shape[0]
    rem_pad = jnp.pad(rem, (W, W))
    top = t_total + W
    rows = rem_pad[:, None]
    k = 1
    while k < W:
        n = rows.shape[0] - k
        rows = jnp.concatenate([rows[:n], rows[k:k + n]], axis=1)
        k *= 2
    s_i = start.astype(jnp.int32)
    e_i = (start + dur).astype(jnp.int32)
    win_s = rows[jnp.clip(s_i + W - mu, 0, top)]
    win_e = rows[jnp.clip(e_i + W - mu, 0, top)]
    return win_s, win_e


def gains_from_windows(win_s, win_e, work, dur, lo_rel, hi_rel, *, mu: int):
    """The kernel's gain matrix from pre-gathered windows, in pure jnp.

    Every delta's vacated/occupied region is a contiguous index range in
    its window, so the masked sums collapse to differences of four prefix
    sums. Bit-identical to :func:`_gain_kernel` (integer summands, exact
    in f32).

    Args:
      win_s, win_e: f32[N, W] from :func:`gather_windows`.
      work, dur:    f32[N].
      lo_rel, hi_rel: f32[N] legal shift bounds RELATIVE to the current
        start (lo_rel > hi_rel marks a row with no legal move).
    Returns:
      f32[N, 2*mu+1]; illegal moves = -1e30.
    """
    pad = mu
    w = work[:, None]
    released_s = jnp.minimum(jnp.maximum(-win_s, 0.0), w)
    released_e = jnp.minimum(jnp.maximum(-win_e, 0.0), w)
    incurred_s = jnp.minimum(jnp.maximum(w - jnp.maximum(win_s, 0.0), 0.0), w)
    incurred_e = jnp.minimum(jnp.maximum(w - jnp.maximum(win_e, 0.0), 0.0), w)

    def csum(x):                                  # [N, W] -> [N, W+1]
        z = jnp.zeros((x.shape[0], 1), x.dtype)
        return jnp.concatenate([z, jnp.cumsum(x, axis=1)], axis=1)

    r_s, r_e = csum(released_s), csum(released_e)
    i_s, i_e = csum(incurred_s), csum(incurred_e)

    delta = jnp.arange(-mu, mu + 1, dtype=jnp.int32)[None, :]   # [1, D]
    ln = jnp.minimum(jnp.abs(delta), dur[:, None].astype(jnp.int32))

    def take(c, i):
        # indices of the inapplicable delta branch may leave [0, W]; they
        # are masked out below, so clip them into range first
        return jnp.take_along_axis(c, jnp.clip(i, 0, W), axis=1)

    # delta > 0: vacated [pad, pad+ln) of win_s, occupied
    # [pad+delta-ln, pad+delta) of win_e
    g_pos = (take(r_s, pad + ln) - r_s[:, pad:pad + 1]) \
        - (take(i_e, pad + delta) - take(i_e, pad + delta - ln))
    # delta < 0: vacated [pad-ln, pad) of win_e, occupied
    # [pad+delta, pad+delta+ln) of win_s
    g_neg = (r_e[:, pad:pad + 1] - take(r_e, pad - ln)) \
        - (take(i_s, pad + delta + ln) - take(i_s, pad + delta))
    gain = jnp.where(delta > 0, g_pos, jnp.where(delta < 0, g_neg, 0.0))

    deltaf = delta.astype(win_s.dtype)
    legal = ((lo_rel[:, None] <= deltaf) & (deltaf <= hi_rel[:, None])
             & (delta != 0) & (work[:, None] > 0))
    return jnp.where(legal, gain, NEG)


def _kernel_call(win_s, win_e, work, dur, lo_rel, hi_rel, *, mu: int,
                 mode: str):
    """Launch :func:`_gain_kernel` over TASK_TILE tiles of the candidate
    axis (``mode`` = "pallas" compiled / "interpret")."""
    n = win_s.shape[0]
    n_pad = -n % TASK_TILE

    def pad2(x, v=0.0):
        return jnp.pad(x, ((0, n_pad), (0, 0)), constant_values=v)

    win_s = pad2(win_s)
    win_e = pad2(win_e)
    w2 = pad2(work[:, None])
    dur2 = pad2(dur[:, None])
    lo2 = pad2(lo_rel[:, None], v=1.0)   # lo > hi on padding => illegal
    hi2 = pad2(hi_rel[:, None], v=-1.0)

    n_tiles = (n + n_pad) // TASK_TILE
    kwargs = {}
    if mode == "pallas":
        # candidate tiles are independent: let Mosaic parallelize the grid
        from jax.experimental.pallas import tpu as pltpu
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel",))
    out = pl.pallas_call(
        functools.partial(_gain_kernel, mu),
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((TASK_TILE, W), lambda i: (i, 0)),
            pl.BlockSpec((TASK_TILE, W), lambda i: (i, 0)),
            pl.BlockSpec((TASK_TILE, 1), lambda i: (i, 0)),
            pl.BlockSpec((TASK_TILE, 1), lambda i: (i, 0)),
            pl.BlockSpec((TASK_TILE, 1), lambda i: (i, 0)),
            pl.BlockSpec((TASK_TILE, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((TASK_TILE, W), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n + n_pad, W), jnp.float32),
        interpret=(mode == "interpret"),
        name="gain_scan",
        **kwargs,
    )(win_s, win_e, w2, dur2, lo2, hi2)
    return out[:n, :2 * mu + 1]


def gains_windows_auto(win_s, win_e, work, dur, lo_rel, hi_rel, *,
                       mu: int, interpret: bool | None = None):
    """Mode-dispatched gain matrix over pre-gathered windows.

    The shared oracle of :func:`gain_scan` and the device-resident climb
    (:mod:`repro.core.local_search_jax`): CPU resolves to the jnp
    prefix-sum twin, TPU/GPU to the compiled tiled kernel,
    ``interpret=True`` forces the Pallas interpreter — all three
    bit-identical (integer summands, exact in f32; tested).
    Bounds are RELATIVE to the current start, as in
    :func:`gains_from_windows`.
    """
    assert mu <= (W // 2) - 22, f"mu={mu} too large for W={W}"
    mode = resolve_mode(interpret)
    if mode == "jnp":
        return gains_from_windows(win_s, win_e, work, dur, lo_rel, hi_rel,
                                  mu=mu)
    return _kernel_call(win_s, win_e, work, dur, lo_rel, hi_rel, mu=mu,
                        mode=mode)


@functools.partial(jax.jit, static_argnames=("mu", "interpret"))
def gain_scan(rem, start, dur, work, lo, hi, *, mu: int = 10,
              interpret: bool | None = None):
    """All-pairs (task, shift) gains.

    Args:
      rem:  f32[T] remaining-budget timeline (g_eff - active work power).
      start, dur, work: f32[N].
      lo, hi: f32[N] legal *absolute* start-time bounds per task.
      mu: max shift.
      interpret: None = auto (jnp twin on CPU, compiled kernel on TPU);
        True = Pallas interpreter; False = compiled kernel.
    Returns:
      f32[N, 2*mu+1]; entry (i, d) = gain of moving task i by (d - mu);
      illegal moves = -1e30.
    """
    win_s, win_e = gather_windows(rem, start, dur, mu=mu)
    return gains_windows_auto(win_s, win_e, work, dur, lo - start,
                              hi - start, mu=mu, interpret=interpret)

