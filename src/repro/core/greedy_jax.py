"""Device-path greedy: the paper's §5.2 loop as a single ``lax.scan``.

Semantically identical to ``core.greedy.greedy_schedule`` (same score order,
same max-budget/earliest-tie placement, same dynamic splits, same endpoint
rule: a task end ``e`` becomes a candidate point only when ``e <= T``), but
the per-step EST/LST relaxation is *closed-form*: a host-precomputed
longest-path matrix ``lp`` (:func:`longest_path_matrix`, profile-independent,
cached on :class:`~repro.core.portfolio.PreparedGraph`) turns the paper's
worklist update into two vectorized ops per placement::

    est = max(est, s + lp[v, :])      # descendants of v move right
    lst = min(lst, s - lp[:, v])      # ancestors of v move left

which equals the worklist fixpoint because ``lp[u, t]`` is the maximum
path weight over *all* u->t paths (any transitive propagation is dominated
by the direct matrix entry). The scan step is O(N + T) with no nested
scans, so the program compiles in a fraction of the old level-relax
formulation's time and executes orders of magnitude faster on CPU.

One launch serves every request shape: :func:`greedy_fanout_grid_jax`
runs the scan vmapped over variants (score orders and candidate masks) x
profiles (an ensemble's budget timelines and masks) x instances (the
stacked rows of one shape bucket) as ONE jitted program (:func:`_impl`),
or ``shard_map``-ed over the instance axis across devices
(:func:`_grid_sharded_impl`); both wrap the same traced function.

Retracing discipline: all inputs are padded to shape buckets
(:func:`pad_dims` — N to multiples of 128, T to multiples of 256) before
they reach the launch, so instances whose real shapes differ hit the same
compiled executable; the jit cache is effectively keyed on the
bucket tuple. Padding is output-invariant: padded tasks have zero
duration/work and place at t=0 (a candidate point on every profile), padded
time units are never feasible starts (mask False, and every real LST is
below the real horizon).

Two longest-path representations serve the scan, chosen by
:func:`repro.kernels.backend.resolve_lp_form` against an ``lp_budget_bytes``
envelope (default :data:`LP_MAX_BYTES`):

* dense — the O(N^2) int32 matrix above, resident on device; the fast path
  for the replanning regime (N ~ 10^2-10^3);
* blocked (:class:`BlockedLP`) — the big-instance path: the scan streams
  the placement order in fixed-width chunks, and per chunk one device
  program runs level-synchronous max-plus sweeps over the graph's level
  tables (:attr:`BlockedLP.sweep_tables`, built once per graph) to produce
  just that chunk's lp rows (descendant distances of the placed tasks) and
  columns (ancestor distances), then feeds them to the chunked scan as
  ``lax.scan`` inputs; the host only uploads the chunk's task ids and
  dispatches, and the greedy state stays device-resident between chunk
  launches. Peak lp memory is O(N * B) for chunk width B
  (:meth:`BlockedLP.chunk_width` picks B from the budget), so instances far
  past the dense envelope schedule on ``engine="jax"`` — bit-identical to
  the dense path by construction (and by ``tests/test_lp_blocked.py``).

Intended for on-device replanning (CarbonGate-scale instances, N ~ 10^2-10^3,
T ~ 10^3-10^4); bigger instances stream through :class:`BlockedLP` or use
the numpy path (no matrix at all).
"""
from __future__ import annotations

import dataclasses
import functools

import numpy as np

from repro import obs
from repro.core.dag import Instance

NEG_PATH = -(1 << 30)                  # "no path" marker in lp (int32-safe)

N_BUCKET = 128                         # task-axis shape bucket
T_BUCKET = 256                         # time-axis shape bucket

# Device envelope for the dense longest-path matrix: the matrix is
# O(N^2) int32 (64 MiB at N=4000), fine for the device path's
# N ~ 10^2-10^3 regime but a silent multi-hundred-MiB allocation beyond
# it. 128 MiB admits N ~ 5800; bigger instances stream through the
# blocked form (BlockedLP) or use engine="numpy" (no matrix at all).
LP_MAX_BYTES = 128 * 2**20


def lp_matrix_bytes(num_tasks: int) -> int:
    """Bytes the dense int32 longest-path matrix of ``num_tasks`` needs."""
    return 4 * int(num_tasks) * int(num_tasks)


def lp_block_bytes(block: int, n_orders: int, num_tasks: int) -> int:
    """Bytes one streamed chunk of the blocked form needs on device:
    ``block`` scan steps x ``n_orders`` score orders x an lp row AND an lp
    column of padded width ``num_tasks``, int32 each."""
    return 2 * 4 * int(block) * int(n_orders) * int(num_tasks)


def longest_path_matrix(inst: Instance,
                        max_bytes: int | None = None) -> np.ndarray:
    """``lp[u, t]`` = max over u->t paths of the path's duration sum
    (excluding ``dur[t]``); ``lp[v, v] = 0``; unreachable = ``NEG_PATH``
    exactly (canonical: every no-path entry holds the sentinel, so the
    dense matrix is bit-comparable with :class:`BlockedLP` blocks, whose
    backward column sweeps would otherwise drift the phantom values
    differently — semantics-free either way, since the scan's est/lst
    updates cannot be won by any value below 0).

    Profile-independent: one O(E*N) host sweep per instance serves every
    profile, variant and replanning round of the device path. The byte
    cost is checked up front against ``max_bytes`` (default
    :data:`LP_MAX_BYTES`) so an oversized instance fails loudly instead
    of silently allocating O(N^2) device memory.
    """
    N = inst.num_tasks
    limit = LP_MAX_BYTES if max_bytes is None else int(max_bytes)
    need = lp_matrix_bytes(N)
    if need > limit:
        raise MemoryError(
            f"longest-path matrix needs {need / 2**20:.1f} MiB "
            f"(N={N} tasks, O(N^2) int32), over the "
            f"{limit / 2**20:.0f} MiB lp budget; the jax engine streams "
            f"such instances through the blocked form instead — raise "
            f"lp_budget_bytes (prepare_graph / schedule_portfolio_grid / "
            f"Planner) or build a BlockedLP(inst) directly; engine="
            f"'numpy' needs no matrix at all")
    # the dense matrix IS the all-rows block of the blocked form — one
    # sweep implementation (BlockedLP.rows) serves both representations,
    # so their bitwise agreement cannot drift
    return BlockedLP(inst, budget_bytes=limit).rows(np.arange(N))


@dataclasses.dataclass
class BlockedLP:
    """Blocked longest-path relaxation: the O(N*B) streaming form.

    Holds no matrix at all. The served scan builds each chunk's lp rows
    and columns on the device (``_blocked_impl``) by level-synchronous
    max-plus sweeps over :attr:`sweep_tables`, the graph's edges grouped
    by level, uploaded once. On the host, :meth:`rows` and :meth:`cols`
    run the same forward/backward sweeps over the topo-ordered adjacency
    for just the requested tasks: they build the dense matrix
    (:func:`longest_path_matrix`), and :meth:`chunk_tensors` assembles
    from them the bucket-padded per-chunk scan inputs the device build
    must equal, bit for bit (the tests' oracle). Values are bit-identical
    to the canonical dense :func:`longest_path_matrix` entries
    (``materialize`` assembles the full matrix for differential tests).

    ``budget_bytes`` bounds the streamed chunk buffers
    (:func:`lp_block_bytes`); :meth:`chunk_width` turns it into the scan
    chunk width and raises ``MemoryError`` when even a single-step chunk
    (the O(N) floor) does not fit.
    """

    inst: Instance
    budget_bytes: int = LP_MAX_BYTES

    def rows(self, tasks) -> np.ndarray:
        """``lp[tasks, :N]`` — descendant distances, one forward sweep."""
        inst = self.inst
        tasks = np.asarray(tasks, dtype=np.int64)
        N = inst.num_tasks
        d = np.full((len(tasks), N), NEG_PATH, dtype=np.int32)
        d[np.arange(len(tasks)), tasks] = 0
        dur = inst.dur.astype(np.int32)
        for v in inst.topo:
            ps = inst.preds(v)
            if len(ps):
                cand = d[:, ps] + dur[ps][None, :]
                np.maximum(d[:, v], cand.max(axis=1), out=d[:, v])
        # canonicalize: phantom entries (sentinel plus dur drift picked up
        # along no-path chains) all become NEG_PATH; true path values are
        # >= 0 (durations are positive, diagonal is 0)
        d[d < 0] = NEG_PATH
        d[np.arange(len(tasks)), tasks] = 0
        return d

    def cols(self, tasks) -> np.ndarray:
        """``lp[:N, tasks].T`` — ancestor distances, one backward sweep."""
        inst = self.inst
        tasks = np.asarray(tasks, dtype=np.int64)
        N = inst.num_tasks
        d = np.full((len(tasks), N), NEG_PATH, dtype=np.int32)
        d[np.arange(len(tasks)), tasks] = 0
        dur = inst.dur.astype(np.int32)
        for v in inst.topo[::-1]:
            ss = inst.succs(v)
            if len(ss):
                cand = d[:, ss] + dur[v]
                np.maximum(d[:, v], cand.max(axis=1), out=d[:, v])
        d[d < 0] = NEG_PATH
        d[np.arange(len(tasks)), tasks] = 0
        return d

    @functools.cached_property
    def sweep_tables(self):
        """Level tables of the device sweeps, built once per graph:
        ``(forward, backward)``, each a triple ``(dst, src, w)`` of int32
        device arrays [L, E].

        Row ``l`` holds the edges into the tasks of level ``l + 1``: for
        the forward sweep (:meth:`rows`) the edges ``u -> v`` by ``v``'s
        longest-path level from the sources, as ``(v, u, dur[u])``; for
        the backward sweep (:meth:`cols`) by ``u``'s level from the
        sinks, as ``(u, v, dur[u])``. No level holds both ends of an
        edge, so relaxing a whole row at once reads only final values.
        L and E are bucketed up (multiples of 8 and 64) so graphs of
        similar shape share one compiled chunk program; the padding is
        the self-loop ``(0, 0, 0)``, which relaxes nothing.
        """
        import jax.numpy as jnp

        inst = self.inst
        N = inst.num_tasks
        dur = inst.dur.astype(np.int32)
        # edges u -> v from the predecessor CSR
        v = np.repeat(np.arange(N), np.diff(inst.pred_ptr))
        u = np.asarray(inst.pred_idx, dtype=np.int64)
        back = np.zeros(N, dtype=np.int64)
        for t in inst.topo[::-1]:
            ss = inst.succs(t)
            if len(ss):
                back[t] = back[ss].max() + 1
        return tuple(
            tuple(jnp.asarray(a) for a in _level_table(lev, dst, src, w))
            for lev, dst, src, w in ((inst.level[v], v, u, dur[u]),
                                     (back[u], u, v, dur[u])))

    def chunk_width(self, n_orders: int, padded_n: int) -> int:
        """Scan chunk width B for ``n_orders`` score orders at padded task
        count ``padded_n``: the largest width whose chunk buffers fit
        ``budget_bytes``, clamped to a divisor of ``padded_n`` so every
        chunk launch shares one compiled shape."""
        floor = lp_block_bytes(1, n_orders, padded_n)
        width = int(self.budget_bytes) // floor
        if width < 1:
            raise MemoryError(
                f"blocked longest-path streaming needs at least {floor} "
                f"bytes (one scan step x {n_orders} orders x 2 lp "
                f"vectors of padded width {padded_n}, int32), over the "
                f"{self.budget_bytes} byte lp budget; raise "
                f"lp_budget_bytes or use engine='numpy'")
        if width >= padded_n:
            return padded_n
        B = 1
        while B * 2 <= width and padded_n % (B * 2) == 0:
            B *= 2
        return B

    def chunk_tensors(self, vs: np.ndarray, padded_n: int):
        """Per-chunk scan inputs for order chunk ``vs`` [V, B], built on
        the host: int32 (rows, cols), each [V, B, padded_n]. Padded task
        ids (>= N) get the padded identity row/column (``NEG_PATH``
        off-diagonal, 0 on it), exactly the dense padded matrix's
        entries. The served path builds the same tensors on the device
        (``_blocked_impl``); this is the oracle it is tested against."""
        V, B = vs.shape
        flat = np.asarray(vs, dtype=np.int64).ravel()
        N = self.inst.num_tasks
        rows = np.full((V * B, padded_n), NEG_PATH, dtype=np.int32)
        cols = np.full((V * B, padded_n), NEG_PATH, dtype=np.int32)
        real = flat < N
        if real.any():
            uniq, inv = np.unique(flat[real], return_inverse=True)
            rows[real, :N] = self.rows(uniq)[inv]
            cols[real, :N] = self.cols(uniq)[inv]
        rows[np.arange(V * B), flat] = 0
        cols[np.arange(V * B), flat] = 0
        return rows.reshape(V, B, padded_n), cols.reshape(V, B, padded_n)

    def materialize(self, block: int = 64) -> np.ndarray:
        """Assemble the full dense matrix from row blocks of width
        ``block`` (differential tests / diagnostics only — this is the
        O(N^2) allocation the streaming path exists to avoid)."""
        N = self.inst.num_tasks
        out = np.empty((N, N), dtype=np.int32)
        for c in range(0, N, max(int(block), 1)):
            idx = np.arange(c, min(c + max(int(block), 1), N))
            out[idx] = self.rows(idx)
        return out


def _level_table(level, dst, src, w) -> np.ndarray:
    """Edges ``(dst, src, w)`` grouped by ``level`` (>= 1) into an int32
    [3, L, E] table: row ``l`` holds level ``l + 1``'s edges sorted by
    ``dst``, after the self-loop ``(0, 0, 0)`` padding (so each row's
    ``dst`` stays sorted). L and E are bucketed up to 8 and 64."""
    L = _bucket_up(int(level.max(initial=0)), 8)
    row = level - 1
    order = np.lexsort((dst, row))
    row, dst, src, w = row[order], dst[order], src[order], w[order]
    counts = np.bincount(row, minlength=L)
    E = _bucket_up(int(counts.max(initial=0)), 64)
    first = np.cumsum(counts) - counts
    slot = np.arange(len(row)) - first[row] + (E - counts[row])
    table = np.zeros((3, L, E), dtype=np.int32)
    table[:, row, slot] = dst, src, w
    return table


def lp_for(inst: Instance, budget_bytes: int | None = None):
    """The dense-or-blocked union: the dense matrix when it fits the
    budget (:func:`repro.kernels.backend.resolve_lp_form`), else a
    :class:`BlockedLP` handle — every lp consumer accepts either."""
    from repro.kernels.backend import resolve_lp_form

    limit = LP_MAX_BYTES if budget_bytes is None else int(budget_bytes)
    if resolve_lp_form(inst.num_tasks, limit) == "dense":
        return longest_path_matrix(inst, max_bytes=limit)
    return BlockedLP(inst, budget_bytes=limit)


def _bucket_up(x: int, q: int) -> int:
    return max(((int(x) + q - 1) // q) * q, q)


def pad_dims(N: int, T: int) -> tuple[int, int]:
    """Shape bucket for an (N tasks, T horizon) instance."""
    return _bucket_up(N, N_BUCKET), _bucket_up(T, T_BUCKET)


def _placement_step(jnp, dur, work):
    """THE §5.2 placement step, shared by the dense scan (which looks
    ``row``/``col`` up in the resident lp matrix) and the chunked blocked
    scan (which receives them as scan inputs) — one body, so the
    blocked==dense bit-identity contract cannot drift."""
    big = jnp.int32(np.iinfo(np.int32).max // 4)

    def step(state, v, row, col):
        rem, mask, est, lst, start = state
        T = rem.shape[0]
        tgrid = jnp.arange(T, dtype=jnp.int32)
        feas = mask[:-1] & (tgrid >= est[v]) & (tgrid <= lst[v])
        any_f = feas.any()
        val = jnp.where(feas, rem, -big)
        s = jnp.where(any_f, jnp.argmax(val).astype(jnp.int32),
                      est[v].astype(jnp.int32))
        e = s + dur[v]
        run = (tgrid >= s) & (tgrid < e)
        rem = rem - jnp.where(run, work[v], 0).astype(rem.dtype)
        mask = mask.at[s].set(True)
        # numpy endpoint rule: e splits an interval only when e <= T; an
        # overrunning task must not spuriously mark T a candidate point.
        eidx = jnp.minimum(e, T)
        mask = mask.at[eidx].set(mask[eidx] | (e <= T))
        est = jnp.maximum(est, s + row)
        lst = jnp.minimum(lst, s - col)
        start = start.at[v].set(s)
        return (rem, mask, est, lst, start)

    return step


@functools.lru_cache(maxsize=1)
def _grid_fn():
    """The unjitted grid launcher: the §5.2 scan vmapped over variants,
    then profiles, then instances.

    Shared by :func:`_impl` (which jits it) and :func:`_grid_sharded_impl`
    (which wraps it in a ``shard_map`` before jitting), so both launch
    paths trace the SAME closure and stay bit-identical by construction.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    def greedy_scan(dur, work, lp, rem0, mask0, est0, lst0, order):
        """One variant's §5.2 greedy over precomputed inputs (vmappable)."""
        core = _placement_step(jnp, dur, work)

        def step(state, v):
            return core(state, v, lp[v], lp[:, v]), None

        N = est0.shape[0]
        state0 = (rem0, mask0, est0, lst0, jnp.zeros(N, jnp.int32))
        (_, _, _, _, start), _ = lax.scan(step, state0, order)
        return start

    # axis spec per argument: (dur, work, lp, rem0, mask0, est0, lst0, order)
    variant_axes = (None, None, None, None, 0, None, None, 0)
    profile_axes = (None, None, None, 0, 0, None, None, None)
    fanout = jax.vmap(greedy_scan, in_axes=variant_axes)
    multi = jax.vmap(fanout, in_axes=profile_axes)
    return jax.vmap(multi, in_axes=(0,) * 8)


@functools.lru_cache(maxsize=1)
def _impl():
    """The jitted single-device grid launch."""
    # no buffer donation: the only output (start times) matches no input's
    # shape, so nothing could alias a donated budget or mask buffer
    import jax

    return jax.jit(_grid_fn())


_sharded_grids: dict = {}      # ndev -> jitted sharded grid, built on use


def _grid_sharded_impl(ndev: int):
    """The grid launcher sharded over ``ndev`` devices, built once per
    device count (kept in ``_sharded_grids`` for the jit-cache probe).

    The instance-row axis of the combined (instances x profiles x
    variants) launch is embarrassingly parallel, so the sharded form is a
    ``shard_map`` of the same grid function over a 1-D "data" mesh
    (``sharding.ctx.grid_mesh``): every device runs ``rows/ndev`` full
    greedy scans with zero cross-device communication, and the result is
    bitwise-identical to the single-device grid (rows are independent and
    the per-row closure is literally the same traced function).
    ``check_vma=False``: no replicated outputs to verify, and the scan
    body trips the conservative varying-axes checker.
    """
    if ndev in _sharded_grids:
        return _sharded_grids[ndev]
    import jax

    from repro.sharding.ctx import grid_mesh
    from repro.sharding.specs import grid_batch_spec

    spec = grid_batch_spec()
    sharded = jax.shard_map(_grid_fn(), mesh=grid_mesh(ndev),
                            in_specs=(spec,) * 8, out_specs=spec,
                            check_vma=False)
    return _sharded_grids.setdefault(ndev, jax.jit(sharded))


def _grid_launch(stacked, devices):
    """Dispatch one stacked dense-bucket grid launch, sharding the
    instance-row axis over ``devices`` when asked (padding the row count
    to a multiple of the device count by repeating the last row, sliced
    off after — shard_map needs equal per-device block sizes)."""
    if devices is None or devices <= 1:
        return _impl()(*stacked)
    import jax.numpy as jnp

    n = stacked[0].shape[0]
    pad = -n % devices
    if pad:
        stacked = tuple(
            jnp.concatenate([a, jnp.repeat(a[-1:], pad, axis=0)])
            for a in stacked)
    out = _grid_sharded_impl(devices)(*stacked)
    return out[:n] if pad else out


def _lp_sweep(jnp, lax, vs, table, padded_n):
    """One chunk's lp rows (forward ``table``) or columns (backward
    ``table``) on the device: int32 [V, B, padded_n] for the sources
    ``vs`` [V, B], bit-identical to :meth:`BlockedLP.chunk_tensors`.

    The state is task-major, ``d[t]`` = task ``t``'s distances from the
    V*B sources, source ``vs[v, b]`` at lane ``b * V + v``: a level's
    gather and scatter move whole rows, one (8, 128) tile each at 1024
    sources. :func:`repro.kernels.maxplus_sweep.relax_levels` relaxes the
    levels in order, each as ``d[dst] = max(d[dst], d[src] + w)``;
    max-plus over int32 is exact and order-free, so this is the host
    sweep's fixpoint. Then the host sweep's canonical form: negatives
    become ``NEG_PATH`` and each source's own entry 0 (padded ids have no
    edges: the identity row).
    """
    from repro.kernels.maxplus_sweep import relax_levels

    V, B = vs.shape
    C = V * B
    lanes = 128 if C % 128 == 0 else C
    shape = (padded_n, C // lanes, lanes)
    # step-major lanes: the transpose back lands in the layout the
    # vmapped scan reads its inputs in, with no second copy
    own = lax.broadcasted_iota(jnp.int32, shape, 0) \
        == vs.T.reshape(1, C // lanes, lanes)
    neg = jnp.int32(NEG_PATH)
    d = relax_levels(jnp.where(own, jnp.int32(0), neg), *table)
    d = jnp.where(own, jnp.int32(0), jnp.where(d < 0, neg, d))
    return d.reshape(padded_n, C).T.reshape(B, V, padded_n).swapaxes(0, 1)


@functools.lru_cache(maxsize=1)
def _blocked_impl():
    """The jitted chunked twin of :func:`_impl`: one program per chunk of
    the placement order that builds the chunk's lp rows and columns on the
    device (:func:`_lp_sweep` over :attr:`BlockedLP.sweep_tables`), then
    runs one ``lax.scan`` over the chunk with those rows/cols as scan
    inputs instead of a device-resident matrix, and returns the full
    greedy state (rem, mask, est, lst, start) so the host chunk loop
    keeps it device-resident between launches. The step body IS
    :func:`_placement_step` — the same closure the dense scan runs — so
    chunked results are bit-identical to the dense scan's by
    construction."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    def chunk_scan(dur, work, rem, mask, est, lst, start, vs, rows, cols):
        core = _placement_step(jnp, dur, work)

        def step(state, xs):
            return core(state, *xs), None

        state, _ = lax.scan(step, (rem, mask, est, lst, start),
                            (vs, rows, cols))
        return state

    # per-argument axes: (dur, work, rem, mask, est, lst, start, vs, rows,
    # cols); unlike the dense scan, est/lst are per-row STATE here (they
    # diverge across variants and profiles between chunk launches)
    variant_axes = (None, None, 0, 0, 0, 0, 0, 0, 0, 0)
    profile_axes = (None, None, 0, 0, 0, 0, 0, None, None, None)
    multi = jax.vmap(jax.vmap(chunk_scan, in_axes=variant_axes),
                     in_axes=profile_axes)

    def chunk(dur, work, rem, mask, est, lst, start, vs, forward,
              backward):
        Np = dur.shape[0]
        rows = _lp_sweep(jnp, lax, vs, forward, Np)
        cols = _lp_sweep(jnp, lax, vs, backward, Np)
        return multi(dur, work, rem, mask, est, lst, start, vs, rows, cols)

    # donate the state buffers so chained chunk launches reuse device
    # memory (the state comes back with the same shapes; on CPU donation
    # is a no-op that only warns, so it is enabled off-CPU only)
    don = tuple(range(2, 7)) if jax.default_backend() != "cpu" else ()
    return jax.jit(chunk, donate_argnums=don)


def _blocked_fanout_padded(dur, work, blp: BlockedLP, budgets, masks,
                           est, lst, orders) -> np.ndarray:
    """All (profile, variant) greedy schedules of one blocked-lp instance,
    chunk-streamed; every input already bucket-padded.

    Args:
      budgets: int [P, Tp]; masks: bool [P, V, Tp+1]; orders: int [V, Np];
      dur/work/est/lst: [Np] (jnp or np).
    Returns:
      int32 np [P, V, Np] start times.
    """
    import jax.numpy as jnp

    budgets = np.asarray(budgets, dtype=np.int32)
    masks = np.asarray(masks, dtype=bool)
    orders = np.asarray(orders, dtype=np.int32)
    P, Tp = budgets.shape
    V, Np = orders.shape
    B = blp.chunk_width(V, Np)
    est = np.asarray(est, dtype=np.int32)
    lst = np.asarray(lst, dtype=np.int32)
    state = (
        jnp.asarray(np.repeat(budgets[:, None, :], V, axis=1)),
        jnp.asarray(masks),
        jnp.asarray(np.broadcast_to(est, (P, V, Np)).copy()),
        jnp.asarray(np.broadcast_to(lst, (P, V, Np)).copy()),
        jnp.asarray(np.zeros((P, V, Np), dtype=np.int32)),
    )
    impl = _blocked_impl()
    tables = blp.sweep_tables
    dur_j, work_j = jnp.asarray(dur), jnp.asarray(work)
    n_chunks = -(-Np // B)
    N = blp.inst.num_tasks
    swept = 0
    with obs.span("blocked_chunk_sweep", N=int(Np), chunk_width=int(B),
                  chunks=n_chunks, rows=int(P * V)):
        for c in range(0, Np, B):
            vs = orders[:, c:c + B]
            tasks = len(np.unique(vs[vs < N]))
            swept += tasks
            # the dispatch of this chunk's program: the device sweeps of
            # its lp rows and columns, then its scan
            with obs.span("blocked_lp_rows", chunk=c // B, tasks=tasks):
                state = impl(dur_j, work_j, *state, jnp.asarray(vs),
                             *tables)
    reg = obs.registry()
    reg.counter(
        "blocked_lp_chunks_total",
        "device chunk launches of the blocked longest-path sweep"
    ).inc(n_chunks)
    reg.counter(
        "blocked_lp_rows_total",
        "unique tasks whose lp rows and columns were swept"
    ).inc(swept)
    reg.counter(
        "blocked_lp_device_sweeps_total",
        "level-synchronous max-plus sweeps run on the device, two a chunk"
    ).inc(2 * n_chunks)
    return np.asarray(state[4])


def padded_shared(inst: Instance, est0, lst0, lp=None):
    """Bucket-padded profile-independent device tensors (jnp).

    Returns ``(dur, work, lp, est, lst, order_tail)`` at the
    :func:`pad_dims` bucket of ``inst``; ``order_tail`` is the suffix of
    padded task ids every padded score order must end with. ``lp`` may be
    a precomputed dense matrix OR a :class:`BlockedLP` — the blocked
    handle passes through in the lp slot (no device matrix exists) and
    :func:`greedy_fanout_grid_jax` routes it to the chunked scan.
    """
    import jax.numpy as jnp

    N = inst.num_tasks
    Np, _ = pad_dims(N, 1)
    if lp is None:
        lp = longest_path_matrix(inst)
    if isinstance(lp, BlockedLP):
        lp_j = lp
    else:
        lp_p = np.full((Np, Np), NEG_PATH, dtype=np.int32)
        lp_p[:N, :N] = lp
        np.fill_diagonal(lp_p[N:, N:], 0)
        lp_j = jnp.asarray(lp_p)
    dur_p = np.zeros(Np, dtype=np.int32)
    dur_p[:N] = inst.dur
    work_p = np.zeros(Np, dtype=np.int32)
    work_p[:N] = inst.task_work
    est_p = np.zeros(Np, dtype=np.int32)
    est_p[:N] = est0
    lst_p = np.zeros(Np, dtype=np.int32)
    lst_p[:N] = lst0
    return (jnp.asarray(dur_p), jnp.asarray(work_p), lp_j,
            jnp.asarray(est_p), jnp.asarray(lst_p),
            np.arange(N, Np, dtype=np.int32))


def pad_orders(orders: np.ndarray, order_tail: np.ndarray) -> np.ndarray:
    """[V, N] score orders -> [V, Np]: padded tasks placed last (no-ops)."""
    V = orders.shape[0]
    return np.concatenate(
        [np.asarray(orders, np.int32),
         np.broadcast_to(order_tail, (V, len(order_tail)))], axis=1)


def pad_masks(masks: np.ndarray, Tp: int) -> np.ndarray:
    """[..., T+1] candidate masks -> [..., Tp+1]: padded units never start."""
    T = masks.shape[-1] - 1
    pad = [(0, 0)] * (masks.ndim - 1) + [(0, Tp - T)]
    return np.pad(np.asarray(masks, bool), pad)


def pad_budget(unit_budget: np.ndarray, Tp: int) -> np.ndarray:
    """[..., T] per-unit budgets -> [..., Tp] (padding value is never read)."""
    T = unit_budget.shape[-1]
    pad = [(0, 0)] * (unit_budget.ndim - 1) + [(0, Tp - T)]
    return np.pad(np.asarray(unit_budget, np.int32), pad)


def greedy_fanout_grid_jax(bucket_rows, devices: int | None = None):
    """All (instance, profile, variant) greedy schedules of one shape bucket
    in ONE launch: the only route to the greedy fan-out.

    Args:
      bucket_rows: per-instance tuples of bucket-padded device inputs in
        ``greedy_scan`` argument order ``(dur, work, lp, rem0 [P, Tp],
        mask0 [P, V, Tp+1], est0, lst0, order [V, Np])``; every row must
        already be padded to the same :func:`pad_dims` bucket (same P, V).
        A row's ``lp`` slot may hold a :class:`BlockedLP` instead of the
        dense matrix — such rows stream through the chunked scan (one
        sequence of launches per blocked row; the dense rows of the
        bucket still ride one grid launch together).
      devices: shard the instance-row axis of the dense launch over this
        many devices (``shard_map`` over ``sharding.ctx.grid_mesh``);
        None / 1 = single-device grid. Results are bitwise-identical
        either way. Blocked rows always stream unsharded (their chunk
        loop is host-driven).
    Returns:
      int32 [I, P, V, Np] start times (caller slices off the task
      padding); a numpy array when any row is blocked, a device array
      otherwise.
    """
    import jax.numpy as jnp

    rows = list(bucket_rows)
    blocked = [isinstance(r[2], BlockedLP) for r in rows]
    if not any(blocked):
        stacked = tuple(jnp.stack([jnp.asarray(r[a]) for r in rows])
                        for a in range(8))
        return _grid_launch(stacked, devices)
    out: list = [None] * len(rows)
    dense_idx = [i for i, b in enumerate(blocked) if not b]
    if dense_idx:
        stacked = tuple(jnp.stack([jnp.asarray(rows[i][a])
                                   for i in dense_idx]) for a in range(8))
        dense_starts = np.asarray(_grid_launch(stacked, devices))
        for j, i in enumerate(dense_idx):
            out[i] = dense_starts[j]
    for i, r in enumerate(rows):
        if blocked[i]:
            dur, work, blp, budgets, masks, est_j, lst_j, orders = r
            out[i] = _blocked_fanout_padded(dur, work, blp, budgets,
                                            masks, est_j, lst_j, orders)
    return np.stack([np.asarray(o) for o in out])
