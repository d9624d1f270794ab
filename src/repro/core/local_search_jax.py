"""The device hill climb: device-resident gain/commit rounds + exact polish.

The paper's local search walks tasks sequentially and applies the first
improving +-mu shift. :func:`local_search_portfolio_multi`, the portfolio
engine's one climber, instead evaluates *all* (task, shift) gains at once
(``kernels.gain_scan``) and commits proposals in gain order with exact
integer re-evaluation. ALL rows (``-LS`` variants x ensemble profiles) of
an instance advance together, and the whole gain/commit round loop runs
device-resident as ONE jitted ``lax.while_loop`` (gains via the compiled
Pallas kernel on TPU, its jnp prefix-sum twin on CPU; commits as an
in-loop top-K scan) — one host sync per hill climb, not one per round.
Rows deactivate individually when a round commits nothing.

After the device climb converges, every row is *polished* with the exact
sequential reference (:func:`repro.core.local_search.reference_round`)
until a full reference round commits nothing. Termination therefore
implies the sequential reference cannot improve the result either — no
variant stops earlier than its sequential reference would (tested), while
cost stays monotonically non-increasing throughout.
"""
from __future__ import annotations

import functools

import numpy as np

from repro import obs
from repro.core.cancel import checkpoint
from repro.core.carbon import work_timeline
from repro.core.dag import Instance
from repro.core.local_search import ls_graph_context, reference_round

_COMMIT_K = 32       # default device commits per row per round
# (the rest wait a round; expose per call as LocalSearchConfig.commit_k)


def auto_commit_k(n_candidates: int,
                  lo: int = 8, hi: int = 128) -> int:
    """Pick the device commit width from instance gain density.

    The ROADMAP's "nothing *chooses* K" item, closed at the small end
    with a simple rule: one commit slot per ~4 candidate segments
    (``n_candidates`` = the instance's candidate-point count, the size of
    the greedy's segment skeleton), clamped to [lo, hi]. Dense-gain
    instances (many candidate segments -> many independent improving
    shifts per round) get wide commits and fewer device rounds; sparse
    instances stay narrow so one round's commits rarely invalidate each
    other. Any width keeps the termination guarantee — the
    sequential-reference polish runs regardless.
    """
    return int(np.clip(int(n_candidates) // 4, lo, hi))


# ---------------------------------------------------------------------------
# Device-resident portfolio climb
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=8)
def _climb_impl(mu: int, max_rounds: int, commit_k: int = _COMMIT_K,
                padded: bool = False):
    import jax
    import jax.numpy as jnp
    from jax import lax

    from repro.kernels.gain_scan import gains_windows_auto, gather_windows

    f32 = jnp.float32

    def climb_row(rem, start, t_real, dur, work, pred_a, succ_a):
        """One row's full hill climb: rounds loop on device, no host sync.

        rem int32 [T], start int32 [N]; pred_a/succ_a describe the direct
        G_c edges — bool [N, N] masks (``padded=False``, the dense form)
        or ``(idx [N, D], ok [N, D])`` padded-CSR gather tables
        (``padded=True``, the blocked big-instance form, bit-identical
        bounds); t_real = the real horizon (T may be padded).
        """
        T = rem.shape[0]
        tgrid = jnp.arange(T, dtype=jnp.int32)
        durf = dur.astype(f32)
        workf = work.astype(f32)

        if padded:
            pidx, pok = pred_a
            sidx, sok = succ_a

            def pred_lo(start):           # max over preds of start + dur
                return jnp.max(jnp.where(pok, (start + dur)[pidx], 0),
                               axis=-1)

            def succ_hi(start):           # min over succs of start
                return jnp.min(jnp.where(sok, start[sidx], t_real),
                               axis=-1)

            def pred_lo_v(start, v):
                return jnp.max(jnp.where(pok[v], (start + dur)[pidx[v]], 0))

            def succ_hi_v(start, v):
                return jnp.min(jnp.where(sok[v], start[sidx[v]], t_real))
        else:
            pred_mask, succ_mask = pred_a, succ_a

            def pred_lo(start):
                return jnp.max(
                    jnp.where(pred_mask, (start + dur)[None, :], 0), axis=1)

            def succ_hi(start):
                return jnp.min(
                    jnp.where(succ_mask, start[None, :], t_real), axis=1)

            def pred_lo_v(start, v):
                return jnp.max(jnp.where(pred_mask[v], start + dur, 0))

            def succ_hi_v(start, v):
                return jnp.min(jnp.where(succ_mask[v], start, t_real))

        def round_gains(rem, start):
            # round-start dynamic bounds, as in dyn_bounds_all
            lo = pred_lo(start)
            hi = succ_hi(start) - dur
            win_s, win_e = gather_windows(rem.astype(f32), start, dur, mu=mu)
            # mode-dispatched oracle: jnp prefix-sum twin on CPU, the
            # compiled tiled Pallas kernel on TPU (bit-identical paths)
            return gains_windows_auto(
                win_s, win_e, workf, durf,
                (lo - start).astype(f32), (hi - start).astype(f32), mu=mu)

        def commit_step(carry, v):
            rem, start, any_commit, best_delta, best_gain = carry
            s = start[v]
            d_v = dur[v]
            w_v = work[v]
            e = s + d_v
            # current-state legal bounds (commits earlier in this scan may
            # have moved neighbours), the clamp of ``local_search.dyn_bounds``
            dlo = pred_lo_v(start, v)
            dhi = succ_hi_v(start, v) - d_v
            new_s = jnp.clip(s + best_delta[v], dlo, dhi)
            dd = new_s - s
            ln = jnp.minimum(jnp.abs(dd), d_v)
            # symmetric difference of old/new windows (move_gain identities)
            vac_lo = jnp.where(dd > 0, s, e - ln)
            occ_hi = jnp.where(dd > 0, new_s + d_v, new_s + ln)
            vac = (tgrid >= vac_lo) & (tgrid < vac_lo + ln)
            occ = (tgrid >= occ_hi - ln) & (tgrid < occ_hi)
            released = jnp.sum(jnp.where(
                vac, jnp.minimum(jnp.maximum(-rem, 0), w_v), 0))
            incurred = jnp.sum(jnp.where(
                occ, jnp.minimum(jnp.maximum(w_v - jnp.maximum(rem, 0), 0),
                                 w_v), 0))
            ok = ((best_gain[v] > 0) & (dlo <= dhi) & (dd != 0)
                  & (released - incurred > 0))
            old = (tgrid >= s) & (tgrid < e)
            new = (tgrid >= new_s) & (tgrid < new_s + d_v)
            rem = jnp.where(ok, rem + w_v * old.astype(rem.dtype)
                            - w_v * new.astype(rem.dtype), rem)
            start = jnp.where(ok, start.at[v].set(new_s), start)
            return (rem, start, any_commit | ok, best_delta, best_gain), None

        def round_body(state):
            rem, start, rounds, _ = state
            g = round_gains(rem, start)
            best_delta = jnp.argmax(g, axis=1).astype(jnp.int32) - mu
            best_gain = g.max(axis=1)
            order = jnp.argsort(-best_gain).astype(jnp.int32)
            k = min(commit_k, order.shape[0])
            carry = (rem, start, jnp.bool_(False), best_delta, best_gain)
            carry, _ = lax.scan(commit_step, carry, order[:k])
            return (carry[0], carry[1], rounds + 1, carry[2])

        def cond(state):
            return state[3] & (state[2] < max_rounds)

        state = (rem, start, jnp.int32(0), jnp.bool_(True))
        state = lax.while_loop(cond, round_body, state)
        # (starts, rounds): the round count is the climb's own
        # observability signal (obs `ls_device_rounds`), surfaced from the
        # device loop at no extra sync — the arrays come back together
        return state[1], state[2]

    rows = jax.vmap(climb_row,
                    in_axes=(0, 0, None, None, None, None, None))
    return jax.jit(rows)


def _dense_adjacency(inst: Instance, ctx: dict | None):
    """bool [N, N] (pred, succ) masks of the direct G_c edges, cached."""
    if ctx is not None and "adj_dense" in ctx:
        return ctx["adj_dense"]
    N = inst.num_tasks
    u = np.repeat(np.arange(N), np.diff(inst.succ_ptr))
    v = inst.succ_idx
    pred = np.zeros((N, N), dtype=bool)
    succ = np.zeros((N, N), dtype=bool)
    pred[v, u] = True
    succ[u, v] = True
    if ctx is not None:
        ctx["adj_dense"] = (pred, succ)
    return pred, succ


def _padded_adjacency(inst: Instance, ctx: dict | None):
    """Padded-CSR gather tables of the direct G_c edges, cached.

    Returns ``(pidx, pok, sidx, sok)``: int32/bool [N, D] with D the max
    degree bucketed up to a multiple of 8 (fewer distinct jit shapes
    across instances). O(N * D) memory — the blocked big-instance twin of
    :func:`_dense_adjacency`'s O(N^2) masks, cached under its own key so
    a graph serving both climb forms keeps both."""
    if ctx is not None and "adj_padded" in ctx:
        return ctx["adj_padded"]
    from repro.core.greedy_jax import _bucket_up

    N = inst.num_tasks
    pdeg = np.diff(inst.pred_ptr)
    sdeg = np.diff(inst.succ_ptr)
    D = _bucket_up(max(int(pdeg.max(initial=1)),
                       int(sdeg.max(initial=1)), 1), 8)
    pidx = np.zeros((N, D), dtype=np.int32)
    pok = np.zeros((N, D), dtype=bool)
    sidx = np.zeros((N, D), dtype=np.int32)
    sok = np.zeros((N, D), dtype=bool)
    r = np.repeat(np.arange(N), pdeg)
    c = np.arange(len(inst.pred_idx)) - np.repeat(inst.pred_ptr[:-1], pdeg)
    pidx[r, c] = inst.pred_idx
    pok[r, c] = True
    r = np.repeat(np.arange(N), sdeg)
    c = np.arange(len(inst.succ_idx)) - np.repeat(inst.succ_ptr[:-1], sdeg)
    sidx[r, c] = inst.succ_idx
    sok[r, c] = True
    out = (pidx, pok, sidx, sok)
    if ctx is not None:
        ctx["adj_padded"] = out
    return out


def local_search_portfolio_multi(inst: Instance, T: int,
                                 unit_budgets: np.ndarray,
                                 starts: np.ndarray, mu: int = 10,
                                 max_rounds: int = 200,
                                 ctx: dict | None = None,
                                 commit_k: int | None = None,
                                 adjacency: str | None = None,
                                 cancel=None) -> np.ndarray:
    """Hill-climb a batch of schedule rows of one instance at once.

    The portfolio engine's climber: rows are any mix of ``-LS`` variants
    and ensemble profiles (each row has its own budget timeline). The whole
    round loop runs device-resident (ONE host sync), then each row is
    polished to sequential-reference local optimality with its own round
    budget.

    Args:
      unit_budgets: int [R, T] per-row effective budget timelines.
      starts:       int [R, N] one greedy schedule per row.
      ctx:          optional shared graph context (``ls_graph_context``;
        extra keys such as ``unit_budget`` are ignored).
      commit_k:     device commits per row per round (None = the module
        default ``_COMMIT_K``); any value yields the same termination
        guarantee — the sequential-reference polish runs regardless — but
        a profile-tuned K can cut device round counts on dense-gain
        instances.
      adjacency:    ``"dense"`` (None, the default) keeps the O(N^2) bool
        edge masks on device; ``"padded"`` uses the O(N * D) padded-CSR
        gather tables instead (:func:`_padded_adjacency`) — bit-identical
        bounds, the form the blocked-lp big-instance path uses so no
        dense N x N tensor exists anywhere in the climb.
      cancel:       optional :class:`repro.core.cancel.CancelToken`,
        polled before the device climb launch and between sequential
        polish rounds (the device ``while_loop`` itself is one
        uninterruptible launch bounded by ``max_rounds``).
    Returns:
      int64 [R, N] improved schedules; per-row cost is monotonically
      non-increasing, and no row terminates while a sequential reference
      round could still improve it.
    """
    import jax.numpy as jnp

    from repro.core.greedy_jax import N_BUCKET, T_BUCKET, _bucket_up

    if adjacency not in (None, "dense", "padded"):
        raise ValueError(f"unknown adjacency form {adjacency!r}")
    padded = adjacency == "padded"
    # host-side climb inputs: per-row remaining budget, bucket padding and
    # the adjacency form, up to the device launch
    with obs.span("ls_prep", padded=padded) as prep_span:
        starts = np.asarray(starts, dtype=np.int64).copy()
        R, N = starts.shape
        prep_span.set(rows=int(R), N=int(N))
        unit_budgets = np.asarray(unit_budgets, dtype=np.int64)
        ctx = ctx if ctx is not None else ls_graph_context(inst)

        rems = unit_budgets - np.stack(
            [work_timeline(inst, T, starts[i]) for i in range(R)])

        # bucket-padded device inputs: padded tasks have work 0 (never
        # legal), padded rows repeat row 0 (computed, discarded), padded
        # time units are unreachable (moves clamp to the real horizon)
        Np = _bucket_up(N, N_BUCKET)
        Tp = _bucket_up(T, T_BUCKET)
        Rp = _bucket_up(R, 8)
        rem_p = np.zeros((Rp, Tp), dtype=np.int32)
        rem_p[:R, :T] = rems
        rem_p[R:] = rem_p[0]
        start_p = np.zeros((Rp, Np), dtype=np.int32)
        start_p[:R, :N] = starts
        start_p[R:] = start_p[0]
        dur_p = np.zeros(Np, dtype=np.int32)
        dur_p[:N] = inst.dur
        work_p = np.zeros(Np, dtype=np.int32)
        work_p[:N] = inst.task_work
        if padded:
            pidx, pok, sidx, sok = _padded_adjacency(inst, ctx)
            D = pidx.shape[1]
            pidx_p = np.zeros((Np, D), dtype=np.int32)
            pidx_p[:N] = pidx
            pok_p = np.zeros((Np, D), dtype=bool)
            pok_p[:N] = pok
            sidx_p = np.zeros((Np, D), dtype=np.int32)
            sidx_p[:N] = sidx
            sok_p = np.zeros((Np, D), dtype=bool)
            sok_p[:N] = sok
            adj_args = ((jnp.asarray(pidx_p), jnp.asarray(pok_p)),
                        (jnp.asarray(sidx_p), jnp.asarray(sok_p)))
        else:
            pred, succ = _dense_adjacency(inst, ctx)
            pred_p = np.zeros((Np, Np), dtype=bool)
            pred_p[:N, :N] = pred
            succ_p = np.zeros((Np, Np), dtype=bool)
            succ_p[:N, :N] = succ
            adj_args = (jnp.asarray(pred_p), jnp.asarray(succ_p))

    checkpoint(cancel)                   # last rung before the device climb
    ck = _COMMIT_K if commit_k is None else int(commit_k)
    with obs.span("ls_device_climb", rows=int(R), N=int(N), T=int(T),
                  commit_k=ck, padded=padded) as climb_span:
        climbed, rounds_dev = _climb_impl(mu, max_rounds, ck, padded)(
            jnp.asarray(rem_p), jnp.asarray(start_p), jnp.int32(T),
            jnp.asarray(dur_p), jnp.asarray(work_p), *adj_args)
        climbed = np.asarray(climbed)
        rounds_dev = np.asarray(rounds_dev)[:R]
        climb_span.set(rounds_max=int(rounds_dev.max(initial=0)))
    rounds_hist = obs.registry().histogram(
        "ls_device_rounds", "device while_loop rounds per climb row",
        buckets=(1, 2, 4, 8, 16, 32, 64, 128, 256), reservoir=256)
    for r in rounds_dev:
        rounds_hist.observe(int(r))
    starts = climbed[:R, :N].astype(np.int64)

    pad = mu
    polish_rounds = 0
    with obs.span("ls_polish", rows=int(R)) as polish_span:
        for i in range(R):
            rem_pad = np.zeros(T + 2 * pad, dtype=np.int64)
            rem_pad[pad:pad + T] = unit_budgets[i] - work_timeline(
                inst, T, starts[i])
            budget = max_rounds               # per-variant round budget
            while budget > 0 and reference_round(inst, T, rem_pad, pad,
                                                 starts[i], mu, ctx):
                budget -= 1
                polish_rounds += 1
                checkpoint(cancel)   # per-polish-round rung
        polish_span.set(rounds=polish_rounds)
    obs.registry().counter(
        "ls_polish_rounds_total",
        "sequential-reference polish rounds run after device climbs"
    ).inc(polish_rounds)
    return starts

