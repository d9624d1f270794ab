"""Plain reference of the served portfolio, on the host, in exact integers.

What one plan cell of the portfolio must hold, written from the paper
(arXiv:2507.08725, §3 and §5) and the planner's documented climb rule,
without importing the planner:

* ``G_c``: every cross-processor edge becomes a communication task on its
  link; chain edges fix the order on every processor (§3).
* ``asap``: every task at its earliest start.
* The 16 CaWoSched variants ``{slack|press}[W][R][-LS]`` (§5.2): tasks in
  score order, each started at the feasible candidate point with the most
  remaining green budget (earliest on ties), budgets and EST/LST updated.
* ``-LS`` (§5.3 as the planner runs it): a climb in rounds, each round
  scoring every (task, shift) of up to +-mu against the round-start
  timeline and committing the ``commit_k`` best proposals in gain order
  (ties by task id), each clamped to its current legal window and kept
  only if its exact gain is positive; rounds until one commits nothing or
  ``max_rounds``. Then sequential first-improvement rounds (tasks by
  processor in non-increasing P_work, shifts earliest first) until a round
  commits nothing or ``max_rounds``.
* The carbon cost: per time unit, work power above the green budget left
  after the idle draw.

``dtype`` and ``score_dtype`` compute the climb's gains and the task
scores in a lower precision than the exact integers and float64 the
planner uses; they exist for the control, which must come out not
correct.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from harness.generate import Cluster, Mapping, Profile, Workflow, \
    topological_order

NEG = np.iinfo(np.int64).min // 4
SCORES = ("slack", "press")


@dataclasses.dataclass(frozen=True)
class Graph:
    """The communication-enhanced DAG: workflow tasks 0..n-1, then one
    communication task per cross-processor edge, link by link."""

    n: int
    dur: np.ndarray          # [N]
    proc: np.ndarray         # [N]
    work: np.ndarray         # [N] P_work of the task's processor
    eu: np.ndarray           # [E] edge sources
    ev: np.ndarray           # [E] edge targets
    preds: tuple             # per task: int array
    succs: tuple
    visit: tuple             # local-search visit order
    topo: tuple
    idle_total: int
    weight: np.ndarray       # [N] (P_idle + P_work) / max over processors

    @property
    def N(self) -> int:
        return len(self.dur)


def build_graph(wf: Workflow, mapping: Mapping, cluster: Cluster) -> Graph:
    n = wf.n
    proc_n = np.asarray(mapping.proc, dtype=np.int64)
    dur = list(np.maximum(np.ceil(wf.node_w / cluster.speed[proc_n])
                          .astype(np.int64), 1))
    proc = list(proc_n)
    first_edge = {}
    for i, (u, v) in enumerate(wf.edges.tolist()):
        first_edge.setdefault((u, v), i)
    comm = {}
    edges = []
    chains = [list(t) for t in mapping.order if t]
    chain_procs = [p for p, t in enumerate(mapping.order) if t]
    for link, pairs in sorted(mapping.comm_order.items()):
        chain = []
        for u, v in pairs:
            cid = len(dur)
            comm[(u, v)] = cid
            dur.append(max(int(wf.edge_w[first_edge[(u, v)]]), 1))
            proc.append(link)
            if chain:
                edges.append((chain[-1], cid))
            chain.append(cid)
        if chain:
            chains.append(chain)
            chain_procs.append(link)
    for u, v in wf.edges.tolist():
        if proc_n[u] == proc_n[v]:
            edges.append((u, v))
        else:
            edges.append((u, comm[(u, v)]))
            edges.append((comm[(u, v)], v))
    for tasks in mapping.order:
        edges.extend(zip(tasks[:-1], tasks[1:]))
    N = len(dur)
    e = np.unique(np.asarray(edges, dtype=np.int64).reshape(-1, 2), axis=0)
    preds = [[] for _ in range(N)]
    succs = [[] for _ in range(N)]
    for u, v in e.tolist():
        preds[v].append(u)
        succs[u].append(v)
    topo = topological_order(N, e)
    if len(topo) != N:
        raise ValueError("G_c has a cycle")
    proc = np.asarray(proc, dtype=np.int64)
    work = cluster.p_work[proc]
    chain_power = cluster.p_work[np.asarray(chain_procs, dtype=np.int64)]
    visit = tuple(v for c in np.argsort(-chain_power, kind="stable")
                  for v in chains[c])
    total = cluster.p_idle + cluster.p_work
    return Graph(n=n, dur=np.asarray(dur, dtype=np.int64), proc=proc,
                 work=work, eu=e[:, 0].copy(), ev=e[:, 1].copy(),
                 preds=tuple(np.asarray(p, dtype=np.int64) for p in preds),
                 succs=tuple(np.asarray(s, dtype=np.int64) for s in succs),
                 visit=visit, topo=tuple(topo),
                 idle_total=cluster.idle_total,
                 weight=total[proc] / total.max())


def earliest_starts(g: Graph) -> np.ndarray:
    est = np.zeros(g.N, dtype=np.int64)
    for v in g.topo:
        ps = g.preds[v]
        if len(ps):
            est[v] = int((est[ps] + g.dur[ps]).max())
    return est


def latest_starts(g: Graph, T: int) -> np.ndarray:
    lst = T - g.dur
    for v in reversed(g.topo):
        ss = g.succs[v]
        if len(ss):
            lst[v] = min(int(lst[ss].min() - g.dur[v]), int(lst[v]))
    return lst


def makespan(g: Graph, start: np.ndarray) -> int:
    return int((start + g.dur).max())


def work_timeline(g: Graph, T: int, start: np.ndarray) -> np.ndarray:
    deltas = np.zeros(T + 1, dtype=np.int64)
    np.add.at(deltas, np.clip(start, 0, T), g.work)
    np.add.at(deltas, np.clip(start + g.dur, 0, T), -g.work)
    return np.cumsum(deltas[:-1])


def unit_budget(g: Graph, profile: Profile) -> np.ndarray:
    """Green budget per time unit left for work after the idle draw."""
    return np.repeat(profile.budget - g.idle_total, np.diff(profile.bounds))


def cost(g: Graph, profile: Profile, start: np.ndarray) -> int:
    over = work_timeline(g, profile.T, start) - unit_budget(g, profile)
    return int(np.maximum(over, 0).sum())


def feasible(g: Graph, T: int, start: np.ndarray) -> bool:
    """Precedence on G_c and the deadline."""
    start = np.asarray(start, dtype=np.int64)
    end = start + g.dur
    return bool((start >= 0).all() and (end <= T).all()
                and (start[g.ev] >= end[g.eu]).all())


def task_order(g: Graph, est, lst, score: str, weighted: bool,
               dtype=np.float64):
    """Most urgent first: slack ascending, or pressure dur / (slack + dur)
    descending, each weighted by the processor's power share when asked;
    ties by task id."""
    slack = (lst - est).astype(dtype)
    weight, dur = g.weight.astype(dtype), g.dur.astype(dtype)
    if score == "slack":
        key = slack / weight if weighted else slack
    else:
        val = dur / (slack + dur)
        key = -(val * weight if weighted else val)
    return np.lexsort((np.arange(g.N), key))


def candidate_mask(g: Graph, profile: Profile, refined: bool,
                   k: int) -> np.ndarray:
    """Candidate start points over [0, T]: the interval bounds, and with
    ``refined`` the starts that align a block of up to ``k`` consecutive
    tasks of one processor to begin or end at a bound (§5.2)."""
    T = profile.T
    mask = np.zeros(T + 1, dtype=bool)
    mask[np.clip(profile.bounds, 0, T)] = True
    if not refined:
        return mask
    bounds = profile.bounds.astype(np.int64)
    by_proc: dict[int, list[int]] = {}
    for v in g.visit:
        by_proc.setdefault(int(g.proc[v]), []).append(v)
    for chain in by_proc.values():
        pref = np.concatenate([[0], np.cumsum(g.dur[chain])])
        m = len(chain)
        for size in range(1, min(k, m) + 1):
            i = np.arange(m - size + 1)[:, None]
            j = np.arange(size)[None, :]
            off = pref[i + j] - pref[i]
            length = pref[i + size] - pref[i]
            pts = np.concatenate([
                (bounds[None, None, :] + off[:, :, None]).ravel(),
                (bounds[None, None, :] - (length - off)[:, :, None]).ravel()])
            mask[pts[(pts >= 0) & (pts <= T)]] = True
    return mask


def greedy(g: Graph, profile: Profile, est0, lst0, order,
           mask: np.ndarray) -> np.ndarray:
    """Each task in turn starts at the candidate point of its window with
    the most remaining budget (earliest on ties); its start and end become
    candidate points; EST/LST of the others follow."""
    T = profile.T
    est, lst = est0.tolist(), lst0.tolist()
    dur, work = g.dur.tolist(), g.work.tolist()
    succs = [s.tolist() for s in g.succs]
    preds = [p.tolist() for p in g.preds]
    mask = mask.copy()
    rem = unit_budget(g, profile).astype(np.int64)
    start = np.zeros(g.N, dtype=np.int64)
    placed = [False] * g.N
    for v in order.tolist():
        a, b = est[v], lst[v]
        cand = np.flatnonzero(mask[a:b + 1]) + a
        s = int(cand[np.argmax(rem[cand])]) if len(cand) else a
        e = s + dur[v]
        start[v] = s
        placed[v] = True
        rem[s:e] -= work[v]
        mask[s] = True
        if e <= T:
            mask[e] = True
        est[v] = max(est[v], s)
        todo = [v]
        while todo:
            u = todo.pop()
            ready = est[u] + dur[u]
            for t in succs[u]:
                if ready > est[t]:
                    est[t] = ready
                    if not placed[t]:
                        todo.append(t)
        lst[v] = min(lst[v], s)
        todo = [v]
        while todo:
            u = todo.pop()
            for p in preds[u]:
                bound = lst[u] - dur[p]
                if bound < lst[p]:
                    lst[p] = bound
                    if not placed[p]:
                        todo.append(p)
    return start


# --- local search ------------------------------------------------------

def legal_window(g: Graph, T: int, start: np.ndarray):
    """Per task, the start range the current neighbours and T allow."""
    lo = np.zeros(g.N, dtype=np.int64)
    np.maximum.at(lo, g.ev, start[g.eu] + g.dur[g.eu])
    hi = np.full(g.N, T, dtype=np.int64)
    np.minimum.at(hi, g.eu, start[g.ev])
    return lo, hi - g.dur


def shift_gains(rem: np.ndarray, start, dur, work, lo_rel, hi_rel, mu: int,
                dtype=None) -> np.ndarray:
    """[N, 2mu+1] exact cost decrease of shifting each task by -mu..mu
    against the timeline ``rem`` (green budget left, the task included);
    ``NEG`` where the shift is illegal. Only the symmetric difference of
    the old and new run counts: units it leaves release up to its work of
    deficit, units it enters add the part of its work the budget left
    there does not cover.

    With ``dtype`` (the control) every value and every sum is rounded to
    that precision, as a kernel computing in it would."""
    N = len(start)
    T = len(rem)
    if dtype is None:
        def rnd(x):
            return x
    else:
        def rnd(x):
            return x.astype(dtype).astype(np.float32)
    pad = np.zeros(T + 4 * mu + 2, dtype=np.int64)
    pad[2 * mu:2 * mu + T] = rem
    j = np.arange(-mu, mu)[None, :]
    w = rnd(work[:, None])
    a = rnd(pad[np.clip(start[:, None] + j + 2 * mu, 0, len(pad) - 1)])
    b = rnd(pad[np.clip((start + dur)[:, None] + j + 2 * mu, 0,
                        len(pad) - 1)])

    def released(x):
        return np.minimum(np.maximum(-x, 0), w)

    def incurred(x):
        return np.minimum(np.maximum(w - np.maximum(x, 0), 0), w)

    def prefix(x):                          # [N, 2mu] -> [N, 2mu+1]
        out = np.zeros((N, x.shape[1] + 1), dtype=x.dtype)
        for i in range(x.shape[1]):
            out[:, i + 1] = rnd(out[:, i] + x[:, i])
        return out

    ra, ia = prefix(released(a)), prefix(incurred(a))
    rb, ib = prefix(released(b)), prefix(incurred(b))
    rows = np.arange(N)[:, None]
    d = np.arange(1, mu + 1)[None, :]
    ln = np.minimum(d, dur[:, None])
    # right by d: leaves [s, s+ln), enters [e+d-ln, e+d)
    right = rnd(rnd(ra[rows, mu + ln] - ra[:, mu:mu + 1])
                - rnd(ib[rows, mu + d] - ib[rows, mu + d - ln]))
    # left by d: leaves [e-ln, e), enters [s-d, s-d+ln)
    left = rnd(rnd(rb[:, mu:mu + 1] - rb[rows, mu - ln])
               - rnd(ia[rows, mu - d + ln] - ia[rows, mu - d]))
    gains = np.concatenate([left[:, ::-1], np.zeros((N, 1), right.dtype),
                            right], axis=1)
    delta = np.arange(-mu, mu + 1)[None, :]
    legal = ((delta >= lo_rel[:, None]) & (delta <= hi_rel[:, None])
             & (delta != 0) & (work[:, None] > 0))
    return np.where(legal, gains, NEG)


def move_gain(rem: np.ndarray, s: int, e: int, new_s: int, w: int) -> int:
    d = new_s - s
    ln = min(abs(d), e - s)
    if d > 0:
        vac, occ = rem[s:s + ln], rem[new_s + (e - s) - ln:new_s + (e - s)]
    else:
        vac, occ = rem[e - ln:e], rem[new_s:new_s + ln]
    released = np.minimum(np.maximum(-vac, 0), w).sum()
    incurred = np.minimum(np.maximum(w - np.maximum(occ, 0), 0), w).sum()
    return int(released - incurred)


def apply_move(rem: np.ndarray, s: int, e: int, new_s: int, w: int) -> None:
    rem[s:e] += w
    rem[new_s:new_s + (e - s)] -= w


def climb(g: Graph, T: int, budget: np.ndarray, start: np.ndarray, mu: int,
          commit_k: int, max_rounds: int, dtype=None) -> np.ndarray:
    """The batched climb of one schedule (see the module docstring)."""
    start = start.copy()
    rem = budget - work_timeline(g, T, start)
    for _ in range(max_rounds):
        lo, hi = legal_window(g, T, start)
        gains = shift_gains(rem, start, g.dur, g.work, lo - start,
                            hi - start, mu, dtype)
        best_delta = np.argmax(gains, axis=1) - mu
        best_gain = gains.max(axis=1)
        committed = False
        for v in np.argsort(-best_gain, kind="stable")[:commit_k]:
            if best_gain[v] <= 0:
                break
            s = int(start[v])
            dv = int(g.dur[v])
            ps, ss = g.preds[v], g.succs[v]
            dlo = int((start[ps] + g.dur[ps]).max()) if len(ps) else 0
            dhi = (int(start[ss].min()) if len(ss) else T) - dv
            new_s = min(max(s + int(best_delta[v]), dlo), dhi)
            if dlo > dhi or new_s == s:
                continue
            w = int(g.work[v])
            if move_gain(rem, s, s + dv, new_s, w) <= 0:
                continue
            apply_move(rem, s, s + dv, new_s, w)
            start[v] = new_s
            committed = True
        if not committed:
            break
    return start


def polish(g: Graph, T: int, budget: np.ndarray, start: np.ndarray, mu: int,
           max_rounds: int) -> np.ndarray:
    """Sequential first-improvement rounds (see the module docstring).

    A round visits every task once against the timeline as it then is.
    Between two commits nothing changes, so one vectorized scoring finds
    the next task (in visit order) that has an improving shift."""
    start = start.copy()
    rem = budget - work_timeline(g, T, start)
    visit = np.asarray(g.visit, dtype=np.int64)
    for _ in range(max_rounds):
        pos, committed = 0, False
        while pos < len(visit):
            lo, hi = legal_window(g, T, start)
            gains = shift_gains(rem, start, g.dur, g.work,
                                np.maximum(lo - start, -mu),
                                np.minimum(hi - start, mu), mu)
            improving = gains[visit[pos:]] > 0
            rows = np.flatnonzero(improving.any(axis=1))
            if not len(rows):
                break
            v = int(visit[pos + rows[0]])
            new_s = int(start[v]) + int(np.argmax(improving[rows[0]])) - mu
            s, dv = int(start[v]), int(g.dur[v])
            apply_move(rem, s, s + dv, new_s, int(g.work[v]))
            start[v] = new_s
            committed = True
            pos += int(rows[0]) + 1
        if not committed:
            break
    return start


def variant_parts(name: str):
    """``pressWR-LS`` -> ("press", weighted, refined, ls)."""
    base, ls = (name[:-3], True) if name.endswith("-LS") else (name, False)
    score = next(s for s in SCORES if base.startswith(s))
    rest = base[len(score):]
    return score, "W" in rest, "R" in rest, ls


def portfolio(g: Graph, profiles, names, *, k: int, mu: int,
              commit_k: int, max_rounds: int, dtype=None,
              score_dtype=np.float64) -> dict:
    """``{(profile index, variant): start}`` for one instance; ``dtype``
    and ``score_dtype`` set the precision of the climb's gains and of the
    task scores (lower ones for the control only)."""
    T = profiles[0].T
    est0 = earliest_starts(g)
    lst0 = latest_starts(g, T)
    if (est0 > lst0).any():
        raise ValueError("infeasible: deadline below the ASAP makespan")
    orders, out = {}, {}
    for p, prof in enumerate(profiles):
        budget = unit_budget(g, prof)
        masks, greedy_starts = {}, {}
        for name in names:
            if name == "asap":
                out[p, name] = est0.copy()
                continue
            score, weighted, refined, ls = variant_parts(name)
            key = (score, weighted, refined)
            if key not in greedy_starts:
                if (score, weighted) not in orders:
                    orders[score, weighted] = task_order(
                        g, est0, lst0, score, weighted, score_dtype)
                if refined not in masks:
                    masks[refined] = candidate_mask(g, prof, refined, k)
                greedy_starts[key] = greedy(g, prof, est0, lst0,
                                            orders[score, weighted],
                                            masks[refined])
            start = greedy_starts[key]
            if ls:
                start = polish(g, T, budget,
                               climb(g, T, budget, start, mu, commit_k,
                                     max_rounds, dtype), mu, max_rounds)
            out[p, name] = start
    return out
