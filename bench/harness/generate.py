"""Deployment data from a seed: cluster, workflows, HEFT mapping, forecasts.

A frozen copy of the paper's §6.1 set-up as the planner's own generators
built it when the benchmark was written (arXiv:2507.08725: nf-core-style
workflow motifs scaled WFGen-style, the Table 1 processor types with
U{1,2} link power, HEFT as the fixed mapping, scenarios S1-S4 over J
green intervals). Plain numpy, no import of the planner.
"""
from __future__ import annotations

import dataclasses

import numpy as np

# Table 1 of the paper: (name, speed, P_idle, P_work)
PROCESSOR_TABLE = (
    ("PT1", 4, 40, 10),
    ("PT2", 6, 60, 30),
    ("PT3", 8, 80, 40),
    ("PT4", 12, 120, 50),
    ("PT5", 16, 150, 70),
    ("PT6", 32, 200, 100),
)


@dataclasses.dataclass(frozen=True)
class Cluster:
    """Compute processors 0..P-1, then one link processor per directed
    pair: link (a, b) is ``P + a*(P-1) + (b if b < a else b-1)``."""

    speed: np.ndarray        # [P]
    p_idle: np.ndarray       # [P*P]
    p_work: np.ndarray       # [P*P]
    type_of: np.ndarray      # [P]

    @property
    def num_compute(self) -> int:
        return len(self.speed)

    @property
    def idle_total(self) -> int:
        return int(self.p_idle.sum())

    def link_id(self, a: int, b: int) -> int:
        P = self.num_compute
        return P + a * (P - 1) + (b if b < a else b - 1)


def make_cluster(nodes_per_type: int, seed: int = 0) -> Cluster:
    """``nodes_per_type`` nodes of each Table 1 type; links draw
    P_idle, P_work ~ U{1, 2}."""
    rng = np.random.default_rng(seed)
    P = nodes_per_type * len(PROCESSOR_TABLE)
    speed = np.empty(P, dtype=np.int64)
    type_of = np.empty(P, dtype=np.int64)
    p_idle = np.zeros(P * P, dtype=np.int64)
    p_work = np.zeros(P * P, dtype=np.int64)
    for t, (_, sp, pi, pw) in enumerate(PROCESSOR_TABLE):
        sl = slice(t * nodes_per_type, (t + 1) * nodes_per_type)
        speed[sl] = sp
        type_of[sl] = t
        p_idle[sl] = pi
        p_work[sl] = pw
    n_links = P * P - P
    p_idle[P:] = rng.integers(1, 3, size=n_links)
    p_work[P:] = rng.integers(1, 3, size=n_links)
    return Cluster(speed=speed, p_idle=p_idle, p_work=p_work, type_of=type_of)


@dataclasses.dataclass(frozen=True)
class Workflow:
    name: str
    node_w: np.ndarray          # [n] computation weight
    edges: np.ndarray           # [m, 2] (u, v), u -> v
    edge_w: np.ndarray          # [m] communication weight

    @property
    def n(self) -> int:
        return len(self.node_w)


def topological_order(n: int, edges) -> list[int]:
    """Kahn's algorithm, FIFO."""
    indeg = np.zeros(n, dtype=np.int64)
    succs: list[list[int]] = [[] for _ in range(n)]
    for u, v in edges:
        succs[int(u)].append(int(v))
        indeg[int(v)] += 1
    queue = [int(i) for i in np.flatnonzero(indeg == 0)]
    head = 0
    while head < len(queue):
        u = queue[head]
        head += 1
        for v in succs[u]:
            indeg[v] -= 1
            if indeg[v] == 0:
                queue.append(v)
    return queue


# A motif is a list of stages: ("chain", k) per-sample chain of k tools;
# ("fan", w, k) per-sample fan-out to w chains of k tools, then a fan-in;
# ("merge", g) barrier over groups of g samples; ("final", k) one
# aggregation chain of k tools over everything.
MOTIFS = {
    "atacseq": [("chain", 3), ("fan", 3, 2), ("chain", 2), ("merge", 4),
                ("final", 4)],
    "bacass": [("chain", 4), ("fan", 2, 2), ("chain", 2), ("final", 3)],
    "eager": [("chain", 5), ("fan", 2, 3), ("chain", 3), ("merge", 3),
              ("final", 5)],
    "methylseq": [("chain", 4), ("fan", 3, 1), ("chain", 2), ("final", 3)],
}


def _tasks_per_sample(motif) -> int:
    per = 0
    for stage in motif:
        if stage[0] == "chain":
            per += stage[1]
        elif stage[0] == "fan":
            per += stage[1] * stage[2] + 1
    return per


def make_workflow(kind: str, n_samples: int, seed: int, name: str
                  ) -> Workflow:
    motif = MOTIFS[kind]
    rng = np.random.default_rng(seed)
    edges: list[tuple[int, int]] = []
    count = 0

    def new_node() -> int:
        nonlocal count
        count += 1
        return count - 1

    heads: dict[int, int | None] = {g: None for g in range(n_samples)}
    for stage in motif:
        if stage[0] == "chain":
            for g in list(heads):
                for _ in range(stage[1]):
                    node = new_node()
                    if heads[g] is not None:
                        edges.append((heads[g], node))
                    heads[g] = node
        elif stage[0] == "fan":
            _, width, k = stage
            for g in list(heads):
                tails = []
                for _ in range(width):
                    prev = heads[g]
                    for _ in range(k):
                        node = new_node()
                        if prev is not None:
                            edges.append((prev, node))
                        prev = node
                    tails.append(prev)
                join = new_node()
                for t in tails:
                    edges.append((t, join))
                heads[g] = join
        elif stage[0] == "merge":
            groups = list(heads)
            merged: dict[int, int | None] = {}
            for i in range(0, len(groups), stage[1]):
                node = new_node()
                for g in groups[i:i + stage[1]]:
                    if heads[g] is not None:
                        edges.append((heads[g], node))
                merged[len(merged)] = node
            heads = merged
        elif stage[0] == "final":
            node = new_node()
            for g in list(heads):
                if heads[g] is not None:
                    edges.append((heads[g], node))
            heads = {0: node}
            for _ in range(stage[1] - 1):
                nxt = new_node()
                edges.append((heads[0], nxt))
                heads[0] = nxt
    e = np.asarray(edges, dtype=np.int64).reshape(-1, 2)
    node_w = np.maximum(rng.normal(120.0, 35.0, size=count), 1.0)
    edge_w = np.maximum(rng.normal(14.0, 5.0, size=len(e)), 1.0)
    return Workflow(name=name, node_w=node_w.astype(np.int64), edges=e,
                    edge_w=edge_w.astype(np.int64))


def wfgen_scale(kind: str, n_target: int, seed: int) -> Workflow:
    """Replicate samples so the workflow has about ``n_target`` tasks."""
    per = max(_tasks_per_sample(MOTIFS[kind]), 1)
    return make_workflow(kind, max(1, round(n_target / per)), seed,
                         name=f"{kind}-n{n_target}-s{seed}")


@dataclasses.dataclass(frozen=True)
class Mapping:
    proc: np.ndarray                                 # [n] compute processor
    order: tuple[tuple[int, ...], ...]               # per compute processor
    comm_order: dict[int, tuple[tuple[int, int], ...]]  # per link id


def heft_mapping(wf: Workflow, cluster: Cluster) -> Mapping:
    """HEFT without special tie-breaking: upward ranks on mean execution
    time, earliest finish with insertion; links ordered by source finish."""
    n = wf.n
    P = cluster.num_compute
    exec_t = np.maximum(np.ceil(wf.node_w[:, None] / cluster.speed[None, :])
                        .astype(np.int64), 1)
    mean_exec = exec_t.mean(axis=1)
    succs: list[list[tuple[int, int]]] = [[] for _ in range(n)]
    preds: list[list[tuple[int, int]]] = [[] for _ in range(n)]
    for (u, v), cw in zip(wf.edges, wf.edge_w):
        succs[int(u)].append((int(v), int(cw)))
        preds[int(v)].append((int(u), int(cw)))
    rank = np.zeros(n, dtype=np.float64)
    for v in reversed(topological_order(n, wf.edges)):
        best = 0.0
        for s, cw in succs[v]:
            best = max(best, cw + rank[s])
        rank[v] = mean_exec[v] + best

    proc = np.full(n, -1, dtype=np.int64)
    aft = np.zeros(n, dtype=np.int64)
    ast = np.zeros(n, dtype=np.int64)
    slots: list[list[tuple[int, int]]] = [[] for _ in range(P)]
    for v in sorted(range(n), key=lambda v: (-rank[v], v)):
        best = None
        for p in range(P):
            ready = 0
            for u, cw in preds[v]:
                ready = max(ready, int(aft[u] + (cw if proc[u] != p else 0)))
            w = int(exec_t[v, p])
            t = ready
            for s0, e0 in slots[p]:
                if t + w <= s0:
                    break
                t = max(t, e0)
            if best is None or t + w < best[0]:
                best = (t + w, p, t)
        eft, p, t = best
        proc[v] = p
        ast[v] = t
        aft[v] = eft
        slots[p].append((t, eft))
        slots[p].sort()

    order = tuple(tuple(sorted((v for v in range(n) if proc[v] == p),
                               key=lambda v: (ast[v], v)))
                  for p in range(P))
    cross = sorted(((int(u), int(v)) for u, v in wf.edges
                    if proc[u] != proc[v]),
                   key=lambda e: (aft[e[0]], ast[e[1]], e))
    comm: dict[int, list[tuple[int, int]]] = {}
    for u, v in cross:
        comm.setdefault(cluster.link_id(int(proc[u]), int(proc[v])),
                        []).append((u, v))
    return Mapping(proc=proc, order=order,
                   comm_order={k: tuple(v) for k, v in comm.items()})


@dataclasses.dataclass(frozen=True)
class Profile:
    """Green budget per time unit, constant on each of J intervals."""

    bounds: np.ndarray       # [J+1], bounds[0] = 0, bounds[J] = T
    budget: np.ndarray       # [J]
    scenario: str

    @property
    def T(self) -> int:
        return int(self.bounds[-1])


def generate_profile(scenario: str, T: int, idle_total: int, capacity: int,
                     J: int, seed: int, perturb: float = 0.1) -> Profile:
    """S1 parabola, S2 the same from midday, S3 sine, S4 constant, each
    perturbed; budgets span ``[idle, idle + 0.8 * capacity]``."""
    rng = np.random.default_rng(seed)
    bounds = np.unique(np.round(np.linspace(0, T, min(J, T) + 1))
                       .astype(np.int64))
    J = len(bounds) - 1
    x = (np.arange(J) + 0.5) / J
    if scenario == "S1":
        frac = 1.0 - (2.0 * x - 1.0) ** 2
    elif scenario == "S2":
        frac = 1.0 - (2.0 * ((x + 0.5) % 1.0) - 1.0) ** 2
    elif scenario == "S3":
        frac = 0.5 * (1.0 + np.sin(2.0 * np.pi * x - 0.5 * np.pi))
    elif scenario == "S4":
        frac = np.full(J, 0.55)
    else:
        raise ValueError(f"unknown scenario {scenario!r}")
    frac = np.clip(frac + rng.normal(0.0, perturb, size=J), 0.0, 1.0)
    budget = (idle_total + np.round(frac * 0.8 * int(capacity))
              ).astype(np.int64)
    return Profile(bounds=bounds, budget=budget, scenario=scenario)
