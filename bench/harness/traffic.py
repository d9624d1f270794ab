"""One general generator: the instance pool, the request stream and the
two load shapes (closed loop, open loop), all read from a traffic file.

Traffic keys:
  loop            "closed" (``clients`` callers, each waits for its answer)
                  or "open" (``rate_per_s`` arrivals, sent when due)
  workflow_seeds  pool = configuration families x these workflow seeds
  draw            "round_robin" over the pool, or "zipf" with ``zipf_s``
  profile_seeds   forecast members per scenario of the configuration
  variants        the variant names every request asks for
  check_requests  answers compared with the reference after the window
  pattern_seed    open loop: the arrival times and the tenant drawn for
                  each, the same for every ``--seed`` (which varies the
                  forecasts), so every run offers the same load; with
                  ``forecast_pool``, the pooled ensembles
  forecast_pool   ensembles per tenant, drawn from ``pattern_seed`` and
                  so the same in every run; ``--seed`` shuffles the order
                  in which each tenant's are served. Without it every
                  request draws a fresh ensemble from ``--seed``
  warm_batches    coalesced batch sizes to compile before the window
"""
from __future__ import annotations

import dataclasses
import itertools
import math
import threading
import time

import numpy as np

from harness import generate, program, reference


def derive(seed: int, *path: int) -> int:
    """A 63-bit seed for ``path`` under the run's ``--seed``."""
    ss = np.random.SeedSequence([seed % 2**64, *path])
    return int(ss.generate_state(1, dtype=np.uint64)[0] >> np.uint64(1))


@dataclasses.dataclass
class Entry:
    """One tenant workflow of the pool, mapped and ready to serve."""

    name: str
    graph: reference.Graph
    T: int
    capacity: int
    instance: object          # the planner's Instance
    bucket: tuple


def build_pool(config: dict, traffic: dict, cluster, plat) -> list[Entry]:
    pool = []
    for ws in traffic["workflow_seeds"]:
        for family in config["families"]:
            wf = generate.wfgen_scale(family, config["target_tasks"], ws)
            mapping = generate.heft_mapping(wf, cluster)
            g = reference.build_graph(wf, mapping, cluster)
            asap = reference.makespan(g, reference.earliest_starts(g))
            capacity = int(reference.work_timeline(
                g, asap, reference.earliest_starts(g)).mean())
            T = int(math.ceil(config["deadline_factor"] * asap))
            inst = program.instance(wf, mapping, plat)
            pool.append(Entry(wf.name, g, T, capacity, inst,
                              program.bucket(g.N, T)))
    return pool


def ensemble(config: dict, traffic: dict, entry: Entry, idle_total: int,
             seed: int, *path: int) -> list:
    """The forecast ensemble of one request: every scenario of the
    configuration x ``profile_seeds`` members."""
    return [generate.generate_profile(
                sc, entry.T, idle_total, entry.capacity, config["intervals"],
                seed=derive(seed, *path, m, si))
            for m in range(traffic["profile_seeds"])
            for si, sc in enumerate(config["scenarios"])]


def forecast_draws(traffic: dict, seed: int, order: list[int]):
    """``i -> (seed, path)`` from which request ``i``'s ensemble is drawn.

    The forecasts set how long the climb runs, so fresh draws per run
    make the work itself differ from seed to seed; a pool makes every
    seed offer the same ensembles, in another order."""
    if "forecast_pool" not in traffic:
        return lambda i: (seed, (0, i))
    size, visits, seen = traffic["forecast_pool"], [], {}
    for entry in order:
        visits.append(seen.get(entry, 0))
        seen[entry] = visits[-1] + 1

    def draw(i: int):
        cycle, slot = divmod(visits[i], size)
        rng = np.random.default_rng(derive(seed, 5, order[i], cycle))
        return traffic["pattern_seed"], (7, order[i],
                                         int(rng.permutation(size)[slot]))
    return draw


@dataclasses.dataclass
class Request:
    index: int
    entry: int                 # pool index
    profiles: list             # reference profiles
    due: float = 0.0           # seconds after the window opens (open loop)


def pool_order(traffic: dict, n_pool: int, count: int) -> list[int]:
    """Pool indices of the first ``count`` requests."""
    if traffic["draw"] == "round_robin":
        return [i % n_pool for i in range(count)]
    rng = np.random.default_rng(traffic["pattern_seed"])
    rank = rng.permutation(n_pool)            # which tenants are hot
    weights = 1.0 / (np.arange(1, n_pool + 1) ** traffic["zipf_s"])
    return [int(x) for x in rank[rng.choice(n_pool, size=count,
                                            p=weights / weights.sum())]]


def arrivals(traffic: dict, seconds: float) -> np.ndarray:
    """Open-loop due times in [0, seconds): Poisson arrivals at the
    traffic's rate, drawn from its ``pattern_seed`` and scaled so the
    window holds exactly ``rate * seconds`` of them."""
    n = max(int(round(traffic["rate_per_s"] * seconds)), 1)
    gaps = np.random.default_rng(traffic["pattern_seed"] + 1).exponential(
        1.0, size=n)
    return seconds * np.concatenate([[0.0], np.cumsum(gaps)[:-1]]) \
        / gaps.sum()


@dataclasses.dataclass
class Record:
    request: Request
    due: float                 # host clock (perf_counter)
    sent: float = math.nan
    done: float | None = None
    ok: bool = False
    cells: int = 0
    result: object = None
    why: str = ""


GRACE_S = 60.0      # how long past its due time an answer is waited for


def serve_one(service, plan_request, rec: Record, engine: str) -> None:
    """Submit, wait for the answer, and judge whether it was served."""
    rec.sent = time.perf_counter()
    try:
        res = service.submit(plan_request).result(
            max(rec.due + GRACE_S - time.perf_counter(), 0.0))
    except TimeoutError:
        rec.why = "no answer within the grace period"
        return
    except program.service_errors() as e:
        rec.done = time.perf_counter()
        rec.why = f"{type(e).__name__}: {e}"
        return
    rec.done = time.perf_counter()
    rec.result = res
    rec.why = program.served_ok(res, engine) or ""
    rec.ok = not rec.why
    rec.cells = len(res.results) * len(res.results[0]) if rec.ok else 0


def closed_loop(service, make_request, clients: int, seconds: float,
                engine: str) -> list[Record]:
    """``clients`` callers each send, wait, and send again, until
    ``seconds`` after the first send; requests started by then finish."""
    records: list[Record] = []
    lock = threading.Lock()
    counter = itertools.count()
    stop_at = time.perf_counter() + seconds

    def client():
        while time.perf_counter() < stop_at:
            req, plan_request = make_request(next(counter))
            rec = Record(req, due=time.perf_counter())
            serve_one(service, plan_request, rec, engine)
            with lock:
                records.append(rec)

    threads = [threading.Thread(target=client, name=f"bench-client-{c}")
               for c in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return sorted(records, key=lambda r: r.request.index)


def open_loop(service, requests, plan_requests,
              engine: str) -> tuple[list[Record], list[float]]:
    """Send each request at its due time, whatever is in flight; wait for
    every answer up to ``GRACE_S`` past its due time. Returns the records
    and how late each send was."""
    t0 = time.perf_counter()
    records = [Record(r, due=t0 + r.due) for r in requests]
    waiters, late = [], []
    for rec, plan_request in zip(records, plan_requests):
        pause = rec.due - time.perf_counter()
        if pause > 0:
            time.sleep(pause)
        late.append(time.perf_counter() - rec.due)
        t = threading.Thread(target=serve_one,
                             args=(service, plan_request, rec, engine),
                             name=f"bench-request-{rec.request.index}")
        t.start()
        waiters.append(t)
    for t in waiters:
        t.join()
    return records, late
