"""Decide ``correct``: what the timed path served against the reference.

Every number compared is a count with the limit 0: the portfolio is
deterministic integer arithmetic, so the served schedules and costs
must equal the reference's exactly.

* ``rows_differing``: (profile, variant) schedules of the sampled
  answers whose start times differ from the reference's, every row of
  an answer whose planner instance is not the reference's G_c task for
  task. This reaches the device climb itself: the polish after it is
  deterministic, so a climb that moved differently ends elsewhere.
* ``costs_differing``: cost-tensor entries of the sampled answers that
  differ from the reference's cost of its own schedule.
* ``requests_missing``: requests due in the window never answered.
* ``requests_failed``: requests answered but not served as asked:
  refused (``Overloaded``), erring, degraded to a lower rung, with
  attempts other than ``("heuristic:ok",)``, or served by another
  engine. The reference is not asked about these: a refusal or a lower
  rung is a different result, not a faster one.
"""
from __future__ import annotations

import ml_dtypes
import numpy as np

from harness import program, reference
from harness.traffic import derive

LIMITS = {"rows_differing": 0, "costs_differing": 0, "requests_missing": 0,
          "requests_failed": 0}


def sample(records, count: int, seed: int) -> list:
    """``count`` served answers drawn from the seed."""
    served = [r for r in records if r.ok]
    rng = np.random.default_rng(derive(seed, 3))
    pick = rng.choice(len(served), size=min(count, len(served)),
                      replace=False)
    return [served[i] for i in sorted(pick)]


def reference_rows(config: dict, graph, profiles, names,
                   control: bool = False):
    """``{(profile, variant): (start, cost)}`` of the reference; with
    ``control``, computed one precision lower: the climb's gains in
    bfloat16 and the task scores in float32."""
    pc = config["planner"]
    lower = dict(dtype=ml_dtypes.bfloat16, score_dtype=np.float32) \
        if control else {}
    starts = reference.portfolio(graph, profiles, names, k=pc["k"],
                                 mu=pc["mu"], commit_k=pc["commit_k"],
                                 max_rounds=pc["ls_max_rounds"], **lower)
    return {key: (s, reference.cost(graph, profiles[key[0]], s))
            for key, s in starts.items()}


def differing(got: dict, want: dict) -> tuple[int, int]:
    """(rows whose starts differ, rows whose costs differ)."""
    rows = costs = 0
    for key, (start, c) in want.items():
        g_start, g_cost = got.get(key, (None, None))
        rows += g_start is None or not np.array_equal(g_start, start)
        costs += g_cost != c
    return rows, costs


def served(entry, rec) -> dict:
    """The rows the timed path produced for one sampled request."""
    return program.rows(rec.result)


def compare(config: dict, traffic: dict, pool, records, seed: int,
            served_rows=None) -> dict:
    """The counts compared, over the answers sampled from ``seed``;
    ``served_rows(entry, record)`` stands in for what was served."""
    served_rows = served_rows or served
    counts = dict.fromkeys(LIMITS, 0)
    counts["requests_missing"] = sum(r.done is None for r in records)
    counts["requests_failed"] = sum(r.done is not None and not r.ok
                                    for r in records)
    for rec in sample(records, traffic["check_requests"], seed):
        entry = pool[rec.request.entry]
        want = reference_rows(config, entry.graph, rec.request.profiles,
                              traffic["variants"])
        rows, costs = differing(served_rows(entry, rec), want)
        if not program.same_graph(entry.instance, entry.graph):
            print(f"request {rec.request.index}: the planner's instance of "
                  f"{entry.name} is not the reference's", flush=True)
            rows = len(want)
        counts["rows_differing"] += rows
        counts["costs_differing"] += costs
    return counts
