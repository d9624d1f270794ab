"""Fig 8 (+ appendix 12/13): scheduler runtime vs workflow size and
deadline factor; also the runtime of the device climb on one row."""
from __future__ import annotations

import time

import numpy as np

from benchmarks.common import build_matrix, emit, run_all_variants, write_csv
from repro.core.local_search_jax import local_search_portfolio_multi
from repro.core.greedy import greedy_schedule


def run(sizes=(200, 1000, 4000), clusters=("small",)):
    rows = []
    t_all = {}
    t0 = time.perf_counter()
    n = 0
    for case in build_matrix(sizes=sizes, clusters=clusters,
                             factors=(1.5,), scenarios=("S1",)):
        res = run_all_variants(case)
        for v, (c, sec) in res.items():
            rows.append([case.name, case.inst.num_tasks, v, f"{sec:.4f}"])
            t_all.setdefault(v, []).append((case.inst.num_tasks, sec))
        n += 1
    # deadline sensitivity (paper: runtime driven by graph size, not T)
    for f in (1.0, 2.0, 3.0):
        for case in build_matrix(sizes=(1000,), clusters=clusters,
                                 factors=(f,), scenarios=("S1",),
                                 kinds=("atacseq",)):
            res = run_all_variants(case, variants=("pressWR-LS",))
            rows.append([f"deadline-{f}", case.inst.num_tasks, "pressWR-LS",
                         f"{res['pressWR-LS'][1]:.4f}"])
    # the served device climb (one row) on the largest instance
    big = next(build_matrix(sizes=(sizes[-1],), clusters=clusters,
                            factors=(1.5,), scenarios=("S1",),
                            kinds=("atacseq",)))
    g = greedy_schedule(big.inst, big.profile, big.platform, score="press",
                        weighted=True, refined=True)
    t1 = time.perf_counter()
    local_search_portfolio_multi(
        big.inst, big.profile.T,
        big.profile.unit_budget(big.inst.idle_total)[None], g[None], mu=10)
    t_dev = time.perf_counter() - t1
    rows.append(["batchedLS-" + big.name, big.inst.num_tasks,
                 "kernelLS", f"{t_dev:.4f}"])
    dt = time.perf_counter() - t0
    write_csv("fig8_runtime.csv", ["case", "n_tasks", "variant", "seconds"],
              rows)
    worst = max(sec for v, xs in t_all.items() for _, sec in xs)
    emit("fig8_runtime", dt / max(n, 1) * 1e6,
         f"max_variant_seconds={worst:.2f};kernelLS_s={t_dev:.2f}")
    return rows


if __name__ == "__main__":
    run()
