"""Portfolio engine benchmark: seed-style per-variant loop vs one-pass
``schedule_portfolio`` vs the device fan-out, plus the multi-profile
replanning engine, machine-readable.

Emits ``benchmarks/out/BENCH_portfolio.json``:
  * ``loop_us_per_instance`` / ``portfolio_us_per_instance`` — live
    measurements of the per-variant ``schedule()`` loop and the portfolio
    engine on the same instances (identical results, tested);
  * ``jax_fanout_us_per_instance`` — the device engine (``engine="jax"``)
    in its replanning regime (steady-state: executables cached per shape
    bucket); ``jax_fanout_cold_us_per_instance`` includes the one-off
    bucket compiles; ``jax_fanout_us_per_instance_before`` is the recorded
    pre-fix number (per-shape retracing, level-relax scan core,
    interpreter-mode gain kernel);
  * ``multi_profile`` — ``schedule_portfolio_multi`` over an ensemble of
    perturbed profiles vs looping ``schedule_portfolio`` per profile;
  * ``planner`` — the Planner facade's overhead over the grid engine it
    wraps (request normalization + cache lookup + result assembly), the
    legacy-shim path for reference, and the combined instance x profile
    fan-out: cells, shape buckets, and the grid jit cache-miss counts
    proving one device launch per bucket (cold) and zero retracing
    (steady);
  * ``gaps`` — the solver-quality table: heuristics vs the exact oracle
    (``solver="exact"``: DP on a uniprocessor chain, ILP on a tiny
    multiprocessor DAG) on small instances, so the perf trajectory also
    tracks solution quality (a speedup that silently costs optimality
    shows up here);
  * ``service`` — serving-tier telemetry: a coalesced burst, forced
    degradations, and structured rejections through ``PlanService``,
    reported as queue depth, coalesce ratio, p50/p99 plan latency, and
    degradation counts; plus worker-pool scaling (the same burst through
    1 vs 4 drain workers) and the cooperative-cancellation latency (time
    for the pool to go idle after ``Ticket.cancel`` lands on a wedged
    solve);
  * ``obs`` — observability overhead, measured: disabled-tracer hot-path
    cost per ``obs.span`` call, spans-per-plan and the enabled-tracer
    wall clock on the same steady-state fan-out, the
    ``disabled_tracer_overhead_frac`` acceptance number (asserted < 2%),
    and the jax hook snapshot (compile events, jit cache entries);
  * ``mapping`` — joint mapping x scheduling vs schedule-only: per
    motif family, the best cost under the fixed HEFT mapping vs the
    candidate-mapping search on a scarce profile, the saving fraction,
    and candidate throughput (acceptance: search strictly wins on >= 3
    of the 4 families);
  * ``sharded`` — multi-device scaling data (:mod:`benchmarks
    .fig_sharded`): the grid launch timed per forced-host-device count
    in a subprocess (bitwise-verified against single-device) and the
    tiled Pallas gain kernel vs its jnp twin (measured honestly: on
    this CPU box the virtual devices share one core and the kernel runs
    interpreted, so ``speedup_vs_1`` ~ 1 and ``crossover_n`` is null —
    the section records real numbers, not extrapolations);
  * ``seed_reference`` — the recorded wall clock of
    ``run.py --only rank,runtime`` at the seed commit vs this one (the
    acceptance trajectory; update SEED_REFERENCE when re-measuring on new
    hardware — run that matrix at the seed commit in a scratch worktree).
"""
from __future__ import annotations

import json
import os
import time

import numpy as np

from benchmarks.common import (
    OUT_DIR,
    build_matrix,
    emit,
    run_all_variants,
    run_variant_loop,
)
from repro.core import generate_profile, schedule_portfolio, \
    schedule_portfolio_multi

# wall clock of `run.py --only rank,runtime` (scaled-down matrix, this
# container), measured at the seed commit and after PR1's engine landed.
SEED_REFERENCE = {
    "matrix": "run.py --only rank,runtime (sizes=(200,)/(200,1000))",
    "seed_commit_seconds": 237.7,     # measured at seed commit, 1-CPU box
    "this_commit_seconds": 46.8,      # same box, portfolio engine (5.1x)
}

# `engine="jax"` per instance before the fan-out fix (per-shape retracing
# of the nested level-relax scan + interpreter-mode gain kernel), recorded
# by this benchmark at the PR1 commit — ON THE REFERENCE MATRIX below.
# A --smoke run measures a different matrix, so the recorded baselines are
# withheld there (comparing live tiny-matrix numbers against recorded
# 200-size baselines would fabricate the speedup).
JAX_FANOUT_BEFORE_US = 2733936.2
REFERENCE_MATRIX = {"sizes": [200], "clusters": ["small"], "n_cases": 6}


def _gap_cases():
    """Tiny instances for the solver-quality table: one uniprocessor
    chain (``solver="exact"`` -> the §4.1 polynomial DP) and one
    multiprocessor DAG (-> the time-indexed ILP), both with budgets tight
    enough that scheduling decisions carry nonzero cost. Short durations
    keep the ILP's time-indexed model small (seconds, smoke-safe)."""
    from repro.cluster import make_cluster
    from repro.core import build_instance, deadline_from_asap
    from repro.core.carbon import PowerProfile
    from repro.core.dag import trivial_mapping
    from repro.workflows import layered_random

    plat = make_cluster(1, seed=0)
    out = []
    for name, by, seed in (("uniproc-chain", "single", 7),
                           ("multiproc-dag", "round_robin", 0)):
        rng = np.random.default_rng(seed)
        wf = layered_random(6, 3, seed=seed)
        dur = rng.integers(1, 6, size=wf.n)
        inst = build_instance(wf, trivial_mapping(wf, plat, by=by), plat,
                              dur=dur)
        T = deadline_from_asap(inst, 1.5)
        bounds = np.unique(np.round(np.linspace(0, T, 5)).astype(np.int64))
        budget = plat.idle_total + rng.integers(
            0, max(int(inst.task_work.max()) // 2, 2),
            size=len(bounds) - 1)
        out.append((name, plat, inst,
                    PowerProfile(bounds=bounds, budget=budget)))
    return out


def _gap_table(gap_time_limit: float) -> dict:
    """heuristics-vs-baseline-vs-exact on the tiny matrix, per case."""
    from repro.api import Planner, PlanRequest

    gaps = {"time_limit": gap_time_limit, "cases": []}
    for name, plat, inst, prof in _gap_cases():
        planner = Planner(plat, engine="numpy")
        req = dict(instances=inst, profiles=prof)
        exact = planner.plan(PlanRequest(
            **req, solver="exact",
            solver_options={"time_limit": gap_time_limit}))
        heur = planner.plan(PlanRequest(**req))
        base = planner.plan(PlanRequest(**req, solver="asap"))
        opt = int(exact.costs[0, 0, 0])
        lb = int(exact.lower_bound[0, 0])

        def ratio(c: int):
            return (c / opt) if opt > 0 else (1.0 if c == 0 else None)

        gaps["cases"].append({
            "case": name,
            "n_tasks": int(inst.num_tasks),
            "T": int(prof.T),
            "solver": exact.solver,
            "optimal": opt,
            "lower_bound": lb,
            "proven": lb == opt,
            "best_heuristic": int(heur.best_costs()[0, 0]),
            "gap_best": float(heur.gap(exact)[0, 0]),
            "gap_asap": ratio(int(base.costs[0, 0, 0])),
            "per_variant": {
                v: ratio(int(heur.costs[0, 0, i]))
                for i, v in enumerate(heur.variants)},
        })
    return gaps


def _lp_blocked_section(cases) -> dict:
    """Dense-vs-blocked longest-path engines on one small instance, plus
    an over-envelope instance only the blocked form can serve (its dense
    matrix raises MemoryError under the same lp budget), plus the
    steady-state jit cache-miss guarantee: the blocked path retraces
    nothing and adds no misses to the dense grid executable either."""
    from repro.cluster import make_cluster
    from repro.core import build_instance, deadline_from_asap, heft_mapping
    from repro.core.greedy_jax import (
        BlockedLP,
        _blocked_impl,
        _impl,
        longest_path_matrix,
        lp_block_bytes,
        lp_matrix_bytes,
        pad_dims,
    )
    from repro.core.portfolio import _COMBOS, prepare_graph, \
        schedule_portfolio_grid
    from repro.workflows import wfgen_scale

    V = len(_COMBOS)        # unique greedy orders the full grid fans out
    c = cases[0]
    inst, plat, prof = c.inst, c.platform, c.profile
    N = inst.num_tasks
    Np, _ = pad_dims(N, prof.T)

    def timed_grid(graph, reps=3):
        best = []
        for _ in range(reps):
            t0 = time.perf_counter()
            res = schedule_portfolio_grid([inst], [[prof]], plat,
                                          engine="jax", graphs=[graph])
            best.append(time.perf_counter() - t0)
        return res, float(np.median(best))

    g_dense = prepare_graph(inst, plat, prof.T)
    timed_grid(g_dense, reps=1)                    # warm the bucket
    res_dense, t_dense = timed_grid(g_dense)

    budget = lp_block_bytes(4, V, Np)
    if budget >= lp_matrix_bytes(N):               # tiny N: force the form
        budget = lp_block_bytes(1, V, Np)
    g_blk = prepare_graph(inst, plat, prof.T)
    g_blk._lp = BlockedLP(inst, budget_bytes=budget)
    timed_grid(g_blk, reps=1)                      # warm the chunk shape
    res_blk, t_blk = timed_grid(g_blk)
    for name, ref in res_dense[0][0].items():      # engines must agree
        assert res_blk[0][0][name].cost == ref.cost, name

    # steady state: re-running the blocked path must add zero jit cache
    # misses — neither to its own chunked executable nor to the dense grid
    grid_fn, blk_fn = _impl(), _blocked_impl()
    before = grid_fn._cache_size() + blk_fn._cache_size()
    schedule_portfolio_grid([inst], [[prof]], plat, engine="jax",
                            graphs=[g_blk])
    misses_steady = grid_fn._cache_size() + blk_fn._cache_size() - before
    assert misses_steady == 0

    # over-envelope: an instance whose dense matrix exceeds the (reduced)
    # lp budget — longest_path_matrix refuses, the blocked form schedules
    wf = wfgen_scale("eager", 3 * N, seed=1)
    big = build_instance(wf, heft_mapping(wf, make_cluster(1, seed=1)),
                         make_cluster(1, seed=1))
    from repro.core import generate_profile
    bT = deadline_from_asap(big, 1.5)
    bprof = generate_profile("S1", bT, make_cluster(1, seed=1), J=16, seed=1)
    bNp, _ = pad_dims(big.num_tasks, bT)
    bbudget = max(lp_matrix_bytes(big.num_tasks) // 8,
                  lp_block_bytes(2, V, bNp))
    dense_raises = False
    try:
        longest_path_matrix(big, max_bytes=bbudget)
    except MemoryError:
        dense_raises = True
    g_big = prepare_graph(big, make_cluster(1, seed=1), bT,
                          lp_budget_bytes=bbudget)
    schedule_portfolio_grid([big], [[bprof]], make_cluster(1, seed=1),
                            engine="jax", graphs=[g_big])       # warm
    t0 = time.perf_counter()
    schedule_portfolio_grid([big], [[bprof]], make_cluster(1, seed=1),
                            engine="jax", graphs=[g_big])
    t_big = time.perf_counter() - t0

    return {
        "small": {
            "case": c.name,
            "n_tasks": N,
            "dense_us": t_dense * 1e6,
            "blocked_us": t_blk * 1e6,
            "blocked_over_dense": t_blk / t_dense,
            "budget_bytes": int(budget),
            "block_width": int(g_blk.lp().chunk_width(V, Np)),
        },
        "over_envelope": {
            "n_tasks": int(big.num_tasks),
            "lp_bytes": int(lp_matrix_bytes(big.num_tasks)),
            "budget_bytes": int(bbudget),
            "dense_raises": dense_raises,
            "blocked_us": t_big * 1e6,
            "block_width": int(g_big.lp().chunk_width(V, bNp)),
        },
        "jit_cache_misses_steady": int(misses_steady),
    }


def _service_section(cases) -> dict:
    """Serving-tier telemetry on a representative burst: a coalesced
    same-key burst (one combined launch serves every caller), two
    zero-budget requests that degrade down the ladder to ``asap``, one
    malformed request rejected at admission, and one load-shed
    :class:`~repro.serve.service.Overloaded` rejection — then the
    :meth:`PlanService.stats` snapshot (queue depth, coalesce ratio,
    p50/p99 plan latency, degradation counts) becomes the payload.

    Two robustness measurements ride along: ``workers_scaling`` times the
    same un-coalescable burst (``max_batch=1``) through a 1-worker and a
    4-worker pool, and ``cancel_latency_ms`` times how long the pool
    takes to go idle after :meth:`Ticket.cancel` lands on a solve wedged
    by an injected hang (the cooperative cancellation path end to end).
    Pure-python numpy solves hold the GIL, so the pool speedup on this
    engine measures dispatch overhead (~1x), not parallel solve
    throughput — the pool exists for isolation and supervision, and
    scales when solves release the GIL (ILP subprocesses, jax device
    launches).

    All services here run with ``compilation_cache=False`` — the bench
    must never flip the persistent jax cache on (see the NOTE in
    :func:`run`)."""
    from repro.api import Planner, PlanRequest
    from repro.runtime.fault import FaultSpec, ServiceFaultInjector
    from repro.serve import InvalidRequest, Overloaded, PlanService

    c = cases[0]
    burst = 6
    planner = Planner(c.platform, engine="numpy")
    req = PlanRequest(instances=c.inst, profiles=c.profile)
    with PlanService(planner, max_queue=burst + 2,
                     compilation_cache=False) as svc:
        svc.pause()                      # let the burst pile up: coalesce
        tickets = [svc.submit(req) for _ in range(burst)]
        svc.resume()
        for t in tickets:
            t.result(timeout=600)
        degraded = [svc.plan(req, budget=0.0) for _ in range(2)]
        try:                             # malformed: structured rejection
            svc.submit(PlanRequest(instances=c.inst, profiles=[]))
        except InvalidRequest:
            pass
        svc.pause()                      # overload: fill the queue, shed
        filler = []
        try:
            for _ in range(svc.max_queue + 1):
                filler.append(svc.submit(req))
        except Overloaded:
            pass
        svc.resume()
        for t in filler:
            t.result(timeout=600)
        stats = svc.stats()
    assert all(d.degraded and d.fallback_stage == "asap" for d in degraded)

    # Worker-count scaling: max_batch=1 defeats coalescing so the burst
    # is `burst` independent solves — the only speedup source is the pool.
    def _pool_burst_seconds(workers: int):
        pool_planner = Planner(c.platform, engine="numpy")
        with PlanService(pool_planner, workers=workers, max_batch=1,
                         max_queue=2 * burst,
                         compilation_cache=False) as pool:
            pool.pause()
            ts = [pool.submit(req) for _ in range(burst)]
            t0 = time.perf_counter()
            pool.resume()
            for t in ts:
                t.result(timeout=600)
            return time.perf_counter() - t0, pool.stats()

    seconds_w1, _ = _pool_burst_seconds(1)
    seconds_w4, stats_w4 = _pool_burst_seconds(4)

    # Cancellation latency: wedge the first solve with an injected hang,
    # cancel its ticket, and time the pool back to inflight_solves == 0 —
    # this is the watchdog->CancelToken->solver-checkpoint path, not a
    # queue drop.
    inj = ServiceFaultInjector(faults=[
        FaultSpec(kind="hang", stage="heuristic", times=1, seconds=60.0)])
    hang_planner = Planner(c.platform, engine="numpy")
    with PlanService(hang_planner, injector=inj,
                     compilation_cache=False) as hang_svc:
        ticket = hang_svc.submit(req)
        deadline = time.monotonic() + 30.0
        while (hang_svc.stats()["inflight_solves"] == 0
               and time.monotonic() < deadline):
            time.sleep(0.002)
        t0 = time.perf_counter()
        ticket.cancel("bench cancellation probe")
        while (hang_svc.stats()["inflight_solves"] > 0
               and time.monotonic() < deadline):
            time.sleep(0.002)
        cancel_latency_ms = (time.perf_counter() - t0) * 1e3
        cancel_stats = hang_svc.stats()

    return {
        "case": c.name,
        "burst": burst,
        "batches": stats["batches"],
        "coalesce_ratio": stats["coalesce_ratio"],
        "max_queue_depth": stats["max_queue_depth"],
        "completed": stats["completed"],
        "degraded": stats["degraded"],
        "rejected_invalid": stats["rejected_invalid"],
        "rejected_overloaded": stats["rejected_overloaded"],
        "stages": stats["stages"],
        "latency_p50_ms": stats["latency"]["p50_ms"],
        "latency_p99_ms": stats["latency"]["p99_ms"],
        "workers_scaling": {
            "burst": burst,
            "seconds_1_worker": seconds_w1,
            "seconds_4_workers": seconds_w4,
            "speedup": (seconds_w1 / seconds_w4
                        if seconds_w4 > 0 else None),
            "worker_restarts": stats_w4["worker_restarts"],
            "priority_inversions": stats_w4["priority_inversions"],
        },
        "cancel_latency_ms": cancel_latency_ms,
        "cancel_checks": cancel_stats["cancel_checks"],
        "cancelled_solves": cancel_stats["cancelled_solves"],
    }


def _obs_section(cases, with_jax: bool) -> dict:
    """Observability overhead, measured: the disabled-tracer hot-path
    cost (one global read + identity check per ``obs.span`` call), the
    span volume and wall-clock of the same steady-state fan-out with a
    live tracer, and the jax runtime hook snapshot (compile events,
    per-launcher jit cache entries, live arrays).

    ``disabled_tracer_overhead_frac`` is the acceptance number: the
    measured per-call disabled cost times the spans the plan would have
    emitted, as a fraction of the disabled-path plan time — asserted
    under 2% (spans are placed at launch/chunk granularity, never
    per-task, so the real figure is orders of magnitude below)."""
    import timeit

    from repro import obs
    from repro.obs import jax_hooks

    jax_hooks.install(obs.registry())
    c = cases[0]
    engine = "jax" if with_jax else "numpy"

    # the disabled span call, isolated: subtract the bare-lambda floor
    n = 200_000
    t_span = timeit.timeit(lambda: obs.span("x"), number=n) / n
    t_base = timeit.timeit(lambda: None, number=n) / n
    null_span_ns = max(t_span - t_base, 0.0) * 1e9

    run_all_variants(c, engine=engine)           # warm caches/executables
    reps = 7
    prev = obs.set_tracer(None)
    try:
        t_dis = []
        for _ in range(reps):
            t0 = time.perf_counter()
            run_all_variants(c, engine=engine)
            t_dis.append(time.perf_counter() - t0)
        t_disabled = float(np.median(t_dis))

        tr = obs.Tracer()
        obs.set_tracer(tr)
        t_en = []
        for _ in range(reps):
            tr.clear()
            t0 = time.perf_counter()
            run_all_variants(c, engine=engine)
            t_en.append(time.perf_counter() - t0)
        t_enabled = float(np.median(t_en))
        spans_per_plan = len(tr.finished())
    finally:
        obs.set_tracer(prev)

    overhead = spans_per_plan * null_span_ns * 1e-9 / t_disabled
    assert overhead < 0.02, (overhead, spans_per_plan, null_span_ns)

    return {
        "case": c.name,
        "engine": engine,
        "null_span_ns": null_span_ns,
        "spans_per_plan": spans_per_plan,
        "steady_plan_us_disabled": t_disabled * 1e6,
        "steady_plan_us_enabled": t_enabled * 1e6,
        "disabled_tracer_overhead_frac": overhead,
        "enabled_tracer_overhead_frac": t_enabled / t_disabled - 1.0,
        "jax": jax_hooks.snapshot(obs.registry()),
    }


def _mapping_section() -> dict:
    """Joint mapping x scheduling vs schedule-only, per motif family.

    For each of the paper's four workflow motifs: plan the same workflow
    with ``mapping="heft"`` (HEFT mapping, schedule-only optimization)
    and ``mapping="search"`` (the alternating candidate-mapping search),
    on a scarce profile where the green budget covers ~40 units of work
    per interval — the regime where the mapping choice actually moves
    carbon cost.  Reports per-motif best costs, the joint-mode saving,
    and candidate throughput; the acceptance bar is a strict search win
    on at least 3 of the 4 families."""
    from repro.api import Planner, PlanRequest
    from repro.cluster import make_cluster
    from repro.core import build_instance, deadline_from_asap, heft_mapping
    from repro.workflows import WORKFLOW_KINDS, make_workflow

    plat = make_cluster(1, seed=0)
    families = []
    wins = 0
    for kind in WORKFLOW_KINDS:
        wf = make_workflow(kind, 2, seed=1)
        inst_h = build_instance(wf, heft_mapping(wf, plat), plat)
        T = deadline_from_asap(inst_h, 3.0)
        prof = generate_profile("S3", T, plat, J=12, seed=2,
                                work_capacity=40)
        planner = Planner(plat, engine="numpy")
        res_h = planner.plan(PlanRequest(instances=wf, profiles=prof,
                                         mapping="heft"))
        t0 = time.perf_counter()
        res_s = planner.plan(PlanRequest(
            instances=wf, profiles=prof, mapping="search",
            mapping_options={"seeds": 6, "rounds": 3, "neighbors": 9,
                             "seed": 0}))
        t_search = time.perf_counter() - t0
        info = res_s.mapping_info[0]
        cost_h = int(res_h.best().cost)
        cost_s = int(res_s.best().cost)
        wins += cost_s < cost_h
        families.append({
            "family": kind,
            "n_tasks": int(wf.n),
            "T": int(T),
            "heft_cost": cost_h,
            "search_cost": cost_s,
            "saving_frac": (cost_h - cost_s) / cost_h if cost_h else 0.0,
            "winner_label": info.label,
            "candidates": info.candidates,
            "rounds": info.rounds,
            "candidates_per_sec": (info.candidates / t_search
                                   if t_search > 0 else None),
            "search_seconds": t_search,
        })
    return {"families": families, "search_wins": wins,
            "n_families": len(families)}


def run(sizes=(200,), clusters=("small",), n_cases: int = 6,
        with_jax: bool = True, n_profiles: int = 8,
        gap_time_limit: float = 20.0, smoke: bool = False):
    # NOTE: the persistent compilation cache
    # (repro.kernels.backend.enable_compilation_cache) is deliberately NOT
    # enabled here: the cold measurement must include the real bucket
    # compiles on every run, or cold-vs-steady comparisons across commits
    # would silently go warm after the first run on a machine.
    cases = []
    for case in build_matrix(sizes=sizes, clusters=clusters,
                             factors=(1.0, 2.0), scenarios=("S1", "S3")):
        cases.append(case)
        if len(cases) >= n_cases:
            break

    t0 = time.perf_counter()
    loop_res = [run_variant_loop(c) for c in cases]
    t_loop = time.perf_counter() - t0

    t0 = time.perf_counter()
    port_res = [run_all_variants(c) for c in cases]
    t_port = time.perf_counter() - t0

    for lr, pr in zip(loop_res, port_res):     # engine must be bit-identical
        for v, (cost, _) in lr.items():
            assert pr[v][0] == cost, v

    t_jax = t_jax_cold = None
    multi = None
    planner_stats = None
    if with_jax:
        t0 = time.perf_counter()
        for c in cases:
            run_all_variants(c, engine="jax")
        t_jax_cold = time.perf_counter() - t0   # includes bucket compiles
        t0 = time.perf_counter()
        for c in cases:
            run_all_variants(c, engine="jax")
        t_jax = time.perf_counter() - t0        # replanning regime

        # multi-profile replanning: one instance x an ensemble of perturbed
        # forecasts; loop re-prepares and re-schedules per member, the
        # engine prepares the graph once and fans members x variants out
        # as one device launch
        c = cases[0]
        profs = [generate_profile(c.profile.scenario, c.profile.T,
                                  c.platform, J=48, seed=100 + s)
                 for s in range(n_profiles)]
        t0 = time.perf_counter()
        ref = [schedule_portfolio(c.inst, p, c.platform) for p in profs]
        t_mloop = time.perf_counter() - t0
        schedule_portfolio_multi(c.inst, profs, c.platform,
                                 engine="jax")   # warm the R-bucket shapes
        t0 = time.perf_counter()
        res = schedule_portfolio_multi(c.inst, profs, c.platform,
                                       engine="jax")
        t_multi = time.perf_counter() - t0
        # greedy rows must agree with the per-profile numpy loop
        for r, rr in zip(ref, res):
            for v in r:
                if not v.endswith("-LS"):
                    assert (r[v].start == rr[v].start).all(), v
        multi = {
            "n_profiles": n_profiles,
            "case": c.name,
            "loop_numpy_us_per_profile": t_mloop / n_profiles * 1e6,
            "multi_jax_us_per_profile": t_multi / n_profiles * 1e6,
            "speedup_multi_over_loop": t_mloop / t_multi,
        }

        # --- Planner facade: overhead over the grid engine it wraps, and
        # the combined instance x profile fan-out as ONE bucketed launch
        from repro.api import Planner, PlanRequest
        from repro.core.greedy_jax import _impl, pad_dims
        from repro.core.portfolio import schedule_portfolio_grid

        reps = 9       # wall-clock drift on the shared box swamps a
        # 5-rep median (single-sample swings of ±10% were observed);
        # 9 rotated reps keep the facade-overhead estimate honest
        planner = Planner(c.platform, engine="jax")
        req = PlanRequest(instances=c.inst, profiles=profs)
        planner.plan(req)                       # warm cache + executables
        graph = planner.prepared(c.inst, profs[0].T)
        contenders = {
            "facade": lambda: planner.plan(req),
            "grid": lambda: schedule_portfolio_grid(
                [c.inst], [profs], c.platform, engine="jax",
                graphs=[graph]),
            "legacy": lambda: schedule_portfolio_multi(     # graph seeded
                c.inst, profs, c.platform, engine="jax", graph=graph),
        }
        samples = {k: [] for k in contenders}
        keys = list(contenders)
        for rep in range(reps):                 # rotate order: de-bias
            for k in keys[rep % 3:] + keys[:rep % 3]:       # load drift
                t0 = time.perf_counter()
                contenders[k]()
                samples[k].append(time.perf_counter() - t0)
        t_facade, t_grid, t_legacy = (float(np.median(samples[k]))
                                      for k in keys)

        # combined grid: 2 instances sharing one shape bucket x ensemble
        # x 17 variants; the greedy fan-out must be ONE device launch per
        # bucket (verified by the jit cache-miss count)
        profs_b = [generate_profile(c.profile.scenario, c.profile.T,
                                    c.platform, J=48, seed=300 + s)
                   for s in range(n_profiles)]
        insts = [c.inst, c.inst]
        grid_req = PlanRequest(instances=insts,
                               profiles=[profs, profs_b])
        buckets = {pad_dims(i.num_tasks, profs[0].T) for i in insts}
        grid_fn = _impl()
        before = grid_fn._cache_size()
        planner.plan(grid_req)                  # cold: compiles per bucket
        misses_cold = grid_fn._cache_size() - before
        before = grid_fn._cache_size()
        t0 = time.perf_counter()
        res = planner.plan(grid_req)
        t_combined = time.perf_counter() - t0
        misses_steady = grid_fn._cache_size() - before
        assert misses_cold == len(buckets), (misses_cold, buckets)
        assert misses_steady == 0               # steady: zero retracing
        n_cells = res.costs.size
        planner_stats = {
            "case": c.name,
            "facade_us": t_facade * 1e6,
            "grid_direct_us": t_grid * 1e6,
            "legacy_shim_us": t_legacy * 1e6,
            "facade_overhead_frac": t_facade / t_grid - 1.0,
            "combined_grid": {
                "n_instances": len(insts),
                "n_profiles": n_profiles,
                "n_variants": res.costs.shape[2],
                "cells": int(n_cells),
                "shape_buckets": len(buckets),
                "jit_cache_misses_cold": int(misses_cold),
                "jit_cache_misses_steady": int(misses_steady),
                "steady_us_per_cell": t_combined / n_cells * 1e6,
            },
        }

    lp_blocked = _lp_blocked_section(cases) if with_jax else None

    service = _service_section(cases)

    obs_stats = _obs_section(cases, with_jax=with_jax)

    gaps = _gap_table(gap_time_limit)

    mapping = _mapping_section()

    sharded = None
    if with_jax:
        from benchmarks.fig_sharded import section as sharded_section

        sharded = sharded_section(smoke=smoke)

    n = len(cases)
    matrix = {"sizes": list(sizes), "clusters": list(clusters),
              "n_cases": n, "n_profiles": n_profiles}
    on_reference = all(matrix[k] == v for k, v in REFERENCE_MATRIX.items())
    payload = {
        "matrix": matrix,
        "n_instances": n,
        "variants_per_instance": 17,
        "loop_us_per_instance": t_loop / n * 1e6,
        "portfolio_us_per_instance": t_port / n * 1e6,
        "speedup_loop_over_portfolio": t_loop / t_port,
        "jax_fanout_us_per_instance": (t_jax / n * 1e6) if t_jax else None,
        "jax_fanout_cold_us_per_instance":
            (t_jax_cold / n * 1e6) if t_jax_cold else None,
        # recorded-baseline fields only apply on the reference matrix
        "jax_fanout_us_per_instance_before":
            JAX_FANOUT_BEFORE_US if on_reference else None,
        "multi_profile": multi,
        "planner": planner_stats,
        "lp_blocked": lp_blocked,
        "service": service,
        "obs": obs_stats,
        "gaps": gaps,
        "mapping": mapping,
        "sharded": sharded,
        "seed_reference": dict(SEED_REFERENCE) if on_reference else None,
    }
    os.makedirs(OUT_DIR, exist_ok=True)
    out_path = os.path.join(OUT_DIR, "BENCH_portfolio.json")
    with open(out_path, "w") as f:
        json.dump(payload, f, indent=2)

    emit("portfolio_engine", t_port / n * 1e6,
         f"loop/portfolio={t_loop / t_port:.2f}x"
         f";jax_us={payload['jax_fanout_us_per_instance'] or 0:.0f}")
    if multi:
        emit("portfolio_multi", multi["multi_jax_us_per_profile"],
             f"multi/loop={multi['speedup_multi_over_loop']:.2f}x"
             f";profiles={n_profiles}")
    if planner_stats:
        g = planner_stats["combined_grid"]
        emit("planner_facade", planner_stats["facade_us"],
             f"overhead={planner_stats['facade_overhead_frac'] * 100:.1f}%"
             f";grid_cells={g['cells']}"
             f";buckets={g['shape_buckets']}"
             f";cold_misses={g['jit_cache_misses_cold']}")
    if lp_blocked:
        sm, ov = lp_blocked["small"], lp_blocked["over_envelope"]
        emit("portfolio_lp_blocked", sm["blocked_us"],
             f"blocked/dense={sm['blocked_over_dense']:.2f}x"
             f";over_envelope_n={ov['n_tasks']}"
             f";dense_raises={ov['dense_raises']}"
             f";steady_misses={lp_blocked['jit_cache_misses_steady']}")
    emit("planner_service", service["latency_p50_ms"] * 1e3,
         f"coalesce={service['coalesce_ratio']:.1f}x"
         f";p99_ms={service['latency_p99_ms']:.1f}"
         f";degraded={service['degraded']}/{service['completed']}"
         f";shed={service['rejected_overloaded']}")
    ws = service["workers_scaling"]
    emit("planner_service_pool", ws["seconds_4_workers"] * 1e6,
         f"speedup_4w={ws['speedup']:.2f}x"
         f";burst={ws['burst']}"
         f";cancel_ms={service['cancel_latency_ms']:.1f}"
         f";cancel_checks={service['cancel_checks']}")
    emit("planner_obs", obs_stats["null_span_ns"],
         f"disabled_overhead="
         f"{obs_stats['disabled_tracer_overhead_frac'] * 100:.4f}%"
         f";spans_per_plan={obs_stats['spans_per_plan']}"
         f";enabled_overhead="
         f"{obs_stats['enabled_tracer_overhead_frac'] * 100:.1f}%")
    cps = [f["candidates_per_sec"] for f in mapping["families"]
           if f["candidates_per_sec"]]
    emit("planner_mapping",
         float(np.median(cps)) if cps else 0.0,
         f"search_wins={mapping['search_wins']}/{mapping['n_families']}"
         f";median_saving="
         f"{np.median([f['saving_frac'] for f in mapping['families']]) * 100:.1f}%")
    if sharded:
        sw, gk = sharded["device_sweep"], sharded["gain_kernel"]
        top = sw["curve"][-1]
        emit("portfolio_sharded", top["steady_us"],
             f"devices={top['devices']}"
             f";speedup_vs_1={top['speedup_vs_1']:.2f}x"
             f";host_cpus={sw['host_cpus']}"
             f";bitwise={all(p['bitwise_identical'] for p in sw['curve'])}"
             f";gain_crossover_n={gk['crossover_n']}"
             f";gain_mode={gk['kernel_mode']}")
    for gc in gaps["cases"]:
        asap_s = ("n/a" if gc["gap_asap"] is None
                  else f"{gc['gap_asap']:.3f}")
        emit("portfolio_gap_" + gc["case"].replace("-", "_"),
             0.0,
             f"gap_best={gc['gap_best']:.3f}"
             f";gap_asap={asap_s}"
             f";optimal={gc['optimal']}"
             f";proven={gc['proven']}")
    return payload


if __name__ == "__main__":
    run()
