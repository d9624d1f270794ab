"""Multi-profile replanning engine: graph/overlay split, multi==loop per
engine, longest-path relaxation identity, jnp gain twin, LS termination
parity, CarbonGate ensemble planning."""
import numpy as np
import pytest

from repro.cluster import make_cluster
from repro.core import (
    PORTFOLIO_VARIANTS,
    build_instance,
    deadline_from_asap,
    generate_profile,
    heft_mapping,
    overlay_profile,
    portfolio_cost_matrix,
    prepare_graph,
    prepare_instance,
    schedule_portfolio,
    schedule_portfolio_multi,
)
from repro.workflows import make_workflow


def _setup(kind="eager", samples=3, seed=3, factor=1.5, scenario="S3"):
    plat = make_cluster(1, seed=seed)
    wf = make_workflow(kind, samples, seed=seed)
    inst = build_instance(wf, heft_mapping(wf, plat), plat)
    T = deadline_from_asap(inst, factor)
    prof = generate_profile(scenario, T, plat, J=16, seed=seed)
    return plat, inst, prof


def _ensemble(plat, T, n, scenario="S3", seed0=100, J=16):
    return [generate_profile(scenario, T, plat, J=J, seed=seed0 + i)
            for i in range(n)]


def test_graph_plus_overlay_bit_identical_to_prepare_instance():
    """Property: prepare_graph(inst) + overlay(profile_i) reproduces every
    field of prepare_instance(inst, profile_i) exactly, for N random
    profiles over one graph."""
    plat, inst, prof = _setup()
    graph = prepare_graph(inst, plat, prof.T)
    rng = np.random.default_rng(0)
    for i in range(6):
        scen = ("S1", "S2", "S3", "S4")[int(rng.integers(4))]
        p = generate_profile(scen, prof.T, plat, J=int(rng.integers(4, 40)),
                             seed=int(rng.integers(1 << 16)))
        split = overlay_profile(graph, p)
        ref = prepare_instance(inst, p, plat)
        assert (graph.est0 == ref.est0).all()
        assert (graph.lst0 == ref.lst0).all()
        assert graph.feasible == ref.feasible
        for sc in ("slack", "press"):
            for wt in (False, True):
                assert (graph.order_for(sc, wt)
                        == ref.graph.order_for(sc, wt)).all()
        for r in (False, True):
            assert (split.masks[r] == ref.masks[r]).all()
            assert (split.segs[r][0] == ref.segs[r][0]).all()
            assert (split.segs[r][1] == ref.segs[r][1]).all()
        assert (split.unit_budget == ref.ls["unit_budget"]).all()
        assert split.ls["visit"] == ref.ls["visit"]


def test_overlay_rejects_horizon_mismatch():
    plat, inst, prof = _setup()
    graph = prepare_graph(inst, plat, prof.T)
    bad = generate_profile("S1", prof.T + 7, plat, J=8, seed=0)
    with pytest.raises(ValueError):
        overlay_profile(graph, bad)


@pytest.mark.parametrize(
    "engine", ["numpy", pytest.param("jax", marks=pytest.mark.device)])
def test_multi_matches_per_profile_loop(engine):
    plat, inst, prof = _setup(samples=2, seed=1)
    profs = _ensemble(plat, prof.T, 4)
    multi = schedule_portfolio_multi(inst, profs, plat, engine=engine)
    assert len(multi) == len(profs)
    for p, res in zip(profs, multi):
        ref = schedule_portfolio(inst, p, plat, engine=engine)
        for name in PORTFOLIO_VARIANTS:
            assert (res[name].start == ref[name].start).all(), name
            assert res[name].cost == ref[name].cost, name


def test_multi_empty_profiles():
    plat, inst, prof = _setup(samples=2, seed=0)
    assert schedule_portfolio_multi(inst, [], plat) == []


def test_cost_matrix_and_robust_pick():
    plat, inst, prof = _setup(samples=2, seed=5)
    profs = _ensemble(plat, prof.T, 3)
    res = schedule_portfolio_multi(inst, profs, plat)
    costs, names = portfolio_cost_matrix(res)
    assert costs.shape == (3, len(PORTFOLIO_VARIANTS))
    for pi, r in enumerate(res):
        for vi, n in enumerate(names):
            assert costs[pi, vi] == r[n].cost
    worst = costs.max(axis=0)
    pick = int(worst.argmin())
    assert worst[pick] <= worst.min(initial=np.iinfo(np.int64).max)


def test_longest_path_matrix_matches_worklist_relaxation():
    """The device greedy's closed-form EST update (max over placed
    ancestors of start + lp) equals the reference worklist fixpoint after
    every placement prefix."""
    from repro.core.estlst import compute_est
    from repro.core.greedy_jax import NEG_PATH, longest_path_matrix

    plat, inst, prof = _setup(kind="bacass", samples=2, seed=7)
    lp = longest_path_matrix(inst)
    N = inst.num_tasks
    # direct edges: lp dominates every edge bound
    for v in range(N):
        for u in inst.preds(v):
            assert lp[u, v] >= inst.dur[u]
    rng = np.random.default_rng(1)
    est = compute_est(inst).copy()
    start_fixed = np.zeros(N, dtype=np.int64)
    fixed = np.zeros(N, dtype=bool)
    est_inc = est.astype(np.int64).copy()
    for v in inst.topo:                   # place in topo order, random slack
        s = int(est_inc[v] + rng.integers(0, 5))
        start_fixed[v] = s
        fixed[v] = True
        # incremental closed-form update
        row = lp[v].astype(np.int64)
        upd = np.where(row > NEG_PATH // 2, s + row, est_inc)
        est_inc = np.maximum(est_inc, upd)
        # reference: full fixpoint with placed tasks pinned
        ref = compute_est(inst, start_fixed, fixed)
        unplaced = ~fixed
        assert (est_inc[unplaced] == ref[unplaced]).all()


@pytest.mark.device
def test_gains_jnp_twin_matches_pallas_interpreter(climb_gains):
    from repro.kernels.gain_scan import gain_scan

    rng = np.random.default_rng(2)
    N, T, mu = 70, 200, 9
    rem = rng.integers(-40, 50, T).astype(np.float32)
    dur = rng.integers(1, 14, N).astype(np.float32)
    work = rng.integers(0, 30, N).astype(np.float32)
    start = rng.integers(0, T - 16, N).astype(np.float32)
    lo = np.maximum(start - rng.integers(0, mu + 4, N), 0).astype(np.float32)
    hi = np.minimum(start + rng.integers(0, mu + 4, N),
                    T - dur).astype(np.float32)
    jnp_path = np.asarray(gain_scan(rem, start, dur, work, lo, hi, mu=mu,
                                    interpret=None))
    pallas = np.asarray(gain_scan(rem, start, dur, work, lo, hi, mu=mu,
                                  interpret=True))
    np.testing.assert_array_equal(jnp_path, pallas)
    # batched twin
    rem2 = np.stack([rem, np.roll(rem, 11)])
    start2 = np.stack([start, start])
    lo2, hi2 = np.stack([lo, lo]), np.stack([hi, hi])
    a = climb_gains(rem2, start2, dur, work, lo2, hi2, mu=mu,
                    interpret=None)
    b = climb_gains(rem2, start2, dur, work, lo2, hi2, mu=mu,
                    interpret=True)
    np.testing.assert_array_equal(a, b)


@pytest.mark.device
def test_portfolio_ls_no_earlier_termination_than_sequential():
    """Every -LS row of the batched climber ends at a state the sequential
    reference cannot improve: one extra reference round is a no-op."""
    from repro.core.local_search import local_search

    plat, inst, prof = _setup(samples=3, seed=4, factor=2.0, scenario="S1")
    res = schedule_portfolio(inst, prof, plat, engine="jax")
    for name in PORTFOLIO_VARIANTS:
        if not name.endswith("-LS"):
            continue
        polished = local_search(inst, prof, plat, res[name].start,
                                max_rounds=1)
        assert (polished == res[name].start).all(), name


@pytest.mark.device
def test_portfolio_ls_monotone_per_row():
    plat, inst, prof = _setup(samples=3, seed=4, factor=2.0, scenario="S1")
    from repro.core import schedule_cost, validate_schedule
    res = schedule_portfolio(inst, prof, plat, engine="jax")
    for name in PORTFOLIO_VARIANTS:
        if not name.endswith("-LS"):
            continue
        base = res[name[:-3]]
        validate_schedule(inst, prof, res[name].start)
        assert res[name].cost <= base.cost, name


def test_carbon_gate_ensemble_plans_robust_variant():
    from repro.runtime.carbon_gate import CarbonGate, fleet_platform

    plat = fleet_platform(pods=2, chip_watts_idle=10, chip_watts_work=25,
                          chips_per_pod=4)
    chunk = [[7, 9, 6, 8, 7, 9], [8, 8, 9, 7, 6, 6]]
    horizon = int(2.5 * max(sum(c) for c in chunk))
    profs = [generate_profile("S3", horizon, plat, J=24, seed=5 + i,
                              work_capacity=int(plat.p_work[:2].sum()))
             for i in range(4)]
    gate = CarbonGate(profs[0], plat, variant="auto", profiles=profs[1:],
                      engine="numpy")
    plan = gate.make_plan(chunk, barriers=[2])
    assert plan.variant in plan.variant_names and plan.variant != "asap"
    assert plan.cost_matrix.shape[0] == 4
    vi = plan.variant_names.index(plan.variant)
    heur = [i for i, n in enumerate(plan.variant_names) if n != "asap"]
    worst = plan.cost_matrix[:, heur].max(axis=0)
    assert plan.robust_cost == plan.cost_matrix[:, vi].max() == worst.min()
    assert plan.cost <= plan.asap_cost
    # the plan's start/cost are the nominal profile's, for the chosen variant
    from repro.core import schedule
    ref = schedule(plan.instance, profs[0], plat, plan.variant)
    assert plan.cost == ref.cost


def test_carbon_gate_pinned_asap_baseline():
    """Regression: a gate pinned to the asap baseline must still plan
    (robust_pick falls back to asap when it is the only variant)."""
    from repro.runtime.carbon_gate import CarbonGate, fleet_platform

    plat = fleet_platform(pods=1, chip_watts_idle=10, chip_watts_work=25,
                          chips_per_pod=4)
    chunk = [[7, 9, 6, 8]]
    horizon = int(3 * sum(chunk[0]))
    prof = generate_profile("S1", horizon, plat, J=16, seed=2,
                            work_capacity=int(plat.p_work[:1].sum()))
    plan = CarbonGate(prof, plat, variant="asap").make_plan(chunk)
    assert plan.variant == "asap"
    assert plan.cost == plan.asap_cost


def test_carbon_gate_single_profile_back_compat():
    from repro.runtime.carbon_gate import CarbonGate, fleet_platform

    plat = fleet_platform(pods=1, chip_watts_idle=10, chip_watts_work=25,
                          chips_per_pod=4)
    chunk = [[7, 9, 6, 8]]
    horizon = int(3 * sum(chunk[0]))
    prof = generate_profile("S1", horizon, plat, J=16, seed=2,
                            work_capacity=int(plat.p_work[:1].sum()))
    gate = CarbonGate(prof, plat, variant="pressWR-LS")
    plan = gate.make_plan(chunk)
    from repro.core import schedule
    ref = schedule(plan.instance, prof, plat, "pressWR-LS")
    assert (plan.start == ref.start).all()
    assert plan.cost == ref.cost and plan.variant == "pressWR-LS"
