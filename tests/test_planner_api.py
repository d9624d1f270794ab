"""Planner API: PlanRequest normalization, equivalence of every request
shape with the legacy entry points per engine (against the independent
sequential oracle), PlanResult accessors, profile windowing, commit-K
regression, engine resolution, and the async rolling-horizon session."""
import numpy as np
import pytest

from repro.api import (
    LocalSearchConfig,
    Planner,
    PlanningSession,
    PlanRequest,
    crop_profile,
    window_profile,
)
from repro.cluster import make_cluster
from repro.core import (
    PORTFOLIO_VARIANTS,
    build_instance,
    deadline_from_asap,
    generate_profile,
    heft_mapping,
    prepare_graph,
    schedule,
    schedule_cost,
    schedule_portfolio,
    schedule_portfolio_multi,
    schedule_reference,
    validate_schedule,
)
from repro.core.local_search import local_search
from repro.core.portfolio import portfolio_cost_matrix, robust_pick
from repro.workflows import make_workflow

jax_engine = pytest.param("jax", marks=pytest.mark.device)


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


# --- request shapes: one code path, oracle equivalence ---------------------

def test_plan_1x1x1_matches_sequential_reference():
    plat, inst, prof = _setup()
    planner = Planner(plat, engine="numpy")
    for v in ("asap", "slack", "pressWR", "slack-LS", "pressWR-LS"):
        res = planner.plan(PlanRequest(instances=inst, profiles=prof,
                                       variants=v))
        assert res.shape == (1, 1, 1)
        ref = schedule_reference(inst, prof, plat, v)
        got = res.result(variant=v)
        assert (got.start == ref.start).all(), v
        assert got.cost == ref.cost == res.costs[0, 0, 0], v


def test_plan_1x1x17_matches_sequential_reference():
    plat, inst, prof = _setup(kind="atacseq", seed=1, factor=1.0,
                              scenario="S1")
    res = Planner(plat, engine="numpy").plan(
        PlanRequest(instances=inst, profiles=prof))
    assert res.shape == (1, 1, 17)
    for vi, name in enumerate(res.variants):
        ref = schedule_reference(inst, prof, plat, name)
        assert (res.results[0][0][name].start == ref.start).all(), name
        assert res.costs[0, 0, vi] == ref.cost, name


def test_plan_1xPx17_and_IxPx17_match_per_cell_reference():
    plat, inst, prof = _setup(samples=2, seed=5)
    profs = _ensemble(plat, prof.T, 3)
    wf2 = make_workflow("eager", 2, seed=9)
    inst2 = build_instance(wf2, heft_mapping(wf2, plat), plat)
    T2 = deadline_from_asap(inst2, 1.5)
    profs2 = _ensemble(plat, T2, 3, scenario="S1", seed0=200)

    planner = Planner(plat, engine="numpy")
    one = planner.plan(PlanRequest(instances=inst, profiles=profs))
    both = planner.plan(PlanRequest(instances=[inst, inst2],
                                    profiles=[profs, profs2]))
    assert one.shape == (1, 3, 17) and both.shape == (2, 3, 17)
    for i, (ins, ps) in enumerate(((inst, profs), (inst2, profs2))):
        for p, pr in enumerate(ps):
            for name in PORTFOLIO_VARIANTS:
                ref = schedule_reference(ins, pr, plat, name)
                got = both.results[i][p][name]
                assert (got.start == ref.start).all(), (i, p, name)
                assert got.cost == ref.cost, (i, p, name)
    # the 1xP slice of the grid equals the standalone 1xP plan
    assert (both.costs[0] == one.costs[0]).all()


@pytest.mark.device
def test_plan_grid_jax_greedy_matches_numpy_and_ls_is_polished():
    plat, inst, prof = _setup(samples=2, seed=1)
    profs = _ensemble(plat, prof.T, 3)
    wf2 = make_workflow("eager", 2, seed=9)
    inst2 = build_instance(wf2, heft_mapping(wf2, plat), plat)
    profs2 = _ensemble(plat, deadline_from_asap(inst2, 1.5), 3, seed0=200)

    req = PlanRequest(instances=[inst, inst2], profiles=[profs, profs2])
    rj = Planner(plat, engine="jax").plan(req)
    rn = Planner(plat, engine="numpy").plan(req)
    assert rj.engine == "jax" and rn.engine == "numpy"
    for i, (ins, ps) in enumerate(((inst, profs), (inst2, profs2))):
        for p, pr in enumerate(ps):
            for name in PORTFOLIO_VARIANTS:
                got = rj.results[i][p][name]
                validate_schedule(ins, pr, got.start)
                if name.endswith("-LS"):
                    # batched climber may differ; never worse than greedy,
                    # never improvable by one sequential reference round
                    assert got.cost <= rj.results[i][p][name[:-3]].cost
                    polished = local_search(ins, pr, plat, got.start,
                                            max_rounds=1)
                    assert (polished == got.start).all(), (i, p, name)
                else:
                    ref = rn.results[i][p][name]
                    assert (got.start == ref.start).all(), (i, p, name)


@pytest.mark.parametrize("engine", ["numpy", jax_engine])
def test_legacy_entry_points_bit_identical_to_planner(engine):
    """The deprecation shims and a direct Planner.plan agree exactly."""
    plat, inst, prof = _setup(samples=2, seed=4, factor=2.0, scenario="S1")
    profs = _ensemble(plat, prof.T, 3)
    planner = Planner(plat, engine=engine)

    port = schedule_portfolio(inst, prof, plat, engine=engine)
    res = planner.plan(PlanRequest(instances=inst, profiles=prof))
    for name in PORTFOLIO_VARIANTS:
        assert (port[name].start == res.results[0][0][name].start).all()
        assert port[name].cost == res.results[0][0][name].cost

    multi = schedule_portfolio_multi(inst, profs, plat, engine=engine)
    resm = planner.plan(PlanRequest(instances=inst, profiles=profs))
    for p in range(len(profs)):
        for name in PORTFOLIO_VARIANTS:
            assert (multi[p][name].start
                    == resm.results[0][p][name].start).all()
            assert multi[p][name].cost == resm.results[0][p][name].cost

    if engine == "numpy":
        one = schedule(inst, prof, plat, "pressWR-LS")
        assert (one.start
                == res.results[0][0]["pressWR-LS"].start).all()


def test_planner_graph_cache_reuse_and_seed():
    plat, inst, prof = _setup()
    planner = Planner(plat, engine="numpy")
    a = planner.plan(PlanRequest(instances=inst, profiles=prof))
    g = planner.prepared(inst, prof.T)
    b = planner.plan(PlanRequest(instances=inst, profiles=prof))
    assert planner.prepared(inst, prof.T) is g           # cache hit
    assert (a.costs == b.costs).all()
    # seeding an external graph is picked up by identity
    g2 = prepare_graph(inst, plat, prof.T)
    planner.seed_graph(g2)
    assert planner.prepared(inst, prof.T) is g2
    # regression: a zero-sized cache still plans (holds the current graph)
    tiny = Planner(plat, engine="numpy", graph_cache=0)
    assert (tiny.plan(PlanRequest(instances=inst, profiles=prof)).costs
            == a.costs).all()


def test_plan_result_accessors_match_portfolio_helpers():
    plat, inst, prof = _setup(samples=2, seed=5)
    profs = _ensemble(plat, prof.T, 3)
    res = Planner(plat, engine="numpy").plan(
        PlanRequest(instances=inst, profiles=profs, robust=True))
    legacy = schedule_portfolio_multi(inst, profs, plat)
    costs, names = portfolio_cost_matrix(legacy)
    got_costs, got_names = res.cost_matrix(0)
    assert got_names == names and (got_costs == costs).all()
    assert res.robust(0) == robust_pick(costs, names)
    # best() = nominal-profile cheapest heuristic
    heur = [n for n in names if n != "asap"]
    want = min(heur, key=lambda n: legacy[0][n].cost)
    assert res.best().cost == legacy[0][want].cost
    # robust request -> pick() executes the robust variant's nominal plan
    assert res.pick().variant == res.robust(0)[0]
    assert str(res.table(0)).count("\n") == len(names)


def test_plan_request_validation():
    plat, inst, prof = _setup()
    with pytest.raises(ValueError):
        PlanRequest(instances=inst, profiles=[]).resolve()
    with pytest.raises(ValueError):
        PlanRequest(instances=inst, profiles=prof,
                    variants=("nope",)).resolve()
    with pytest.raises(ValueError):
        PlanRequest(instances=[inst, inst],
                    profiles=[[prof], [prof, prof]]).resolve()
    with pytest.raises(ValueError):
        Planner(plat, engine="tpu")
    with pytest.raises(TypeError):
        Planner(plat).plan(PlanRequest(instances=inst, profiles=prof),
                           instances=inst)


def test_deadline_scale_crops_long_forecast():
    plat, inst, _ = _setup()
    T = deadline_from_asap(inst, 1.5)
    long = generate_profile("S3", 4 * T, plat, J=64, seed=11)
    res = Planner(plat, engine="numpy").plan(PlanRequest(
        instances=inst, profiles=long, deadline_scale=1.5))
    cropped = crop_profile(long, T)
    assert cropped.T == T
    assert (cropped.unit_budget(plat.idle_total)
            == long.unit_budget(plat.idle_total)[:T]).all()
    ref = schedule_portfolio(inst, cropped, plat)
    for name in PORTFOLIO_VARIANTS:
        assert (res.results[0][0][name].start == ref[name].start).all()
    with pytest.raises(ValueError):
        crop_profile(cropped, T + 1)


def test_window_profile_slices_unit_budget():
    plat, inst, _ = _setup()
    W = deadline_from_asap(inst, 1.5)
    long = generate_profile("S1", 3 * W + 5, plat, J=40, seed=13)
    ub = long.unit_budget(plat.idle_total)
    for t0 in (0, 1, W, 2 * W + 3):
        w = window_profile(long, t0, W)
        assert w.T == W
        assert (w.unit_budget(plat.idle_total) == ub[t0:t0 + W]).all()
    with pytest.raises(ValueError):
        window_profile(long, 3 * W, W + 6)


# --- engine / backend resolution -------------------------------------------

def test_resolve_engine_rules():
    from repro.kernels.backend import resolve_engine

    assert resolve_engine("numpy") == "numpy"
    assert resolve_engine("jax", fanout=1) == "jax"
    assert resolve_engine("auto", fanout=1) == "numpy"
    assert resolve_engine("auto", fanout=2) == "jax"
    assert resolve_engine(None, fanout=8) == "jax"
    with pytest.raises(ValueError):
        resolve_engine("cuda")


def test_resolve_interpret_routes_through_resolve_mode():
    from repro.kernels.backend import resolve_interpret, resolve_mode

    for flag in (None, True, False):
        assert resolve_interpret(flag) == (resolve_mode(flag) != "pallas")


# --- commit width (LocalSearchConfig.commit_k) -----------------------------

@pytest.mark.device
def test_nondefault_commit_k_still_matches_sequential_reference():
    """ROADMAP open item: the device climb's commit width is tunable; any
    K must land on a state the sequential reference cannot improve."""
    from repro.core.greedy import greedy_schedule
    from repro.core.local_search_jax import local_search_portfolio_multi

    plat, inst, prof = _setup(samples=3, seed=4, factor=2.0, scenario="S1")
    combos = (("press", False, True), ("slack", True, False),
              ("press", True, True))
    stack = np.stack([greedy_schedule(inst, prof, plat, s, w, r)
                      for (s, w, r) in combos])
    base = [schedule_cost(inst, prof, st) for st in stack]
    budgets = np.broadcast_to(prof.unit_budget(inst.idle_total),
                              (len(stack), prof.T))
    for kk in (1, 4, 96):
        improved = local_search_portfolio_multi(
            inst, prof.T, budgets, stack, mu=10, commit_k=kk)
        for i in range(len(combos)):
            validate_schedule(inst, prof, improved[i])
            assert schedule_cost(inst, prof, improved[i]) <= base[i]
            polished = local_search(inst, prof, plat, improved[i],
                                    max_rounds=1)
            assert (polished == improved[i]).all(), (kk, i)


@pytest.mark.device
def test_planner_threads_commit_k_to_device_climb():
    plat, inst, prof = _setup(samples=3, seed=4, factor=2.0, scenario="S1")
    res = Planner(plat, engine="jax",
                  ls=LocalSearchConfig(commit_k=4)).plan(
        PlanRequest(instances=inst, profiles=prof))
    for name in PORTFOLIO_VARIANTS:
        if not name.endswith("-LS"):
            continue
        got = res.results[0][0][name]
        validate_schedule(inst, prof, got.start)
        assert got.cost <= res.results[0][0][name[:-3]].cost
        polished = local_search(inst, prof, plat, got.start, max_rounds=1)
        assert (polished == got.start).all(), name
    with pytest.raises(ValueError):
        LocalSearchConfig(commit_k=0)


# --- async rolling-horizon session -----------------------------------------

def _session_fixture(n_windows=3, samples=3, seed=3):
    plat, inst, _ = _setup(samples=samples, seed=seed, factor=1.6)
    W = deadline_from_asap(inst, 1.6)
    long = generate_profile("S3", n_windows * W, plat, J=48, seed=7)

    def wprofs(k):
        base = window_profile(long, k * W, W)
        return [base] + [generate_profile("S3", W, plat, J=16,
                                          seed=50 + 10 * k + j)
                         for j in range(2)]

    return plat, inst, wprofs


def test_session_three_windows_reproduce_eager_plans():
    plat, inst, wprofs = _session_fixture()
    planner = Planner(plat, engine="numpy")
    with planner.session(inst, wprofs, n_windows=3) as sess:
        got = [sess.plan_for(k) for k in range(3)]
    eager = Planner(plat, engine="numpy")
    for k, res in enumerate(got):
        ref = eager.plan(PlanRequest(instances=inst, profiles=wprofs(k),
                                     robust=True))
        assert (res.costs == ref.costs).all(), k
        for p in range(res.shape[1]):
            for name in res.variants:
                assert (res.results[0][p][name].start
                        == ref.results[0][p][name].start).all(), (k, name)
        assert res.pick(0).variant == ref.robust(0)[0]


def test_session_prefetches_next_window():
    plat, inst, wprofs = _session_fixture()
    with PlanningSession(Planner(plat, engine="numpy"), inst, wprofs,
                         n_windows=3, lookahead=1) as sess:
        sess.plan_for(0)
        assert 1 in sess._plans          # window 1 in flight/done
        assert 2 not in sess._plans
        sess.plan_for(1)
        assert 2 in sess._plans
        with pytest.raises(IndexError):
            sess.plan_for(3)
    with pytest.raises(RuntimeError):
        sess.plan_for(0)                 # closed session fails loudly


def test_session_sequence_source_and_out_of_range():
    plat, inst, wprofs = _session_fixture()
    seq = [wprofs(k) for k in range(2)]
    with PlanningSession(Planner(plat, engine="numpy"), inst, seq) as sess:
        assert sess.n_windows == 2
        a = sess.plan_for(1)
    assert a.shape[1] == 3
    with pytest.raises(ValueError):
        PlanningSession(Planner(plat, engine="numpy"), inst, wprofs)


def test_carbon_gate_replan_session_matches_gate_plans():
    from repro.runtime.carbon_gate import CarbonGate, fleet_platform

    plat = fleet_platform(pods=1, chip_watts_idle=10, chip_watts_work=25,
                          chips_per_pod=4)
    chunk = [[7, 9, 6, 8]]
    horizon = int(3 * sum(chunk[0]))
    profs = [generate_profile("S1", horizon, plat, J=16, seed=2 + i,
                              work_capacity=int(plat.p_work[:1].sum()))
             for i in range(4)]
    gate = CarbonGate(profs[0], plat, variant="auto", profiles=profs[1:],
                      engine="numpy")
    windows = [[profs[k]] for k in range(3)]
    with gate.replan_session(chunk, windows) as sess:
        for k in range(3):
            res = sess.plan_for(k)
            single = CarbonGate(profs[k], plat, variant="auto",
                                engine="numpy")
            plan = single.make_plan(chunk)
            name, _ = res.robust(0)
            assert name == plan.variant
            assert (res.results[0][0][name].start == plan.start).all()
