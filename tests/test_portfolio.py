"""Portfolio engine: equivalence with the per-variant loop, segment greedy,
endpoint-rule regression, batched local search, batched gain kernel."""
import numpy as np
import pytest

from repro.cluster import make_cluster
from repro.core import (
    PORTFOLIO_VARIANTS,
    build_instance,
    deadline_from_asap,
    generate_profile,
    heft_mapping,
    prepare_instance,
    schedule,
    schedule_cost,
    schedule_portfolio,
    validate_schedule,
)
from repro.core.greedy import (
    greedy_core_segments,
    greedy_schedule,
    greedy_schedule_segments,
    segment_state,
)
from repro.core.local_search_jax import local_search_portfolio_multi
from repro.core.scores import task_order
from repro.core.subdivide import candidate_mask
from repro.workflows import make_workflow


def _setup(kind="eager", samples=3, seed=3, factor=1.5, scenario="S3"):
    plat = make_cluster(1, seed=seed)
    wf = make_workflow(kind, samples, seed=seed)
    inst = build_instance(wf, heft_mapping(wf, plat), plat)
    T = deadline_from_asap(inst, factor)
    prof = generate_profile(scenario, T, plat, J=16, seed=seed)
    return plat, inst, prof


@pytest.mark.parametrize("seed,kind,scenario,factor", [
    (3, "eager", "S3", 1.5),
    (1, "atacseq", "S1", 1.0),
    (7, "bacass", "S4", 2.0),
    (5, "methylseq", "S2", 1.5),
])
def test_portfolio_bit_identical_to_variant_loop(seed, kind, scenario,
                                                 factor):
    from repro.core import schedule_reference

    plat, inst, prof = _setup(kind=kind, seed=seed, factor=factor,
                              scenario=scenario)
    port = schedule_portfolio(inst, prof, plat)
    assert set(port) == set(PORTFOLIO_VARIANTS)
    for name in PORTFOLIO_VARIANTS:
        # schedule_reference is the independent sequential oracle
        # (schedule() itself is a Planner shim since the API redesign)
        ref = schedule_reference(inst, prof, plat, name)
        assert (port[name].start == ref.start).all(), name
        assert port[name].cost == ref.cost, name
        shim = schedule(inst, prof, plat, name)
        assert (shim.start == ref.start).all(), name


def test_portfolio_reuses_prepared_instance():
    plat, inst, prof = _setup()
    prep = prepare_instance(inst, prof, plat)
    a = schedule_portfolio(inst, prof, plat, prep=prep)
    b = schedule_portfolio(inst, prof, plat, prep=prep)
    for name in PORTFOLIO_VARIANTS:
        assert (a[name].start == b[name].start).all()
    # prep is never mutated: est0 still equals a fresh EST computation
    assert (prep.est0 == prepare_instance(inst, prof, plat).est0).all()


@pytest.mark.parametrize("sc,wt,rf", [
    ("press", True, True), ("slack", False, False), ("press", False, True),
    ("slack", True, False),
])
def test_segment_greedy_matches_per_unit(sc, wt, rf):
    for seed in (0, 4):
        plat, inst, prof = _setup(seed=seed, factor=1.0, scenario="S1")
        a = greedy_schedule(inst, prof, plat, sc, wt, rf)
        b = greedy_schedule_segments(inst, prof, plat, sc, wt, rf)
        assert (a == b).all()


def _per_unit_reference(inst, profile, est, lst, order):
    """greedy_schedule's loop body with injected EST/LST/order (mirrors
    repro.core.greedy so overrun states can be exercised directly)."""
    from repro.core.estlst import lower_lst_from, raise_est_from

    T = profile.T
    est, lst = est.copy(), lst.copy()
    mask = candidate_mask(inst, profile, refined=False)
    rem = profile.unit_budget(inst.idle_total).astype(np.int64).copy()
    start = np.zeros(inst.num_tasks, dtype=np.int64)
    scheduled = np.zeros(inst.num_tasks, dtype=bool)
    for v in order:
        a, b = int(est[v]), int(lst[v])
        cand = np.flatnonzero(mask[a:b + 1])
        s = a if len(cand) == 0 else int(cand[np.argmax(rem[cand + a])] + a)
        e = s + int(inst.dur[v])
        start[v] = s
        scheduled[v] = True
        rem[s:e] -= int(inst.task_work[v])
        mask[s] = True
        if e <= T:                       # the endpoint rule under test
            mask[e] = True
        raise_est_from(inst, est, int(v), s, scheduled)
        lower_lst_from(inst, lst, int(v), s, scheduled)
    return start, mask


def test_endpoint_rule_on_overrunning_task():
    """Regression (jax/segment endpoint semantics): a task whose end
    overruns the horizon must NOT create a candidate point at T — both
    interval representations must keep identical candidate sets and starts
    even when a (pathologically placed) task clips at the deadline."""
    plat, inst, prof = _setup(samples=2, seed=2, factor=1.5)
    T = prof.T
    from repro.core.estlst import compute_est, compute_lst
    est = compute_est(inst)
    lst = compute_lst(inst, T)
    order = task_order(inst, est, lst, "press", False, plat)
    # force a sink task, placed LAST, to overrun the horizon: pin its window
    # to T - 1 so e = s + dur > T (no successors -> no cascading placements)
    sinks = np.flatnonzero(np.diff(inst.succ_ptr) == 0)
    v0 = int(sinks[np.argmax(inst.dur[sinks])])
    assert inst.dur[v0] >= 2, "need a clipping sink task"
    order = np.concatenate([order[order != v0], [v0]])
    est = est.copy()
    lst = lst.copy()
    est[v0] = lst[v0] = T - 1            # e = T - 1 + dur > T
    ref_start, ref_mask = _per_unit_reference(inst, prof, est, lst, order)
    pts0, vals0 = segment_state(inst, prof, refined=False)
    seg_start = greedy_core_segments(inst, T, est, lst, order, pts0, vals0)
    assert (ref_start == seg_start).all()
    assert ref_start[v0] + int(inst.dur[v0]) > T   # it really clipped
    # T is a profile bound, not a task endpoint: the overrun must not have
    # added any new candidate point at or beyond T
    assert ref_mask[T]                   # from the profile bounds
    assert not (ref_start[v0] + inst.dur[v0] <= T)


@pytest.mark.device
def test_device_greedy_matches_numpy_at_tight_deadline():
    """Regression companion: the jax scan uses the numpy endpoint rule."""
    from repro.core import schedule_portfolio_grid

    for seed, kind in ((0, "eager"), (6, "bacass")):
        plat, inst, prof = _setup(kind=kind, seed=seed, factor=1.0,
                                  scenario="S2")
        a = greedy_schedule(inst, prof, plat, "press", True, False)
        (cells,) = schedule_portfolio_grid([inst], [[prof]], plat,
                                           variants=["pressW"], engine="jax",
                                           validate=True)
        assert (a == cells[0]["pressW"].start).all()


@pytest.mark.device
def test_jax_engine_greedy_rows_match_numpy():
    plat, inst, prof = _setup(samples=2, seed=1)
    pn = schedule_portfolio(inst, prof, plat, engine="numpy")
    pj = schedule_portfolio(inst, prof, plat, engine="jax")
    for name in PORTFOLIO_VARIANTS:
        if name.endswith("-LS"):
            continue                      # batched climber differs by design
        assert (pn[name].start == pj[name].start).all(), name


@pytest.mark.device
def test_instance_batched_fanout_matches_reference():
    """Instances in two shape buckets, two of them sharing one (same
    workflow/platform, different profile budgets), ride one jax-engine
    grid call; every (instance, profile, combo) row must equal the numpy
    engine's and the numpy reference greedy."""
    from repro.core import schedule_portfolio_grid
    from repro.core.cawosched import Variant
    from repro.core.greedy_jax import pad_dims
    from repro.core.portfolio import _COMBOS

    plat = make_cluster(1, seed=3)
    instances, grid = [], []
    for kind, samples in (("eager", 2), ("eager", 2), ("bacass", 6)):
        wf = make_workflow(kind, samples, seed=3)
        inst = build_instance(wf, heft_mapping(wf, plat), plat)
        T = deadline_from_asap(inst, 1.5)
        instances.append(inst)
        grid.append([generate_profile(s, T, plat, J=12,
                                      seed=3 + len(instances))
                     for s in ("S1", "S4")])
    buckets = {pad_dims(i.num_tasks, g[0].T)
               for i, g in zip(instances, grid)}
    assert len(buckets) == 2
    combos = _COMBOS[:3]
    names = [Variant(sc, wt, rf, ls=False).name for sc, wt, rf in combos]
    got = schedule_portfolio_grid(instances, grid, plat, variants=names,
                                  engine="jax")
    want = schedule_portfolio_grid(instances, grid, plat, variants=names,
                                   engine="numpy")
    for inst, profs, row, ref_row in zip(instances, grid, got, want):
        for prof, cell, ref_cell in zip(profs, row, ref_row):
            for name, (sc, wt, rf) in zip(names, combos):
                ref = greedy_schedule(inst, prof, plat, sc, wt, rf)
                assert (cell[name].start == ref).all(), name
                assert (cell[name].start == ref_cell[name].start).all()


@pytest.mark.device
def test_jax_engine_asap_only_does_not_fan_out():
    """Regression: an empty greedy combo set (asap-only request) must not
    crash the jax engine's fan-out stacking."""
    plat, inst, prof = _setup(samples=2, seed=0)
    res = schedule_portfolio(inst, prof, plat, variants=("asap",),
                             engine="jax")
    assert set(res) == {"asap"}
    ref = schedule(inst, prof, plat, "asap")
    assert (res["asap"].start == ref.start).all()


@pytest.mark.device
def test_batched_portfolio_local_search_monotone_and_valid():
    plat, inst, prof = _setup(samples=3, seed=4, factor=2.0, scenario="S1")
    combos = (("press", False, True), ("slack", True, False),
              ("press", True, True))
    stack = np.stack([greedy_schedule(inst, prof, plat, s, w, r)
                      for (s, w, r) in combos])
    base = [schedule_cost(inst, prof, st) for st in stack]
    budgets = np.broadcast_to(prof.unit_budget(inst.idle_total),
                              (len(stack), prof.T))
    improved = local_search_portfolio_multi(inst, prof.T, budgets, stack,
                                            mu=10)
    for i in range(len(combos)):
        validate_schedule(inst, prof, improved[i])
        assert schedule_cost(inst, prof, improved[i]) <= base[i]


@pytest.mark.device
def test_gain_scan_batched_matches_rows(climb_gains):
    """The climb's batched gain call equals ``gain_scan`` row for row, on
    the jnp twin and under the Pallas interpreter."""
    from repro.kernels.gain_scan import gain_scan

    rng = np.random.default_rng(0)
    B, N, T, mu = 3, 40, 160, 6
    rem = rng.integers(-30, 60, (B, T)).astype(np.float32)
    dur = rng.integers(1, 12, N).astype(np.float32)
    work = rng.integers(0, 25, N).astype(np.float32)
    start = np.stack([rng.integers(0, T - 15, N) for _ in range(B)]) \
        .astype(np.float32)
    lo = np.maximum(start - rng.integers(0, mu + 3, (B, N)), 0) \
        .astype(np.float32)
    hi = np.minimum(start + rng.integers(0, mu + 3, (B, N)), T - dur) \
        .astype(np.float32)
    for interpret in (None, True):
        got = climb_gains(rem, start, dur, work, lo, hi, mu=mu,
                          interpret=interpret)
        for b in range(B):
            want = np.asarray(gain_scan(rem[b], start[b], dur, work, lo[b],
                                        hi[b], mu=mu, interpret=interpret))
            np.testing.assert_allclose(got[b], want, rtol=0, atol=0)


def test_interpret_autodetect_resolves_cpu():
    import jax

    from repro.kernels.backend import resolve_interpret

    assert resolve_interpret(None) == (jax.default_backend() == "cpu")
    assert resolve_interpret(True) is True
    assert resolve_interpret(False) is False
