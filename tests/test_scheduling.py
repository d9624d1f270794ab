"""Greedy variants, EST/LST, local search, ASAP — behavioural tests."""
import numpy as np
import pytest

from repro.cluster import make_cluster
from repro.core import (
    ALL_VARIANTS,
    asap_schedule,
    build_instance,
    compute_est,
    compute_lst,
    deadline_from_asap,
    generate_profile,
    heft_mapping,
    makespan,
    schedule,
    schedule_cost,
    validate_schedule,
)
from repro.core.estlst import est_lst_jnp
from repro.core.greedy import greedy_schedule
from repro.core.local_search import local_search, move_gain, apply_move, timeline_cost
from repro.core.local_search_jax import local_search_portfolio_multi
from repro.workflows import make_workflow


def _setup(kind="eager", samples=5, seed=3, factor=1.5, scenario="S3"):
    plat = make_cluster(1, seed=seed)
    wf = make_workflow(kind, samples, seed=seed)
    inst = build_instance(wf, heft_mapping(wf, plat), plat)
    T = deadline_from_asap(inst, factor)
    prof = generate_profile(scenario, T, plat, J=16, seed=seed)
    return plat, inst, prof


def test_est_lst_sanity():
    plat, inst, prof = _setup()
    est = compute_est(inst)
    lst = compute_lst(inst, prof.T)
    assert (est <= lst).all()
    # ASAP = EST, makespan = max completion
    asap = asap_schedule(inst)
    assert (asap == est).all()
    assert makespan(inst, asap) <= prof.T
    ej, lj = est_lst_jnp(inst, prof.T)
    assert (np.asarray(ej) == est).all()
    assert (np.asarray(lj) == lst).all()


@pytest.mark.parametrize("variant", [v.name for v in ALL_VARIANTS] + ["asap"])
def test_all_variants_valid(variant):
    plat, inst, prof = _setup()
    r = schedule(inst, prof, plat, variant)
    validate_schedule(inst, prof, r.start)


def test_greedy_deterministic():
    plat, inst, prof = _setup()
    a = greedy_schedule(inst, prof, plat, score="press", weighted=True,
                        refined=True)
    b = greedy_schedule(inst, prof, plat, score="press", weighted=True,
                        refined=True)
    assert (a == b).all()


def test_local_search_monotone_and_valid():
    plat, inst, prof = _setup(factor=2.0)
    g = greedy_schedule(inst, prof, plat, score="slack")
    c0 = schedule_cost(inst, prof, g)
    s = local_search(inst, prof, plat, g, mu=10)
    validate_schedule(inst, prof, s)
    assert schedule_cost(inst, prof, s) <= c0


def test_batched_ls_matches_reference_quality():
    plat, inst, prof = _setup(factor=2.0, scenario="S1")
    g = greedy_schedule(inst, prof, plat, score="press", refined=True)
    c0 = schedule_cost(inst, prof, g)
    ref = schedule_cost(inst, prof, local_search(inst, prof, plat, g))
    (climbed,) = local_search_portfolio_multi(
        inst, prof.T, prof.unit_budget(inst.idle_total)[None], g[None])
    bat = schedule_cost(inst, prof, climbed)
    assert bat <= c0
    # both hill climbers should land in the same ballpark
    assert bat <= max(1.15 * ref, ref + 50)


def test_move_gain_matches_recompute():
    rng = np.random.default_rng(0)
    T = 200
    rem = rng.integers(-50, 80, T).astype(np.int64)
    for _ in range(50):
        w = int(rng.integers(1, 40))
        dur = int(rng.integers(1, 30))
        s = int(rng.integers(0, T - dur - 25))
        new_s = s + int(rng.integers(-min(20, s), 20))
        new_s = max(0, min(new_s, T - dur))
        base = rem.copy()
        base[s:s + dur] -= w            # place the task
        g = move_gain(base, s, s + dur, new_s, w)
        after = base.copy()
        apply_move(after, s, s + dur, new_s, w)
        assert timeline_cost(base) - timeline_cost(after) == g


def test_greedy_beats_asap_usually():
    wins = total = 0
    for seed in range(4):
        plat, inst, prof = _setup(seed=seed, factor=2.0, scenario="S1")
        base = schedule(inst, prof, plat, "asap").cost
        best = min(schedule(inst, prof, plat, v.name).cost
                   for v in ALL_VARIANTS)
        total += 1
        if best <= base:
            wins += 1
    assert wins == total            # with 2x deadline slack we never lose
