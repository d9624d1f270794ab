"""Device (jittable) scheduler paths and .dot I/O."""
import os

import numpy as np
import pytest

from repro.cluster import make_cluster
from repro.core import (
    build_instance,
    deadline_from_asap,
    generate_profile,
    heft_mapping,
    schedule_cost,
    schedule_portfolio_grid,
    schedule_reference,
    validate_schedule,
)
from repro.core.cawosched import Variant
from repro.core.greedy import greedy_schedule
from repro.workflows import make_workflow
from repro.workflows.dot_io import load_dot, save_dot


@pytest.mark.device
@pytest.mark.parametrize("seed,kind,scen,sc,wt,rf", [
    (3, "eager", "S3", "press", True, True),
    (1, "atacseq", "S1", "slack", False, False),
    (7, "bacass", "S4", "press", False, True),
    (2, "methylseq", "S2", "slack", True, False),
])
def test_device_greedy_matches_reference_exactly(seed, kind, scen, sc, wt,
                                                 rf):
    plat = make_cluster(1, seed=seed)
    wf = make_workflow(kind, 3, seed=seed)
    inst = build_instance(wf, heft_mapping(wf, plat), plat)
    T = deadline_from_asap(inst, 1.5)
    prof = generate_profile(scen, T, plat, J=12, seed=seed)
    name = Variant(sc, wt, rf, ls=False).name
    a = greedy_schedule(inst, prof, plat, score=sc, weighted=wt, refined=rf)
    (cells,) = schedule_portfolio_grid([inst], [[prof]], plat,
                                       variants=[name], engine="jax",
                                       validate=True)
    b = cells[0][name].start
    assert (a == b).all()
    validate_schedule(inst, prof, b)
    assert schedule_cost(inst, prof, a) == schedule_cost(inst, prof, b)
    # the numpy engine and the sequential reference land on the same row
    (ref_cells,) = schedule_portfolio_grid([inst], [[prof]], plat,
                                           variants=[name], engine="numpy")
    assert (ref_cells[0][name].start == b).all()
    ref = schedule_reference(inst, prof, plat, name)
    assert (ref.start == b).all() and ref.cost == cells[0][name].cost


def _grid_row(inst, profs, plat, combos, est0, lst0, lp):
    """One instance's bucket-padded launch row, built from the reference's
    own est/lst, orders, masks and budgets."""
    from repro.core.greedy_jax import pad_budget, pad_dims, pad_masks, \
        pad_orders, padded_shared
    from repro.core.scores import task_order
    from repro.core.subdivide import candidate_mask

    _, Tp = pad_dims(inst.num_tasks, profs[0].T)
    dur, work, lp_j, est_j, lst_j, tail = padded_shared(inst, est0, lst0, lp)
    budgets = pad_budget(np.stack([p.unit_budget(inst.idle_total)
                                   for p in profs]), Tp)
    masks = pad_masks(np.stack([np.stack([candidate_mask(inst, p, r)
                                          for (_, _, r) in combos])
                                for p in profs]), Tp)
    orders = pad_orders(np.stack([task_order(inst, est0, lst0, s, w, plat)
                                  for (s, w, _) in combos]), tail)
    return (dur, work, lp_j, budgets, masks, est_j, lst_j, orders)


def _fallbacks(inst, prof, order, start, refined):
    """Placements whose start was no candidate point when the task was
    placed: the reference's ``s = est[v]`` fallback (no feasible slot)."""
    from repro.core.subdivide import candidate_mask

    points = set(np.flatnonzero(candidate_mask(inst, prof, refined)))
    n = 0
    for v in order:
        s = int(start[v])
        e = s + int(inst.dur[v])
        n += s not in points
        points.add(s)
        if e <= prof.T:
            points.add(e)
    return n


@pytest.mark.device
@pytest.mark.parametrize("form", ["dense", "blocked"])
@pytest.mark.parametrize("case", ["diverging", "tight", "overrun"])
def test_batched_step_matches_reference_row_by_row(monkeypatch, case, form):
    """Every (profile, order) row of one batched scan equals
    ``greedy_schedule`` for that profile and variant, through the dense
    grid and the blocked chunk scan. Two profiles and all eight orders, so
    the rows' est/lst diverge; the bucket pads the tasks. ``tight`` sets
    the deadline at the ASAP makespan, so windows hold no candidate point
    (the ``est[v]`` fallback) and critical tasks end at T; ``overrun``
    pins a sink at ``T - 1`` on a horizon with no padding (T a multiple
    of 256), so its end overruns T and marks no point."""
    from repro.core import BlockedLP, longest_path_matrix, lp_block_bytes
    from repro.core.estlst import compute_est, compute_lst
    from repro.core.greedy_jax import greedy_fanout_grid_jax, pad_dims
    from repro.core.portfolio import _COMBOS
    from repro.core.scores import task_order

    plat = make_cluster(1, seed=4)
    wf = make_workflow("eager", 3, seed=4)
    inst = build_instance(wf, heft_mapping(wf, plat), plat)
    N = inst.num_tasks
    T = deadline_from_asap(inst, 1.0 if case == "tight" else 1.5)
    if case == "overrun":
        T = -(-T // 256) * 256
    profs = [generate_profile(s, T, plat, J=12, seed=9)
             for s in ("S1", "S4")]
    est0, lst0 = compute_est(inst), compute_lst(inst, T)
    sink = None
    if case == "overrun":
        sink = next(v for v in range(N)
                    if not len(inst.succs(v)) and inst.dur[v] >= 2)
        est0[sink] = lst0[sink] = T - 1
        monkeypatch.setattr("repro.core.greedy.compute_est",
                            lambda _: est0.copy())
        monkeypatch.setattr("repro.core.greedy.compute_lst",
                            lambda *_: lst0.copy())
    Np, Tp = pad_dims(N, T)
    assert Np > N                                     # padded tasks ride
    V = len(_COMBOS)
    lp = longest_path_matrix(inst) if form == "dense" else \
        BlockedLP(inst, budget_bytes=lp_block_bytes(32, V, Np))
    row = _grid_row(inst, profs, plat, _COMBOS, est0, lst0, lp)
    got = np.asarray(greedy_fanout_grid_jax([row]))[0, :, :, :N]

    want = np.stack([[greedy_schedule(inst, p, plat, s, w, r)
                      for (s, w, r) in _COMBOS] for p in profs])
    assert np.array_equal(got, want)
    # the rows diverge: across profiles, and across orders
    assert (want[0] != want[1]).any()
    assert (want[:, :1] != want[:, 1:]).any()
    if case == "tight":
        assert sum(_fallbacks(inst, p, task_order(inst, est0, lst0, s, w,
                                                  plat), want[i, c], r)
                   for i, p in enumerate(profs)
                   for c, (s, w, r) in enumerate(_COMBOS)) > 0
        assert ((want + inst.dur).max(axis=-1) == T).all()
    if case == "overrun":
        assert Tp == T
        assert (want[:, :, sink] + inst.dur[sink] > T).all()


def test_dot_roundtrip(tmp_path):
    wf = make_workflow("bacass", 3, seed=5)
    p = os.path.join(tmp_path, "wf.dot")
    save_dot(wf, p)
    wf2 = load_dot(p, name=wf.name)
    assert wf2.n == wf.n and wf2.m == wf.m
    np.testing.assert_array_equal(np.sort(wf.edges, axis=0),
                                  np.sort(wf2.edges, axis=0))
    np.testing.assert_array_equal(wf.node_w, wf2.node_w)


def test_dot_pseudo_task_cleanup(tmp_path):
    p = os.path.join(tmp_path, "nf.dot")
    with open(p, "w") as f:
        f.write("""digraph G {
  a [weight=10];
  nf_internal_1;
  b [weight=20];
  a -> nf_internal_1;
  nf_internal_1 -> b;
  a -> b [weight=3];
}
""")
    wf = load_dot(p, pseudo_patterns=(r"nf_internal",), seed=0)
    assert wf.n == 2
    # reconnection keeps a -> b (deduplicated)
    assert wf.m == 1
    wf.validate()
