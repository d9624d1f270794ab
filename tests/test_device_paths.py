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
