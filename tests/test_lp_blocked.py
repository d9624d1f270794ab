"""Blocked longest-path formulation: generator-driven differential suite.

The blocked form (``greedy_jax.BlockedLP``) must be bit-identical to the
dense ``longest_path_matrix`` — in the matrix values themselves (every
block width, every generator family) AND downstream (the jax engine's
greedy fan-out and device local search produce the same schedules whether
the lp rides resident on device or streams in chunks). The big-instance
regression (``pytest.mark.big``, ``make test-big``) proves the point of
the formulation: an instance past the dense ``LP_MAX_BYTES`` envelope
schedules on ``engine="jax"`` without the O(N^2) matrix ever existing,
matching the sequential ``schedule_reference`` oracle.
"""
import numpy as np
import pytest

from repro.cluster import make_cluster
from repro.core import (
    BlockedLP,
    LP_MAX_BYTES,
    build_instance,
    deadline_from_asap,
    generate_profile,
    heft_mapping,
    longest_path_matrix,
    lp_block_bytes,
    lp_matrix_bytes,
    prepare_graph,
    schedule_portfolio_grid,
    schedule_reference,
    trivial_mapping,
)
from repro.core.greedy_jax import NEG_PATH, pad_dims
from repro.workflows import make_workflow, wfgen_scale
from repro.workflows.generators import independent_tasks, layered_random

# one representative per workflows.generators family (the paper's suite):
# the four nf-core pipeline motifs, a WFGen scale-up, a layered random
# DAG, and the edge-free UCAS instances
FAMILIES = {
    "atacseq": lambda seed: make_workflow("atacseq", 3, seed=seed),
    "bacass": lambda seed: make_workflow("bacass", 4, seed=seed),
    "eager": lambda seed: make_workflow("eager", 3, seed=seed),
    "methylseq": lambda seed: make_workflow("methylseq", 4, seed=seed),
    "wfgen_scale": lambda seed: wfgen_scale("eager", 120, seed=seed),
    "layered_random": lambda seed: layered_random(48, 6, seed=seed),
    "independent_tasks": lambda seed: independent_tasks(
        np.random.default_rng(seed).integers(1, 9, size=60),
        name=f"independent-{seed}"),
}


def _instance(family, seed, mapping="heft"):
    plat = make_cluster(1, seed=seed)
    wf = FAMILIES[family](seed)
    mp = heft_mapping(wf, plat) if mapping == "heft" \
        else trivial_mapping(wf, plat)
    return build_instance(wf, mp, plat), plat


# --- matrix bit-identity ----------------------------------------------------

@pytest.mark.parametrize("seed", [0, 3, 11])
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_blocked_matrix_bit_identical(family, seed):
    inst, _ = _instance(family, seed)
    N = inst.num_tasks
    lp = longest_path_matrix(inst)
    blp = BlockedLP(inst)
    for block in (1, 7, 64, N):
        assert (blp.materialize(block) == lp).all(), (family, seed, block)
    # the backward column sweeps (what the chunked scan actually consumes
    # for the lst updates) must canonicalize to the same entries
    idx = np.arange(0, N, max(N // 9, 1))
    assert (blp.cols(idx) == lp[:, idx].T).all()
    assert (blp.rows(idx) == lp[idx]).all()
    # canonical sentinel: every no-path entry is exactly NEG_PATH
    assert np.isin(lp[lp < 0], (NEG_PATH,)).all()


def test_blocked_property_random_dags():
    hypothesis = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=25, deadline=None)
    @given(n=st.integers(6, 40), layers=st.integers(2, 6),
           p=st.floats(0.05, 0.5), seed=st.integers(0, 10_000))
    def prop(n, layers, p, seed):
        wf = layered_random(n, layers, p_edge=p, seed=seed)
        plat = make_cluster(1, seed=seed % 5)
        inst = build_instance(wf, trivial_mapping(wf, plat), plat)
        lp = longest_path_matrix(inst)
        blp = BlockedLP(inst)
        N = inst.num_tasks
        for block in (1, 7, 64, N):
            assert (blp.materialize(block) == lp).all(), block

    prop()


# --- the device build of a chunk's rows and columns -------------------------

def _device_chunk(blp, vs, padded_n):
    """The served chunk program's lp rows and columns for sources ``vs``,
    built on the device from the graph's level tables."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from repro.core.greedy_jax import _lp_sweep

    forward, backward = blp.sweep_tables
    sweep = jax.jit(lambda v, t: _lp_sweep(jnp, lax, v, t, padded_n))
    vs = jnp.asarray(vs, dtype=jnp.int32)
    return np.asarray(sweep(vs, forward)), np.asarray(sweep(vs, backward))


def _chunk_shapes(N, Np, rng):
    """Source chunks [V, B]: B = 1, B = Np (padded ids included), and a
    chunk in which one task appears in several orders and twice in one."""
    many = rng.integers(0, N, size=(3, 8)).astype(np.int32)
    many[:, 0] = many[0, 0]                  # one task in every order
    many[1, 5] = many[0, 0]                  # and twice in one order
    return [rng.integers(0, N, size=(4, 1)),
            np.stack([rng.permutation(Np) for _ in range(2)]),
            np.full((2, 4), Np - 1),         # a padded id, or the last task
            many]


@pytest.mark.device
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_device_chunk_rows_and_cols_equal_the_host_sweep(family):
    inst, _ = _instance(family, 5)
    N = inst.num_tasks
    Np, _ = pad_dims(N, 1)
    blp = BlockedLP(inst)
    for vs in _chunk_shapes(N, Np, np.random.default_rng(5)):
        want = blp.chunk_tensors(vs, Np)
        got = _device_chunk(blp, vs, Np)
        for g, w, what in zip(got, want, ("rows", "cols")):
            assert g.dtype == w.dtype and g.shape == w.shape, what
            assert np.array_equal(g, w), (family, vs.shape, what)


@pytest.mark.device
@pytest.mark.parametrize("family", ["atacseq", "eager", "layered_random"])
def test_maxplus_kernel_equals_its_jnp_twin(family):
    """The Pallas sweep (under the interpreter) and the jnp twin that
    serves CPU relax a chunk's state to the same int32 values, in both
    directions and in both row layouts the chunk program uses."""
    import jax.numpy as jnp

    from repro.kernels.maxplus_sweep import relax_levels

    inst, _ = _instance(family, 1)
    Np, _ = pad_dims(inst.num_tasks, 1)
    rng = np.random.default_rng(1)
    for table in BlockedLP(inst).sweep_tables:
        for shape in ((Np, 1, 12), (Np, 2, 128)):
            d0 = jnp.asarray(np.where(rng.random(shape) < 0.05, 0,
                                      NEG_PATH).astype(np.int32))
            twin = np.asarray(relax_levels(d0, *table))
            kernel = np.asarray(relax_levels(d0, *table, interpret=True))
            assert np.array_equal(kernel, twin), (family, shape)
            assert (twin >= np.asarray(d0)).all()


@pytest.mark.device
def test_device_chunk_property_random_dags():
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=15, deadline=None)
    @given(n=st.integers(6, 40), layers=st.integers(2, 6),
           p=st.floats(0.05, 0.5), seed=st.integers(0, 10_000),
           V=st.integers(1, 4), B=st.integers(1, 16))
    def prop(n, layers, p, seed, V, B):
        wf = layered_random(n, layers, p_edge=p, seed=seed)
        plat = make_cluster(1, seed=seed % 5)
        inst = build_instance(wf, trivial_mapping(wf, plat), plat)
        Np, _ = pad_dims(inst.num_tasks, 1)
        vs = np.random.default_rng(seed).integers(0, Np, size=(V, B))
        blp = BlockedLP(inst)
        want = blp.chunk_tensors(vs, Np)
        got = _device_chunk(blp, vs, Np)
        assert np.array_equal(got[0], want[0])
        assert np.array_equal(got[1], want[1])

    prop()


@pytest.mark.parametrize("family", ["atacseq", "independent_tasks",
                                    "layered_random"])
def test_sweep_tables_hold_each_edge_once_by_level(family):
    """Each edge sits in exactly one row of each table, in the row of its
    head's level, with a tail of a lower level; the rest of a row is the
    no-op self-loop (0, 0, 0) ahead of the row's sorted heads."""
    inst, _ = _instance(family, 2)
    N = inst.num_tasks
    v = np.repeat(np.arange(N), np.diff(inst.pred_ptr))
    u = inst.pred_idx
    back = np.zeros(N, dtype=np.int64)
    for t in inst.topo[::-1]:
        if len(inst.succs(t)):
            back[t] = back[inst.succs(t)].max() + 1
    want = {"forward": (inst.level, v, u), "backward": (back, u, v)}
    for name, table in zip(want, BlockedLP(inst).sweep_tables):
        dst, src, w = (np.asarray(a) for a in table)
        level, heads, tails = want[name]
        L, E = dst.shape
        assert L % 8 == 0 and E % 64 == 0 and L >= level.max()
        real = ~((dst == 0) & (src == 0) & (w == 0))
        rows = np.nonzero(real)[0]
        got = sorted(zip(dst[real], src[real], w[real]))
        assert got == sorted(zip(heads, tails, inst.dur[u])), name
        assert (level[dst[real]] == rows + 1).all(), name
        assert (level[src[real]] < level[dst[real]]).all(), name
        for row in range(L):
            assert (np.diff(dst[row]) >= 0).all(), (name, row)
            k = int((~real[row]).sum())
            assert not real[row, :k].any(), (name, row)


@pytest.mark.device
def test_served_blocked_plan_runs_no_host_sweep(monkeypatch):
    """The served blocked path builds its lp rows and columns on the
    device: with the host sweeps tripwired, the blocked grid still runs
    and equals the dense one, start for start."""
    inst, plat = _instance("methylseq", 4)
    T = deadline_from_asap(inst, 1.5)
    prof = generate_profile("S2", T, plat, J=16, seed=4)
    dense = schedule_portfolio_grid([inst], [[prof]], plat, engine="jax",
                                    variants=("press", "slackWR"))
    graph = prepare_graph(inst, plat, T,
                          lp_budget_bytes=_force_blocked_budget(inst, T))
    assert graph.lp_is_blocked

    def _no_host_sweep(*a, **k):
        raise AssertionError("host longest-path sweep on the served path")

    for name in ("rows", "cols", "chunk_tensors"):
        monkeypatch.setattr(BlockedLP, name, _no_host_sweep)
    blocked = schedule_portfolio_grid([inst], [[prof]], plat, engine="jax",
                                      variants=("press", "slackWR"),
                                      graphs=[graph])
    for name, ref in dense[0][0].items():
        assert np.array_equal(blocked[0][0][name].start, ref.start), name
        assert blocked[0][0][name].cost == ref.cost, name


# --- downstream schedules (greedy fan-out + device local search) ------------

def _n_orders():
    """Unique greedy configurations the full portfolio fans out — what
    the grid passes to ``BlockedLP.chunk_width``."""
    from repro.core.portfolio import _COMBOS
    return len(_COMBOS)


def _force_blocked_budget(inst, T, n_orders=None):
    """A budget that forces the blocked form but still admits >= 1 step."""
    n_orders = _n_orders() if n_orders is None else n_orders
    Np, _ = pad_dims(inst.num_tasks, T)
    budget = lp_block_bytes(2, n_orders, Np)
    if budget >= lp_matrix_bytes(inst.num_tasks):
        budget = lp_block_bytes(1, n_orders, Np)
    assert budget < lp_matrix_bytes(inst.num_tasks)
    return budget


@pytest.mark.device
@pytest.mark.parametrize("family,seed,factor,scenario", [
    ("atacseq", 3, 1.5, "S3"),
    ("wfgen_scale", 1, 2.0, "S1"),
    ("layered_random", 7, 1.5, "S4"),
    ("independent_tasks", 5, 2.0, "S2"),
])
def test_blocked_schedules_bit_identical(family, seed, factor, scenario):
    """Full 17-variant jax grid, dense lp vs streamed BlockedLP: greedy
    starts, -LS climbs and costs must match bit for bit."""
    inst, plat = _instance(family, seed)
    T = deadline_from_asap(inst, factor)
    prof = generate_profile(scenario, T, plat, J=16, seed=seed)
    dense = schedule_portfolio_grid([inst], [[prof]], plat, engine="jax")
    graph = prepare_graph(inst, plat, T,
                          lp_budget_bytes=_force_blocked_budget(inst, T))
    assert graph.lp_is_blocked
    blocked = schedule_portfolio_grid([inst], [[prof]], plat, engine="jax",
                                      graphs=[graph])
    for name, ref in dense[0][0].items():
        got = blocked[0][0][name]
        assert (got.start == ref.start).all(), name
        assert got.cost == ref.cost, name
        if not name.endswith("-LS") and name != "asap":
            oracle = schedule_reference(inst, prof, plat, name)
            assert got.cost == oracle.cost, name


@pytest.mark.device
def test_blocked_multi_profile_grid():
    """Profile-ensemble fan-out through the blocked path: every cell
    bit-identical to the dense engine's."""
    inst, plat = _instance("eager", 3)
    T = deadline_from_asap(inst, 1.5)
    profs = [generate_profile("S3", T, plat, J=16, seed=s) for s in (3, 9)]
    dense = schedule_portfolio_grid([inst], [profs], plat, engine="jax")
    blocked = schedule_portfolio_grid(
        [inst], [profs], plat, engine="jax",
        lp_budget_bytes=_force_blocked_budget(inst, T))
    for p in range(len(profs)):
        for name, ref in dense[0][p].items():
            assert (blocked[0][p][name].start == ref.start).all(), name
            assert blocked[0][p][name].cost == ref.cost, name


@pytest.mark.device
def test_mixed_dense_blocked_bucket():
    """One grid bucket mixing a dense-lp and a blocked-lp instance: the
    dense rows still ride the batched launch, the blocked row streams,
    and both match the all-dense grid."""
    inst_a, plat = _instance("bacass", 2)
    inst_b, _ = _instance("bacass", 6)
    T = max(deadline_from_asap(inst_a, 1.5), deadline_from_asap(inst_b, 1.5))
    profs = [[generate_profile("S1", T, plat, J=16, seed=1)]] * 2
    dense = schedule_portfolio_grid([inst_a, inst_b], profs, plat,
                                    engine="jax")
    graphs = [None,
              prepare_graph(inst_b, plat, T,
                            lp_budget_bytes=_force_blocked_budget(inst_b, T))]
    mixed = schedule_portfolio_grid([inst_a, inst_b], profs, plat,
                                    engine="jax", graphs=graphs)
    for i in range(2):
        for name, ref in dense[i][0].items():
            assert (mixed[i][0][name].start == ref.start).all(), (i, name)


# --- failure-mode boundary --------------------------------------------------

def test_dense_guard_names_shipped_api():
    inst, _ = _instance("bacass", 0)
    with pytest.raises(MemoryError, match="BlockedLP"):
        longest_path_matrix(inst, max_bytes=8)
    with pytest.raises(MemoryError, match="lp_budget_bytes"):
        longest_path_matrix(inst, max_bytes=8)


def test_blocked_floor_raises_with_byte_estimate():
    inst, _ = _instance("bacass", 0)
    blp = BlockedLP(inst, budget_bytes=64)
    V = _n_orders()
    floor = lp_block_bytes(1, V, 128)
    with pytest.raises(MemoryError, match=rf"{floor} bytes"):
        blp.chunk_width(V, 128)
    # one-step chunks are the floor: exactly the floor budget admits B=1
    assert BlockedLP(inst, budget_bytes=floor).chunk_width(V, 128) == 1


@pytest.mark.device
def test_grid_over_blocked_floor_raises():
    inst, plat = _instance("bacass", 0)
    T = deadline_from_asap(inst, 1.5)
    prof = generate_profile("S1", T, plat, J=16, seed=0)
    with pytest.raises(MemoryError, match="lp budget"):
        schedule_portfolio_grid([inst], [[prof]], plat, engine="jax",
                                lp_budget_bytes=64)


def test_resolve_lp_form_envelope():
    from repro.kernels.backend import resolve_lp_form

    assert resolve_lp_form(5000) == "dense"          # under LP_MAX_BYTES
    assert resolve_lp_form(6000) == "blocked"        # over it
    assert resolve_lp_form(100, lp_matrix_bytes(100)) == "dense"
    assert resolve_lp_form(100, lp_matrix_bytes(100) - 1) == "blocked"


def test_chunk_width_divides_padded_n():
    inst, _ = _instance("bacass", 0)
    for budget_steps, Np in ((3, 384), (9, 384), (64, 1024), (10_000, 640)):
        blp = BlockedLP(inst, budget_bytes=lp_block_bytes(budget_steps, 1,
                                                          Np))
        B = blp.chunk_width(1, Np)
        assert Np % B == 0 and B <= max(budget_steps, Np)


# --- big-instance regression (make test-big) --------------------------------

@pytest.mark.big
@pytest.mark.device
def test_big_instance_schedules_without_dense_matrix(monkeypatch):
    """An instance past LP_MAX_BYTES schedules on engine="jax" under a
    small lp_budget_bytes, bit-identical in cost (and starts) to the
    sequential schedule_reference oracle — with the dense-matrix
    constructor tripwired to prove it is never touched."""
    import repro.core.greedy_jax as gj

    plat = make_cluster(1, seed=0)
    wf = wfgen_scale("bacass", 3200, seed=0)
    rng = np.random.default_rng(0)
    inst = build_instance(wf, trivial_mapping(wf, plat), plat,
                          dur=rng.integers(1, 4, size=wf.n))
    assert lp_matrix_bytes(inst.num_tasks) > LP_MAX_BYTES
    T = deadline_from_asap(inst, 1.2)
    prof = generate_profile("S3", T, plat, J=24, seed=0)
    graph = prepare_graph(inst, plat, T, lp_budget_bytes=8 * 2**20)
    assert graph.lp_is_blocked

    def _no_dense(*a, **k):
        raise AssertionError("dense longest-path matrix materialized")

    monkeypatch.setattr(gj, "longest_path_matrix", _no_dense)
    res = schedule_portfolio_grid([inst], [[prof]], plat,
                                  variants=("press",), engine="jax",
                                  graphs=[graph])
    got = res[0][0]["press"]
    ref = schedule_reference(inst, prof, plat, "press")
    assert got.cost == ref.cost
    assert (got.start == ref.start).all()
