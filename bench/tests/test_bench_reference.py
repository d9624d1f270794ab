"""The plain reference against the planner at small sizes on the CPU, and
its control in a lower precision, which must not pass."""
import ml_dtypes
import numpy as np
import pytest

from harness import check, generate, program, reference, traffic

VARIANTS = ["asap"] + [s + w + r + l for s in ("slack", "press")
                       for w in ("", "W") for r in ("", "R")
                       for l in ("", "-LS")]
CONFIG = {"families": list(generate.MOTIFS), "target_tasks": 60,
          "nodes_per_type": 1, "cluster_seed": 0, "deadline_factor": 2.0,
          "scenarios": ["S1", "S2", "S3", "S4"], "intervals": 48,
          "engine": "jax",
          "planner": {"k": 3, "mu": 10, "ls_max_rounds": 200, "commit_k": 32},
          "service": {"workers": 1, "max_batch": 8, "max_queue": 64}}
TRAFFIC = {"workflow_seeds": [3], "profile_seeds": 2, "variants": VARIANTS}


@pytest.fixture(scope="module")
def tenants():
    cluster = generate.make_cluster(CONFIG["nodes_per_type"], seed=0)
    plat = program.platform(cluster)
    pool = traffic.build_pool(CONFIG, TRAFFIC, cluster, plat)
    return cluster, plat, pool


def test_the_reference_equals_the_jax_engine_schedule_for_schedule(tenants):
    from repro.api import LocalSearchConfig, Planner

    cluster, plat, pool = tenants
    planner = Planner(plat, engine="jax",
                      ls=LocalSearchConfig(mu=10, max_rounds=200,
                                           commit_k=32))
    for i, entry in enumerate(pool):
        profiles = traffic.ensemble(CONFIG, TRAFFIC, entry,
                                    cluster.idle_total, 2**31 + 7, 0, i)
        assert program.same_graph(entry.instance, entry.graph)
        res = planner.plan(program.request(entry.instance, profiles,
                                           VARIANTS))
        want = check.reference_rows(CONFIG, entry.graph, profiles, VARIANTS)
        assert check.differing(program.rows(res), want) == (0, 0)
        for (p, v), (start, _) in want.items():
            assert reference.feasible(entry.graph, profiles[p].T, start)


def test_shift_gains_equal_the_move_by_move_definition():
    rng = np.random.default_rng(0)
    T, N, mu = 60, 12, 10
    rem = rng.integers(-30, 30, T)
    start = rng.integers(0, T - 12, N)
    dur = rng.integers(1, 12, N)
    work = rng.integers(0, 20, N)
    lo = np.maximum(start - mu, 0) - start
    hi = np.minimum(start + mu, T - dur) - start
    gains = reference.shift_gains(rem, start, dur, work, lo, hi, mu)
    for v in range(N):
        s, e = int(start[v]), int(start[v] + dur[v])
        for d in range(-mu, mu + 1):
            legal = lo[v] <= d <= hi[v] and d != 0 and work[v] > 0
            want = reference.move_gain(rem, s, e, s + d, int(work[v])) \
                if legal else reference.NEG
            assert gains[v, d + mu] == want


def test_the_climb_in_bfloat16_is_not_correct(tenants):
    cluster, _, pool = tenants
    for i, entry in enumerate(pool):
        profiles = traffic.ensemble(CONFIG, TRAFFIC, entry,
                                    cluster.idle_total, 2**31 + 9, 0, i)
        want = check.reference_rows(CONFIG, entry.graph, profiles, VARIANTS)
        got = {key: (start, reference.cost(entry.graph, profiles[key[0]],
                                           start))
               for key, start in reference.portfolio(
                   entry.graph, profiles, VARIANTS, k=3, mu=10, commit_k=32,
                   max_rounds=200, dtype=ml_dtypes.bfloat16).items()}
        rows, costs = check.differing(got, want)
        assert rows > 0 and costs > 0


def test_the_control_one_precision_lower_is_not_correct(tenants):
    cluster, _, pool = tenants
    entry = pool[0]
    profiles = traffic.ensemble(CONFIG, TRAFFIC, entry, cluster.idle_total,
                                2**31 + 11, 0, 0)
    want = check.reference_rows(CONFIG, entry.graph, profiles, VARIANTS)
    got = check.reference_rows(CONFIG, entry.graph, profiles, VARIANTS,
                               control=True)
    assert check.differing(got, want)[0] > 0
