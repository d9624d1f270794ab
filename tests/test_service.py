"""PlanService tier-1 suite: admission, coalescing, structured errors,
deadline budgets, the degradation ladder's fast paths, resolved-grid
validation, mip_gap surfacing, and the PlanningSession robustness fixes.

The heavier fault-matrix scenarios (seeded sweeps, watchdog hangs,
quarantine bisects) live in tests/test_chaos.py behind the ``chaos``
marker (`make test-chaos`); this file keeps the acceptance-critical
behaviours in the default tier-1 gate.
"""
import os
import threading
import time

import numpy as np
import pytest

from repro.api import Planner, PlanRequest, PlanningSession
from repro.api.request import validate_resolved
from repro.cluster import make_cluster
from repro.core import (
    build_instance,
    deadline_from_asap,
    generate_profile,
    heft_mapping,
    validate_schedule,
)
from repro.runtime.fault import FaultSpec, ServiceFaultInjector
from repro.serve import (
    InvalidRequest,
    Overloaded,
    PlanFailure,
    PlanService,
    ServiceClosed,
    ServiceError,
    TicketCancelled,
    TicketJournal,
    decode_ticket,
    encode_ticket,
)
from repro.workflows import make_workflow


def _setup(kind="eager", samples=3, seed=3, factor=1.5, scenario="S3"):
    plat = make_cluster(1, seed=seed)
    wf = make_workflow(kind, samples, seed=seed)
    inst = build_instance(wf, heft_mapping(wf, plat), plat)
    T = deadline_from_asap(inst, factor)
    prof = generate_profile(scenario, T, plat, J=16, seed=seed)
    return plat, inst, prof


def _assert_same_plan(a, b):
    """Bit-identity of two PlanResults: costs, and every cell's starts."""
    assert a.variants == b.variants
    assert (a.costs == b.costs).all()
    for ra, rb in zip(a.results, b.results):
        for ca, cb in zip(ra, rb):
            for name in ca:
                assert (ca[name].start == cb[name].start).all(), name


# --- fault-free service == direct Planner.plan -----------------------------

def test_service_fault_free_bit_identical_to_planner():
    plat, inst, prof = _setup()
    planner = Planner(plat, engine="numpy")
    direct = planner.plan(PlanRequest(instances=inst, profiles=prof))
    with PlanService(planner.clone()) as svc:
        res = svc.plan(PlanRequest(instances=inst, profiles=prof))
    _assert_same_plan(res, direct)
    assert not res.degraded
    assert res.fallback_stage == "heuristic"
    assert res.attempts == ("heuristic:ok",)


def test_service_coalesces_concurrent_requests_bit_identically():
    plat, inst, prof = _setup(samples=2, seed=5)
    wf2 = make_workflow("eager", 2, seed=9)
    inst2 = build_instance(wf2, heft_mapping(wf2, plat), plat)
    prof2 = generate_profile("S1", deadline_from_asap(inst2, 1.5), plat,
                             J=16, seed=7)
    planner = Planner(plat, engine="numpy")
    d1 = planner.plan(PlanRequest(instances=inst, profiles=prof))
    d2 = planner.plan(PlanRequest(instances=inst2, profiles=prof2))
    with PlanService(planner.clone()) as svc:
        svc.pause()                      # hold the worker: deterministic
        t1 = svc.submit(PlanRequest(instances=inst, profiles=prof))
        t2 = svc.submit(PlanRequest(instances=inst2, profiles=prof2))
        t3 = svc.submit(PlanRequest(instances=inst, profiles=prof))
        svc.resume()
        r1, r2, r3 = (t.result(timeout=120) for t in (t1, t2, t3))
        stats = svc.stats()
    _assert_same_plan(r1, d1)
    _assert_same_plan(r2, d2)
    _assert_same_plan(r3, d1)
    # all three tickets share one coalesce key -> ONE combined launch
    assert stats["batches"] == 1
    assert stats["coalesced_requests"] == 3
    assert stats["coalesce_ratio"] == 3.0
    assert stats["completed"] == 3 and stats["degraded"] == 0
    assert stats["latency"]["n"] == 3


def test_service_mixed_solver_queue_groups_by_key():
    plat, inst, prof = _setup()
    planner = Planner(plat, engine="numpy")
    da = planner.plan(PlanRequest(instances=inst, profiles=prof,
                                  solver="asap"))
    dh = planner.plan(PlanRequest(instances=inst, profiles=prof))
    with PlanService(planner.clone()) as svc:
        svc.pause()
        ta = svc.submit(PlanRequest(instances=inst, profiles=prof,
                                    solver="asap"))
        th = svc.submit(PlanRequest(instances=inst, profiles=prof))
        svc.resume()
        ra, rh = ta.result(timeout=120), th.result(timeout=120)
        assert svc.stats()["batches"] == 2      # different solver keys
    _assert_same_plan(ra, da)
    _assert_same_plan(rh, dh)
    assert ra.solver == "asap" and not ra.degraded


# --- structured rejections -------------------------------------------------

def test_service_overloaded_is_structured():
    plat, inst, prof = _setup()
    with PlanService(Planner(plat, engine="numpy"), max_queue=2) as svc:
        svc.pause()
        svc.submit(PlanRequest(instances=inst, profiles=prof))
        svc.submit(PlanRequest(instances=inst, profiles=prof))
        with pytest.raises(Overloaded) as ei:
            svc.submit(PlanRequest(instances=inst, profiles=prof))
        d = ei.value.to_dict()
        assert d["code"] == "overloaded"
        assert d["queue_depth"] == 2 and d["max_queue"] == 2
        assert svc.stats()["rejected_overloaded"] == 1
        svc.resume()


def test_service_invalid_request_rejected_at_admission():
    plat, inst, prof = _setup()
    with PlanService(Planner(plat, engine="numpy")) as svc:
        with pytest.raises(InvalidRequest) as ei:
            svc.submit(PlanRequest(instances=inst, profiles=[]))
        assert ei.value.to_dict()["code"] == "invalid_request"
        # an infeasible horizon is caught structurally, not downstream
        tiny = generate_profile("S1", 2, plat, J=1, seed=0)
        with pytest.raises(InvalidRequest):
            svc.submit(PlanRequest(instances=inst, profiles=tiny))
        assert svc.stats()["rejected_invalid"] == 2
        # the service still serves healthy requests afterwards
        res = svc.plan(PlanRequest(instances=inst, profiles=prof))
        assert not res.degraded


def test_service_closed_rejects_new_and_pending():
    plat, inst, prof = _setup()
    svc = PlanService(Planner(plat, engine="numpy"))
    svc.pause()
    t = svc.submit(PlanRequest(instances=inst, profiles=prof))
    svc.close()
    with pytest.raises(ServiceClosed):
        t.result(timeout=10)
    with pytest.raises(ServiceClosed):
        svc.submit(PlanRequest(instances=inst, profiles=prof))


# --- deadline budgets + fast ladder paths ----------------------------------

def test_service_exhausted_budget_still_returns_feasible_asap():
    plat, inst, prof = _setup()
    planner = Planner(plat, engine="numpy")
    with PlanService(planner.clone()) as svc:
        res = svc.plan(PlanRequest(instances=inst, profiles=prof),
                       budget=0.0)
    assert res.degraded and res.fallback_stage == "asap"
    assert res.attempts == ("heuristic:skipped", "asap:ok")
    validate_schedule(inst, prof, res.result(variant="asap").start)


def test_service_solver_crash_degrades_to_feasible_schedule():
    plat, inst, prof = _setup()
    planner = Planner(plat, engine="numpy")
    inj = ServiceFaultInjector(
        faults=[FaultSpec(kind="crash", stage="heuristic", times=10)])
    with PlanService(planner.clone(), injector=inj, retries=1,
                     backoff=0.01) as svc:
        res = svc.plan(PlanRequest(instances=inst, profiles=prof))
    assert res.degraded and res.fallback_stage == "asap"
    assert res.attempts == ("heuristic:crash", "heuristic:crash", "asap:ok")
    validate_schedule(inst, prof, res.result(variant="asap").start)


def test_service_transient_crash_retries_to_full_fidelity():
    plat, inst, prof = _setup()
    planner = Planner(plat, engine="numpy")
    direct = planner.plan(PlanRequest(instances=inst, profiles=prof))
    inj = ServiceFaultInjector(
        faults=[FaultSpec(kind="crash", stage="heuristic", times=1)])
    with PlanService(planner.clone(), injector=inj, retries=2,
                     backoff=0.01) as svc:
        res = svc.plan(PlanRequest(instances=inst, profiles=prof))
        assert svc.stats()["retries"] == 1
    _assert_same_plan(res, direct)          # retry healed: NOT degraded
    assert not res.degraded
    assert res.attempts == ("heuristic:crash", "heuristic:ok")


def test_service_device_oom_retries_on_blocked_lp_planner():
    plat, inst, prof = _setup()
    planner = Planner(plat, engine="numpy")
    direct = planner.plan(PlanRequest(instances=inst, profiles=prof))
    inj = ServiceFaultInjector(
        faults=[FaultSpec(kind="oom", stage="heuristic", times=1)])
    with PlanService(planner.clone(), injector=inj) as svc:
        res = svc.plan(PlanRequest(instances=inst, profiles=prof))
        assert svc.stats()["oom_retries"] == 1
    _assert_same_plan(res, direct)
    assert not res.degraded
    assert res.attempts == ("heuristic:oom",
                            "heuristic:oom-retry-blocked-lp",
                            "heuristic:ok")


# --- priority admission + aging --------------------------------------------

def _completion_order(named_tickets, timeout=60.0):
    """The order in which the tickets resolve: each is recorded by a
    done-callback on its future, in the thread that resolves it, so two
    tickets that finish between two looks keep their true order."""
    order, resolved = [], threading.Condition()

    def record(name):
        def done(_fut):
            with resolved:
                order.append(name)
                resolved.notify_all()
        return done

    for name, t in named_tickets.items():
        t._fut.add_done_callback(record(name))
    with resolved:
        resolved.wait_for(lambda: len(order) == len(named_tickets), timeout)
        missing = sorted(set(named_tickets) - set(order))
        assert not missing, f"tickets never resolved: {missing}"
        return list(order)


def test_priority_admission_serves_earliest_deadline_first():
    plat, inst, prof = _setup()
    planner = Planner(plat, engine="numpy")
    with PlanService(planner.clone(), max_batch=1) as svc:
        svc.pause()
        # submitted FIRST but budget-less: virtual deadline = now + aging
        slow = svc.submit(PlanRequest(instances=inst, profiles=prof))
        urgent = svc.submit(PlanRequest(instances=inst, profiles=prof,
                                        solver="asap"), budget=10.0)
        svc.resume()
        order = _completion_order({"slow": slow, "urgent": urgent})
    assert order == ["urgent", "slow"]


def test_aging_prevents_starvation_of_budgetless_tickets():
    plat, inst, prof = _setup()
    planner = Planner(plat, engine="numpy")
    with PlanService(planner.clone(), max_batch=1, aging=0.05) as svc:
        svc.pause()
        old = svc.submit(PlanRequest(instances=inst, profiles=prof))
        time.sleep(0.1)
        # arrives more than `aging` after `old`: the aged budget-less
        # ticket now outranks even a tight real deadline
        urgent = svc.submit(PlanRequest(instances=inst, profiles=prof,
                                        solver="asap"), budget=10.0)
        svc.resume()
        order = _completion_order({"old": old, "urgent": urgent})
    assert order == ["old", "urgent"]


# --- cooperative cancellation ----------------------------------------------

def test_cancel_queued_ticket_never_runs():
    plat, inst, prof = _setup()
    planner = Planner(plat, engine="numpy")
    with PlanService(planner.clone()) as svc:
        svc.pause()
        t = svc.submit(PlanRequest(instances=inst, profiles=prof))
        assert t.cancel("changed my mind")
        assert not t.cancel()                # second cancel lost: resolved
        svc.resume()
        with pytest.raises(TicketCancelled) as ei:
            t.result(timeout=10)
        assert ei.value.to_dict()["reason"] == "changed my mind"
        res = svc.plan(PlanRequest(instances=inst, profiles=prof))
        stats = svc.stats()
    assert not res.degraded                  # service healthy afterwards
    assert stats["cancelled"] == 1
    assert stats["completed"] == 1           # the cancelled ticket never ran


def test_cancel_stops_inflight_solve_within_rung_budget():
    """Tentpole acceptance: cancellation is cooperative all the way down —
    after Ticket.cancel() the solve pool goes idle within one rung budget
    (observed via the solver-side token polls), not after the 30s hang."""
    plat, inst, prof = _setup()
    planner = Planner(plat, engine="numpy")
    inj = ServiceFaultInjector(
        faults=[FaultSpec(kind="hang", stage="heuristic", times=1,
                          seconds=30.0)])
    with PlanService(planner.clone(), injector=inj) as svc:
        t = svc.submit(PlanRequest(instances=inst, profiles=prof))
        deadline = time.monotonic() + 10
        while svc.stats()["inflight_solves"] == 0 \
                and time.monotonic() < deadline:
            time.sleep(0.005)
        assert svc.stats()["inflight_solves"] == 1
        t0 = time.monotonic()
        assert t.cancel()
        while svc.stats()["inflight_solves"] > 0 \
                and time.monotonic() < deadline:
            time.sleep(0.005)
        latency = time.monotonic() - t0
        stats = svc.stats()
        with pytest.raises(TicketCancelled):
            t.result(timeout=5)
    assert stats["inflight_solves"] == 0
    assert latency < 2.0, latency            # one rung, not the 30s hang
    assert stats["cancel_checks"] > 0        # the solver really polled
    assert stats["cancelled"] == 1 and stats["cancelled_solves"] == 1
    assert stats["completed"] == 0 and stats["failed"] == 0


# --- wire shapes round-trip -------------------------------------------------

def test_service_error_wire_round_trip():
    import json

    errs = [
        ServiceError("plain", hint="x"),
        Overloaded("queue full", queue_depth=3, max_queue=2),
        InvalidRequest("bad profile", reason="budget length"),
        PlanFailure("every stage failed",
                    attempts=("heuristic:crash", "asap:crash"),
                    last_error=None),
        ServiceClosed("closed"),
        TicketCancelled("ticket cancelled: bye", reason="bye"),
    ]
    for e in errs:
        d = e.to_dict()
        assert d == json.loads(json.dumps(d)), type(e).__name__
        back = ServiceError.from_dict(d)
        assert type(back) is type(e)
        assert str(back) == str(e)
        assert back.to_dict() == d           # lossless round-trip


def test_plan_result_summary_dict_round_trips_losslessly():
    import dataclasses
    import json

    plat, inst, prof = _setup()
    planner = Planner(plat, engine="numpy")
    res = planner.plan(PlanRequest(instances=inst, profiles=[prof, prof]))
    gap = np.full(res.costs.shape[:2], np.nan)
    gap[0, 0] = 0.25                         # mixed known/NaN gap cells
    res = dataclasses.replace(
        res, degraded=True, fallback_stage="ilp",
        attempts=("ilp:timeout", "heuristic:ok"),
        lower_bound=res.best_costs(), mip_gap=gap)
    d = res.summary_dict()
    assert d == json.loads(json.dumps(d))    # JSON-safe, NaN travels as None
    back = type(res).summary_from_dict(d)
    assert back.summary_dict() == d          # lossless round-trip
    assert (back.costs == res.costs).all()
    assert back.attempts == res.attempts and back.degraded
    assert np.isnan(back.mip_gap[0, 1]) and back.mip_gap[0, 0] == 0.25


# --- write-ahead ticket journal ---------------------------------------------

def test_ticket_journal_round_trips_and_resolves(tmp_path):
    plat, inst, prof = _setup()
    j = TicketJournal(str(tmp_path / "journal"))
    assert j.next_seq() == 0 and j.pending() == []
    state = encode_ticket([inst], [[prof]], ("asap", "pressWR-LS"),
                          "heuristic", True, {"x": 1}, 2.5)
    j.record(j.next_seq(), state)
    j.record(j.next_seq(), state)
    pend = j.pending()
    assert [s for s, _ in pend] == [0, 1] and j.next_seq() == 2
    insts, grid, names, solver, robust, options, budget = \
        decode_ticket(pend[0][1])
    assert names == ("asap", "pressWR-LS") and solver == "heuristic"
    assert robust is True and options == {"x": 1} and budget == 2.5
    back = insts[0]
    assert back.name == inst.name and back.proc_chains == inst.proc_chains
    for f in ("dur", "proc", "task_work", "pred_ptr", "pred_idx",
              "succ_ptr", "succ_idx", "chain_proc_ids", "topo", "level"):
        assert (np.asarray(getattr(back, f))
                == np.asarray(getattr(inst, f))).all(), f
    p = grid[0][0]
    assert (p.bounds == prof.bounds).all() and \
        (p.budget == prof.budget).all() and p.scenario == prof.scenario
    j.resolve(0)
    j.resolve(0)                             # idempotent
    assert [s for s, _ in j.pending()] == [1]


def test_kill_then_restart_replays_admitted_tickets(tmp_path):
    plat, inst, prof = _setup()
    planner = Planner(plat, engine="numpy")
    direct = planner.plan(PlanRequest(instances=inst, profiles=prof))
    jdir = str(tmp_path / "journal")
    svc = PlanService(planner.clone(), journal_dir=jdir)
    svc.pause()
    t1 = svc.submit(PlanRequest(instances=inst, profiles=prof))
    t2 = svc.submit(PlanRequest(instances=inst, profiles=prof))
    svc.kill()                               # abrupt death: futures hang,
    assert not t1.done() and not t2.done()   # journal keeps both entries
    svc2 = PlanService(planner.clone(), journal_dir=jdir)
    assert len(svc2.replayed) == 2
    results = [t.result(timeout=120) for t in svc2.replayed]
    assert svc2.stats()["replayed"] == 2
    svc2.close()
    for r in results:
        _assert_same_plan(r, direct)         # replay serves full fidelity
        assert not r.degraded
    # every replayed ticket resolved -> the journal is empty again
    svc3 = PlanService(planner.clone(), journal_dir=jdir)
    assert svc3.replayed == []
    svc3.close()


def test_clean_close_leaves_empty_journal(tmp_path):
    plat, inst, prof = _setup()
    planner = Planner(plat, engine="numpy")
    jdir = str(tmp_path / "journal")
    with PlanService(planner.clone(), journal_dir=jdir) as svc:
        res = svc.plan(PlanRequest(instances=inst, profiles=prof))
        assert not res.degraded
    assert TicketJournal(jdir).pending() == []


# --- compilation cache wiring ------------------------------------------------

def test_service_enables_compilation_cache_with_opt_out():
    plat, _, _ = _setup()
    with PlanService(Planner(plat, engine="numpy")) as svc:
        assert svc.compile_cache_dir          # enabled by default
    with PlanService(Planner(plat, engine="numpy"),
                     compilation_cache=False) as svc:
        assert svc.compile_cache_dir is None  # explicit opt-out


def test_compilation_cache_dir_env_wins_else_fixed_checkout_path(
        monkeypatch, tmp_path):
    from repro.kernels import backend

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert backend.compilation_cache_dir() == os.path.join(root,
                                                           ".jax_cache")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert backend.compilation_cache_dir() == str(tmp_path)


_WARM_RESTART_SCRIPT = """
from repro.api import Planner, PlanRequest
from repro.cluster import make_cluster
from repro.core import (build_instance, deadline_from_asap,
                        generate_profile, heft_mapping)
from repro.serve import PlanService
from repro.workflows import make_workflow

plat = make_cluster(1, seed=3)
wf = make_workflow("eager", 2, seed=3)
inst = build_instance(wf, heft_mapping(wf, plat), plat)
prof = generate_profile("S3", deadline_from_asap(inst, 1.5), plat, J=8,
                        seed=3)
svc = PlanService(Planner(plat, engine="jax"))
assert svc.compile_cache_dir, "compilation cache not enabled"
res = svc.plan(PlanRequest(instances=inst, profiles=[prof, prof]))
assert not res.degraded
svc.close()
print("CACHE_DIR=" + svc.compile_cache_dir)
"""


@pytest.mark.device
def test_service_restart_reuses_persistent_compilation_cache(tmp_path):
    """Warm-restart compiles drop to zero: the first service process
    populates the persistent jax compilation cache the startup hook
    enables; an identical second process adds no new entries (every
    compile is a cache hit)."""
    import subprocess
    import sys

    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               PYTHONPATH=os.pathsep.join(sys.path))
    cache_dir = None
    counts = []
    for _ in range(2):
        out = subprocess.run(
            [sys.executable, "-c", _WARM_RESTART_SCRIPT], env=env,
            capture_output=True, text=True, timeout=300)
        assert out.returncode == 0, out.stderr
        line = [ln for ln in out.stdout.splitlines()
                if ln.startswith("CACHE_DIR=")][0]
        cache_dir = line[len("CACHE_DIR="):]
        counts.append(len(os.listdir(cache_dir)))
    assert cache_dir == str(tmp_path)
    assert counts[0] > 0, "cold run persisted no compiled executables"
    assert counts[1] == counts[0], \
        f"warm restart recompiled: {counts[0]} -> {counts[1]} entries"


# --- resolved-grid validation (the quarantine check) -----------------------

def test_validate_resolved_catches_structural_corruption():
    from repro.runtime.fault import corrupt_profile

    plat, inst, prof = _setup()
    validate_resolved([inst], [[prof]])                  # healthy passes
    with pytest.raises(ValueError, match="budget length"):
        validate_resolved([inst], [[corrupt_profile(prof)]])
    with pytest.raises(ValueError, match="critical path"):
        validate_resolved([inst], [[generate_profile("S1", 2, plat, J=1,
                                                     seed=0)]])
    import dataclasses

    idx = inst.succ_idx.copy()
    idx[0] = inst.num_tasks + 5                          # dangling edge
    bad = dataclasses.replace(inst, succ_idx=idx)
    with pytest.raises(ValueError, match="adjacency"):
        validate_resolved([bad], [[prof]])


# --- mip_gap / lower_bound surfacing (ilp time-limit exits) ----------------

def test_ilp_time_limit_exit_surfaces_gap_not_failure(monkeypatch):
    """A time-limited ILP that returns an incumbent is a degraded success:
    the PlanResult carries the schedule + lower_bound + mip_gap, and the
    service flags it degraded without walking further down the chain."""
    import repro.core.ilp as ilp_mod
    from repro.core.ilp import ILPResult

    plat, inst, prof = _setup(samples=2, seed=5)
    asap = Planner(plat, engine="numpy").plan(
        PlanRequest(instances=inst, profiles=prof, solver="asap"))
    incumbent = asap.result(variant="asap").start
    cost = int(asap.costs[0, 0, 0])

    def fake_solve(inst_, prof_, time_limit=300.0, mip_gap=0.0,
                   cancel=None):
        return ILPResult(cost=float(cost), start=incumbent.copy(),
                         status=1, message="time limit reached",
                         lower_bound=cost * 0.5, mip_gap=0.5)

    monkeypatch.setattr(ilp_mod, "solve_ilp", fake_solve)
    planner = Planner(plat, engine="numpy")
    res = planner.plan(PlanRequest(instances=inst, profiles=prof,
                                   solver="ilp"))
    assert res.mip_gap is not None and res.mip_gap[0, 0] == 0.5
    assert res.lower_bound[0, 0] == int(np.ceil(cost * 0.5 - 1e-6))
    with PlanService(planner.clone()) as svc:
        served = svc.plan(PlanRequest(instances=inst, profiles=prof,
                                      solver="ilp"))
    assert served.degraded                       # open gap => degraded
    assert served.fallback_stage == "ilp"        # but NOT a fallback
    assert served.attempts == ("ilp:ok",)
    assert served.mip_gap[0, 0] == 0.5
    validate_schedule(inst, prof, served.result(variant="ilp").start)


@pytest.mark.ilp
def test_exact_through_service_matches_direct_and_certifies():
    pytest.importorskip("scipy.optimize", reason="needs scipy HiGHS")
    from repro.core.carbon import PowerProfile
    from repro.core.dag import trivial_mapping
    from repro.workflows import layered_random

    rng = np.random.default_rng(0)
    plat = make_cluster(1, seed=0)
    wf = layered_random(6, 3, seed=0)
    inst = build_instance(wf, trivial_mapping(wf, plat, by="round_robin"),
                          plat, dur=rng.integers(1, 6, size=wf.n))
    T = deadline_from_asap(inst, 1.5)
    bounds = np.unique(np.round(np.linspace(0, T, 5)).astype(np.int64))
    budget = plat.idle_total + rng.integers(
        0, max(int(inst.task_work.max()) // 2, 2), size=len(bounds) - 1)
    prof = PowerProfile(bounds=bounds, budget=budget)

    planner = Planner(plat, engine="numpy")
    direct = planner.plan(PlanRequest(instances=inst, profiles=prof,
                                      solver="exact"))
    with PlanService(planner.clone()) as svc:
        res = svc.plan(PlanRequest(instances=inst, profiles=prof,
                                   solver="exact"))
    _assert_same_plan(res, direct)
    assert not res.degraded                      # proven optimum
    assert res.lower_bound[0, 0] == res.costs[0, 0, 0]


# --- PlanningSession robustness fixes --------------------------------------

def _session_fixture(n_windows=3):
    plat, inst, _ = _setup(factor=1.6)
    from repro.api.request import window_profile

    W = deadline_from_asap(inst, 1.6)
    long = generate_profile("S3", n_windows * W, plat, J=48, seed=7)
    return plat, inst, lambda k: window_profile(long, k * W, W)


def test_session_evicts_failed_future_and_resubmits_once():
    plat, inst, wprofs = _session_fixture()
    planner = Planner(plat, engine="numpy")
    real_plan = planner.plan
    boom = {"left": 1}

    def flaky_plan(request, cancel=None):
        if boom["left"]:
            boom["left"] -= 1
            raise RuntimeError("transient device hiccup")
        return real_plan(request)

    planner.plan = flaky_plan
    with PlanningSession(planner, inst, wprofs, n_windows=3,
                         lookahead=0) as sess:
        res = sess.plan_for(0)           # first background plan fails,
        assert res.shape[0] == 1         # eviction + resubmit heals it
        ref = real_plan(sess.request_for(0))
        assert (res.costs == ref.costs).all()


def test_session_second_failure_propagates_and_sticks():
    plat, inst, wprofs = _session_fixture()
    planner = Planner(plat, engine="numpy")

    def always_fail(request, cancel=None):
        raise RuntimeError("persistent failure")

    planner.plan = always_fail
    with PlanningSession(planner, inst, wprofs, n_windows=3,
                         lookahead=0) as sess:
        with pytest.raises(RuntimeError, match="persistent"):
            sess.plan_for(0)             # retried once, then propagates
        with pytest.raises(RuntimeError, match="persistent"):
            sess.plan_for(0)             # sticky: no unbounded resubmits


def test_session_close_cancels_prefetched_windows():
    plat, inst, wprofs = _session_fixture(n_windows=8)
    planner = Planner(plat, engine="numpy")
    real_plan = planner.plan

    def slow_plan(request, cancel=None):
        time.sleep(0.25)
        return real_plan(request, cancel=cancel)

    planner.plan = slow_plan
    sess = PlanningSession(planner, inst, wprofs, n_windows=8, lookahead=6)
    sess.plan_for(0)                     # queues 6 lookahead windows
    t0 = time.monotonic()
    sess.close()                         # cancel_futures: don't drain them
    closed_in = time.monotonic() - t0
    # closing waits for at most the one in-flight plan, not 6 queued ones
    assert closed_in < 1.5, closed_in
    with pytest.raises(RuntimeError):
        sess.plan_for(1)


def test_session_close_cancels_in_flight_solve_via_token():
    """close() stops the ONE in-flight background solve through its
    CancelToken, not just the queued prefetches — an endless solve that
    polls its token unwinds within a chunk instead of pinning close()."""
    plat, inst, wprofs = _session_fixture()
    planner = Planner(plat, engine="numpy")

    def endless_plan(request, cancel=None):
        while True:                      # a solver chunk loop in miniature
            if cancel is not None:
                cancel.check()
            time.sleep(0.01)

    planner.plan = endless_plan
    sess = PlanningSession(planner, inst, wprofs, n_windows=3, lookahead=0)
    sess._submit(0)                      # in flight, would never finish
    time.sleep(0.1)
    t0 = time.monotonic()
    sess.close()                         # shutdown(wait=True) + token cancel
    assert time.monotonic() - t0 < 1.0
    with pytest.raises(RuntimeError):
        sess.plan_for(0)
