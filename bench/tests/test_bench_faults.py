"""A whole run of a tiny cell on the CPU, past the look for a chip, with
what the timed path produced replaced by a faulty answer: ``correct``
must come out false for each fault the cells can have, and true for the
answer as served."""
import dataclasses
import itertools
import time

import numpy as np
import pytest

from harness import check, reference, runner, spec, traffic

VARIANTS = ["asap"] + [s + w + r + l for s in ("slack", "press")
                       for w in ("", "W") for r in ("", "R")
                       for l in ("", "-LS")]


def tiny_cell():
    cell = spec.load_cell("nfcore-1k.replan-ens8")
    cell.config = dict(cell.config, nodes_per_type=1, target_tasks=60)
    cell.traffic = dict(cell.traffic, check_requests=2, variants=VARIANTS)
    return cell


def reference_in_place(control=False, climb=None):
    """The reference put in the program's place, with a fault planted."""
    def served(entry, rec):
        saved = reference.climb
        if climb is not None:
            reference.climb = climb
        try:
            return check.reference_rows(tiny_cell().config, entry.graph,
                                        rec.request.profiles, VARIANTS,
                                        control=control)
        finally:
            reference.climb = saved
    return served


def half_the_ensemble(entry, rec):
    """Half of the forecast members computed, the rest copied over."""
    rows = check.program.rows(rec.result)
    half = len(rec.request.profiles) // 2
    return {(p, v): rows[p % half, v] for p, v in rows}


def one_start_altered(entry, rec):
    rows = dict(check.program.rows(rec.result))
    start, cost = rows[0, "pressWR-LS"]
    start = start.copy()
    start[np.argmax(entry.graph.dur)] += 1
    rows[0, "pressWR-LS"] = (start, cost)
    return rows


def instance_built_wrong(monkeypatch):
    """The planner is handed an instance with one duration changed."""
    from harness import program

    build = program.instance

    def wrong(*args):
        inst = build(*args)
        dur = inst.dur.copy()
        dur[0] += 1
        return dataclasses.replace(inst, dur=dur)
    monkeypatch.setattr(program, "instance", wrong)


def window_request_answered_as(monkeypatch, answer):
    """The service hands the window's first request (the second one it
    sees, after the one warm-up request of the tiny cell's one shape
    bucket) to ``answer(submit, request)`` instead of its own queue."""
    from harness import program

    make = program.service

    def service(*args):
        svc = make(*args)
        submit, count = svc.submit, itertools.count()

        def faulty(request):
            return answer(submit, request) if next(count) == 1 \
                else submit(request)
        svc.submit = faulty
        return svc
    monkeypatch.setattr(program, "service", service)


def answer_never_comes(monkeypatch):
    """The request is admitted and never answered."""
    import concurrent.futures

    monkeypatch.setattr(traffic, "GRACE_S", 1.0)
    window_request_answered_as(
        monkeypatch,
        lambda submit, request: (submit(request),
                                 concurrent.futures.Future())[1])


def request_refused(monkeypatch):
    """The request is refused as if the admission queue were full."""
    from repro.serve import Overloaded

    def refuse(submit, request):
        raise Overloaded("admission queue full (planted)")
    window_request_answered_as(monkeypatch, refuse)


def answer_degraded(monkeypatch):
    """The request is answered by a lower rung of the service ladder."""
    class Degraded:
        def __init__(self, ticket):
            self.ticket = ticket

        def result(self, timeout=None):
            return dataclasses.replace(
                self.ticket.result(timeout), degraded=True,
                fallback_stage="asap",
                attempts=("heuristic:timeout", "asap:ok"))
    window_request_answered_as(
        monkeypatch, lambda submit, request: Degraded(submit(request)))


FAULTS = {
    "control_one_precision_lower": reference_in_place(control=True),
    "climb_returns_its_state_unchanged": reference_in_place(
        climb=lambda g, T, budget, start, *a, **k: start.copy()),
    "half_the_ensemble_left_out": half_the_ensemble,
    "one_answer_altered": one_start_altered,
}
PLANTED = {
    "instance_built_wrong": (instance_built_wrong, "rows_differing"),
    "answer_never_comes": (answer_never_comes, "requests_missing"),
    "request_refused": (request_refused, "requests_failed"),
    "answer_degraded": (answer_degraded, "requests_failed"),
}


def run_once(monkeypatch, served=None):
    if served is not None:
        monkeypatch.setattr(check, "served", served)
    return runner.execute(tiny_cell(), 2**31 + 77, 0.5, False,
                          time.perf_counter(), require_tpu=False)


def test_the_answers_as_served_are_correct(monkeypatch):
    result = run_once(monkeypatch)
    assert result["correct"] is True
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert list(result)[-1] == "checks"
    assert all(c["value"] == 0 for c in result["checks"].values())


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_faulty_answer_is_not_correct(monkeypatch, fault):
    result = run_once(monkeypatch, FAULTS[fault])
    assert result["correct"] is False
    assert result["checks"]["rows_differing"]["value"] > 0


@pytest.mark.parametrize("fault", sorted(PLANTED))
def test_a_fault_planted_in_the_served_path_is_not_correct(monkeypatch,
                                                          fault):
    plant, number = PLANTED[fault]
    plant(monkeypatch)
    result = run_once(monkeypatch)
    assert result["correct"] is False
    assert result["checks"][number]["value"] > 0
