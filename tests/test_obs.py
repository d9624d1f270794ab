"""Observability subsystem suite: tracing, metrics, service integration.

Covers the PR's acceptance criteria:

* a forced fallback-chain solve through :class:`PlanService` produces a
  SINGLE connected trace — admission -> queue wait -> per-rung attempts
  -> resolution — with per-rung timings;
* the JSONL export is loadable with ``json.loads`` line by line;
* ``PlanService.stats()`` (the legacy wire shape) is exactly a read of
  the per-service metrics registry;
* the Prometheus text exposition parses and its histogram invariants
  hold;
* journal compaction is lossless under replay, and the replay cap
  defers (never drops) excess entries;
* the no-leaked-spans fixture guards every traced test.
"""
import json
import re
import threading

import numpy as np
import pytest

from repro import obs
from repro.api import Planner, PlanRequest
from repro.cluster import make_cluster
from repro.core import (
    build_instance,
    deadline_from_asap,
    generate_profile,
    heft_mapping,
    validate_schedule,
)
from repro.core.cancel import Cancelled, CancelToken
from repro.runtime.fault import FaultSpec, ServiceFaultInjector
from repro.serve import PlanService, TicketJournal, decode_ticket
from repro.serve.service import _STAT_EVENTS
from repro.workflows import make_workflow


def _setup(kind="eager", samples=3, seed=3, factor=1.5, scenario="S3"):
    plat = make_cluster(1, seed=seed)
    wf = make_workflow(kind, samples, seed=seed)
    inst = build_instance(wf, heft_mapping(wf, plat), plat)
    T = deadline_from_asap(inst, factor)
    prof = generate_profile(scenario, T, plat, J=16, seed=seed)
    return plat, inst, prof


@pytest.fixture
def traced():
    """A fresh process tracer; fails the test if any span leaks open."""
    prev = obs.set_tracer(obs.Tracer())
    tr = obs.tracer()
    try:
        yield tr
        leaked = tr.open_spans()
        assert not leaked, f"leaked open spans: {leaked}"
    finally:
        obs.set_tracer(prev)


# --- tracer primitives -----------------------------------------------------

def test_span_nesting_and_idempotent_end(traced):
    with traced.span("root") as root:
        with traced.span("child", k=1) as child:
            assert child.parent_id == root.span_id
            assert child.trace_id == root.trace_id
    child.end()                                # second end: no-op
    assert len(traced.finished()) == 2
    tree = traced.tree(root.trace_id)
    assert [n["name"] for n in tree] == ["root"]
    assert [n["name"] for n in tree[0]["children"]] == ["child"]


def test_span_records_exception_as_error_attr(traced):
    with pytest.raises(ValueError):
        with traced.span("boom"):
            raise ValueError("x")
    (sp,) = traced.finished()
    assert sp.attrs["error"] == "ValueError"


def test_attach_reanchors_worker_thread(traced):
    with traced.span("parent") as parent:
        seen = {}

        def worker():
            with traced.attach(parent):
                with traced.span("inner") as sp:
                    seen["parent_id"] = sp.parent_id

        t = threading.Thread(target=worker)
        t.start()
        t.join()
    assert seen["parent_id"] == parent.span_id


def test_disabled_tracing_returns_null_span():
    prev = obs.set_tracer(None)
    try:
        sp = obs.span("anything", k=1)
        assert sp is obs.NULL_SPAN and not sp
        with sp:
            sp.set(x=2).end()
        assert obs.current_span() is None
    finally:
        obs.set_tracer(prev)


def test_jsonl_export_loads_line_by_line(traced, tmp_path):
    plat, inst, prof = _setup()
    planner = Planner(plat, engine="numpy")
    planner.plan(PlanRequest(instances=inst, profiles=prof))
    path = tmp_path / "trace.jsonl"
    n = traced.dump_jsonl(str(path))
    lines = path.read_text().strip().split("\n")
    assert len(lines) == n > 0
    events = [json.loads(line) for line in lines]   # every line parses
    for ev in events:
        assert ev["ph"] == "X"
        assert ev["ts"] >= 0 and ev["dur"] >= 0
        assert "span_id" in ev["args"]
    assert any(ev["name"] == "plan" for ev in events)


# --- the acceptance trace: forced fallback chain through the service -------

def test_forced_fallback_chain_is_one_connected_trace(traced):
    plat, inst, prof = _setup()
    planner = Planner(plat, engine="numpy")
    inj = ServiceFaultInjector(
        faults=[FaultSpec(kind="crash", stage="heuristic", times=10)])
    with PlanService(planner.clone(), injector=inj, retries=1,
                     backoff=0.01) as svc:
        res = svc.plan(PlanRequest(instances=inst, profiles=prof))
    assert res.degraded and res.fallback_stage == "asap"
    assert res.attempts == ("heuristic:crash", "heuristic:crash",
                            "asap:ok")

    spans = traced.finished()
    roots = [s for s in spans if s.name == "request"]
    assert len(roots) == 1
    root = roots[0]
    ours = [s for s in spans if s.trace_id == root.trace_id]
    # single CONNECTED trace: every span of this request shares the
    # root's trace id and reaches the root through parent links
    by_id = {s.span_id: s for s in ours}
    for s in ours:
        node = s
        while node.parent_id:
            node = by_id[node.parent_id]
        assert node is root

    names = [s.name for s in ours]
    assert "admission" in names and "queue_wait" in names
    assert "resolution" in names
    rungs = sorted((s for s in ours if s.name.startswith("rung:")),
                   key=lambda s: s.t0)
    assert [(s.attrs["stage"], s.attrs["outcome"]) for s in rungs] == \
        [("heuristic", "crash"), ("heuristic", "crash"), ("asap", "ok")]
    for s in rungs:                      # per-rung timings
        assert s.t1 is not None and s.duration >= 0
        assert s.parent_id == root.span_id
    # the winning rung ran a solve that reached the planner layer
    ok_rung = rungs[-1]
    solves = [s for s in ours if s.name == "solve"
              and s.parent_id == ok_rung.span_id]
    assert len(solves) == 1
    assert any(s.name == "plan" and s.parent_id == solves[0].span_id
               for s in ours)
    assert root.attrs["outcome"] == "completed"


# --- metrics: stats() is a registry read -----------------------------------

def test_stats_equals_registry_read(traced):
    plat, inst, prof = _setup()
    planner = Planner(plat, engine="numpy")
    inj = ServiceFaultInjector(
        faults=[FaultSpec(kind="crash", stage="heuristic", times=1)])
    with PlanService(planner.clone(), injector=inj, retries=2,
                     backoff=0.01) as svc:
        for _ in range(3):
            svc.plan(PlanRequest(instances=inst, profiles=prof))
        with pytest.raises(Exception):
            svc.plan(PlanRequest(instances=inst, profiles=[]))
        stats = svc.stats()
        reg = svc.registry
        ev = reg.get("plan_service_events_total")
        for key in _STAT_EVENTS:
            assert stats[key] == int(ev.value(event=key)), key
        assert stats["submitted"] == 3 and stats["completed"] == 3
        assert stats["retries"] == 1 and stats["rejected_invalid"] == 1
        stage_counter = reg.get("plan_service_stage_served_total")
        assert stats["stages"] == {
            k[0]: int(v) for k, v in stage_counter.values().items()}
        lat = reg.get("plan_service_plan_latency_seconds")
        assert stats["latency"]["n"] == len(lat.samples()) == 3
        assert stats["latency"]["p50_ms"] == pytest.approx(
            float(np.percentile(np.asarray(lat.samples()), 50) * 1e3))
        assert stats["inflight_solves"] == 0
        assert stats["max_queue_depth"] == int(
            reg.get("plan_service_max_queue_depth").value())


def test_two_services_never_cross_count():
    plat, inst, prof = _setup()
    planner = Planner(plat, engine="numpy")
    with PlanService(planner.clone()) as a, \
            PlanService(planner.clone()) as b:
        a.plan(PlanRequest(instances=inst, profiles=prof))
        assert a.stats()["submitted"] == 1
        assert b.stats()["submitted"] == 0
        assert a.registry is not b.registry


# --- Prometheus exposition -------------------------------------------------

_SAMPLE_RE = re.compile(
    r'^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[a-zA-Z0-9_]+="[^"]*"'
    r'(,[a-zA-Z0-9_]+="[^"]*")*\})? [^ ]+$')


def test_prometheus_exposition_parses(traced):
    plat, inst, prof = _setup()
    planner = Planner(plat, engine="numpy")
    with PlanService(planner.clone()) as svc:
        svc.plan(PlanRequest(instances=inst, profiles=prof))
        text = svc.metrics_text()
    typed = set()
    for line in text.strip().split("\n"):
        if line.startswith("# TYPE "):
            name, kind = line.split()[2:4]
            assert kind in ("counter", "gauge", "histogram")
            typed.add(name)
        elif not line.startswith("#"):
            assert _SAMPLE_RE.match(line), line
            metric = line.split("{")[0].split(" ")[0]
            base = re.sub(r"_(bucket|sum|count)$", "", metric)
            assert metric in typed or base in typed, line
    assert "plan_service_events_total" in typed
    assert "plan_service_plan_latency_seconds" in typed
    # histogram invariants: buckets cumulative, +Inf == _count
    hist = [line for line in text.split("\n")
            if line.startswith("plan_service_plan_latency_seconds")]
    buckets = [float(line.split()[-1]) for line in hist
               if "_bucket" in line]
    assert buckets == sorted(buckets)
    count = next(float(line.split()[-1]) for line in hist
                 if line.startswith("plan_service_plan_latency_seconds_count"))
    inf = next(float(line.split()[-1]) for line in hist
               if 'le="+Inf"' in line)
    assert inf == count == 1


def test_metric_type_and_label_safety():
    reg = obs.MetricsRegistry()
    c = reg.counter("x_total", labels=("a",))
    with pytest.raises(ValueError):
        c.inc(-1, a="v")
    with pytest.raises(ValueError):
        c.inc(a="v", b="w")
    with pytest.raises(TypeError):
        reg.gauge("x_total")
    with pytest.raises(ValueError):
        reg.counter("x_total", labels=("other",))
    g = reg.gauge("depth")
    g.set_max(5)
    g.set_max(3)
    assert g.value() == 5


def test_cancel_latency_histogram_observes():
    hist = obs.registry().get("cancel_observe_latency_seconds")
    before = hist.count()
    token = CancelToken()
    token.cancel("test")
    with pytest.raises(Cancelled):
        token.check()
    with pytest.raises(Cancelled):
        token.check()                   # latency recorded exactly once
    assert hist.count() == before + 1


# --- journal compaction + replay cap ---------------------------------------

def _fill_killed_journal(tmp_path, n, samples=2):
    plat, inst, prof = _setup(samples=samples)
    planner = Planner(plat, engine="numpy")
    jd = str(tmp_path / "journal")
    svc = PlanService(planner.clone(), journal_dir=jd)
    svc.pause()
    for k in range(n):
        svc.submit(PlanRequest(instances=inst, profiles=prof))
    svc.kill()
    return planner, inst, prof, jd


def test_journal_compaction_lossless_replay(tmp_path):
    planner, inst, prof, jd = _fill_killed_journal(tmp_path, 3)
    journal = TicketJournal(jd)
    assert len(journal) == 3
    journal.resolve(1)                         # punch a hole: seqs 0, 2
    before = {seq: decode_ticket(state) for seq, state in journal.pending()}
    mapping = journal.compact()
    assert mapping == {0: 0, 2: 1}
    after = {seq: decode_ticket(state) for seq, state in journal.pending()}
    assert sorted(after) == [0, 1]
    # lossless: entry content survives renumbering bit-for-bit
    for old, new in mapping.items():
        old_inst = before[old][0]
        new_inst = after[new][0]
        assert len(old_inst) == len(new_inst)
        for a, b in zip(old_inst, new_inst):
            assert (a.dur == b.dur).all() and (a.proc == b.proc).all()
    # a service on the compacted journal replays and serves both
    direct = planner.plan(PlanRequest(instances=inst, profiles=prof))
    with PlanService(planner.clone(), journal_dir=jd) as svc:
        assert len(svc.replayed) == 2
        for t in svc.replayed:
            res = t.result(timeout=60)
            assert (res.costs == direct.costs).all()
    assert len(TicketJournal(jd)) == 0         # clean close, all resolved


def test_journal_replay_cap_defers_excess(tmp_path):
    planner, inst, prof, jd = _fill_killed_journal(tmp_path, 4)
    with PlanService(planner.clone(), journal_dir=jd,
                     journal_replay_cap=2) as svc:
        assert len(svc.replayed) == 2
        assert [t.journal_seq for t in svc.replayed] == [0, 1]  # oldest
        assert svc.stats()["replay_deferred"] == 2
        for t in svc.replayed:
            t.result(timeout=60)
        # a new admission must not collide with the deferred entries
        t = svc.submit(PlanRequest(instances=inst, profiles=prof))
        assert t.journal_seq >= 4
        t.result(timeout=60)
    # deferred entries survived on disk; an uncapped restart drains them
    assert len(TicketJournal(jd)) == 2
    with PlanService(planner.clone(), journal_dir=jd) as svc2:
        assert len(svc2.replayed) == 2
        assert svc2.stats()["replay_deferred"] == 0
        for t in svc2.replayed:
            res = t.result(timeout=60)
            validate_schedule(inst, prof, res.result().start)
    assert len(TicketJournal(jd)) == 0


# --- planner/core layer metrics --------------------------------------------

def test_planner_metrics_count_plans_and_cache_hits():
    plat, inst, prof = _setup()
    reg = obs.registry()
    plans = reg.counter("planner_plans_total",
                        labels=("solver", "engine"))
    cache = reg.counter("planner_graph_cache_total", labels=("outcome",))
    p0 = plans.value(solver="heuristic", engine="numpy")
    h0, m0 = cache.value(outcome="hit"), cache.value(outcome="miss")
    planner = Planner(plat, engine="numpy")
    planner.plan(PlanRequest(instances=inst, profiles=prof))
    planner.plan(PlanRequest(instances=inst, profiles=prof))
    assert plans.value(solver="heuristic", engine="numpy") == p0 + 2
    assert cache.value(outcome="miss") == m0 + 1      # first prepare
    assert cache.value(outcome="hit") >= h0 + 1       # second reuses


def test_jax_hooks_snapshot_shape():
    from repro.obs import jax_hooks
    reg = obs.MetricsRegistry()
    jax_hooks.install(reg)
    jax_hooks.install(reg)                     # idempotent
    jax_hooks.update_device_gauges(reg)
    snap = jax_hooks.snapshot(reg)
    assert set(snap) >= {"compile_events", "compile_seconds",
                         "jit_cache_entries", "live_arrays"}
    assert isinstance(snap["jit_cache_entries"], dict)
    # after a jax-engine plan the single grid launcher is counted
    planner, req = _jax_plan()
    planner.plan(req)
    entries = jax_hooks.snapshot(reg)["jit_cache_entries"]
    assert entries["greedy.grid"] >= 1


# --- host stages of the jax engine and their profiler mirror ---------------

def _jax_plan():
    """A small jax-engine request: two forecast members; a greedy, a
    refined greedy (through its -LS variant) and the baseline."""
    plat, inst, prof = _setup()
    other = generate_profile("S1", prof.T, plat, J=16, seed=4)
    req = PlanRequest(instances=inst, profiles=[prof, other],
                      variants=("asap", "slack", "slackR-LS"))
    return Planner(plat, engine="jax"), req


def _profile_options():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0        # TraceMe annotations only
    return opts


def _host_events(log_dir, names):
    """``{name: [(line index, event), ...]}`` of the host plane's events
    in the newest ``.xplane.pb`` under ``log_dir``."""
    import jax

    (path,) = log_dir.glob("**/*.xplane.pb")
    data = jax.profiler.ProfileData.from_file(str(path))
    out: dict = {}
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for k, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name in names:
                    out.setdefault(ev.name, []).append((k, ev))
    return out


def _inside(inner, outer) -> bool:
    return outer.start_ns <= inner.start_ns and inner.end_ns <= outer.end_ns


@pytest.mark.device
def test_jax_plan_spans_every_host_stage(traced):
    planner, req = _jax_plan()
    res = planner.plan(req)
    assert res.engine == "jax"
    by: dict = {}
    for sp in traced.finished():
        by.setdefault(sp.name, []).append(sp)

    def one(name, parent):
        (sp,) = by[name]
        assert sp.parent_id == parent.span_id, name
        return sp

    (plan,) = by["plan"]
    overlays = one("overlays", plan)
    launch = one("bucket_launch", plan)
    rows = one("bucket_rows", launch)
    climb = one("ls_climb", plan)
    prep = one("ls_prep", climb)
    device = one("ls_device_climb", climb)
    polish = one("ls_polish", climb)
    assemble = one("assemble", plan)
    validates = by["validate"]
    assert [v.parent_id for v in validates] == [assemble.span_id] * 2

    assert overlays.attrs == {"rows": 2, "refined": (False, True)}
    assert rows.attrs == {"rows": launch.attrs["rows"]} == {"rows": 4}
    assert prep.attrs == {"padded": False, "rows": 2,
                          "N": req.instances.num_tasks}
    assert assemble.attrs == {"cells": 2}
    assert [v.attrs for v in validates] == [{"variants": 3}] * 2
    assert overlays.t1 <= launch.t0 <= rows.t0 <= rows.t1 <= launch.t1
    assert prep.t1 <= device.t0 <= device.t1 <= polish.t0
    assert launch.t1 <= climb.t0 and climb.t1 <= assemble.t0

    # ScheduleResult.seconds still times the whole launch and the whole
    # climb, the host stages inside them included, and nothing past plan
    cell = res.results[0][0]
    greedy_s = cell["slack"].seconds
    ls_s = cell["slackR-LS"].seconds - greedy_s
    assert launch.duration <= greedy_s * launch.attrs["rows"] + 1e-9
    assert greedy_s * launch.attrs["rows"] <= plan.duration
    assert climb.duration <= ls_s * climb.attrs["rows"] + 1e-9
    assert ls_s * climb.attrs["rows"] <= plan.duration


@pytest.mark.device
def test_an_untraced_plan_creates_no_span(monkeypatch):
    planner, req = _jax_plan()
    made = []
    init = obs.Span.__init__

    def counting(self, name, *args, **kwargs):
        made.append(name)
        init(self, name, *args, **kwargs)

    monkeypatch.setattr(obs.Span, "__init__", counting)
    prev = obs.set_tracer(None)
    try:
        planner.plan(req)
        assert made == []
        obs.set_tracer(obs.Tracer())        # the count does see spans
        planner.plan(req)
        assert {"plan", "overlays", "bucket_rows", "ls_prep", "assemble",
                "validate"} <= set(made)
    finally:
        obs.set_tracer(prev)


@pytest.mark.device
@pytest.mark.parametrize("variants", [("slack",),
                                      ("slack", "pressW", "slackWR")])
def test_blocked_lp_rows_spans_each_chunk_sweep(traced, variants):
    from repro.core.greedy_jax import lp_block_bytes, lp_matrix_bytes, \
        pad_dims

    plat, inst, prof = _setup()
    N = inst.num_tasks
    Np, _ = pad_dims(N, prof.T)
    budget = lp_block_bytes(4, len(variants), Np)   # chunks of 4 steps
    assert budget < lp_matrix_bytes(N)
    req = PlanRequest(instances=inst, profiles=[prof], variants=variants)
    swept = obs.registry().counter("blocked_lp_rows_total")
    sweeps = obs.registry().counter("blocked_lp_device_sweeps_total")
    before, sweeps_before = swept.value(), sweeps.value()
    blocked = Planner(plat, engine="jax", lp_budget_bytes=budget).plan(req)

    (sweep,) = [sp for sp in traced.finished()
                if sp.name == "blocked_chunk_sweep"]
    rows = [sp for sp in traced.finished() if sp.name == "blocked_lp_rows"]
    assert sweep.attrs["chunks"] == Np // 4 == len(rows)
    assert [sp.attrs["chunk"] for sp in rows] == list(range(len(rows)))
    assert all(sp.parent_id == sweep.span_id for sp in rows)
    assert all(sweep.t0 <= sp.t0 <= sp.t1 <= sweep.t1 for sp in rows)
    # a chunk sweeps each of its real tasks once, whatever the orders
    # share; with one order every task is swept exactly once
    tasks = [sp.attrs["tasks"] for sp in rows]
    assert all(t <= 4 * len(variants) for t in tasks)
    assert swept.value() - before == sum(tasks)
    if len(variants) == 1:
        assert sum(tasks) == N
    # each chunk's rows and columns: two device sweeps a chunk
    assert sweeps.value() - sweeps_before == 2 * len(rows)

    prev = obs.set_tracer(None)
    try:
        untraced = Planner(plat, engine="jax",
                           lp_budget_bytes=budget).plan(req)
    finally:
        obs.set_tracer(prev)
    dense = Planner(plat, engine="jax").plan(req)
    for v in variants:
        start = blocked.results[0][0][v].start
        assert np.array_equal(start, untraced.results[0][0][v].start), v
        assert np.array_equal(start, dense.results[0][0][v].start), v


@pytest.mark.device
def test_spans_mirror_onto_the_profiler_host_plane(traced, tmp_path):
    import jax

    planner, req = _jax_plan()
    planner.plan(req)                       # compile outside the profile
    traced.clear()
    with jax.profiler.trace(str(tmp_path),
                            profiler_options=_profile_options()):
        planner.plan(req)
    spans = {sp.name: sp for sp in traced.finished()}
    names = ("plan", "overlays", "bucket_launch", "bucket_rows", "ls_climb",
             "assemble", "validate")
    events = _host_events(tmp_path, names)
    ev = {}
    for name in names[:-1]:
        ((_, ev[name]),) = events[name]
        assert abs(ev[name].duration_ns * 1e-9 - spans[name].duration) \
            < 1e-3, name
    for name in names[1:-1]:
        assert _inside(ev[name], ev["plan"]), name
    assert _inside(ev["bucket_rows"], ev["bucket_launch"])
    assert len(events["validate"]) == 2
    assert all(_inside(v, ev["assemble"]) for _, v in events["validate"])


def test_a_span_ended_on_another_thread_is_mirrored_where_it_ends(
        traced, tmp_path):
    import jax

    with jax.profiler.trace(str(tmp_path),
                            profiler_options=_profile_options()):
        with traced.span("starter"):
            handed = traced.start("handed_over")

        def finish():
            with traced.span("ender"):
                handed.end()

        t = threading.Thread(target=finish)
        t.start()
        t.join()
    events = _host_events(tmp_path, {"starter", "handed_over", "ender"})
    ((k_start, starter),) = events["starter"]
    ((k_end, ender),) = events["ender"]
    ((k, ev),) = events["handed_over"]
    assert k == k_end != k_start            # on the ending thread's line
    assert starter.start_ns <= ev.start_ns <= starter.end_ns
    assert ender.start_ns <= ev.end_ns <= ender.end_ns
    assert abs(ev.duration_ns * 1e-9 - handed.duration) < 1e-3
