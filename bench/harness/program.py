"""Every place the benchmark touches the system under test.

The benchmark hands the planner its inputs in the planner's own types,
serves them through ``PlanService``, and reads back results, spans and
compile events. Nothing else of the program is used.
"""
from __future__ import annotations

import numpy as np


def platform(cluster):
    from repro.cluster import Platform

    return Platform(speed=cluster.speed, p_idle=cluster.p_idle,
                    p_work=cluster.p_work, type_of=cluster.type_of)


def instance(wf, mapping, plat):
    from repro.core.dag import FixedMapping, build_instance
    from repro.workflows.generators import Workflow

    return build_instance(
        Workflow(name=wf.name, node_w=wf.node_w, edges=wf.edges,
                 edge_w=wf.edge_w),
        FixedMapping(proc=mapping.proc, order=mapping.order,
                     comm_order=mapping.comm_order), plat)


def same_graph(inst, graph) -> bool:
    """The planner's instance is the reference's G_c, task for task."""
    if inst.num_tasks != graph.N:
        return False
    u = np.repeat(np.arange(inst.num_tasks), np.diff(inst.succ_ptr))
    return bool(np.array_equal(inst.dur, graph.dur)
                and np.array_equal(inst.proc, graph.proc)
                and np.array_equal(inst.task_work, graph.work)
                and np.array_equal(u, graph.eu)
                and np.array_equal(inst.succ_idx, graph.ev))


def request(inst, profiles, variants):
    from repro.api import PlanRequest
    from repro.core.carbon import PowerProfile

    return PlanRequest(
        instances=inst,
        profiles=[PowerProfile(bounds=p.bounds, budget=p.budget,
                               scenario=p.scenario) for p in profiles],
        variants=tuple(variants))


def service(config: dict, plat):
    from repro.api import LocalSearchConfig, Planner
    from repro.serve import PlanService

    pc = config["planner"]
    planner = Planner(plat, engine=config["engine"], k=pc["k"],
                      ls=LocalSearchConfig(mu=pc["mu"],
                                           max_rounds=pc["ls_max_rounds"],
                                           commit_k=pc["commit_k"]))
    sc = config["service"]
    return PlanService(planner, workers=sc["workers"],
                       max_batch=sc["max_batch"], max_queue=sc["max_queue"])


def service_errors() -> tuple:
    from repro.serve import ServiceError

    return (ServiceError,)


def served_ok(res, engine: str) -> str | None:
    """Why a delivered result counts as failed, or None."""
    if res.degraded:
        return f"degraded to {res.fallback_stage}"
    if tuple(res.attempts) != ("heuristic:ok",):
        return f"attempts {tuple(res.attempts)}"
    if res.engine != engine:
        return f"engine {res.engine}"
    return None


def rows(res) -> dict:
    """``{(profile, variant): (start, cost)}`` of a one-instance result."""
    return {(p, v): (np.asarray(res.results[0][p][v].start, dtype=np.int64),
                     int(res.costs[0, p, vi]))
            for p in range(len(res.results[0]))
            for vi, v in enumerate(res.variants)}


def bucket(num_tasks: int, T: int) -> tuple:
    """The planner's shape bucket of an instance (its compiled programs
    are keyed by it)."""
    from repro.core.greedy_jax import pad_dims

    return tuple(pad_dims(num_tasks, T))


def compile_counter():
    """Callable returning (backend compiles, traces) so far."""
    from repro import obs

    obs.jax_hooks.install(obs.registry())
    events = obs.registry().counter("jax_compile_events_total",
                                    labels=("event",))

    def read():
        return (int(events.value(event="backend_compile_duration")),
                int(events.value(event="jaxpr_trace_duration")))
    return read


def start_spans():
    from repro import obs

    tracer, _ = obs.configure(tracing=True)
    return tracer


def stop_spans(tracer) -> list:
    from repro import obs

    obs.set_tracer(None)
    return [s for s in tracer.finished() if s.t1 is not None]
