"""The 4000-task class at a small size on the CPU: the cell
``nfcore-4k.greedy-ens8`` serves its instances through the blocked
longest-path form, and what ``PlanService`` serves there equals the
plain reference and the dense form, start for start."""
import time
import types

import numpy as np
import pytest

from harness import check, generate, program, runner, spec, traffic

CELL = "nfcore-4k.greedy-ens8"
SMALL_TASKS = 150           # about 196 tasks with communication tasks


def small_cell():
    cell = spec.load_cell(CELL)
    cell.config = dict(cell.config, target_tasks=SMALL_TASKS)
    return cell


def blocked_budget(num_tasks: int, T: int) -> int:
    """An lp budget under the dense matrix of ``num_tasks`` that streams
    the scan in chunks of 8 steps for the 8 greedy configurations."""
    from repro.core.greedy_jax import lp_block_bytes, lp_matrix_bytes, \
        pad_dims

    budget = lp_block_bytes(8, 8, pad_dims(num_tasks, T)[0])
    assert budget < lp_matrix_bytes(num_tasks)
    return budget


@pytest.fixture(scope="module")
def deployment():
    cell = small_cell()
    cluster = generate.make_cluster(cell.config["nodes_per_type"],
                                    seed=cell.config["cluster_seed"])
    plat = program.platform(cluster)
    pool = traffic.build_pool(cell.config, cell.traffic, cluster, plat)
    return cell, cluster, plat, pool


def served(cell, plat, requests):
    """The results of ``requests``, served one after another through the
    cell's ``PlanService``."""
    with program.service(cell.config, plat) as svc:
        results = [svc.submit(r).result(600) for r in requests]
    for res in results:
        assert program.served_ok(res, cell.config["engine"]) is None
    return results


def test_the_cell_loads_and_its_class_needs_the_blocked_form():
    from repro.core.greedy_jax import LP_MAX_BYTES, lp_matrix_bytes
    from repro.kernels.backend import resolve_lp_form

    cell = spec.load_cell(CELL)
    assert cell.chips == 1
    assert cell.config["name"] == "nfcore-4k-large"
    assert cell.config["families"] == ["atacseq"]
    assert cell.config["target_tasks"] == 4000
    assert cell.config["nodes_per_type"] == 24
    assert not any(v.endswith("-LS") for v in cell.traffic["variants"])
    assert {"blocked_sweep_ms.batch", "blocked_rows_ms.batch",
            "greedy_launch_ms.batch", "device_idle_pct.batch"} \
        <= {m["name"] for m in cell.per_layer}
    assert not {"ls_climb_ms.batch", "ls_polish_ms.batch",
                "gain_kernel_roofline"} & {m["name"] for m in cell.per_layer}
    # the class's 8607 tasks (with communication tasks) need 296 MB of
    # dense int32 longest paths, past the default 128 MiB budget
    assert lp_matrix_bytes(8607) == 296_321_796 > LP_MAX_BYTES
    assert resolve_lp_form(8607) == "blocked"


def span(i, name, t0, t1, parent=0):
    return types.SimpleNamespace(span_id=i, name=name, t0=t0, t1=t1,
                                 parent_id=parent, attrs={})


# two requests' blocked sweeps, each of two chunks
SPANS = [
    span(1, "bucket_launch", 0.0, 1.0),
    span(2, "blocked_chunk_sweep", 0.0, 0.9, 1),
    span(3, "blocked_lp_rows", 0.0, 0.4, 2),
    span(4, "blocked_lp_rows", 0.45, 0.8, 2),
    span(5, "bucket_launch", 2.0, 2.5),
    span(6, "blocked_chunk_sweep", 2.0, 2.4, 5),
    span(7, "blocked_lp_rows", 2.0, 2.1, 6),
    span(8, "blocked_lp_rows", 2.15, 2.3, 6),
]


@pytest.mark.parametrize("metric, stage, stage_s", [
    ("blocked_sweep_ms.batch", "blocked_chunk_sweep", 0.9 + 0.4),
    ("blocked_rows_ms.batch", "blocked_lp_rows", 0.4 + 0.35 + 0.1 + 0.15),
])
def test_a_blocked_reader_is_its_spans_in_ms_per_request_served(
        metric, stage, stage_s):
    read = spec.reader(metric)
    served_ = [types.SimpleNamespace(ok=ok) for ok in (True, True, False)]
    run = types.SimpleNamespace(spans=SPANS, records=served_)
    assert read(run) == pytest.approx(1e3 * stage_s / 2)
    # a program without the span (the parent of the rows span), or a
    # window that served nothing, reads nothing
    run.spans = [s for s in SPANS if s.name != stage]
    assert read(run) is None
    run.spans, run.records = SPANS, served_[2:]
    assert read(run) is None


@pytest.mark.device
def test_the_blocked_form_serves_the_reference_and_the_dense_starts(
        deployment, monkeypatch):
    import repro.core.greedy_jax as gj
    from repro import obs
    from repro.kernels.backend import resolve_lp_form

    cell, cluster, plat, pool = deployment
    (entry,) = pool
    assert entry.graph.N == 196
    requests = [program.request(entry.instance, traffic.ensemble(
        cell.config, cell.traffic, entry, cluster.idle_total, 2**31 + 13,
        0, i), cell.traffic["variants"]) for i in range(2)]

    monkeypatch.setattr(gj, "LP_MAX_BYTES",
                        blocked_budget(entry.graph.N, entry.T))
    assert resolve_lp_form(entry.graph.N) == "blocked"
    chunks = obs.registry().counter("blocked_lp_chunks_total")
    c0 = chunks.value()
    tracer = program.start_spans()
    try:
        blocked = served(cell, plat, requests)
    finally:
        spans = program.stop_spans(tracer)
    sweeps = [s for s in spans if s.name == "blocked_chunk_sweep"]
    assert len(sweeps) == len(requests)
    assert all(s.attrs["chunk_width"] == 8 for s in sweeps)
    assert chunks.value() - c0 == sum(s.attrs["chunks"] for s in sweeps) \
        == len(requests) * 256 // 8

    for req, res in zip(requests, blocked):
        want = check.reference_rows(cell.config, entry.graph, req.profiles,
                                    cell.traffic["variants"])
        assert len(want) == 8 * 9
        assert check.differing(program.rows(res), want) == (0, 0)

    monkeypatch.undo()
    assert resolve_lp_form(entry.graph.N) == "dense"
    dense = served(cell, plat, requests)
    for got, ref in zip(blocked, dense):
        for key, (start, cost) in program.rows(ref).items():
            g_start, g_cost = program.rows(got)[key]
            assert np.array_equal(g_start, start) and g_cost == cost, key


@pytest.mark.device
def test_a_traced_run_of_the_cell_reads_both_blocked_metrics(
        deployment, monkeypatch):
    import repro.core.greedy_jax as gj

    cell, _, _, pool = deployment
    monkeypatch.setattr(gj, "LP_MAX_BYTES",
                        blocked_budget(pool[0].graph.N, pool[0].T))
    result = runner.execute(small_cell(), 2**31 + 17, 0.5, True,
                            time.perf_counter(), require_tpu=False)
    assert result["correct"] is True and result["failed"] == 0
    metrics = result["metrics"]
    sweep = metrics["blocked_sweep_ms.batch"]["value"]
    rows = metrics["blocked_rows_ms.batch"]["value"]
    assert 0 < rows <= sweep <= metrics["greedy_launch_ms.batch"]["value"]
