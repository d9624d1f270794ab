"""The metric arithmetic, the trace reduction and the kernel's counts, on
small made-up inputs."""
import json
import math
import types
from pathlib import Path

import pytest

from harness import roofline, stats, trace, traffic

FIXTURE = Path(__file__).with_name("trace_fixture.json")


def record(due, sent, done, ok=True, cells=8):
    return types.SimpleNamespace(due=due, sent=sent, done=done, ok=ok,
                                 cells=cells)


def test_cells_per_s_counts_whole_requests_over_the_whole_window():
    recs = [record(0.0, 0.0, 2.0), record(2.0, 2.0, 5.0),
            record(5.0, 5.0, 9.0, ok=False, cells=0)]
    # 16 cells answered; the window runs to the last answer, failed or not
    assert stats.cells_per_s(recs) == pytest.approx(16 / 9.0)
    assert stats.cells_per_s([record(0, 0, 1, ok=False)]) is None


def test_latency_is_timed_from_the_due_time_when_the_generator_is_late():
    # the generator sent 0.5 s late; the answer came 0.2 s after the send
    recs = [record(1.0, 1.5, 1.7)] + [record(0.0, 0.0, 0.1)] * 19
    lat = stats.latencies(recs)
    assert lat[0] == pytest.approx(0.7)
    assert stats.nearest_rank(lat, 0.95) == pytest.approx(0.1)
    assert stats.nearest_rank(lat, 1.0) == pytest.approx(0.7)


def test_a_failed_request_misses_every_latency_limit():
    recs = [record(0.0, 0.0, 0.2, ok=False)] + [record(0.0, 0.0, 0.1)] * 9
    lat = stats.latencies(recs)
    assert lat[0] == stats.FAILED_S
    assert stats.nearest_rank(lat, 0.95) == stats.FAILED_S
    assert math.isfinite(stats.nearest_rank(lat, 0.95))


def test_a_forecast_pool_offers_every_seed_the_same_ensembles():
    pool = {"forecast_pool": 3, "pattern_seed": 0}
    order = [i % 2 for i in range(12)]           # 2 tenants, 2 cycles each
    runs = [[traffic.forecast_draws(pool, seed, order)(i) for i in range(12)]
            for seed in (2**31 + 1, 2**31 + 2, 2**33 + 3)]
    assert all(seed == 0 for run in runs for seed, _ in run)
    for run in runs:
        for cycle in (range(0, 6), range(6, 12)):
            assert sorted(run[i][1] for i in cycle) == sorted(
                (7, tenant, k) for tenant in (0, 1) for k in range(3))
    assert len({tuple(run) for run in runs}) > 1  # in another order
    # without a pool every request draws afresh from the run's seed
    fresh = traffic.forecast_draws({}, 2**31 + 1, order)
    assert fresh(5) == (2**31 + 1, (0, 5))


def test_nearest_rank():
    xs = list(range(1, 101))
    assert stats.nearest_rank(xs, 0.95) == 95
    assert stats.nearest_rank(xs, 0.90) == 90
    assert stats.nearest_rank(xs, 0.50) == 50
    assert stats.nearest_rank([], 0.5) is None


def test_span_self_time_and_per_request_time():
    def span(i, name, t0, t1, parent=0):
        return types.SimpleNamespace(span_id=i, name=name, t0=t0, t1=t1,
                                     parent_id=parent)

    spans = [span(1, "plan", 0.0, 1.0), span(2, "bucket_launch", 0.1, 0.3, 1),
             span(3, "ls_climb", 0.3, 0.8, 1),
             span(4, "ls_polish", 0.5, 0.8, 3), span(5, "plan", 2.0, 2.5)]
    assert stats.self_ms(spans, "plan", ("bucket_launch", "ls_climb"),
                         2) == pytest.approx(400.0)
    assert stats.per_request_ms(spans, {"ls_polish"}, 3) == \
        pytest.approx(100.0)
    assert stats.per_request_ms(spans, {"nothing"}, 3) is None


def load_fixture():
    data = json.loads(FIXTURE.read_text())
    return [(p, line, [tuple(e) for e in evs]) for p, line, evs in
            data["planes"]], data


def test_trace_reduction_busy_union_idle_gaps_and_anchor():
    planes, data = load_fixture()
    assert trace.anchor_ns(planes) == data["anchor_ns"]
    # the anchor opened at host time 100.0 s; the window is its first 1 ms
    dt = trace.reduce(planes, (100.0, 100.001), 100.0)
    assert dt.devices == 1
    assert dt.busy_s == pytest.approx(data["busy_s"])
    assert dt.window_s == pytest.approx(1e-3)
    assert [(round((s - 100.0) * 1e9), round((e - 100.0) * 1e9))
            for s, e in dt.gaps] == [tuple(g) for g in data["gaps_ns"]]
    # each op's own time: the loop less the kernel inside it
    top = dict(trace.top_ops(dt))
    assert top.keys() == data["self_s"].keys()
    for name, seconds in data["self_s"].items():
        assert top[name] == pytest.approx(seconds)


def test_idle_gaps_are_named_by_the_innermost_open_span():
    planes, _ = load_fixture()
    dt = trace.reduce(planes, (100.0, 100.001), 100.0)
    spans = [types.SimpleNamespace(name="plan", t0=99.0, t1=100.0006),
             types.SimpleNamespace(name="ls_polish", t0=100.0004,
                                   t1=100.0006)]
    labels = dict((round(sec * 1e9), name)
                  for name, sec in trace.label_gaps(dt, spans))
    assert labels == {100000: "plan", 120000: "ls_polish",
                      200000: "no request in service"}


def test_op_names_shorten_hlo_text_and_mark_kernels():
    assert trace.op_name('%fusion.3 = f32[8]{0} fusion(f32[8]{0} %p)') \
        == "fusion.3"
    assert trace.op_name('%body.14 = f32[64,256,128]{2,1,0} custom-call('
                         'f32[64,256,128]{2,1,0} %a), custom_call_target='
                         '"tpu_custom_call"') \
        == "body.14 " + trace.KERNEL_MARK


def test_a_trace_without_device_ops_reduces_to_nothing():
    planes = [("/host:CPU", "python", [(trace.ANCHOR, 0, 10)])]
    assert trace.reduce(planes, (0.0, 1.0), 0.0) is None


def test_gain_kernel_counts_and_the_peak_table():
    nbytes, ops = roofline.gain_kernel(rows=64, tasks=1000, mu=10)
    # per task: two 20-unit windows and four scalars in, 21 gains out
    assert nbytes == 64 * 1000 * 4 * (40 + 4 + 21)
    assert ops == 64 * 1000 * (40 * 7 + 4 * 20 + 21 * 7)
    v5e = roofline.peak("TPU v5 lite")
    assert v5e["bytes_per_s"] == 819e9 and v5e["flops_per_s"] == 197e12
    # far below the chip's ridge point: the bytes bound the kernel
    assert ops / nbytes < v5e["flops_per_s"] / v5e["bytes_per_s"]
    with pytest.raises(KeyError):
        roofline.peak("some other chip")


def test_a_real_profiler_trace_loads_and_anchors(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x * 2.0).sum())
    x = jnp.ones((64,))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path), profiler_options=trace.options())
    with jax.profiler.TraceAnnotation(trace.ANCHOR):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    planes = trace.load(str(tmp_path))
    assert trace.anchor_ns(planes) is not None
    assert any(p.startswith("/host:") for p, _, _ in planes)


def test_the_kernel_roofline_sizes_each_call_by_its_climb_span():
    from harness import spec

    planes, data = load_fixture()
    dt = trace.reduce(planes, (100.0, 100.001), 100.0)
    climb = types.SimpleNamespace(name="ls_device_climb", t0=100.0002,
                                  t1=100.0005, attrs={"rows": 64, "N": 1000})
    run = types.SimpleNamespace(trace=dt, spans=[climb],
                                device_kind="TPU v5 lite",
                                config={"planner": {"mu": 10}})
    share = spec.reader("gain_kernel_roofline")(run)
    nbytes, _ = roofline.gain_kernel(64, 1000, 10)
    assert share == pytest.approx(100.0 * nbytes / 819e9 / 190e-6)
    # a kernel outside any climb, or no trace, reads nothing
    run.spans = []
    assert spec.reader("gain_kernel_roofline")(run) is None
    run.trace = None
    assert spec.reader("gain_kernel_roofline")(run) is None
