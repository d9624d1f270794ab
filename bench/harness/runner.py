"""One run of one cell: set up, warm up, measure, check, report."""
from __future__ import annotations

import shutil
import sys
import time
import types

import numpy as np

from harness import check, generate, program, spec, trace, traffic
from harness.spec import BENCH_DIR


class NoAccelerator(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


def say(*parts) -> None:
    print(*parts, flush=True)


def devices_for(cell, require_tpu: bool):
    import jax

    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise NoAccelerator(f"no TPU: JAX runs on {devices[0].platform}")
    if len(devices) < cell.chips:
        raise NoAccelerator(f"{cell.chips} chips asked for, "
                            f"{len(devices)} visible")
    return devices


class Setup:
    """What a run builds before its window, kept for more windows: the
    devices, the tenant pool, the planner's inputs."""

    def __init__(self, cell: spec.Cell, require_tpu: bool = True):
        self.cell = cell
        self.devices = devices_for(cell, require_tpu)
        cfg, tr = cell.config, cell.traffic
        say(f"device: {self.devices[0].platform} "
            f"{self.devices[0].device_kind} x {len(self.devices)}")
        self.cluster = generate.make_cluster(cfg["nodes_per_type"],
                                             seed=cfg["cluster_seed"])
        self.plat = program.platform(self.cluster)
        self.pool = traffic.build_pool(cfg, tr, self.cluster, self.plat)
        pool = self.pool
        say(f"pool: {len(pool)} workflows, tasks "
            f"{min(e.graph.N for e in pool)}-"
            f"{max(e.graph.N for e in pool)}, T {min(e.T for e in pool)}-"
            f"{max(e.T for e in pool)}, buckets "
            f"{sorted({e.bucket for e in pool})}")
        self.compiles = program.compile_counter()

    def service(self):
        return program.service(self.cell.config, self.plat)

    def request(self, seed: int, i: int, entry: int, due: float = 0.0,
                *path: int):
        """Request ``i`` of the stream under ``seed``, and its planner form."""
        cfg, tr = self.cell.config, self.cell.traffic
        req = traffic.Request(i, entry, traffic.ensemble(
            cfg, tr, self.pool[entry], self.cluster.idle_total, seed,
            *(path or (0, i))), due)
        return req, program.request(self.pool[entry].instance, req.profiles,
                                    tr["variants"])

    def warm_up(self, svc, seed: int) -> None:
        """Serve, per shape bucket of the pool, one coalesced batch of
        each size in ``warm_batches``, at the cell's own profile count and
        variants, so every program the window runs is compiled."""
        buckets: dict[tuple, list[int]] = {}
        for i, entry in enumerate(self.pool):
            buckets.setdefault(entry.bucket, []).append(i)
        sizes = self.cell.traffic["warm_batches"]
        failed = 0
        for b, idx in enumerate(buckets.values()):
            for size in sizes:
                reqs = [self.request(seed, -1, idx[j % len(idx)], 0.0,
                                     9, b, size, j)[1] for j in range(size)]
                svc.pause()
                tickets = [svc.submit(r) for r in reqs]
                svc.resume()
                for t in tickets:
                    failed += program.served_ok(
                        t.result(), self.cell.config["engine"]) is not None
        say(f"warm-up: {len(buckets)} shape buckets x batch sizes {sizes}, "
            f"{failed} failed")

    def stream(self, seed: int, seconds: float):
        """The window's requests: the open loop's whole schedule, built
        now; the closed loop's maker, called as clients ask."""
        tr = self.cell.traffic
        if tr["loop"] == "open":
            due = traffic.arrivals(tr, seconds)
            order = traffic.pool_order(tr, len(self.pool), len(due))
            return [self.request(seed, i, order[i], d)
                    for i, d in enumerate(due)]
        order = traffic.pool_order(tr, len(self.pool), 10000)
        draw = traffic.forecast_draws(tr, seed, order)

        def make(i: int):
            forecast_seed, path = draw(i)
            return self.request(forecast_seed, i, order[i], 0.0, *path)
        return make

    def drive(self, svc, stream, seconds: float):
        """Serve one window: (records, how late each open-loop send was)."""
        tr, engine = self.cell.traffic, self.cell.config["engine"]
        if tr["loop"] == "open":
            return traffic.open_loop(svc, [r for r, _ in stream],
                                     [p for _, p in stream], engine)
        return traffic.closed_loop(svc, stream, tr["clients"], seconds,
                                   engine), []


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def execute(cell: spec.Cell, seed: int, seconds: float, traced: bool,
            t_process: float, require_tpu: bool = True) -> dict:
    setup = Setup(cell, require_tpu)
    devices, cfg, tr = setup.devices, cell.config, cell.traffic
    log_dir = BENCH_DIR / "out" / f"trace-{cell.name}-{seed}"
    with setup.service() as svc:
        setup.warm_up(svc, seed)
        stream = setup.stream(seed, seconds)
        if traced:
            shutil.rmtree(log_dir, ignore_errors=True)
            tracer = program.start_spans()
            profiler = trace.Profiler(str(log_dir),
                                      max(seconds - trace.TRACED_S, 0.0))
        c0 = setup.compiles()
        setup_s = time.perf_counter() - t_process
        records, late = setup.drive(svc, stream, seconds)
        c1 = setup.compiles()
        if traced:
            spans = program.stop_spans(tracer)
            t0 = time.perf_counter()
            profiler.stop()
            say(f"profiler stopped in {time.perf_counter() - t0:.3f} s")
        peak = memory_peak(devices[:cell.chips])
    window = (min(r.due for r in records),
              max((r.done for r in records if r.done is not None),
                  default=time.perf_counter()))
    failed = [r for r in records if not r.ok]
    say(f"window: {window[1] - window[0]:.4f} s, {len(records)} requests, "
        f"{len(failed)} failed, {c1[0] - c0[0]} compiles and "
        f"{c1[1] - c0[1]} traces inside it")
    if late:
        say(f"generator lateness: max {max(late) * 1e3:.3f} ms, p95 "
            f"{np.percentile(late, 95) * 1e3:.3f} ms over {len(late)} sends")
    for r in failed[:5]:
        say(f"failed request {r.request.index}: {r.why}")

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak}
    run = types.SimpleNamespace(
        records=records, window=window, setup_s=setup_s,
        spans=[s for s in spans if s.t1 > window[0] and s.t0 < window[1]]
        if traced else [],
        trace=None, device_kind=devices[0].device_kind, config=cfg)
    out = {}
    if traced:
        t0 = time.perf_counter()
        planes = trace.load(str(log_dir))
        t1 = time.perf_counter()
        if profiler.anchor is not None:
            run.trace = trace.reduce(planes, (profiler.anchor, window[1]),
                                     profiler.anchor)
        say(f"trace: {sum(len(e) for _, _, e in planes)} events loaded in "
            f"{t1 - t0:.3f} s, reduced in {time.perf_counter() - t1:.3f} s")
        shutil.rmtree(log_dir, ignore_errors=True)
        if run.trace is not None:
            device["busy_s"] = run.trace.busy_s
            device["window_s"] = run.trace.window_s
            out["breakdown"] = {
                "device_ops": trace.top_ops(run.trace),
                "idle_gaps": trace.label_gaps(run.trace, run.spans)}
    metrics = {}
    for m in (cell.per_layer if traced else cell.end_to_end):
        value = spec.reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    counts = check.compare(cfg, tr, setup.pool, records, seed)
    checks = {k: {"value": v, "limit": check.LIMITS[k]}
              for k, v in counts.items()}
    result = {"correct": all(c["value"] <= c["limit"]
                             for c in checks.values()),
              "attempted": len(records), "failed": len(failed),
              "metrics": metrics, "device": device, **out,
              "checks": checks}
    for k, c in checks.items():
        print(f"check {k}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    return result
