"""Drive the planner's device path once on a TPU, at paper scale.

Run from the repository root, in one process (it holds the chip):

    python chip_smoke.py            # one chip: the served path
    python chip_smoke.py --chips 4  # four chips: only the sharded grid

One chip: ``PlanService(Planner(make_cluster(24), engine="jax"))`` serves
one request per workflow family at 1000 tasks and one 4000-task request
(past the dense longest-path envelope, so the blocked form and the
padded-CSR climb run), each with an 8-member forecast ensemble
(scenarios S1-S4 x 2 seeds), a 2.0 deadline factor, HEFT mapping and
all 17 variants. The requests are submitted while the service is paused
so they coalesce into one grid. The results are checked against
references that run on the host: the greedy variants must equal the
numpy engine start for start; every ``-LS`` row must be valid, no
costlier than its greedy row, and unimproved by one sequential
reference round. The compiled gain kernel must equal its jnp twin bit
for bit at N >= 4096. A second identical pass must compile nothing.

Four chips (``--chips 4``): the grid launch sharded over 4 devices
(``devices=4``) against the single-device launch (``devices=None``) over
16 paper-scale instance rows of one shape bucket, compared bitwise.

Lines before the last say what ran and how long it took (cold = compile
plus run, steady = a second identical pass). The last line is one JSON
object naming the device; it is printed only when every check passed.
The script exits non-zero, printing no result, when JAX finds no TPU,
when a request is degraded or fails, or when a check fails.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

WORKFLOW_SIZE = 1000          # paper §6.1 sizes: 1000 and 4000 tasks
BIG_WORKFLOW_SIZE = 4000
DEADLINE_FACTOR = 2.0
SCENARIOS = ("S1", "S2", "S3", "S4")
PROFILE_SEEDS = (0, 1)        # x SCENARIOS = the 8-member ensemble
KERNEL_SIZES = (4096, 8704)   # 8704: the 4000-task instance's task bucket
KERNEL_MUS = (10, 21)
SHARDED_ROWS = 16             # instance rows of the four-chip comparison


class SmokeFailure(RuntimeError):
    """A phase of the smoke did not do what it must."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def say(*parts) -> None:
    print(*parts, flush=True)


def require_tpu():
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SmokeFailure(f"no TPU: jax runs on {devices[0].platform}")
    return devices


def paper_instance(platform, kind: str, size: int):
    """A HEFT-mapped ``wfgen_scale`` instance, its horizon at the deadline
    factor, and its green capacity: the workload's mean ASAP draw, as the
    benchmark matrix calibrates it (``benchmarks/common.build_matrix``),
    so that scheduling decisions matter."""
    from repro.core import (asap_schedule, build_instance, deadline_from_asap,
                            heft_mapping)
    from repro.core.carbon import work_timeline
    from repro.workflows import wfgen_scale

    wf = wfgen_scale(kind, size, seed=0)
    inst = build_instance(wf, heft_mapping(wf, platform), platform)
    capacity = int(work_timeline(inst, deadline_from_asap(inst, 1.0),
                                 asap_schedule(inst)).mean())
    return inst, deadline_from_asap(inst, DEADLINE_FACTOR), capacity


def ensemble(platform, T: int, capacity: int, seed: int = 0):
    """The 8-member forecast ensemble: S1-S4 x 2 profile seeds."""
    from repro.core import generate_profile

    return [generate_profile(sc, T, platform, J=48,
                             seed=100 * seed + 10 * ps + si,
                             work_capacity=capacity)
            for ps in PROFILE_SEEDS for si, sc in enumerate(SCENARIOS)]


def describe(name: str, inst, profiles) -> None:
    from repro.core.greedy_jax import pad_dims
    from repro.kernels.backend import resolve_lp_form

    T = profiles[0].T
    n_pad, t_pad = pad_dims(inst.num_tasks, T)
    say(f"instance {name}: tasks={inst.num_tasks} T={T} "
        f"bucket={n_pad}x{t_pad} lp={resolve_lp_form(inst.num_tasks)} "
        f"profiles={len(profiles)}")


def compile_counter():
    """Callable returning (backend compiles, traces, compile seconds) so
    far, from jax's monitoring events via the repo's obs hooks."""
    from repro import obs

    obs.jax_hooks.install(obs.registry())
    events = obs.registry().counter("jax_compile_events_total",
                                    labels=("event",))
    seconds = obs.registry().counter("jax_compile_seconds_total",
                                     labels=("event",))

    def read():
        return (int(events.value(event="backend_compile_duration")),
                int(events.value(event="jaxpr_trace_duration")),
                seconds.value(event="backend_compile_duration"))
    return read


def kernel_phase() -> None:
    """The compiled gain kernel equals its jnp twin bit for bit."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.backend import resolve_mode
    from repro.kernels.gain_scan import gains_from_windows, \
        gains_windows_auto, gather_windows

    check(resolve_mode(None) == "pallas",
          f"gain kernel resolves to {resolve_mode(None)!r}, not the "
          f"compiled kernel")
    for n in KERNEL_SIZES:
        for mu in KERNEL_MUS:
            rng = np.random.default_rng(n + mu)
            t = 1024
            rem = jnp.asarray(rng.integers(-9, 9, t).astype(np.float32))
            dur = jnp.asarray(rng.integers(1, 9, n).astype(np.float32))
            start = jnp.asarray(
                rng.integers(0, t - 10, n).astype(np.float32))
            work = jnp.asarray(rng.integers(0, 7, n).astype(np.float32))
            lo = jnp.asarray(-rng.integers(0, 2 * mu + 5, n)
                             .astype(np.float32))
            hi = jnp.asarray(rng.integers(0, 2 * mu + 5, n)
                             .astype(np.float32))
            win_s, win_e = gather_windows(rem, start, dur, mu=mu)
            args = (win_s, win_e, work, dur, lo, hi)
            kernel = jax.jit(functools.partial(gains_windows_auto, mu=mu))
            twin = jax.jit(functools.partial(gains_from_windows, mu=mu))
            t0 = time.perf_counter()
            got = np.asarray(kernel(*args))
            cold = time.perf_counter() - t0
            want = np.asarray(twin(*args))
            check(got.shape == (n, 2 * mu + 1), f"kernel shape {got.shape}")
            check(np.isfinite(got).all(), "kernel output not finite")
            check(np.array_equal(got.view(np.uint32),
                                 want.view(np.uint32)),
                  f"gain kernel != jnp twin at N={n} mu={mu}: "
                  f"{int((got != want).sum())} entries differ")
            say(f"kernel N={n} mu={mu}: bitwise equal to the jnp twin "
                f"(cold {cold:.3f} s)")


def reference_check(platform, cases, results) -> None:
    """Greedy rows equal the numpy engine; -LS rows are polished."""
    import numpy as np

    from repro.api import Planner, PlanRequest
    from repro.core import PORTFOLIO_VARIANTS, validate_schedule
    from repro.core.local_search import local_search

    greedy_names = tuple(v for v in PORTFOLIO_VARIANTS
                         if not v.endswith("-LS"))
    t0 = time.perf_counter()
    ref = Planner(platform, engine="numpy").plan(PlanRequest(
        instances=[inst for _, inst, _ in cases],
        profiles=[profs for _, _, profs in cases], variants=greedy_names))
    say(f"numpy reference: {len(cases)} instances x "
        f"{len(cases[0][2])} profiles x {len(greedy_names)} variants in "
        f"{time.perf_counter() - t0:.2f} s")
    t0 = time.perf_counter()
    n_greedy = n_ls = 0
    for i, ((name, inst, profs), res) in enumerate(zip(cases, results)):
        for p, prof in enumerate(profs):
            cell = res.results[0][p]
            check(set(cell) == set(PORTFOLIO_VARIANTS),
                  f"{name}: variants {sorted(cell)}")
            for v, got in cell.items():
                validate_schedule(inst, prof, got.start)
                if v.endswith("-LS"):
                    check(got.cost <= cell[v[:-3]].cost,
                          f"{name} p{p} {v}: LS cost {got.cost} above "
                          f"greedy {cell[v[:-3]].cost}")
                    again = local_search(inst, prof, platform, got.start,
                                         max_rounds=1)
                    check(np.array_equal(again, got.start),
                          f"{name} p{p} {v}: one reference round still "
                          f"improves the device climb's result")
                    n_ls += 1
                else:
                    want = ref.results[i][p][v].start
                    check(np.array_equal(np.asarray(got.start),
                                         np.asarray(want)),
                          f"{name} p{p} {v}: starts differ from the numpy "
                          f"engine")
                    n_greedy += 1
    say(f"checked {n_greedy} greedy rows against numpy and {n_ls} LS rows "
        f"for polish in {time.perf_counter() - t0:.2f} s")


def serve_pass(service, cases):
    """Submit every case while paused (so they coalesce), then drain."""
    from repro.api import PlanRequest

    service.pause()
    tickets = [service.submit(PlanRequest(instances=inst, profiles=profs))
               for _, inst, profs in cases]
    t0 = time.perf_counter()
    service.resume()
    results = [t.result() for t in tickets]
    seconds = time.perf_counter() - t0
    for (name, _, _), res in zip(cases, results):
        check(res.engine == "jax", f"{name}: engine {res.engine}")
        check(res.degraded is False,
              f"{name}: degraded to {res.fallback_stage} ({res.attempts})")
        check(res.attempts == ("heuristic:ok",),
              f"{name}: attempts {res.attempts}")
    return results, seconds


# the planner's layers, in path order (the obs spans each one opens)
LAYER_SPANS = ("prepare_graph", "bucket_launch", "blocked_chunk_sweep",
               "ls_device_climb", "ls_polish", "plan")


def span_seconds(tracer) -> dict:
    """Seconds per layer span finished since the last call."""
    totals = dict.fromkeys(LAYER_SPANS, 0.0)
    for s in tracer.finished():
        if s.name in totals:
            totals[s.name] += s.duration
    tracer.clear()
    return totals


def one_chip(platform) -> None:
    from repro import obs
    from repro.api import Planner
    from repro.serve import PlanService
    from repro.workflows import WORKFLOW_KINDS

    compiles = compile_counter()
    kernel_phase()

    t0 = time.perf_counter()
    cases = []
    for kind, size in [(k, WORKFLOW_SIZE) for k in WORKFLOW_KINDS] + [
            ("atacseq", BIG_WORKFLOW_SIZE)]:
        inst, T, capacity = paper_instance(platform, kind, size)
        cases.append((f"{kind}-{size}", inst,
                      ensemble(platform, T, capacity)))
    say(f"built {len(cases)} instances in {time.perf_counter() - t0:.2f} s")
    for name, inst, profs in cases:
        describe(name, inst, profs)

    tracer, _ = obs.configure(tracing=True)
    with PlanService(Planner(platform, engine="jax")) as service:
        check(service.compile_cache_dir is not None,
              "persistent compilation cache could not be enabled")
        say(f"compile cache: {service.compile_cache_dir}")
        c0 = compiles()
        results, cold = serve_pass(service, cases)
        c1 = compiles()
        spans = span_seconds(tracer)
        say(f"cold pass: {cold:.2f} s, {c1[0] - c0[0]} compiles "
            f"({c1[2] - c0[2]:.2f} s compiling), {c1[1] - c0[1]} traces")
        say("cold layers (s): " + ", ".join(
            f"{k}={v:.2f}" for k, v in spans.items()))
        again, steady = serve_pass(service, cases)
        c2 = compiles()
        spans = span_seconds(tracer)
        say(f"steady pass: {steady:.2f} s, {c2[0] - c1[0]} compiles, "
            f"{c2[1] - c1[1]} traces")
        say("steady layers (s): " + ", ".join(
            f"{k}={v:.2f}" for k, v in spans.items()))
        check(c2[0] == c1[0], f"steady pass compiled {c2[0] - c1[0]} "
              f"programs (expected 0)")
    obs.set_tracer(None)
    for (name, _, _), a, b in zip(cases, results, again):
        check(all((a.results[0][p][v].start == b.results[0][p][v].start)
                  .all() for p in range(len(a.results[0]))
                  for v in a.variants),
              f"{name}: the steady pass changed a schedule")
    reference_check(platform, cases, results)
    stats = platform_memory()
    if stats:
        say(f"device memory: {stats}")


def platform_memory() -> str:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return ", ".join(f"{k}={stats[k]}" for k in
                     ("peak_bytes_in_use", "bytes_limit") if k in stats)


def four_chips(platform, n_devices: int) -> None:
    """The grid launch sharded over ``n_devices`` against one device."""
    import numpy as np

    from repro import obs
    from repro.api import Planner, PlanRequest
    from repro.core import PORTFOLIO_VARIANTS

    greedy_names = tuple(v for v in PORTFOLIO_VARIANTS
                         if not v.endswith("-LS"))
    # 16 rows of one shape bucket: one instance, 16 distinct ensembles
    inst, T, capacity = paper_instance(platform, "atacseq", WORKFLOW_SIZE)
    grid = [ensemble(platform, T, capacity, seed=r)
            for r in range(SHARDED_ROWS)]
    describe(f"atacseq-{WORKFLOW_SIZE} x {SHARDED_ROWS} rows", inst, grid[0])
    planner = Planner(platform, engine="jax")
    request = dict(instances=[inst] * SHARDED_ROWS, profiles=grid,
                   variants=greedy_names)
    tracer, _ = obs.configure(tracing=True)
    out = {}
    for devices in (None, n_devices, n_devices, None):
        t0 = time.perf_counter()
        res = planner.plan(PlanRequest(devices=devices, **request))
        wall = time.perf_counter() - t0
        launch = span_seconds(tracer)["bucket_launch"]
        key = devices or 1
        say(f"devices={key}: {'steady' if key in out else 'cold'} plan "
            f"{wall:.3f} s, grid launch {launch:.3f} s")
        out[key] = res
    obs.set_tracer(None)
    base, sharded = out[1], out[n_devices]
    for i in range(SHARDED_ROWS):
        for p in range(len(grid[i])):
            for v in greedy_names:
                check(np.array_equal(base.results[i][p][v].start,
                                     sharded.results[i][p][v].start),
                      f"row {i} p{p} {v}: devices={n_devices} differs "
                      f"from one device")
    say(f"sharded grid over {n_devices} devices is bitwise equal to one "
        f"device on {SHARDED_ROWS} rows x {len(grid[0])} profiles x "
        f"{len(greedy_names)} variants")


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="4: run only the sharded grid over 4 chips")
    args = parser.parse_args(argv)
    try:
        devices = require_tpu()
        check(len(devices) >= args.chips,
              f"{args.chips} chips asked for, {len(devices)} visible")
        from repro.cluster import LARGE_CLUSTER_NODES_PER_TYPE, make_cluster

        platform = make_cluster(LARGE_CLUSTER_NODES_PER_TYPE)
        say(f"device: {devices[0].device_kind} x {len(devices)}; cluster "
            f"of {len(platform.speed)} nodes")
        t0 = time.perf_counter()
        if args.chips == 4:
            four_chips(platform, 4)
        else:
            one_chip(platform)
        say(f"total {time.perf_counter() - t0:.2f} s")
    except SmokeFailure as e:
        print(f"chip smoke FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
