"""Sharded portfolio grid + tiled gain kernel: honest device-scaling data.

Feeds the ``sharded`` section of ``benchmarks/out/BENCH_portfolio.json``
(via :mod:`benchmarks.fig_portfolio`; also runnable standalone):

* ``device_sweep`` — the combined grid launch
  (``schedule_portfolio_grid(..., devices=d)``) timed at each device
  count over the SAME instance rows, bitwise-verified against the
  single-device launch.  On an accelerator the sweep runs in-process
  over the real devices (a chip belongs to one process, so a child
  could not reach it).  On CPU it runs in a subprocess so
  ``--xla_force_host_platform_device_count`` lands before the jax
  backend initializes; every "device" is then a slice of the same host
  CPU (``host_cpus`` is recorded next to the curve), so the numbers
  measure partitioning overhead, not parallel speedup.
* ``gain_kernel`` — the tiled Pallas gain kernel vs its jnp prefix-sum
  twin across task counts, both over the same gathered windows.  On a
  chip the kernel is the compiled one (``kernel_mode: "pallas"``); on
  CPU it executes under the Pallas interpreter (``"interpret"``, orders
  of magnitude slower than compiled jnp), so ``crossover_n`` is
  ``null`` there.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

import numpy as np

_SWEEP_VARIANTS = ("asap", "pressW", "pressWR")


def _build_rows(n_inst: int):
    from repro.cluster import make_cluster
    from repro.core import (build_instance, deadline_from_asap,
                            generate_profile, heft_mapping)
    from repro.workflows import WORKFLOW_KINDS, make_workflow

    plat = make_cluster(1, seed=0)
    insts, rows = [], []
    for i in range(n_inst):
        wf = make_workflow(WORKFLOW_KINDS[i % len(WORKFLOW_KINDS)], 2,
                           seed=i)
        inst = build_instance(wf, heft_mapping(wf, plat), plat)
        T = deadline_from_asap(inst, 2.0)
        insts.append(inst)
        rows.append([generate_profile("S3", T, plat, J=8, seed=i)])
    return plat, insts, rows


def _sweep(devices: list[int], n_inst: int, reps: int) -> dict:
    """Time the grid launch per device count over the visible devices and
    prove bitwise identity against the single-device baseline."""
    import jax

    from repro.core.portfolio import schedule_portfolio_grid

    plat, insts, rows = _build_rows(n_inst)

    def launch(d):
        return schedule_portfolio_grid(insts, rows, plat,
                                       variants=_SWEEP_VARIANTS,
                                       engine="jax", devices=d)

    base = launch(None)
    curve = []
    for d in devices:
        launch(d)                                   # compile this mesh
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            res = launch(d)
            ts.append(time.perf_counter() - t0)
        for i, row in enumerate(base):              # bitwise, every cell
            for p, cell in enumerate(row):
                for name, r in cell.items():
                    got = res[i][p][name]
                    assert np.array_equal(np.asarray(r.start),
                                          np.asarray(got.start)), \
                        (d, i, p, name)
                    assert r.cost == got.cost, (d, i, p, name)
        curve.append({"devices": d, "steady_us": float(np.median(ts)) * 1e6,
                      "bitwise_identical": True})
    one = curve[0]["steady_us"]
    for pt in curve:
        pt["speedup_vs_1"] = one / pt["steady_us"]
    return {
        "jax_devices": len(jax.devices()),
        "host_cpus": os.cpu_count(),
        "n_instances": n_inst,
        "n_profiles": len(rows[0]),
        "variants": list(_SWEEP_VARIANTS),
        "curve": curve,
        "note": ("virtual host devices share one CPU: the curve measures "
                 "shard_map partitioning overhead, not parallel speedup")
        if jax.default_backend() == "cpu" else "real devices",
    }


def device_sweep(devices=(1, 2, 8), n_inst: int = 8, reps: int = 3) -> dict:
    """Run :func:`_sweep` over the real devices in-process on an
    accelerator (counts past the visible devices are dropped), or in a
    subprocess with the forced host device count on CPU, so the parent's
    already-initialized backend is moot."""
    import jax

    if jax.default_backend() != "cpu":
        n = len(jax.devices())
        return _sweep([d for d in devices if d <= n], n_inst, reps)
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        " --xla_force_host_platform_device_count="
                        f"{max(devices)}").strip()
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in ("src", env.get("PYTHONPATH", "")) if p)
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.fig_sharded", "--child",
         "--devices", ",".join(map(str, devices)),
         "--n-inst", str(n_inst), "--reps", str(reps)],
        env=env, capture_output=True, text=True, timeout=900)
    if proc.returncode != 0:
        raise RuntimeError(f"sharded sweep subprocess failed:\n"
                           f"{proc.stdout}\n{proc.stderr}")
    return json.loads(proc.stdout.splitlines()[-1])


def gain_kernel_crossover(sizes=(256, 1024), t: int = 512, mu: int = 21,
                          reps: int = 3) -> dict:
    """jnp prefix-sum twin vs the Pallas gain kernel (compiled on a chip,
    interpreted on CPU) over the same gathered windows."""
    import functools

    import jax
    import jax.numpy as jnp

    from repro.kernels.gain_scan import _kernel_call, gains_from_windows, \
        gather_windows

    backend = jax.default_backend()
    kernel_mode = "interpret" if backend == "cpu" else "pallas"
    twin = jax.jit(functools.partial(gains_from_windows, mu=mu))
    kernel = jax.jit(functools.partial(_kernel_call, mu=mu,
                                       mode=kernel_mode))
    points = []
    for n in sizes:
        rng = np.random.default_rng(n)
        rem = jnp.asarray(rng.integers(-9, 9, t).astype(np.float32))
        dur = jnp.asarray(rng.integers(1, 9, n).astype(np.float32))
        start = jnp.asarray(rng.integers(0, t - 10, n).astype(np.float32))
        work = jnp.asarray(rng.integers(0, 7, n).astype(np.float32))
        win_s, win_e = gather_windows(rem, start, dur, mu=mu)
        lo_rel = jnp.maximum(start - 30, 0) - start
        args = (win_s, win_e, work, dur, lo_rel, jnp.full_like(start, 30))

        def timed(fn):
            fn(*args).block_until_ready()                       # warm
            ts = []
            for _ in range(reps):
                t0 = time.perf_counter()
                fn(*args).block_until_ready()
                ts.append(time.perf_counter() - t0)
            return float(np.median(ts)) * 1e6

        points.append({"n_tasks": n, "t": t, "jnp_twin_us": timed(twin),
                       "kernel_us": timed(kernel)})
    faster = [p["n_tasks"] for p in points
              if p["kernel_us"] < p["jnp_twin_us"]]
    return {
        "backend": backend,
        "mu": mu,
        "kernel_mode": kernel_mode,
        "points": points,
        # smallest N where the kernel wins; null on CPU, where the
        # interpreter (not the Mosaic lowering) runs the kernel
        "crossover_n": min(faster) if faster else None,
    }


def section(smoke: bool = False) -> dict:
    if smoke:
        sweep = device_sweep(devices=(1, 2, 8), n_inst=8, reps=3)
        kern = gain_kernel_crossover(sizes=(256, 1024))
    else:
        sweep = device_sweep(devices=(1, 2, 4, 8), n_inst=16, reps=5)
        kern = gain_kernel_crossover(sizes=(256, 1024, 4096))
    return {"device_sweep": sweep, "gain_kernel": kern}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--child", action="store_true")
    ap.add_argument("--devices", default="1,2,8")
    ap.add_argument("--n-inst", type=int, default=8)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args()
    if args.child:
        out = _sweep([int(d) for d in args.devices.split(",")],
                           args.n_inst, args.reps)
        print(json.dumps(out))
    else:
        print(json.dumps(section(smoke=args.smoke), indent=2))


if __name__ == "__main__":
    main()
