"""Run one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

From the root of a checkout. ``BENCHMARK.json`` names the cell's
configuration (``bench/configs/``), traffic (``bench/traffic/``) and
metrics (``bench/metrics/``). Set-up builds the tenant pool from the
seed and compiles every shape the window will use; the window then
serves the traffic through ``PlanService`` for ``--seconds``; after it,
sampled answers are compared with the plain reference
(``bench/harness/reference.py``). With ``--trace 1`` the window runs
under the JAX profiler and span tracing, and the per-layer metrics are
reported instead of the end-to-end ones.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last ``checks``, each compared number with its
limit); the last lines of standard error repeat the checks. Without a
TPU, or with fewer chips than the cell asks for, it prints no result
and exits with code 3.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(HERE), "src")]


def main(argv=None, require_tpu: bool = True) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    from harness import runner, spec

    cell = spec.load_cell(args.workload)
    try:
        result = runner.execute(cell, args.seed, args.seconds,
                                bool(args.trace), T_PROCESS, require_tpu)
    except runner.NoAccelerator as e:
        print(f"benchmark not run: {e}", file=sys.stderr, flush=True)
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
