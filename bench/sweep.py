"""Offer an open-loop cell's traffic at several fixed rates, one process.

    python3 bench/sweep.py --workload nfcore-200.open-ens8 \
        --rates 2,4,8 --seconds 20 --seed 7

Prints one JSON line per rate (latency median and 95th percentile,
requests attempted and failed). The highest rate at which nothing fails
and the 95th percentile stays near the median's scale is what the system
sustains; the cell's traffic file offers about four fifths of it.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(HERE), "src")]


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--rates", required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args()

    from harness import runner, spec

    cell = spec.load_cell(args.workload)
    for rate in (float(r) for r in args.rates.split(",")):
        cell.traffic = dict(cell.traffic, rate_per_s=rate)
        try:
            res = runner.execute(cell, args.seed, args.seconds, False,
                                 time.perf_counter())
        except runner.NoAccelerator as e:
            print(f"sweep not run: {e}", file=sys.stderr)
            return 3
        print(json.dumps({"rate_per_s": rate, "attempted": res["attempted"],
                          "failed": res["failed"], "correct": res["correct"],
                          **{k: v["value"] for k, v in
                             res["metrics"].items()}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
