"""The readings that set ``correct``'s limits, for one cell, one process.

    python3 bench/readings.py --workload nfcore-1k.replan-ens8 \
        --seconds 8 --seeds 1,2,3 --control-seeds 1,2,3

Sets the cell up once, then for each seed serves a short window at the
cell's own load and compares the sampled answers with the reference, as
a run does (the program's reading). For each control seed it also puts
the reference computed one precision lower in the program's place (the
control's reading). One JSON line per seed.
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
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--control-seeds", default="")
    args = parser.parse_args()

    from harness import check, runner, spec

    cell = spec.load_cell(args.workload)
    cfg, tr = cell.config, cell.traffic
    try:
        setup = runner.Setup(cell)
    except runner.NoAccelerator as e:
        print(f"readings not taken: {e}", file=sys.stderr)
        return 3

    def control(entry, rec):
        return check.reference_rows(cfg, entry.graph, rec.request.profiles,
                                    tr["variants"], control=True)

    controls = {int(s) for s in args.control_seeds.split(",") if s}
    with setup.service() as svc:
        setup.warm_up(svc, 0)
        for seed in (int(s) for s in args.seeds.split(",")):
            records, _ = setup.drive(svc, setup.stream(seed, args.seconds),
                                     args.seconds)
            t0 = time.perf_counter()
            line = {"seed": seed, "requests": len(records),
                    "failed": sum(not r.ok for r in records),
                    "program": check.compare(cfg, tr, setup.pool, records,
                                             seed)}
            line["check_s"] = time.perf_counter() - t0
            if seed in controls:
                line["control"] = check.compare(cfg, tr, setup.pool, records,
                                                seed, served_rows=control)
            print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
