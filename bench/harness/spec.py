"""Find a cell's files by name: ``BENCHMARK.json`` at the checkout root
names the cell's configuration and traffic; each lives in a file of its
own under ``bench/configs/`` and ``bench/traffic/``, and each metric is a
reader in ``bench/metrics/<metric name>.py``."""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent.parent
ROOT = BENCH_DIR.parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list        # metric entries of BENCHMARK.json for this cell
    per_layer: list


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(workload: str, root: Path = ROOT) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    entry = next((w for w in bench["workloads"] if w["name"] == workload),
                 None)
    if entry is None:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    config = next(c for c in bench["configs"] if c["name"] == entry["config"])
    return Cell(
        name=workload, chips=int(entry["chips"]),
        config=json.loads((root / config["file"]).read_text()),
        traffic=json.loads((root / "bench" / "traffic"
                            / f"{entry['traffic']}.json").read_text()),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, workload)])


def reader(metric_name: str, root: Path = ROOT):
    """The ``read(run)`` function of ``bench/metrics/<metric_name>.py``."""
    path = root / "bench" / "metrics" / f"{metric_name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric_name.replace(".", "_").replace("-", "_"),
        path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read
