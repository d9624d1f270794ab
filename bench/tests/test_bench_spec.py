"""The harness is driven by data: every cell resolves its files by name,
and a new cell, configuration, traffic mix and metric need new files and
entries only."""
import importlib.util
import json
import re
import shutil
import types

import pytest

from harness import spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def benchmark():
    return json.loads((spec.ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("workload", [w["name"] for w in
                                      benchmark()["workloads"]])
def test_every_cell_resolves_its_files_by_name(workload):
    cell = spec.load_cell(workload)
    assert cell.config["engine"] == "jax"
    assert cell.config["reduced"] == []
    assert {"loop", "variants", "check_requests", "warm_batches"} \
        <= set(cell.traffic)
    assert any(m["name"] == "setup_s" for m in cell.end_to_end)
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    for m in cell.end_to_end + cell.per_layer:
        assert callable(spec.reader(m["name"]))


def test_benchmark_json_keeps_to_its_shape():
    b = benchmark()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in b[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/configs/")
        assert json.loads((spec.ROOT / c["file"]).read_text())["name"] \
            == c["name"]
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert len(w["why"]) <= 200
    for m in b["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    layers = {m["layer"] for m in b["per_layer"]}
    moved = {m["name"] for m in b["end_to_end"]}
    assert all(m["moves"] in moved for m in b["per_layer"])
    assert layers


def test_a_new_cell_config_and_metric_need_only_new_files(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(spec.ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    b = benchmark()
    before = {p.relative_to(root): p.read_bytes()
              for p in (root / "bench").rglob("*") if p.is_file()}
    # the new files
    config = json.loads((root / "bench/configs/nfcore-200-large.json")
                        .read_text())
    config.update(name="nfcore-200-small", nodes_per_type=12)
    (root / "bench/configs/nfcore-200-small.json").write_text(
        json.dumps(config))
    traffic = json.loads((root / "bench/traffic/open-ens8.json").read_text())
    traffic.update(rate_per_s=1.0)
    (root / "bench/traffic/open-slow.json").write_text(json.dumps(traffic))
    (root / "bench/metrics/attempted_requests.py").write_text(
        "def read(run):\n    return len(run.records)\n")
    # the new entries
    b["configs"].append(dict(b["configs"][0], name="nfcore-200-small",
                             file="bench/configs/nfcore-200-small.json"))
    b["workloads"].append({"name": "nfcore-200s.open-slow",
                           "config": "nfcore-200-small",
                           "traffic": "open-slow", "chips": 1, "why": "x"})
    b["end_to_end"].append({"name": "plan_latency_p90_s", "unit": "s",
                            "better": "lower", "bound": 0.25,
                            "source": "host_clock",
                            "workloads": ["nfcore-200s.open-slow"]})
    b["per_layer"].append({"name": "attempted_requests", "unit": "requests",
                           "better": "higher", "source": "host_clock",
                           "layer": "load", "moves": "plan_latency_p90_s",
                           "workloads": ["nfcore-200s.open-slow"]})
    (root / "BENCHMARK.json").write_text(json.dumps(b))

    cell = spec.load_cell("nfcore-200s.open-slow", root=root)
    assert cell.config["nodes_per_type"] == 12
    assert cell.traffic["rate_per_s"] == 1.0
    assert [m["name"] for m in cell.per_layer][-1] == "attempted_requests"
    assert {m["name"] for m in cell.end_to_end} == {"plan_latency_p90_s",
                                                    "setup_s"}
    run = types.SimpleNamespace(records=[1, 2, 3])
    assert spec.reader("attempted_requests", root=root)(run) == 3
    # and no file that was there changed
    after = {p.relative_to(root): p.read_bytes()
             for p in (root / "bench").rglob("*") if p.is_file()}
    assert all(after[k] == v for k, v in before.items())


def test_without_a_tpu_the_harness_exits_non_zero_and_prints_no_result(
        capsys):
    path = spec.BENCH_DIR / "run.py"
    module_spec = importlib.util.spec_from_file_location("bench_run", path)
    run = importlib.util.module_from_spec(module_spec)
    module_spec.loader.exec_module(run)
    rc = run.main(["--workload", "nfcore-1k.replan-ens8", "--seed",
                   str(2**31 + 5), "--seconds", "1", "--trace", "0"])
    out, err = capsys.readouterr()
    assert rc != 0
    assert "no TPU" in err
    assert "{" not in out and "metrics" not in out
