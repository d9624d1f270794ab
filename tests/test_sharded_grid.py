"""Multi-device sharded portfolio grid: sharded == single-device BITWISE.

Forces 8 virtual host devices (must happen before the jax backend
initializes — same module-import pattern as tests/test_pipeline.py) and
proves the `shard_map` grid launch (`devices=` on `Planner` /
`PlanRequest` / `schedule_portfolio_grid`) changes nothing but the
device placement: the greedy scan is integer arithmetic over independent
vmap rows, so every start time and cost must match the single-device
launch exactly, through every entry layer.

Run via `make test-sharded` (wired into `make verify`), which sets the
forced-host-device-count flag so the multi-device path cannot rot on
CPU-only CI.
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import numpy as np
import pytest

from repro.api import Planner, PlanRequest
from repro.cluster import make_cluster
from repro.core import (build_instance, deadline_from_asap,
                        generate_profile, heft_mapping)
from repro.core.portfolio import schedule_portfolio_grid
from repro.workflows import make_workflow

pytestmark = pytest.mark.device

VARIANTS = ("asap", "pressWR-LS", "pressW")


@pytest.fixture(scope="module")
def platform():
    return make_cluster(1, seed=0)


@pytest.fixture(scope="module")
def grid_case(platform):
    """5 instances x 2 profiles (odd instance count exercises the
    pad-rows-to-device-multiple path at ndev=8)."""
    kinds = ["eager", "atacseq", "eager", "bacass", "methylseq"]
    insts, rows = [], []
    for i, kind in enumerate(kinds):
        wf = make_workflow(kind, 2, seed=i)
        inst = build_instance(wf, heft_mapping(wf, platform), platform)
        T = deadline_from_asap(inst, 2.0)
        insts.append(inst)
        rows.append([generate_profile("S3", T, platform, J=8, seed=i),
                     generate_profile("S1", T, platform, J=8, seed=i + 50)])
    return insts, rows


def _flatten(cells):
    out = {}
    for i, row in enumerate(cells):
        for p, cell in enumerate(row):
            for name, r in cell.items():
                out[(i, p, name)] = (np.asarray(r.start), int(r.cost))
    return out


def test_eight_virtual_devices_visible():
    import jax

    assert len(jax.devices()) == 8


def test_grid_mesh_and_spec():
    import jax
    from jax.sharding import PartitionSpec as P

    from repro.sharding.ctx import grid_mesh
    from repro.sharding.specs import grid_batch_spec

    mesh = grid_mesh()
    assert mesh.axis_names == ("data",)
    assert mesh.shape["data"] == len(jax.devices()) == 8
    assert grid_mesh(3).shape["data"] == 3
    assert grid_batch_spec() == P("data")
    with pytest.raises(ValueError, match="devices"):
        grid_mesh(99)
    with pytest.raises(ValueError, match="devices"):
        grid_mesh(0)


@pytest.mark.parametrize("ndev", [2, 8])
def test_sharded_grid_bitwise_identical(grid_case, platform, ndev):
    insts, rows = grid_case
    base = _flatten(schedule_portfolio_grid(
        insts, rows, platform, variants=VARIANTS, engine="jax"))
    shard = _flatten(schedule_portfolio_grid(
        insts, rows, platform, variants=VARIANTS, engine="jax",
        devices=ndev))
    assert base.keys() == shard.keys()
    for key in base:
        assert np.array_equal(base[key][0], shard[key][0]), key
        assert base[key][1] == shard[key][1], key
    # the sharded launcher's compiles are counted under its own key
    from repro.obs.jax_hooks import jit_cache_entries
    assert jit_cache_entries()[f"greedy.grid_sharded.{ndev}"] >= 1


def test_planner_devices_knob_bitwise(grid_case, platform):
    insts, rows = grid_case
    res1 = Planner(platform, engine="jax").plan(
        instances=insts, profiles=rows, variants=VARIANTS)
    res8 = Planner(platform, engine="jax", devices=8).plan(
        instances=insts, profiles=rows, variants=VARIANTS)
    assert np.array_equal(res1.costs, res8.costs)
    a, b = _flatten(res1.results), _flatten(res8.results)
    for key in a:
        assert np.array_equal(a[key][0], b[key][0]), key


def test_request_devices_overrides_planner(grid_case, platform):
    insts, rows = grid_case
    planner = Planner(platform, engine="jax", devices=2)
    assert planner.clone().devices == 2      # clone carries the knob
    res = planner.plan(PlanRequest(instances=insts, profiles=rows,
                                   variants=VARIANTS, devices=8))
    base = Planner(platform, engine="jax").plan(
        instances=insts, profiles=rows, variants=VARIANTS)
    assert np.array_equal(res.costs, base.costs)


def test_single_instance_pads_to_device_multiple(grid_case, platform):
    """I=1 at ndev=8: rows pad 1 -> 8 by repeating, result sliced back."""
    insts, rows = grid_case
    base = _flatten(schedule_portfolio_grid(
        insts[:1], rows[:1], platform, variants=VARIANTS, engine="jax"))
    shard = _flatten(schedule_portfolio_grid(
        insts[:1], rows[:1], platform, variants=VARIANTS, engine="jax",
        devices=8))
    assert base.keys() == shard.keys()
    for key in base:
        assert np.array_equal(base[key][0], shard[key][0]), key


def test_devices_request_validation(grid_case, platform):
    insts, rows = grid_case
    with pytest.raises(ValueError, match="devices"):
        PlanRequest(instances=insts, profiles=rows, variants=VARIANTS,
                    devices=0).resolve()
    with pytest.raises(ValueError, match="devices"):
        PlanRequest(instances=insts, profiles=rows, variants=VARIANTS,
                    devices=2.5).resolve()
