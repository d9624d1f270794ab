"""Ahead-of-time compiles of the device path for a TPU v5e chip.

The TPU compiler is installed with jax, so these compile the planner's
kernels and launches for a described (not attached) v5e chip at the sizes
the paper-scale requests reach: a 1000-task workflow becomes ~1,735
instance tasks (the 1792-task bucket, horizon bucket 512), a 4000-task one
~8,600 tasks (the 8704-task bucket, horizon bucket 1024). A compile that
passes here is not a chip run; it catches what Mosaic or XLA would refuse
(lowering, tiling, VMEM) before a chip call does.

The topology is described only inside the ``topo`` fixture: loading the
TPU library while a module is imported would break multi-worker runs.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import local_search_jax
from repro.kernels import gain_scan
from repro.kernels.carbon_cost import deficit_timeline

N_BUCKET_1K, T_BUCKET_1K = 1792, 512      # wfgen_scale(.., 1000), factor 2
N_BUCKET_4K, T_BUCKET_4K = 8704, 1024     # wfgen_scale(.., 4000), factor 2
PROFILES, COMBOS, LS_VARIANTS = 8, 8, 8


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("mu", [10, 21])
def test_gain_kernel_compiles(one_chip, mu):
    n = 4096
    win = _spec(one_chip, (n, gain_scan.W), jnp.float32)
    vec = _spec(one_chip, (n,), jnp.float32)
    call = jax.jit(lambda *a: gain_scan._kernel_call(*a, mu=mu,
                                                     mode="pallas"))
    hlo = call.lower(win, win, vec, vec, vec, vec).compile().as_text()
    assert "tpu_custom_call" in hlo


def test_deficit_timeline_compiles(one_chip):
    tasks = _spec(one_chip, (4096,), jnp.float32)
    g = _spec(one_chip, (8192,), jnp.float32)
    hlo = deficit_timeline.lower(tasks, tasks, tasks, g,
                                 interpret=False).compile().as_text()
    assert "tpu_custom_call" in hlo


def test_greedy_grid_compiles(one_chip):
    from repro.core.greedy_jax import _impl

    inst, n, t = 4, N_BUCKET_1K, T_BUCKET_1K
    i32 = jnp.int32
    args = (_spec(one_chip, (inst, n), i32),                 # dur
            _spec(one_chip, (inst, n), i32),                 # work
            _spec(one_chip, (inst, n, n), i32),              # lp
            _spec(one_chip, (inst, PROFILES, t), i32),       # rem0
            _spec(one_chip, (inst, PROFILES, COMBOS, t + 1), jnp.bool_),
            _spec(one_chip, (inst, n), i32),                 # est0
            _spec(one_chip, (inst, n), i32),                 # lst0
            _spec(one_chip, (inst, COMBOS, n), i32))         # orders
    compiled = _impl().lower(*args).compile()
    assert compiled.memory_analysis() is not None


def _chunk_args(one_chip, n, t, steps, levels, edges):
    """The blocked chunk program's arguments: the donated [P, V, .] state,
    the chunk's task ids and the two level tables."""
    i32 = jnp.int32
    state = (_spec(one_chip, (PROFILES, COMBOS, t), i32),            # rem
             _spec(one_chip, (PROFILES, COMBOS, t + 1), jnp.bool_),  # mask
             *(_spec(one_chip, (PROFILES, COMBOS, n), i32),) * 3)
    table = (_spec(one_chip, (levels, edges), i32),) * 3
    return (_spec(one_chip, (n,), i32), _spec(one_chip, (n,), i32), *state,
            _spec(one_chip, (COMBOS, steps), i32), table, table)


def test_blocked_chunk_program_compiles(one_chip, monkeypatch):
    """The blocked form's chunk program, the device sweeps of a chunk's lp
    rows and columns and then its scan, at a small bucket: 1024 tasks, 32
    levels of up to 128 edges, chunks of 64 steps x 8 orders."""
    from repro.core.greedy_jax import _blocked_impl
    from repro.kernels import maxplus_sweep

    # the sweep picks its executor by the backend it sees, which is the
    # CPU here: steer it to the compiled kernel the chip would take
    monkeypatch.setattr(maxplus_sweep, "resolve_mode", lambda _: "pallas")

    n, t, steps, levels, edges = 1024, 256, 64, 32, 128
    compiled = _blocked_impl().lower(
        *_chunk_args(one_chip, n, t, steps, levels, edges)).compile()
    hlo = compiled.as_text()
    # one Mosaic kernel a sweep: the rows', then the columns'
    assert hlo.count('custom_call_target="tpu_custom_call"') == 2
    # the working set is the chunk's rows and columns, not more: 2 x 8
    # orders x 64 steps x 1024 tasks x 4 bytes, plus the sweep state
    lp = 2 * COMBOS * steps * n * 4
    assert compiled.memory_analysis().temp_size_in_bytes <= 2 * lp


@pytest.mark.parametrize("program", ["dense-1k", "chunk-4k"])
def test_greedy_scan_carry_is_lane_dense(one_chip, monkeypatch, program):
    """The greedy scan's est/lst carry is one [rows, tasks] array each,
    tasks minor (on the lanes), in the while loop's state: the dense grid
    at the 1k bucket (one instance) and the blocked chunk program at the
    4k bucket (chunks of 128 steps, tables 64 levels x 448 edges)."""
    from repro.core.greedy_jax import _blocked_impl, _impl
    from repro.kernels import maxplus_sweep

    monkeypatch.setattr(maxplus_sweep, "resolve_mode", lambda _: "pallas")
    i32, rows = jnp.int32, PROFILES * COMBOS
    if program == "dense-1k":
        n, t = N_BUCKET_1K, T_BUCKET_1K
        args = (_spec(one_chip, (1, n), i32), _spec(one_chip, (1, n), i32),
                _spec(one_chip, (1, n, n), i32),
                _spec(one_chip, (1, PROFILES, t), i32),
                _spec(one_chip, (1, PROFILES, COMBOS, t + 1), jnp.bool_),
                _spec(one_chip, (1, n), i32), _spec(one_chip, (1, n), i32),
                _spec(one_chip, (1, COMBOS, n), i32))
        compiled = _impl().lower(*args).compile()
    else:
        n = N_BUCKET_4K
        compiled = _blocked_impl().lower(
            *_chunk_args(one_chip, n, T_BUCKET_4K, 128, 64, 448)).compile()
    states = re.findall(r"= \((.*)\) while\(", compiled.as_text())
    assert states, "no while loop"
    layouts = [lay for state in states for lay in
               re.findall(rf"s32\[{rows},{n}\]\{{([0-9,]+)", state)]
    # est and lst, each with the task axis minor
    assert len(layouts) >= 2, states
    assert all(lay == "1,0" for lay in layouts), layouts


@pytest.mark.parametrize("padded,n,t", [(False, N_BUCKET_1K, T_BUCKET_1K),
                                        (True, N_BUCKET_4K, T_BUCKET_4K)])
def test_climb_compiles_with_gain_kernel(one_chip, monkeypatch, padded, n,
                                         t):
    # the climb picks its gain executor by the backend it sees, which is
    # the CPU here: steer it to the compiled kernel the chip would take
    monkeypatch.setattr(gain_scan, "resolve_mode", lambda _: "pallas")
    rows, i32 = PROFILES * LS_VARIANTS, jnp.int32
    climb = local_search_jax._climb_impl.__wrapped__(
        10, 200, local_search_jax._COMMIT_K, padded)
    if padded:
        deg = 16
        adj = ((_spec(one_chip, (n, deg), i32),
                _spec(one_chip, (n, deg), jnp.bool_)),) * 2
    else:
        adj = (_spec(one_chip, (n, n), jnp.bool_),) * 2
    lowered = climb.lower(_spec(one_chip, (rows, t), i32),
                          _spec(one_chip, (rows, n), i32),
                          _spec(one_chip, (), i32),
                          _spec(one_chip, (n,), i32),
                          _spec(one_chip, (n,), i32), *adj)
    hlo = lowered.compile().as_text()
    assert "tpu_custom_call" in hlo
    # the gain kernel is the climb's only Mosaic kernel: the benchmark
    # times every custom call inside the climb as that kernel
    assert hlo.count('custom_call_target="tpu_custom_call"') == 1
    # the sliding-window matrix is built lane-dense, not by a concatenate
    # of W single-lane columns
    widest = max((len(re.findall(r"%[\w.\-]+", args)) for args in
                  re.findall(r" concatenate\(([^)]*)\)", hlo)), default=0)
    assert widest <= 8, f"a concatenate of {widest} operands"
