import os
import sys

# NOTE: do NOT set xla_force_host_platform_device_count here — smoke tests
# and benches must see 1 device (dryrun.py sets its own flag in-process).
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np
import pytest

from repro.cluster import make_cluster
from repro.core import build_instance, heft_mapping
from repro.workflows import make_workflow


def pytest_addoption(parser):
    parser.addoption(
        "--chaos-workers", action="store", type=int, default=1,
        help="PlanService drain-worker count the chaos suite runs under "
             "(make test-chaos sweeps 1 and 4)")
    parser.addoption(
        "--chaos-seed", action="store", type=int, default=0,
        help="seed offset for the chaos suite's scenario generators "
             "(the flake guard repeats the suite across several seeds)")


@pytest.fixture
def chaos_workers(request):
    return request.config.getoption("--chaos-workers")


@pytest.fixture
def chaos_seed(request):
    return request.config.getoption("--chaos-seed")


@pytest.fixture(scope="session")
def small_platform():
    return make_cluster(1, seed=0)      # 6 compute processors


@pytest.fixture(scope="session")
def medium_instance(small_platform):
    wf = make_workflow("eager", 6, seed=3)
    mp = heft_mapping(wf, small_platform)
    return build_instance(wf, mp, small_platform)


@pytest.fixture(scope="session")
def climb_gains():
    """The device climb's gain call over a batch of rows: every row's
    timeline windows gathered (``vmap`` of ``gather_windows``), then all
    rows' tasks flattened into ONE ``gains_windows_auto`` call. Takes
    ``gain_scan``'s arguments with a leading row axis on ``rem``,
    ``start``, ``lo`` and ``hi`` (absolute bounds); returns f32 numpy
    [B, N, 2*mu+1]."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.gain_scan import gains_windows_auto, gather_windows

    def gains(rem, start, dur, work, lo, hi, *, mu, interpret=None):
        rem, start, dur, work, lo, hi = (
            jnp.asarray(a, jnp.float32)
            for a in (rem, start, dur, work, lo, hi))
        B, n = start.shape
        win_s, win_e = jax.vmap(
            lambda r, s: gather_windows(r, s, dur, mu=mu))(rem, start)
        flat = gains_windows_auto(
            win_s.reshape(B * n, -1), win_e.reshape(B * n, -1),
            jnp.tile(work, B), jnp.tile(dur, B),
            (lo - start).reshape(B * n), (hi - start).reshape(B * n),
            mu=mu, interpret=interpret)
        return np.asarray(flat).reshape(B, n, 2 * mu + 1)

    return gains


def random_instance(n_tasks=24, seed=0, platform=None, kind="atacseq"):
    platform = platform or make_cluster(1, seed=seed)
    wf = make_workflow(kind, max(n_tasks // 12, 1), seed=seed)
    mp = heft_mapping(wf, platform)
    return build_instance(wf, mp, platform), platform
