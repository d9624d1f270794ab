"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps + real instances."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cluster import make_cluster
from repro.core import (
    asap_schedule,
    build_instance,
    deadline_from_asap,
    generate_profile,
    heft_mapping,
    schedule_cost,
)
from repro.core.carbon import work_timeline
from repro.kernels.carbon_cost import deficit_timeline
from repro.kernels.gain_scan import gain_scan
from repro.kernels.ops import carbon_cost
from repro.kernels.ref import deficit_timeline_ref, gain_scan_ref
from repro.workflows import make_workflow


def _rand(n, t, seed):
    rng = np.random.default_rng(seed)
    starts = rng.integers(0, max(t - 20, 1), n).astype(np.float32)
    durs = rng.integers(1, 20, n).astype(np.float32)
    works = rng.integers(0, 120, n).astype(np.float32)
    g = rng.integers(0, 2500, t).astype(np.float32)
    return starts, durs, works, g


@pytest.mark.parametrize("n", [1, 7, 63, 300, 1000])
@pytest.mark.parametrize("t", [16, 700, 2048])
def test_deficit_timeline_sweep(n, t):
    starts, durs, works, g = _rand(n, t, seed=n * 1000 + t)
    got = np.asarray(deficit_timeline(jnp.asarray(starts),
                                      jnp.asarray(starts + durs),
                                      jnp.asarray(works), jnp.asarray(g)))
    want = np.asarray(deficit_timeline_ref(jnp.asarray(starts),
                                           jnp.asarray(starts + durs),
                                           jnp.asarray(works),
                                           jnp.asarray(g)))
    np.testing.assert_allclose(got, want, rtol=0, atol=0)


@pytest.mark.parametrize("n,t,mu", [(1, 64, 1), (17, 300, 5), (120, 900, 10),
                                    (256, 512, 20), (300, 2048, 42)])
def test_gain_scan_sweep(n, t, mu):
    rng = np.random.default_rng(n + t + mu)
    starts, durs, works, g = _rand(n, t, seed=n + t)
    starts = np.minimum(starts, t - durs - 1)
    power = np.asarray(deficit_timeline_ref(
        jnp.asarray(starts), jnp.asarray(starts + durs), jnp.asarray(works),
        jnp.asarray(np.zeros(t, np.float32))))
    rem = (g - power).astype(np.float32)
    lo = np.maximum(starts - rng.integers(0, 30, n), 0).astype(np.float32)
    hi = np.minimum(starts + rng.integers(0, 30, n),
                    t - durs).astype(np.float32)
    got = np.asarray(gain_scan(jnp.asarray(rem), jnp.asarray(starts),
                               jnp.asarray(durs), jnp.asarray(works),
                               jnp.asarray(lo), jnp.asarray(hi), mu=mu))
    want = np.asarray(gain_scan_ref(jnp.asarray(rem), jnp.asarray(starts),
                                    jnp.asarray(durs), jnp.asarray(works),
                                    jnp.asarray(lo), jnp.asarray(hi), mu=mu))
    legal = want > -1e29
    assert (legal == (got > -1e29)).all()
    np.testing.assert_allclose(got[legal], want[legal], atol=1e-3)


def _window_case(n, t, mu, rows=None):
    return pytest.param(n, t, mu, rows, id=f"{n}-{t}-{mu}" + (
        f"-vmap{rows}" if rows else ""))


@pytest.mark.parametrize("n,t,mu,rows", [
    _window_case(5, 16, 1), _window_case(300, 512, 10),
    _window_case(64, 777, 42),
    _window_case(1792, 512, 10),           # the 1000-task cell's buckets
    _window_case(40, 100, 10),             # T shorter than W, not 2**k
    _window_case(1792, 512, 10, rows=8),   # the climb's vmap over rows
])
def test_gather_windows_matches_elementwise_definition(n, t, mu, rows):
    from repro.kernels.gain_scan import W, gather_windows

    rng = np.random.default_rng(n + t + mu)
    b = rows or 1
    rem = rng.integers(-9, 9, (b, t)).astype(np.float32)
    dur = rng.integers(1, 9, n)
    start = rng.integers(0, t - dur + 1, (b, n))
    start[:, :2], dur[:2] = (0, t - 3), (1, 3)    # both horizon edges
    args = (jnp.asarray(rem), jnp.asarray(start, jnp.int32))
    dur_j = jnp.asarray(dur, jnp.int32)
    if rows:
        win_s, win_e = jax.vmap(
            lambda r, s: gather_windows(r, s, dur_j, mu=mu))(*args)
    else:
        win_s, win_e = gather_windows(args[0][0], args[1][0], dur_j, mu=mu)
        win_s, win_e = win_s[None], win_e[None]
    j = np.arange(W)[None, :] - mu + W
    for r in range(b):
        rem_pad = np.pad(rem[r], (W, W))
        np.testing.assert_array_equal(np.asarray(win_s[r]),
                                      rem_pad[start[r][:, None] + j])
        np.testing.assert_array_equal(np.asarray(win_e[r]),
                                      rem_pad[(start[r] + dur)[:, None] + j])


def test_kernel_cost_matches_core_oracle():
    plat = make_cluster(1, seed=2)
    wf = make_workflow("eager", 5, seed=4)
    inst = build_instance(wf, heft_mapping(wf, plat), plat)
    T = deadline_from_asap(inst, 1.4)
    prof = generate_profile("S3", T, plat, J=12, seed=3)
    start = asap_schedule(inst)
    want = schedule_cost(inst, prof, start)
    got = float(carbon_cost(start, inst.dur, inst.task_work,
                            prof.unit_budget(inst.idle_total)))
    assert abs(got - want) < 1e-3 * max(want, 1)


def test_gain_kernel_on_real_instance():
    plat = make_cluster(1, seed=5)
    wf = make_workflow("methylseq", 4, seed=6)
    inst = build_instance(wf, heft_mapping(wf, plat), plat)
    T = deadline_from_asap(inst, 2.0)
    prof = generate_profile("S1", T, plat, J=12, seed=3)
    start = asap_schedule(inst)
    rem = prof.unit_budget(inst.idle_total) - work_timeline(inst, T, start)
    N = inst.num_tasks
    lo = np.zeros(N)
    hi = np.full(N, T) - inst.dur
    gains = np.asarray(gain_scan(
        *(jnp.asarray(a, jnp.float32)
          for a in (rem, start, inst.dur, inst.task_work, lo, hi)), mu=6))
    base = schedule_cost(inst, prof, start)
    # applying any positive-gain single move must reduce the exact cost by
    # exactly that gain
    idx = np.argwhere(gains > 0)
    for (v, d) in idx[:20]:
        s2 = start.copy()
        s2[v] += d - 6
        c2 = schedule_cost(inst, prof, s2)
        assert abs((base - c2) - gains[v, d]) < 1e-3


@pytest.mark.device
class TestGainKernelBitIdentity:
    """The tiled Pallas gain kernel vs the jnp prefix-sum twin.

    All gain summands are integers below 2^24, so f32 accumulation is
    exact in any order — the two executors must agree BITWISE, not just
    within tolerance. On CPU the kernel path runs under the Pallas
    interpreter (``interpret=True``), which executes the same kernel
    body the TPU/GPU compiled path lowers.
    """

    @staticmethod
    def _case(n, t, mu, seed):
        rng = np.random.default_rng(seed)
        rem = rng.integers(-9, 9, t).astype(np.float32)
        dur = rng.integers(1, 9, n).astype(np.float32)
        start = rng.integers(0, max(t - 10, 1), n).astype(np.float32)
        work = rng.integers(0, 7, n).astype(np.float32)
        lo = np.maximum(start - rng.integers(0, 2 * mu + 5, n), 0)
        hi = start + rng.integers(0, 2 * mu + 5, n)
        return tuple(jnp.asarray(a) for a in (rem, start, dur, work,
                                              lo.astype(np.float32),
                                              hi.astype(np.float32)))

    @pytest.mark.parametrize("mu", [1, 5, 10, 21, 42])
    @pytest.mark.parametrize("n,t", [(1, 64), (63, 300), (257, 777)])
    def test_bit_identity_across_mu(self, n, t, mu):
        args = self._case(n, t, mu, seed=n * t + mu)
        twin = np.asarray(gain_scan(*args, mu=mu, interpret=None))
        kern = np.asarray(gain_scan(*args, mu=mu, interpret=True))
        assert (twin == kern).all()

    def test_bit_identity_masked_edges(self):
        """Window clipping at both horizon edges, rows with no legal
        move (lo > hi), and zero-work rows — all exactly NEG-masked the
        same way on both paths."""
        mu = 10
        t = 96
        rem = jnp.asarray(np.tile([-3.0, 2.0, -1.0, 4.0], t // 4),
                          jnp.float32)
        start = jnp.asarray([0.0, 1.0, 90.0, 40.0, 40.0, 88.0], jnp.float32)
        dur = jnp.asarray([4.0, 2.0, 6.0, 5.0, 5.0, 8.0], jnp.float32)
        work = jnp.asarray([3.0, 2.0, 1.0, 2.0, 0.0, 5.0], jnp.float32)
        lo = jnp.asarray([0.0, 0.0, 80.0, 41.0, 30.0, 0.0], jnp.float32)
        hi = jnp.asarray([12.0, 9.0, 90.0, 39.0, 50.0, 88.0], jnp.float32)
        twin = np.asarray(gain_scan(rem, start, dur, work, lo, hi, mu=mu,
                                    interpret=None))
        kern = np.asarray(gain_scan(rem, start, dur, work, lo, hi, mu=mu,
                                    interpret=True))
        assert (twin == kern).all()
        assert (twin[3] == -1e30).all()      # no legal move: lo > hi
        assert (twin[4] == -1e30).all()      # zero-work row all-illegal
        assert (twin[:, mu] == -1e30).all()  # delta=0 always illegal

    @pytest.mark.parametrize("mu", [3, 17])
    def test_batched_bit_identity(self, mu, climb_gains):
        rng = np.random.default_rng(mu)
        B, n, t = 3, 40, 256
        rem = rng.integers(-9, 9, (B, t)).astype(np.float32)
        dur = rng.integers(1, 9, n).astype(np.float32)
        work = rng.integers(0, 7, n).astype(np.float32)
        start = rng.integers(0, t - 10, (B, n)).astype(np.float32)
        lo = np.maximum(start - 20, 0).astype(np.float32)
        hi = (start + 20).astype(np.float32)
        args = (rem, start, dur, work, lo, hi)
        twin = climb_gains(*args, mu=mu, interpret=None)
        kern = climb_gains(*args, mu=mu, interpret=True)
        assert twin.shape == (B, n, 2 * mu + 1)
        assert (twin == kern).all()

    def test_windows_auto_dispatch(self):
        """gains_windows_auto is the climb's oracle: explicit interpret
        settings pick the kernel/twin, both bitwise-equal."""
        from repro.kernels.gain_scan import (gains_from_windows,
                                             gains_windows_auto,
                                             gather_windows)

        mu = 8
        rng = np.random.default_rng(0)
        rem = jnp.asarray(rng.integers(-5, 5, 128).astype(np.float32))
        start = jnp.asarray(rng.integers(0, 100, 30).astype(np.float32))
        dur = jnp.asarray(rng.integers(1, 8, 30).astype(np.float32))
        work = jnp.asarray(rng.integers(0, 6, 30).astype(np.float32))
        win_s, win_e = gather_windows(rem, start, dur, mu=mu)
        lo_rel = jnp.full(30, -5.0, jnp.float32)
        hi_rel = jnp.full(30, 5.0, jnp.float32)
        twin = np.asarray(gains_from_windows(win_s, win_e, work, dur,
                                             lo_rel, hi_rel, mu=mu))
        auto = np.asarray(gains_windows_auto(win_s, win_e, work, dur,
                                             lo_rel, hi_rel, mu=mu))
        kern = np.asarray(gains_windows_auto(win_s, win_e, work, dur,
                                             lo_rel, hi_rel, mu=mu,
                                             interpret=True))
        assert (twin == auto).all()          # CPU auto = the jnp twin
        assert (twin == kern).all()          # interpreter = same bits


@pytest.mark.parametrize("B,S,H,hd,causal,dtype", [
    (2, 128, 2, 64, True, jnp.float32),
    (1, 256, 4, 128, True, jnp.float32),
    (2, 200, 2, 64, False, jnp.float32),     # non-multiple S (padding path)
    (1, 384, 1, 128, True, jnp.bfloat16),
    (1, 130, 3, 64, True, jnp.float32),
])
def test_flash_attention_sweep(B, S, H, hd, causal, dtype):
    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.ref import flash_attention_ref

    ks = jax.random.split(jax.random.PRNGKey(B * S + H), 3)
    q = jax.random.normal(ks[0], (B, S, H, hd), dtype)
    k = jax.random.normal(ks[1], (B, S, H, hd), dtype)
    v = jax.random.normal(ks[2], (B, S, H, hd), dtype)
    got = np.asarray(flash_attention(q, k, v, causal=causal), np.float32)
    want = np.asarray(flash_attention_ref(q, k, v, causal=causal),
                      np.float32)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)
