"""Backend auto-detection for the Pallas kernels.

``interpret=None`` (the default everywhere) resolves to "interpret exactly
when the JAX default backend is CPU": the container runs the kernels through
the Pallas interpreter, while on a TPU runtime the same call sites compile
to Mosaic with no caller changes.
"""
from __future__ import annotations

import os
from pathlib import Path


def resolve_interpret(interpret: bool | None) -> bool:
    """Resolve the tri-state ``interpret`` flag against the active backend.

    A thin projection of :func:`resolve_mode` for kernels that only have a
    compiled and an interpreted path (no jnp twin): every ``interpret=None``
    decision in the tree routes through the same mode resolution, so no two
    call sites can disagree on the active backend.
    """
    return resolve_mode(interpret) != "pallas"


def resolve_mode(interpret: bool | None) -> str:
    """Kernel execution mode for the tri-state ``interpret`` flag.

    ``None`` (auto) picks the fastest exact path for the backend: the
    Mosaic-compiled Pallas kernel on TPU, the pure-jnp XLA formulation on
    CPU (bit-identical outputs, orders of magnitude faster than the Pallas
    interpreter). Explicit ``True`` forces the Pallas interpreter (the
    kernel-logic test path); explicit ``False`` forces the compiled kernel.
    """
    if interpret is None:
        import jax
        return "jnp" if jax.default_backend() == "cpu" else "pallas"
    return "interpret" if interpret else "pallas"


def resolve_solver(solver: str | None):
    """Resolve a ``PlanRequest.solver`` spelling to a registered
    :class:`repro.core.solvers.Solver`.

    The solver-axis generalization of :func:`resolve_engine`: the solver
    picks WHICH backend serves the grid (heuristic portfolio, exact
    ILP/DP dispatch, asap baseline), while ``engine=`` remains the
    heuristic solver's sub-knob (numpy vs jax fan-out). ``None``/"auto"
    resolve to the heuristic solver — the historical behaviour of every
    request that predates the axis.
    """
    from repro.core.solvers import get_solver

    return get_solver("heuristic" if solver in (None, "auto") else solver)


def resolve_lp_form(num_tasks: int, budget_bytes: int | None = None) -> str:
    """Longest-path representation for the jax engine: ``"dense"`` or
    ``"blocked"``.

    THE dense-vs-blocked decision rule, shared by
    :meth:`repro.core.portfolio.PreparedGraph.lp` and
    :func:`repro.core.greedy_jax.lp_for`: the O(N^2) int32 matrix when it
    fits ``budget_bytes`` (default
    :data:`repro.core.greedy_jax.LP_MAX_BYTES`) — the fast path, resident
    on device — and the O(N * B) streamed
    :class:`repro.core.greedy_jax.BlockedLP` form past it. Centralized
    here next to :func:`resolve_engine`/:func:`resolve_mode` so no two
    call sites can disagree on where the envelope sits.
    """
    from repro.core.greedy_jax import LP_MAX_BYTES, lp_matrix_bytes

    limit = LP_MAX_BYTES if budget_bytes is None else int(budget_bytes)
    return "dense" if lp_matrix_bytes(num_tasks) <= limit else "blocked"


def resolve_engine(engine: str | None, fanout: int = 1) -> str:
    """Resolve a scheduling-engine request to ``"numpy"`` or ``"jax"``.

    The single source of the ``engine="auto"`` rule shared by
    :class:`repro.api.Planner` and :class:`repro.runtime.carbon_gate
    .CarbonGate`: ``auto`` picks the device fan-out as soon as the request
    actually fans out (``fanout`` = number of (instance, profile) cells
    > 1 — replanning loops amortize the jit cache and the vmapped launch
    pays off immediately), and the numpy engine for one-off single-cell
    calls (where compile latency would dominate). The heuristic-solver
    sub-knob of the wider :func:`resolve_solver` axis.
    """
    if engine in (None, "auto"):
        return "jax" if fanout > 1 else "numpy"
    if engine not in ("numpy", "jax"):
        raise ValueError(f"unknown engine {engine!r}")
    return engine


# The persistent compile cache's directory when JAX_COMPILATION_CACHE_DIR
# is unset: one fixed path inside the checkout (listed in .gitignore). The
# path is part of the cache key, so it must not move between processes.
CHECKOUT_CACHE_DIR = str(Path(__file__).resolve().parents[3] / ".jax_cache")


def compilation_cache_dir() -> str:
    """``$JAX_COMPILATION_CACHE_DIR`` when set, else
    :data:`CHECKOUT_CACHE_DIR`."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or CHECKOUT_CACHE_DIR


def enable_compilation_cache() -> str:
    """Persist compiled executables across processes.

    The in-process jit cache already reuses executables across calls (the
    fan-out pads its inputs to shape buckets precisely so distinct
    instances hit it); this extends the reuse across process restarts —
    benchmark re-runs and replanning daemons skip the cold compile.
    Returns the cache dir (:func:`compilation_cache_dir`); raises
    ``OSError`` when it cannot be created.
    """
    import jax

    path = compilation_cache_dir()
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path
