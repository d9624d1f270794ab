"""Public entry point of the carbon-cost Pallas kernel.

``interpret=None`` auto-detects the backend (interpret on CPU, compile on
TPU — see :mod:`repro.kernels.backend`); pass an explicit bool to override.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.carbon_cost import deficit_timeline


def carbon_cost(starts, durs, works, g_eff, *, interpret: bool | None = None):
    """Total carbon cost of a schedule (scalar f32)."""
    starts = jnp.asarray(starts, jnp.float32)
    ends = starts + jnp.asarray(durs, jnp.float32)
    return deficit_timeline(
        starts, ends, jnp.asarray(works, jnp.float32),
        jnp.asarray(g_eff, jnp.float32), interpret=interpret).sum()

