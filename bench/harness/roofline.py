"""Peaks of a device, and the least bytes and operations of a kernel."""
from __future__ import annotations

import json

from harness.spec import BENCH_DIR


def peak(device_kind: str) -> dict:
    """``{"flops_per_s", "bytes_per_s", "source"}`` of one chip of this
    kind, from ``bench/peaks.json``; an unknown kind is an error."""
    table = json.loads((BENCH_DIR / "peaks.json").read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json")
    return table[device_kind]


def gain_kernel(rows: int, tasks: int, mu: int) -> tuple[int, int]:
    """(bytes, operations) the gain sweep must at least move and do for
    ``rows`` schedules of ``tasks`` tasks: per task, the budget left on
    the 2*mu units around its start and around its end and its four f32
    scalars (work, duration, two shift bounds) read once, its 2*mu+1
    gains written once; per window unit one released and one incurred
    term (3 and 4 operations), per window four prefix sums (one add per
    unit), per shift four differences, two bound tests and a select.

    Counted on the real rows and tasks, not the padded shapes the call
    is launched at, so padding can only lower the share."""
    units = 2 * mu
    nbytes = 4 * (2 * units + 4 + (2 * mu + 1))
    ops = 2 * units * 7 + 4 * units + (2 * mu + 1) * 7
    return rows * tasks * nbytes, rows * tasks * ops
