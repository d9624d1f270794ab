"""The arithmetic of the metrics, on plain numbers."""
from __future__ import annotations

import math


def nearest_rank(values, q: float) -> float | None:
    """The q-quantile by nearest rank: the smallest value with at least a
    share q of the values at or below it."""
    xs = sorted(values)
    if not xs:
        return None
    return xs[max(math.ceil(q * len(xs)) - 1, 0)]


def cells_per_s(records) -> float | None:
    """Plan cells completed per second of the window: whole requests only,
    the window from the first send to the last answer."""
    done = [r for r in records if r.ok]
    if not done:
        return None
    t0 = min(r.sent for r in records)
    t1 = max(r.done for r in records if r.done is not None)
    return sum(r.cells for r in done) / (t1 - t0)


FAILED_S = 60.0   # the grace period for an answer: a failed one's floor


def latencies(records) -> list[float]:
    """Seconds from each request's due time to its answer; a request that
    failed or never came counts as late by ``FAILED_S`` at least, so it
    misses any latency limit yet keeps the percentile finite."""
    return [r.done - r.due if r.ok
            else max((r.done or r.due) - r.due, FAILED_S) for r in records]


def per_request_ms(spans, names, n_requests: int) -> float | None:
    """Total duration of the named spans, in ms per request served."""
    if not n_requests:
        return None
    picked = [s for s in spans if s.name in names]
    if not picked:
        return None
    return 1e3 * sum(s.t1 - s.t0 for s in picked) / n_requests


def self_ms(spans, parent: str, children, n_requests: int) -> float | None:
    """The ``parent`` spans' duration less that of their direct children
    named in ``children``, in ms per request served."""
    tops = {s.span_id: s for s in spans if s.name == parent}
    if not tops or not n_requests:
        return None
    total = sum(s.t1 - s.t0 for s in tops.values())
    total -= sum(s.t1 - s.t0 for s in spans
                 if s.name in children and s.parent_id in tops)
    return 1e3 * total / n_requests
