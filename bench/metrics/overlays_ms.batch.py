"""Milliseconds per request of the planner's ``overlays`` spans
(``core/portfolio.py``: each forecast member laid over the cached graph,
budgets, masks and segments, before any launch)."""
from harness import stats


def read(run):
    return stats.per_request_ms(run.spans, {"overlays"},
                                sum(r.ok for r in run.records))
