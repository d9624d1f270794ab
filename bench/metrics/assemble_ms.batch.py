"""Milliseconds per request of the planner's ``assemble`` spans
(``core/portfolio.py``: every cell's results, costs and validation,
after the launches)."""
from harness import stats


def read(run):
    return stats.per_request_ms(run.spans, {"assemble"},
                                sum(r.ok for r in run.records))
