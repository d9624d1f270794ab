"""Milliseconds per request of the ``validate`` spans
(``core/portfolio.py``: each cell's schedules checked for their start,
deadline and precedence; part of ``assemble``)."""
from harness import stats


def read(run):
    return stats.per_request_ms(run.spans, {"validate"},
                                sum(r.ok for r in run.records))
