"""Milliseconds per request of the greedy fan-out's ``bucket_rows``
spans (``core/portfolio.py``: the host build of a bucket launch's padded
rows, inside ``bucket_launch`` and before the upload)."""
from harness import stats


def read(run):
    return stats.per_request_ms(run.spans, {"bucket_rows"},
                                sum(r.ok for r in run.records))
