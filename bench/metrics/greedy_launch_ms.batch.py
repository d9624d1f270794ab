"""Milliseconds per request of the greedy fan-out's ``bucket_launch``
spans (``core/greedy_jax.py`` grid launches, from host padding to the
starts back on the host)."""
from harness import stats


def read(run):
    return stats.per_request_ms(run.spans, {"bucket_launch"},
                                sum(r.ok for r in run.records))
