"""Milliseconds per request of the host polish's ``ls_polish`` spans
(``core/local_search_jax.py``, sequential reference rounds)."""
from harness import stats


def read(run):
    return stats.per_request_ms(run.spans, {"ls_polish"},
                                sum(r.ok for r in run.records))
