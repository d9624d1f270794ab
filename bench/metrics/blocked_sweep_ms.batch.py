"""Milliseconds per request of the blocked longest-path form's
``blocked_chunk_sweep`` spans (``core/greedy_jax.py``: for each chunk of
the placement order, the host sweep of its lp rows and columns and the
asynchronous chunk launch; the wait for the last chunk is outside)."""
from harness import stats


def read(run):
    return stats.per_request_ms(run.spans, {"blocked_chunk_sweep"},
                                sum(r.ok for r in run.records))
