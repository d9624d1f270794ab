"""Milliseconds per request of the ``blocked_lp_rows`` spans
(``core/greedy_jax.py``: the host max-plus sweeps that build one chunk's
lp rows and columns, inside ``blocked_chunk_sweep``). A program without
the span reads nothing."""
from harness import stats


def read(run):
    return stats.per_request_ms(run.spans, {"blocked_lp_rows"},
                                sum(r.ok for r in run.records))
