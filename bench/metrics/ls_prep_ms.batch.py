"""Milliseconds per request of the climb's ``ls_prep`` spans
(``core/local_search_jax.py``: per-row remaining budgets, bucket padding
and adjacency, up to the ``ls_device_climb`` launch)."""
from harness import stats


def read(run):
    return stats.per_request_ms(run.spans, {"ls_prep"},
                                sum(r.ok for r in run.records))
