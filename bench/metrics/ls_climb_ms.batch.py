"""Milliseconds per request of the device climb's ``ls_device_climb``
spans (``core/local_search_jax.py``, launch to results on the host)."""
from harness import stats


def read(run):
    return stats.per_request_ms(run.spans, {"ls_device_climb"},
                                sum(r.ok for r in run.records))
