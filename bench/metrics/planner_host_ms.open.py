"""Host time of the planner per request served: the ``plan`` spans less
their ``bucket_launch`` and ``ls_climb`` children, so graph cache,
overlays, assembly and validation (``api/planner.py``,
``core/portfolio.py``)."""
from harness import stats


def read(run):
    served = sum(r.ok for r in run.records)
    return stats.self_ms(run.spans, "plan", ("bucket_launch", "ls_climb"),
                         served)
