"""90th percentile (nearest rank) of the service's ``queue_wait`` spans:
admission to a worker claiming the ticket (``serve/service.py``)."""
from harness import stats


def read(run):
    return stats.nearest_rank([s.t1 - s.t0 for s in run.spans
                               if s.name == "queue_wait"], 0.90)
