"""90th percentile (nearest rank) of plan latency over every request due
in the window, from its due time to its answer; a failed request counts
as late by the whole grace period at least. The 90th, not the 95th: a
window of 160 arrivals leaves 16 samples beyond it, and at least ten
are needed."""
from harness import stats


def read(run):
    return stats.nearest_rank(stats.latencies(run.records), 0.90)
