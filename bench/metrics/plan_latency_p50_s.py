"""Median (nearest rank) of plan latency, taken as for the 90th
percentile."""
from harness import stats


def read(run):
    return stats.nearest_rank(stats.latencies(run.records), 0.50)
