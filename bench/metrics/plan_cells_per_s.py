"""Plan cells (instance x forecast member) answered per second, over the
whole window: first send to last answer, whole requests only."""
from harness import stats


def read(run):
    return stats.cells_per_s(run.records)
