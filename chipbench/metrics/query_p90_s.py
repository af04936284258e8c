"""90th percentile, by nearest rank, of the wall latency of every query
completed in the window."""
from chipbench.stats import nearest_rank


def read(run):
    if not run.window:
        return None
    return nearest_rank([r["end"] - r["start"] for r in run.window], 90)
