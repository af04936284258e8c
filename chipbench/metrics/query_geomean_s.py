"""TPC-H's power statistic over the window: the geometric mean, over the
query types completed in it, of each type's geometric-mean wall latency.
Every completed query counts, and each type weighs alike however many of
it the window's partial last pass holds."""
from collections import defaultdict

from chipbench.stats import geomean


def read(run):
    if not run.window:
        return None
    by_type = defaultdict(list)
    for r in run.window:
        by_type[r["query"]].append(r["end"] - r["start"])
    return geomean(geomean(v) for v in by_type.values())
