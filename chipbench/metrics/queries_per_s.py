"""Queries' worth of work done in the window over its length: each query
completed in it counts 1, and a query in flight at the close counts the
share of its wall time that lay inside the window."""


def read(run):
    done = len(run.window)
    partial = sum((run.end - r["start"]) / (r["end"] - r["start"])
                  for r in run.in_flight)
    return (done + partial) / run.seconds
