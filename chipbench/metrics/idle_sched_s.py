"""Device-idle seconds per query run under the trace during which no
``repro.task`` span is open on any host thread: the scheduler's and the
benchmark's own time between tasks (``spans.reduce``, cause ``sched``)."""
from chipbench.spans import SCHED


def read(run):
    reduced = getattr(run, "spans", None)
    if reduced is None or not reduced["queries"] or not run.traced:
        return None
    return reduced["idle_causes"].get(SCHED, 0.0) / len(run.traced)
