"""Host seconds the coordinator spends on its own per query run under the
trace: the self time of the program's ``repro.query`` spans (their
duration less what ``repro.sched.wait``, the event loop blocked on the
workers, covers on the same thread), from ``spans.reduce``: planning,
the event loop, thread-pool start-up and shut-down."""


def read(run):
    reduced = getattr(run, "spans", None)
    if reduced is None or not reduced["queries"] or not run.traced:
        return None
    return reduced["sched_self_s"] / len(run.traced)
