"""Device seconds of the worker-operator programs (``jit__program`` and
``jit__head`` of ``relational/device_ops.py``) in the trace, per query
run under it (the trace lasts until the last of them has returned)."""

PROGRAMS = ("jit__program", "jit__head")


def read(run):
    if run.trace is None or not run.traced:
        return None
    total = sum(run.trace["program_s"].get(p, 0.0) for p in PROGRAMS)
    return total / len(run.traced) if total > 0 else None
