"""Device seconds per query run under the trace in the operators' radix
sorts: the union, on the first chip, of the intervals of every op whose
op_name lies under a ``radix_sort`` name scope (``spans.reduce``, read
through ``scopes.py``)."""


def read(run):
    reduced = getattr(run, "spans", None)
    if reduced is None or not run.traced or reduced["sort_device_s"] <= 0:
        return None
    return reduced["sort_device_s"] / len(run.traced)
