"""Device seconds per query run under the trace in the operators' radix
sorts: each chip's union of the intervals of every op whose op_name lies
under a ``radix_sort`` name scope, summed over chips (``spans.reduce``,
read through ``scopes.py``)."""


def read(run):
    reduced = getattr(run, "spans", None)
    if reduced is None or not run.traced or reduced["sort_device_s"] <= 0:
        return None
    return reduced["sort_device_s"] / len(run.traced)
