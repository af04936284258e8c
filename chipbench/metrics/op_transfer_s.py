"""Host seconds per query run under the trace in the operators' copies:
the program's ``repro.ops.stage`` (spec, pad and ``device_put``) and
``repro.ops.fetch`` (device-to-host copy) spans, summed over the
executor threads (``spans.reduce``)."""


def read(run):
    reduced = getattr(run, "spans", None)
    if reduced is None or not reduced["queries"] or not run.traced:
        return None
    return reduced["op_transfer_s"] / len(run.traced)
