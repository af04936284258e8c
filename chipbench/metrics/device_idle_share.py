"""Share of the traced window, in percent, in which no operation ran on
the device: 1 - (union of device-op intervals) / (window). The traced
window lasts from the start of the trace until the last query run under
it has returned."""


def read(run):
    if run.trace is None or run.trace_window_s <= 0:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace_window_s)
