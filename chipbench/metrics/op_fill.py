"""Share of the operators' padded rows that are true rows, in percent:
100 x the program's ``rows`` counter over its ``rows_padded`` counter,
totalled over the traced window (``spans.RowCounter``). Padding to
power-of-two buckets, and a join's re-run after its matches overflow,
lower it."""


def read(run):
    counters = getattr(run, "counters", None)
    if not counters or not counters.get("rows_padded"):
        return None
    return 100.0 * counters["rows"] / counters["rows_padded"]
