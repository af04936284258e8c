"""How evenly the coordinator's task placement loads the chips, in
percent: 100 x the mean over the program's chips of the padded rows
each processed (the ``rows_padded_chip<k>`` counters, totalled over the
traced window by ``spans.RowCounter``) over the largest of them. A chip
the coordinator holds but gave no task reads 0 and counts in the mean;
100 is an even load."""
import re

CHIP = re.compile(r"rows_padded_chip\d+")


def read(run):
    counters = getattr(run, "counters", None) or {}
    rows = [v for k, v in counters.items() if CHIP.fullmatch(k)]
    if not rows or max(rows) <= 0:
        return None
    return 100.0 * sum(rows) / len(rows) / max(rows)
