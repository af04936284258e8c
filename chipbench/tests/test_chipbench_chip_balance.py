"""``chip_balance``: the mean over the program's chips of the padded rows
each processed, over the largest, from ``Run.counters``."""
import types

import pytest

from chipbench import harness


@pytest.mark.parametrize("counters, want", [
    ({"rows_padded_chip0": 4096}, 100.0),
    ({"rows_padded_chip0": 300, "rows_padded_chip1": 300,
      "rows_padded_chip2": 300, "rows_padded_chip3": 300}, 100.0),
    # three big scans on three chips, two on the fourth
    ({"rows_padded_chip0": 3, "rows_padded_chip1": 3,
      "rows_padded_chip2": 3, "rows_padded_chip3": 2}, 100.0 * 11 / 12),
    # a chip held but given no task reads 0, and counts
    ({"rows_padded_chip0": 900, "rows_padded_chip1": 0,
      "rows_padded_chip2": 0, "rows_padded_chip3": 0}, 25.0),
    # the other counters do not count
    ({"rows_padded_chip0": 50, "rows_padded_chip1": 100,
      "rows_padded": 150, "rows": 120, "agg_columns": 6}, 75.0),
])
def test_chip_balance(counters, want):
    run = types.SimpleNamespace(counters=counters)
    assert harness.reader("chip_balance")(run) == pytest.approx(want)


@pytest.mark.parametrize("counters", [
    None,                                    # an untraced run
    {},
    {"rows": 10, "rows_padded": 1024},       # a program without placement
    {"rows_padded_chip0": 0, "rows_padded_chip1": 0},  # no task ran
])
def test_chip_balance_reads_nothing_without_chip_counters(counters):
    run = types.SimpleNamespace(counters=counters)
    assert harness.reader("chip_balance")(run) is None
