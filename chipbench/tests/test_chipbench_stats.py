"""The metric arithmetic and the comparison, on numbers worked by hand."""
import math
import types

import numpy as np
import pytest

from chipbench import harness
from chipbench.compare import as_arrays, compare
from chipbench.stats import completed_in, geomean, nearest_rank, table_nbytes


class _T:
    def __init__(self, cols):
        self.cols = cols


def test_geomean():
    assert geomean([1.0, 4.0]) == pytest.approx(2.0)
    assert geomean([2.0, 2.0, 2.0]) == pytest.approx(2.0)
    with pytest.raises(ValueError):
        geomean([1.0, 0.0])


@pytest.mark.parametrize("n,want", [(1, 1), (9, 9), (10, 9), (11, 10),
                                    (20, 18), (45, 41)])
def test_nearest_rank_p90(n, want):
    assert nearest_rank(list(range(n, 0, -1)), 90) == want


def test_completed_in_window_leaves_out_late_and_failed():
    recs = [{"start": 0.0, "end": 1.0, "failed": False},
            {"start": 1.0, "end": 2.5, "failed": False},   # in flight
            {"start": 0.5, "end": 0.9, "failed": True},
            {"start": -0.1, "end": 0.5, "failed": False}]  # before start
    assert completed_in(recs, 0.0, 2.0) == recs[:1]


def test_table_nbytes_counts_true_rows_and_codes():
    dc = types.SimpleNamespace(codes=np.zeros(5, np.uint32), values=[b"a"])
    t = _T({"a": np.zeros(5, np.int64), "b": np.zeros(5, np.float64),
            "c": dc})
    assert table_nbytes(t) == 5 * 8 + 5 * 8 + 5 * 4


def _run(**kw):
    base = dict(seconds=10.0, setup_s=3.0, attempted=4, end=10.0, window=[
        {"query": "q1", "start": 0.0, "end": 1.0},
        {"query": "q5", "start": 1.0, "end": 5.0},
        {"query": "q1", "start": 5.0, "end": 9.0}],
        in_flight=[{"query": "q6", "start": 9.0, "end": 13.0}])
    base.update(kw)
    return harness.Run(**base)


def test_end_to_end_readers():
    run = _run()
    # q1: geomean of 1 and 4 is 2; q5: 4; over the two types: sqrt(8)
    assert harness.reader("query_geomean_s")(run) == \
        pytest.approx(math.sqrt(8.0))
    assert harness.reader("query_p90_s")(run) == pytest.approx(4.0)
    # a metric split by cell is read by its base's reader
    assert harness.reader("query_p90_s.scan")(run) == pytest.approx(4.0)
    # 3 done, and a quarter of the one in flight lay inside the window
    assert harness.reader("queries_per_s")(run) == pytest.approx(0.325)
    assert harness.reader("setup_s")(run) == 3.0


def test_per_layer_readers():
    probes = types.SimpleNamespace(
        op_bytes=int(819e9 * 0.5), calls={},
        host_s={"decode_object": 1.0, "serialize_table": 2.0,
                "run": 9.0})
    trace = {"program_s": {"jit__program": 8.0, "jit__head": 2.0,
                           "jit_other": 5.0},
             "busy_s": 6.0}
    run = _run(probes=probes, trace=trace, trace_window_s=8.0,
               peaks={"hbm_bytes_per_s": 819e9})
    run.traced = run.window + run.in_flight
    assert harness.reader("op_device_s")(run) == pytest.approx(10.0 / 4)
    assert harness.reader("op_roofline")(run) == pytest.approx(5.0)
    assert harness.reader("host_format_s")(run) == pytest.approx(3.0 / 4)
    assert harness.reader("device_idle_share")(run) == pytest.approx(25.0)
    # nothing to read: the metric is left out, never reported as 0
    bare = _run()
    for name in ("op_device_s", "op_roofline", "host_format_s",
                 "device_idle_share"):
        assert harness.reader(name)(bare) is None


def test_compare():
    want = {"k": np.asarray([b"A", b"B"]), "n": np.asarray([3, 4]),
            "s": np.asarray([100.0, 200.0])}
    same = {"k": np.asarray([b"A", b"B"]), "n": np.asarray([3, 4]),
            "s": np.asarray([100.0, 200.0 + 2e-8])}
    differs, err = compare(same, want)
    assert not differs and err == pytest.approx(1e-10)
    for bad in ({**same, "k": np.asarray([b"B", b"A"])},
                {**same, "n": np.asarray([3, 5])},
                {k: v[:1] for k, v in same.items()},
                {"k": same["k"], "s": same["s"]}):
        differs, err = compare(bad, want)
        assert differs and math.isnan(err)


def test_as_arrays_decodes_dictionary_columns():
    dc = types.SimpleNamespace(codes=np.asarray([1, 0, 1], np.uint32),
                               values=[b"x", b"y"])
    got = as_arrays(_T({"c": dc, "v": np.asarray([1.0, 2.0, 3.0])}))
    assert got["c"].tolist() == [b"y", b"x", b"y"]
