"""The reduction by the program's own spans and scopes (``spans.py``,
``scopes.py``) and its metric readers, on hand-built inputs and on a small
trace recorded on a TPU v5e chip (``data/spans.xplane.pb.gz``, made by
``record_trace.py`` and gzip: q6 and q12 at SF 0.01 through ``Session``,
with the program's ``repro.*`` spans and operator scopes)."""
import gzip
import pathlib
import types

import jax
import pytest
from jax.profiler import ProfileData

from chipbench import harness, scopes, spans, trace

DATA = pathlib.Path(__file__).resolve().parent / "data" / \
    "spans.xplane.pb.gz"
NEW_METRICS = ("sched_self_s", "idle_sched_s", "idle_format_s",
               "op_transfer_s", "sort_device_s", "op_fill")


# --------------------------------------------------------------------------
# the wire reader, on a hand-built XSpace
# --------------------------------------------------------------------------

def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _len(field: int, payload: bytes) -> bytes:
    return _varint(field << 3 | 2) + _varint(len(payload)) + payload


def _int(field: int, n: int) -> bytes:
    return _varint(field << 3) + _varint(n)


def _str(field: int, text: str) -> bytes:
    return _len(field, text.encode())


def _event_meta(mid: int, name: str, stats: list[bytes]) -> bytes:
    value = _int(1, mid) + _str(2, name) + b"".join(_len(5, s)
                                                     for s in stats)
    return _len(4, _int(1, mid) + _len(2, value))


def _stat_meta(sid: int, name: str) -> bytes:
    return _len(5, _int(1, sid) + _len(2, _int(1, sid) + _str(2, name)))


def _plane(name: str, metas: list[bytes], stat_names: dict) -> bytes:
    lines = _len(3, _str(2, "XLA Ops") + _len(4, b"\x08\x01\x10\x05"))
    body = _int(1, 7) + _str(2, name) + lines + b"".join(metas) + \
        b"".join(_stat_meta(k, v) for k, v in stat_names.items())
    return _len(1, body)


def test_wire_reader_reads_op_names_from_event_metadata():
    stat_names = {3: "hlo_category", 9: "tf_op",
                  11: "jit(_program)/output/radix_sort/while:while"}
    metas = [
        # op_name as a string value, with its ":<type>" suffix
        _event_meta(1, "%fusion.504 = u32[8] fusion()",
                    [_int(1, 3) + _str(5, "loop fusion"),
                     _int(1, 9) + _str(5, "jit(_program)/join/radix_sort/"
                                          "while/body/gather:")]),
        # op_name as a reference to a stat metadata entry's name
        _event_meta(2, "%while.38 = (u32[]) while()",
                    [_int(1, 9) + _int(7, 11)]),
        # no op_name at all
        _event_meta(3, "%copy.1 = s32[8] copy()", []),
        # one name, two op_names: ambiguous
        _event_meta(4, "%fusion.5 = s32[8] fusion()",
                    [_int(1, 9) + _str(5, "jit(_program)/join/gather:")]),
        _event_meta(5, "%fusion.5 = s32[8] fusion()",
                    [_int(1, 9) + _str(5, "jit(_program)/output/gather:")]),
    ]
    raw = _plane("/host:CPU", [_event_meta(1, "repro.task", [])], {}) + \
        _plane("/device:TPU:0", metas, stat_names)
    got = scopes.op_names(raw, "/device:TPU:")
    assert list(got) == ["/device:TPU:0"]
    names = got["/device:TPU:0"]
    assert names == {
        "%fusion.504 = u32[8] fusion()":
            {"jit(_program)/join/radix_sort/while/body/gather"},
        "%while.38 = (u32[]) while()":
            {"jit(_program)/output/radix_sort/while"},
        "%copy.1 = s32[8] copy()": set(),
        "%fusion.5 = s32[8] fusion()":
            {"jit(_program)/join/gather", "jit(_program)/output/gather"},
    }
    assert scopes.ambiguous(names) == ["%fusion.5 = s32[8] fusion()"]


@pytest.mark.parametrize("op_name, scope", [
    ("jit(_program)/join/radix_sort/while/body/gather", "join/radix_sort"),
    ("jit(_program)/aggregate/radix_sort/iota", "aggregate/radix_sort"),
    ("jit(_program)/output/radix_sort/while", "output/radix_sort"),
    ("jit(_program)/jit(searchsorted)/join/while/body/gather", "join"),
    ("jit(_program)/partition/mul", "partition"),
    ("jit(_program)/gather", ""),
    ("jit(_head)/slice", ""),
])
def test_scope_of(op_name, scope):
    assert spans.scope_of(op_name) == scope


# --------------------------------------------------------------------------
# the idle rule, on hand-built spans
# --------------------------------------------------------------------------

THREADS = {
    "main": [(0, 100, "repro.query"), (10, 90, "repro.sched.wait")],
    "w1": [(10, 50, "repro.task"), (20, 30, "repro.format.decode"),
           (35, 45, "repro.ops.launch")],
    "w2": [(40, 80, "repro.task"), (60, 70, "repro.format.encode")],
}


def test_innermost_span_of_one_thread():
    assert spans.innermost(THREADS["w1"]) == [
        (10, 20, "repro.task"), (20, 30, "repro.format.decode"),
        (30, 35, "repro.task"), (35, 45, "repro.ops.launch"),
        (45, 50, "repro.task")]


def test_idle_causes_sweep_every_gap_exactly():
    got = spans.idle_causes([(0, 15), (25, 100)], THREADS)
    want = {  # ns; the loop blocked in repro.sched.wait casts no vote
        "sched": 10 + 20,                    # no task open: 0-10, 80-100
        "repro.task": 5 + 5 + 5 + 10 + 10,
        "repro.format.decode": 5,
        "repro.ops.launch": 10,              # ties repro.task, sorts first
        "repro.format.encode": 10,
    }
    assert got == pytest.approx({k: v * 1e-9 for k, v in want.items()})
    assert sum(got.values()) == pytest.approx(90e-9)
    assert spans.idle_causes([(200, 210)], THREADS) == \
        pytest.approx({"sched": 10e-9})
    assert spans.idle_causes([(0, 5)], {}) == pytest.approx({"sched": 5e-9})


def test_query_self_time_leaves_out_the_waits():
    assert spans._self_s(THREADS) == pytest.approx(20e-9)


def test_row_counter_totals_the_program_counters():
    with spans.RowCounter() as rows:
        jax.monitoring.record_scalar(spans.ROWS, 600)
        jax.monitoring.record_scalar(spans.ROWS_PADDED, 1024)
        jax.monitoring.record_scalar(spans.ROWS, 10)
        jax.monitoring.record_scalar(spans.ROWS_PADDED, 2048)
        jax.monitoring.record_scalar("/other/counter", 5)
    jax.monitoring.record_scalar(spans.ROWS, 99)     # after the window
    assert rows.totals == {"rows": 610, "rows_padded": 3072}
    run = types.SimpleNamespace(counters=rows.totals)
    assert harness.reader("op_fill")(run) == pytest.approx(
        100.0 * 610 / 3072)


def test_readers_read_nothing_from_a_run_without_spans():
    run = types.SimpleNamespace(traced=[{}], trace=None, probes=None)
    for name in NEW_METRICS:
        assert harness.reader(name)(run) is None


# --------------------------------------------------------------------------
# the recorded trace
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def recorded():
    raw = gzip.decompress(DATA.read_bytes())
    old = trace.reduce_profile(ProfileData.from_serialized_xspace(raw))
    return raw, old, spans.reduce_bytes(raw)


def test_idle_causes_sum_to_the_idle_time(recorded):
    _, old, red = recorded
    idle = old["span_s"] - old["busy_s"]
    assert red["idle_s"] == pytest.approx(idle, abs=1e-9)
    assert abs(sum(red["idle_causes"].values()) - idle) <= 1e-3
    assert red["queries"] == 2                   # q6 and q12
    assert any(c.startswith("repro.") for c in red["idle_causes"])


def test_sorts_are_found_and_fit_in_the_busy_time(recorded):
    _, old, red = recorded
    assert 0 < red["sort_device_s"] <= old["busy_s"]
    sorts = [k for k in red["scope_device_s"] if k.endswith("/radix_sort")]
    assert "output/radix_sort" in sorts and "join/radix_sort" in sorts


def test_scopes_cover_the_operator_programs(recorded):
    _, old, red = recorded
    assert red["program_s"] == pytest.approx(old["program_s"]["jit__program"])
    assert red["scoped_device_s"] >= 0.95 * red["program_s"]
    assert red["scoped_device_s"] <= red["program_s"] * 1.001


def test_host_spans_of_the_recorded_trace(recorded):
    _, _, red = recorded
    assert red["sched_self_s"] > 0
    assert red["op_transfer_s"] > 0


def test_readers_on_the_recorded_trace(recorded):
    _, old, red = recorded
    run = types.SimpleNamespace(traced=[{}, {}], trace=old, spans=red,
                                counters={"rows": 57, "rows_padded": 100})
    got = {name: harness.reader(name)(run) for name in NEW_METRICS}
    assert all(v is not None and v >= 0 for v in got.values()), got
    assert got["sort_device_s"] == pytest.approx(red["sort_device_s"] / 2)
    assert got["op_fill"] == pytest.approx(57.0)
    assert got["idle_sched_s"] + got["idle_format_s"] <= \
        red["idle_s"] / 2 + 1e-9
