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


def test_counter_totals_every_program_scalar_of_one_q1_task():
    from repro.relational import device_ops
    from repro.relational.tpch import QUERIES, generate
    stage = next(st for st in QUERIES["q1"]()["stages"]
                 if st["kind"] == "scan" and st["table"] == "lineitem")
    t = generate(0.001, seed=0)["lineitem"].project(stage["columns"])
    with spans.RowCounter() as counters:
        device_ops.run(t, stage["ops"], {})
        jax.monitoring.record_scalar("/jax/other/rows", 5)
        jax.monitoring.record_scalar("repro/rows", 5)
    assert counters.totals == {"rows": len(t),
                               "rows_padded": device_ops.bucket(len(t)),
                               "agg_columns": 6, "agg_scatters": 1}


def test_readers_read_nothing_from_a_run_without_spans():
    run = types.SimpleNamespace(traced=[{}], trace=None, probes=None)
    for name in NEW_METRICS:
        assert harness.reader(name)(run) is None


# --------------------------------------------------------------------------
# the recorded trace
# --------------------------------------------------------------------------

# ``spans.reduce_bytes`` of the recorded trace as it read when it reduced
# only the first device plane
ONE_CHIP = {
    "idle_s": 0.05266677,
    "idle_causes": {
        "repro.ops.stage": 0.02328602000000001,
        "repro.ops.launch": 0.002847993999999942,
        "repro.ops.wait": 0.004459590000000034,
        "repro.task": 0.00036233000000000006,
        "repro.ops.fetch": 0.002684714,
        "repro.ops.split": 5.991e-05,
        "repro.format.encode": 0.00048547300000000005,
        "repro.store.put": 0.00010729000000000001,
        "sched": 0.00427367,
        "repro.store.get": 2.3610000000000003e-05,
        "repro.format.decode": 0.008913309,
        "repro.merge": 2.4500000000000003e-05,
        "repro.query": 0.005138360000000001},
    "queries": 2,
    "sched_self_s": 0.047194036,
    "op_transfer_s": 0.15829773600000002,
    "scope_device_s": {
        "aggregate": 0.00632,
        "aggregate/radix_sort": 0.000381097,
        "filter": 2.1340000000000002e-06,
        "join": 0.00463409,
        "join/radix_sort": 0.0017833310000000002,
        "output": 0.006002062000000001,
        "output/radix_sort": 0.0036970960000000004,
        "partition": 3.8186e-05},
    "sort_device_s": 0.005861524000000001,
    "scoped_device_s": 0.022857996000000002,
    "program_s": 0.023025301,
    "ambiguous": [],
}


def _with_second_chip(raw: bytes) -> bytes:
    """The serialized XSpace with a copy of its ``/device:TPU:0`` plane,
    renamed ``/device:TPU:1``, appended: a second chip that ran the same
    ops at the same times."""
    buf = memoryview(raw)
    for field, _, span in scopes.fields(buf):
        if field != 1:
            continue
        name, plane = None, b""
        for pf, wire, pv in scopes.fields(buf, *span):
            if wire == 2:
                payload = bytes(buf[pv[0]:pv[1]])
                if pf == 2:
                    name, payload = payload.decode(), b"/device:TPU:1"
                plane += _len(pf, payload)
            elif wire == 0:
                plane += _int(pf, pv)
            else:
                plane += _varint(pf << 3 | wire) + pv
        if name == "/device:TPU:0":
            return raw + _len(1, plane)
    raise ValueError("no /device:TPU:0 plane")


@pytest.fixture(scope="module")
def recorded():
    raw = gzip.decompress(DATA.read_bytes())
    old = trace.reduce_profile(ProfileData.from_serialized_xspace(raw))
    return raw, old, spans.reduce_bytes(raw)


@pytest.fixture(scope="module")
def two_chips(recorded):
    raw = _with_second_chip(recorded[0])
    return (trace.reduce_profile(ProfileData.from_serialized_xspace(raw)),
            spans.reduce_bytes(raw))


def test_one_chip_reduction_is_unchanged(recorded):
    assert recorded[2] == ONE_CHIP
    assert list(recorded[2]["idle_causes"]) == list(ONE_CHIP["idle_causes"])


def test_busy_s_is_the_mean_over_chips(recorded, two_chips):
    one, two = recorded[1], two_chips[0]
    assert one["busy_by_chip_s"] == [one["busy_s"]]
    assert two["chips"] == 2
    assert two["busy_by_chip_s"] == [one["busy_s"]] * 2
    assert two["busy_s"] == pytest.approx(
        sum(two["busy_by_chip_s"]) / 2, rel=1e-12)
    assert two["program_s"] == pytest.approx(
        {k: 2 * v for k, v in one["program_s"].items()}, rel=1e-12)


def test_every_chip_counts_in_the_reduction(recorded, two_chips):
    _, old, one = recorded
    two = two_chips[1]
    for key in ("idle_s", "sort_device_s", "scoped_device_s",
                "program_s"):
        assert two[key] == pytest.approx(2 * one[key], rel=1e-12), key
    assert two["scope_device_s"] == pytest.approx(
        {k: 2 * v for k, v in one["scope_device_s"].items()}, rel=1e-12)
    assert two["idle_causes"] == pytest.approx(
        {k: 2 * v for k, v in one["idle_causes"].items()}, rel=1e-9)
    idle = 2 * (old["span_s"] - old["busy_s"])
    assert abs(sum(two["idle_causes"].values()) - idle) <= 1e-3
    for key in ("queries", "sched_self_s", "op_transfer_s", "ambiguous"):
        assert two[key] == one[key], key


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
    run = harness.Run(seconds=1.0, setup_s=1.0, window=[], attempted=2,
                      traced=[{}, {}], trace=old, spans=red,
                      counters={"rows": 57, "rows_padded": 100})
    got = {name: harness.reader(name)(run) for name in NEW_METRICS}
    assert all(v is not None and v >= 0 for v in got.values()), got
    assert got["sort_device_s"] == pytest.approx(red["sort_device_s"] / 2)
    assert got["op_fill"] == pytest.approx(57.0)
    assert got["idle_sched_s"] + got["idle_format_s"] <= \
        red["idle_s"] / 2 + 1e-9
