"""The query path's wall-clock spans, device name scopes and row counters
(``repro.obs.spans``), read back from a profiler trace on the CPU."""
import contextlib
import glob
import re
import threading

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core.session import Session
from repro.obs import spans
from repro.relational import device_ops as D
from repro.relational.table import Table, serialize_table
from repro.relational.tpch import QUERIES, generate

HOST_PLANE = "/host:CPU"
TASK_SPANS = (spans.STORE_GET, spans.STORE_PUT, spans.FORMAT_DECODE,
              spans.FORMAT_ENCODE, spans.OPS_STAGE, spans.OPS_LAUNCH,
              spans.OPS_WAIT, spans.OPS_FETCH, spans.OPS_SPLIT, spans.MERGE)


def _session():
    return Session(sf=0.002, seed=3, compute_scale=0)


def _host_spans(log_dir: str) -> list[dict]:
    """Every ``repro.*`` event of the host plane, with its thread line."""
    path, = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != HOST_PLANE:
            continue
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith("repro."):
                    out.append({"name": ev.name, "line": i,
                                "start": ev.start_ns,
                                "end": ev.start_ns + ev.duration_ns,
                                "ids": dict(ev.stats)})
    return out


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """q12 (scan, join, partition, final merge) once warm, then once
    under the profiler: (its spans, its result)."""
    sess = _session()
    sess.submit("q12")
    log_dir = str(tmp_path_factory.mktemp("trace"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(log_dir, profiler_options=opts):
        res = sess.submit("q12")
    return _host_spans(log_dir), res


def _within(inner, outer) -> bool:
    return outer["start"] <= inner["start"] and inner["end"] <= outer["end"]


def test_trace_holds_the_documented_spans(traced):
    evs, _ = traced
    names = {e["name"] for e in evs}
    assert {spans.QUERY, spans.PLAN, spans.SCHED_WAIT, spans.TASK} <= names
    assert set(TASK_SPANS) <= names
    query, = [e for e in evs if e["name"] == spans.QUERY]
    # the second run of a plan name gets the unique name "<name>@1"
    assert query["ids"] == {"query": "q12@1"}
    plan, = [e for e in evs if e["name"] == spans.PLAN]
    assert plan["ids"] == {"query": "q12@1"} and _within(plan, query)
    for e in evs:
        if e["name"] == spans.SCHED_WAIT:
            assert e["line"] == query["line"] and _within(e, query)


def test_tasks_nest_inside_the_query_and_hold_the_work(traced):
    evs, _ = traced
    query, = [e for e in evs if e["name"] == spans.QUERY]
    tasks = [e for e in evs if e["name"] == spans.TASK]
    assert tasks
    for t in tasks:
        assert _within(t, query)
        assert t["ids"]["query"] == "q12@1"
        stage = t["ids"]["stage"]
        assert re.fullmatch(re.escape(stage) + r"\[\d+\]", t["ids"]["task"])
    stages = {t["ids"]["stage"] for t in tasks}
    assert len(stages) >= 3          # scans, the join, the final merge
    # every format, store, operator and merge span lies inside a task
    # span of its own thread
    for e in evs:
        if e["name"] in TASK_SPANS:
            assert any(t["line"] == e["line"] and _within(e, t)
                       for t in tasks), e["name"]


def test_operator_tasks_carry_their_chip(traced):
    evs, _ = traced
    tasks = [e for e in evs if e["name"] == spans.TASK]
    chip = jax.devices()[0].id
    for t in tasks:
        if t["ids"]["stage"] == "final":        # the merge, on the host
            assert "chip" not in t["ids"]
        else:
            assert int(t["ids"]["chip"]) == chip, t["ids"]
    assert any("chip" in t["ids"] for t in tasks)


def test_results_are_bit_identical_with_a_trace_running(traced):
    _, res = traced
    plain = _session()
    plain.submit("q12")
    want = plain.submit("q12")
    assert serialize_table(res.result) == serialize_table(want.result)
    assert res.latency_s == want.latency_s


class _Scalars:
    """``jax.monitoring`` scalars by event name, from any thread."""

    def __init__(self):
        self.lock = threading.Lock()
        self.seen: dict[str, list] = {}

    def __call__(self, event: str, value, **kw) -> None:
        with self.lock:
            self.seen.setdefault(event, []).append(value)

    def __enter__(self):
        jax.monitoring.register_scalar_listener(self)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_scalar_listener(self)


def _table(n: int, key_hi: int, seed: int = 0) -> Table:
    rng = np.random.default_rng(seed)
    return Table({"k": rng.integers(0, key_hi, n).astype(np.int64),
                  "a": rng.uniform(0, 1, n)})


@pytest.mark.parametrize("n", [1, 700, 1500])
def test_row_counters_of_one_task(n):
    t = _table(n, 10)
    ops = [{"op": "filter", "pred": {"fn": "lt", "args": ["a", 0.5]}}]
    with _Scalars() as sc:
        D.run(t, ops, {})
    assert sc.seen[spans.ROWS] == [n]
    assert sc.seen[spans.ROWS_PADDED] == [D.bucket(n)]


def test_chip_counter_of_one_task():
    t = _table(700, 10)
    ops = [{"op": "filter", "pred": {"fn": "lt", "args": ["a", 0.5]}}]
    dev = jax.devices()[0]
    with _Scalars() as sc:
        with D.on_chip(dev):            # a task placed on a chip
            D.run(t, ops, {})
        D.run(t, ops, {})               # JAX's default device: no chip's
    assert sc.seen[spans.rows_padded_chip(dev.id)] == [D.bucket(700)]
    assert sc.seen[spans.ROWS_PADDED] == [D.bucket(700)] * 2
    assert spans.rows_padded_chip(dev.id) == \
        f"/repro/device_ops/rows_padded_chip{dev.id}"


class _IdleChip:
    """A chip the coordinator holds; placement gives it only the host's
    final merge, so it runs no device program."""
    id = 7


def test_a_chip_given_no_task_reads_zero():
    from repro.core.coordinator import Coordinator
    from repro.core.engine import load_base_tables
    from repro.objectstore.store import ObjectStore, StoreConfig
    store = ObjectStore(StoreConfig(seed=0, time_scale=0.0,
                                    simulate_visibility_lag=False))
    splits = load_base_tables(store, generate(0.002, seed=0), 64 << 20)
    dev = jax.devices()[0]
    coord = Coordinator(store, splits, seed=0, compute_scale=0,
                        devices=[dev, _IdleChip()])
    with _Scalars() as sc:
        res = coord.run_query(QUERIES["q6"]())   # one scan task, then merge
    assert not res.failed
    assert sc.seen[spans.rows_padded_chip(7)] == [0]
    ran = sc.seen[spans.rows_padded_chip(dev.id)]
    assert ran[0] == 0 and sum(ran) == sum(sc.seen[spans.ROWS_PADDED]) > 0


def test_join_overflow_counts_its_rerun():
    # 40 probe rows, each matching 50 build rows: 2000 matches overflow
    # the first capacity (the probe's 1024-row bucket), so the program
    # runs twice over the same padded inputs
    probe = Table({"k": np.zeros(40, np.int64), "a": np.arange(40.0)})
    build = Table({"bk": np.zeros(50, np.int64), "v": np.arange(50.0)})
    ops = [{"op": "join", "table": "B", "lkey": "k", "rkey": "bk"}]
    with _Scalars() as sc:
        out = D.run(probe, ops, {"B": build})
    assert len(out) == 2000
    assert sc.seen[spans.ROWS] == [90]
    assert sc.seen[spans.ROWS_PADDED] == [2 * (D.bucket(40) + D.bucket(50))]


@pytest.mark.parametrize("query, columns, scatters", [
    ("q1", 6, 1),       # four distinct value columns, all summed
    ("q6", 1, 1),
    ("mix", 5, 3),      # min, max, and a sum beside an avg and its count
    ("q12", None, None),  # its scan task has no partial aggregate
])
def test_aggregate_counters_of_one_task(query, columns, scatters):
    tables = generate(0.001, seed=0)
    if query == "mix":
        t = _table(3000, 10)
        ops = [{"op": "partial_agg", "keys": ["k"],
                "aggs": [["lo", "min", "a"], ["hi", "max", "a"],
                         ["m", "avg", "a"], ["s", "sum", "a"]]}]
    else:
        st = next(s for s in QUERIES[query]()["stages"]
                  if s["kind"] == "scan" and s["table"] == "lineitem")
        t, ops = tables["lineitem"].project(st["columns"]), st["ops"]
    with _Scalars() as sc:
        D.run(t, ops, {})
    if columns is None:
        assert spans.AGG_COLUMNS not in sc.seen
        assert spans.AGG_SCATTERS not in sc.seen
    else:
        assert sc.seen[spans.AGG_COLUMNS] == [columns]
        assert sc.seen[spans.AGG_SCATTERS] == [scatters]


def _strip_metadata(hlo: str) -> str:
    """Compiled HLO text without op metadata and the source tables."""
    hlo = re.sub(r", metadata=\{[^}]*\}", "", hlo)
    hlo = re.sub(r"\n(FileNames|FunctionNames|FileLocations|StackFrames)\n"
                 r"(.+\n)*", "\n", hlo)
    return hlo


def _compiled_program() -> str:
    """The compiled text of one task program: a join, a filter, a grouped
    partial aggregate and a hash partition, traced afresh."""
    t, build = _table(700, 20, 1), _table(300, 20, 2)
    build = Table({"bk": build.cols["k"], "v": build.cols["a"]})
    ops = [{"op": "join", "table": "B", "lkey": "k", "rkey": "bk"},
           {"op": "filter", "pred": {"fn": "lt", "args": ["a", 0.5]}},
           {"op": "compute", "name": "x",
            "expr": {"fn": "mul", "args": ["a", "v"]}},
           {"op": "partial_agg", "keys": ["k"],
            "aggs": [["s", "sum", "x"], ["c", "count", None]]}]
    with jax.enable_x64(True):
        spec, _, _ = D._spec(ops, t, {"B": build}, ("k", 4),
                             D.bucket(len(t)))
        cols, n = D._to_device(t)
        builds = {"B": D._to_device(build)}
        # a function of its own, so that JAX's trace cache misses
        program = jax.jit(lambda *a, spec: D._program.__wrapped__(
            *a, spec=spec), static_argnames=("spec",))
        return program.lower(cols, n, builds, np.uint64(4),
                             spec=spec).compile().as_text()


def test_name_scopes_change_only_metadata(monkeypatch):
    scoped = _compiled_program()
    for scope in ("join/radix_sort", "aggregate/radix_sort",
                  "aggregate/segment/", "output/radix_sort", "filter/",
                  "compute/", "partition/"):
        assert scope in scoped, scope
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = _compiled_program()
    assert "radix_sort" not in plain
    assert _strip_metadata(scoped) == _strip_metadata(plain)
