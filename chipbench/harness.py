"""One run of one cell: set-up, warm-up, the measured window, the check.

Everything a cell is made of is found by name: the cell in
``BENCHMARK.json``, its configuration in ``configs/<config>.json``, its
traffic in ``traffic/<traffic>.json``, each metric's reader in
``metrics/<metric>.py`` and the chip's peaks in ``peaks.json``.

Traffic is one closed stream: it sends its next query when the previous
one has returned, cycling through its fixed order, through
``Session.submit``, timed on the host clock around the call, which
returns once the result is on the host. The data is the benchmark's own
(``dbgen.py``), loaded into the program's store as the configuration's
objects.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import math
import pathlib
import shutil
import sys
import tempfile
import threading
import time

import numpy as np

from chipbench import dbgen, reference
from chipbench.compare import as_arrays, compare
from chipbench.stats import completed_in

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
# fired around every program build, whether compiled or loaded from the
# persistent cache; a load also fires CACHE_HIT_EVENT
BUILD_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


# ---------------------------------------------------------------------------
# finding a cell's parts by name
# ---------------------------------------------------------------------------

def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def resolve(workload: str, root: pathlib.Path = ROOT) -> dict:
    """The cell named ``workload`` with its configuration, traffic and
    metric entries, read from ``BENCHMARK.json`` and the files it names."""
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; have {sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    entry = configs[cell["config"]]
    config = load_json(root / entry["file"])
    traffic = load_json(HERE / "traffic" / f"{cell['traffic']}.json")

    def applies(m):
        return "workloads" not in m or workload in m["workloads"]

    return {"cell": cell, "config": config, "traffic": traffic,
            "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
            "per_layer": [m for m in bench["per_layer"] if applies(m)]}


def reader(name: str):
    """``read(run)`` of ``metrics/<name>.py``. A metric split by cell,
    ``<base>.<cells>``, is read by ``metrics/<base>.py`` unless it has a
    file of its own."""
    path = HERE / "metrics" / f"{name}.py"
    if not path.is_file():
        path = HERE / "metrics" / f"{name.split('.')[0]}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peaks(device_kind: str) -> dict:
    table = load_json(HERE / "peaks.json")
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"have {sorted(table)}")
    return table[device_kind]


# ---------------------------------------------------------------------------
# the device and its counters
# ---------------------------------------------------------------------------

def require_tpu(chips: int):
    """JAX's devices, or SystemExit when they are not ``chips`` TPUs or
    more: there is no fallback to another platform."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(f"chipbench: needs {chips} TPU chip(s), JAX found "
              f"{len(devs)} {devs[0].platform!r} device(s)", file=sys.stderr)
        raise SystemExit(1)
    return devs


class BuildCounter:
    """Programs built (compiled, or loaded from the persistent cache) and
    cache hits among them, from JAX's monitoring events; the listeners
    run on whichever thread builds."""

    def __init__(self):
        self.lock = threading.Lock()
        self.builds = 0
        self.cache_hits = 0

    def _on_duration(self, event: str, duration_secs: float, **kw) -> None:
        if event == BUILD_EVENT:
            with self.lock:
                self.builds += 1

    def _on_event(self, event: str, **kw) -> None:
        if event == CACHE_HIT_EVENT:
            with self.lock:
                self.cache_hits += 1

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)


# ---------------------------------------------------------------------------
# traffic
# ---------------------------------------------------------------------------

def _record(query: str, start: float, end: float, res) -> dict:
    return {"query": query, "start": start, "end": end,
            "failed": bool(res.failed), "result": res.result}


def drive(sess, traffic: dict, seconds: float | None) -> tuple[list, float]:
    """Run the traffic. ``seconds=None`` makes one pass of the stream
    (the warm-up); otherwise queries are sent until ``seconds`` have
    passed. Returns (records, start of the run on the host clock)."""
    order = traffic["order"]
    t_start = time.perf_counter()
    deadline = math.inf if seconds is None else t_start + seconds
    records = []
    i = 0
    while (i < len(order)) if seconds is None else \
            time.perf_counter() < deadline:
        q = order[i % len(order)]
        t0 = time.perf_counter()
        res = sess.submit(q)
        records.append(_record(q, t0, time.perf_counter(), res))
        i += 1
    return records, t_start


# ---------------------------------------------------------------------------
# the check against the reference
# ---------------------------------------------------------------------------

def check(records: list[dict], config: dict, seed: int) -> dict:
    """Compare every returned result with the reference's answer over
    the benchmark's own tables. Returns each number compared with its
    limit, and ``correct``."""
    tables = dbgen.generate(config["scale_factor"], seed)
    answers = {q: reference.answer(q, tables)
               for q in sorted({r["query"] for r in records})}
    bad, err = 0, 0.0
    for r in records:
        if r["failed"]:
            continue
        differs, e = compare(as_arrays(r["result"]),
                             answers[r["query"]])
        if differs:
            bad += 1
        else:
            err = max(err, e)
    limits = config["correct"]
    numbers = {
        "results_checked": {"value": sum(not r["failed"] for r in records),
                            "limit": 1, "at_least": True},
        "queries_failed": {"value": sum(r["failed"] for r in records),
                           "limit": 0},
        "wrong_results": {"value": bad, "limit": limits["wrong_results"]},
        "max_rel_err": {"value": err, "limit": limits["max_rel_err"]},
    }
    ok = all((n["value"] >= n["limit"]) if n.get("at_least")
             else (n["value"] <= n["limit"]) for n in numbers.values())
    return {"correct": ok, "numbers": numbers}


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Run:
    """What a metric's reader reads."""
    seconds: float
    setup_s: float
    window: list                      # records completed in the window
    attempted: int
    end: float = 0.0                  # the window's close, host clock
    in_flight: list = dataclasses.field(default_factory=list)
    traced: list = dataclasses.field(default_factory=list)  # every
    #                                   query of a traced run's trace
    probes: object = None             # probes.Probes of a traced run
    trace: dict | None = None         # trace.reduce() of a traced run
    spans: dict | None = None         # spans.reduce() of a traced run
    counters: dict | None = None      # the program's counters' totals over
    #                                   a traced run's window
    trace_window_s: float = 0.0
    peaks: dict | None = None


def program_tables(tables: dict) -> dict:
    """The generator's tables as the program's ``Table``s: strings
    dictionary-encoded, over the whole domain where the column has one."""
    from repro.relational.table import DictColumn, Table
    out = {}
    for name, cols in tables.items():
        conv = {}
        for col, v in cols.items():
            if v.dtype.kind == "S":
                dom = dbgen.DOMAINS.get(col)
                if dom is None:
                    uniq, codes = np.unique(v, return_inverse=True)
                    dom = uniq.tolist()
                else:
                    codes = np.searchsorted(np.asarray(dom), v)
                v = DictColumn(codes.astype(np.uint32), list(dom))
            conv[col] = v
        out[name] = Table(conv)
    return out


def build(config: dict, seed: int):
    """A ``Session`` over a fresh store that holds the seed's tables as
    row-sliced objects of about ``object_bytes``."""
    from repro.core.coordinator import Coordinator
    from repro.core.engine import load_base_tables
    from repro.core.session import Session
    from repro.objectstore.store import ObjectStore, StoreConfig
    tables = program_tables(dbgen.generate(config["scale_factor"], seed))
    store = ObjectStore(StoreConfig(seed=seed, time_scale=0.0,
                                    simulate_visibility_lag=False))
    splits = load_base_tables(store, tables, config["object_bytes"])
    del tables
    return Session.from_coordinator(
        Coordinator(store, splits, None, seed=seed, compute_scale=0))


def memory_peaks(chips) -> dict:
    """``memory_peak_bytes``, the peak of the fullest of the cell's chips,
    and ``memory_peak_bytes_by_chip``, each chip's in device order."""
    by_chip = [(d.memory_stats() or {}).get("peak_bytes_in_use")
               for d in chips]
    return {"memory_peak_bytes": max(
                (p for p in by_chip if p is not None), default=None),
            "memory_peak_bytes_by_chip": by_chip}


def run_cell(parts: dict, seed: int, seconds: float, trace: bool,
             t_process: float, devices, log=print) -> dict:
    """Set up, warm up, measure for ``seconds`` and check; returns the
    result line as a dict. ``devices`` are JAX's, already checked."""
    import jax
    config, traffic = parts["config"], parts["traffic"]
    dev = devices[0]
    chips = devices[:parts["cell"]["chips"]]
    sess = build(config, seed)
    with BuildCounter() as warm_builds:
        t0 = time.perf_counter()
        warm, _ = drive(sess, traffic, None)
        warm_s = time.perf_counter() - t0
    log(f"warm-up: {len(warm)} queries, {warm_builds.builds} programs "
        f"built ({warm_builds.cache_hits} from the persistent cache), "
        f"last pass {warm_s:.3f} s")

    probes = tdir = None
    counters = contextlib.nullcontext()
    if trace:
        from chipbench.probes import Probes
        from chipbench.spans import RowCounter
        probes = Probes().install()
        counters = RowCounter()
        tdir = tempfile.mkdtemp(prefix="chipbench-trace-")
    gc.collect()
    setup_s = time.perf_counter() - t_process
    with BuildCounter() as window_builds, counters:
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(tdir, profiler_options=opts)
        t_trace = time.perf_counter()
        records, t_start = drive(sess, traffic, seconds)
        t_end = t_start + seconds
        if trace:
            trace_window_s = time.perf_counter() - t_trace
            jax.profiler.stop_trace()
    if probes is not None:
        probes.uninstall()
    print(f"programs built in window: {window_builds.builds}", flush=True)
    memory = memory_peaks(chips)
    del sess
    gc.collect()

    window = completed_in(records, t_start, t_end)
    log("window: " + json.dumps(
        [[r["query"], round(r["start"] - t_start, 4),
          round(r["end"] - r["start"], 4)] for r in records]))
    run = Run(seconds=seconds, setup_s=setup_s, window=window,
              attempted=sum(r["start"] < t_end for r in records),
              end=t_end, in_flight=[r for r in records
                                    if r["start"] < t_end < r["end"]],
              traced=records if trace else [], probes=probes,
              counters=counters.totals if trace else None)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), **memory}
    line = {"correct": False, "attempted": run.attempted,
            "failed": sum(r["failed"] for r in records), "metrics": {},
            "device": device}
    if trace:
        from chipbench import spans as S
        from chipbench import trace as T
        run.peaks = peaks(dev.device_kind)
        xplane = T.find_xplane(tdir)
        t0 = time.perf_counter()
        run.trace = T.reduce(xplane)
        t1 = time.perf_counter()
        run.spans = S.reduce(xplane)
        log(f"trace reduced in {t1 - t0:.3f} s, by spans in "
            f"{time.perf_counter() - t1:.3f} s; busy s by chip "
            f"{run.trace['busy_by_chip_s']}")
        log("spans and counters: " + json.dumps(
            {"spans": run.spans, "counters": run.counters}))
        run.trace_window_s = trace_window_s
        shutil.rmtree(tdir, ignore_errors=True)
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = trace_window_s
        line["breakdown"] = {"device_ops": run.trace["device_ops"],
                             "idle_gaps": run.trace["idle_gaps"]}
    for m in parts["per_layer" if trace else "end_to_end"]:
        v = reader(m["name"])(run)
        if v is not None:
            line["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}

    verdict = check(warm + records, config, seed)
    line["correct"] = verdict["correct"]
    line["checks"] = verdict["numbers"]
    return line
