"""End-to-end glue: load base tables into the store, run queries, oracle.

``oracle`` executes the same logical query single-threaded over the full
tables with the numpy reference operators of ``relational.ops`` — no
store, no shuffle, no partitioning, no device — giving an independent
reference for the distributed engine's results, whose workers run the
device path (tests/test_query_engine.py).
"""
from __future__ import annotations

import dataclasses

import numpy as np

from repro.core.coordinator import Coordinator, QueryResult
from repro.core.stragglers import StragglerConfig
from repro.objectstore.store import ObjectStore, StoreConfig
from repro.relational import ops as OPS
from repro.relational.table import Table, serialize_table, table_to_object
from repro.relational.tpch import QUERIES, generate


def load_base_tables(store: ObjectStore, tables: dict[str, Table],
                     target_bytes: int = 4 << 20) -> dict[str, list[str]]:
    """Write each table as row-sliced COLUMNAR objects (~target_bytes):
    single-partition §3.2 partitioned objects whose headers carry
    per-column offsets + zone maps, so scans can project and prune.

    The paper stores base tables as ORC objects of a few hundred MB; scaled
    down here with the dataset scale.
    """
    splits: dict[str, list[str]] = {}
    for name, t in tables.items():
        n = len(t)
        total = len(serialize_table(t)) if n else 1
        nsplit = max(1, int(round(total / target_bytes)))
        rows = max(1, n // nsplit)
        ks = []
        for i in range(0, max(n, 1), rows):
            idx = np.arange(i, min(i + rows, n))
            key = f"base/{name}/p{len(ks)}"
            store.put(key, table_to_object(t.take(idx)))
            ks.append(key)
        splits[name] = ks
    return splits


def make_engine(sf: float = 0.002, *, seed: int = 0,
                data_seed: int | None = None,
                policy: StragglerConfig | None = None,
                max_parallel: int = 1000, target_bytes: int = 1 << 20,
                compute_scale: float = 1.0,
                executor_workers: int | None = None,
                record_events: bool = False, max_events: int | None = None,
                faults=None, coldstart=None, retry=None, journal=None):
    """(coordinator, tables) over a fresh simulated store.

    ``compute_scale=0`` makes virtual latency independent of measured
    compute (fully deterministic); ``executor_workers`` sizes the
    coordinator's thread pool for real task execution. ``seed`` drives the
    *simulation* randomness (store latencies, stragglers, arrivals);
    ``data_seed`` (default: ``seed``) drives the generated dataset — pass a
    fixed ``data_seed`` to vary timing randomness over one dataset, e.g.
    sweeping contention without also regenerating the data (Fig 13).
    ``record_events=True`` keeps the coordinator's request-level event log
    (GET/PUT issue/done, DUP_FIRE, VISIBLE_AT, BACKUP_FIRE) in
    ``coord.event_log`` for the straggler benchmarks and tests;
    ``max_events`` caps that list (drops counted in
    ``coord.dropped_events`` — see repro.obs for the streaming
    alternative that needs no cap).
    ``faults``/``coldstart``/``retry``/``journal`` configure the §3 fault
    path (repro.faults); all default off, in which case the engine is
    bit-identical to the fault-free one.
    """
    tables = generate(sf, seed=seed if data_seed is None else data_seed)
    store = ObjectStore(StoreConfig(seed=seed, time_scale=0.0,
                                    simulate_visibility_lag=False))
    splits = load_base_tables(store, tables, target_bytes)
    coord = Coordinator(store, splits, policy, seed=seed,
                        max_parallel=max_parallel,
                        compute_scale=compute_scale,
                        executor_workers=executor_workers,
                        record_events=record_events, max_events=max_events,
                        faults=faults, coldstart=coldstart, retry=retry,
                        journal=journal)
    return coord, tables


def build_plan(name: str, tuning=None, **plan_kw) -> dict:
    """One physical plan with tuning applied. ``tuning`` takes any form
    ``planner.model.coerce_config`` accepts — a plain per-stage ntasks
    dict, a planner ``PlanConfig``, the two-part ``{"ntasks", "plan_kw"}``
    dict, or None — all normalized through the one canonical
    ``PlanConfig.plan_kwargs`` path (core.session.QuerySpec uses the
    same path, so every entry point builds identical plans)."""
    from repro.core.session import QuerySpec
    return QuerySpec(name, tuning, plan_kw or None).build_plan()


def run_query(coord: Coordinator, name: str, ntasks=None, **plan_kw
              ) -> QueryResult:
    """Deprecated shim — use ``core.session.Session.submit``. Kept for
    callers holding a bare coordinator; bit-identical to the Session
    path (tests/test_session.py)."""
    from repro.core.session import QuerySpec, Session
    return Session.from_coordinator(coord).submit(
        QuerySpec(name, ntasks, plan_kw or None))


def run_queries(coord: Coordinator, specs, arrival_times=None, after=None
                ) -> list[QueryResult]:
    """Deprecated shim — use ``core.session.Session.run``. ``specs``
    entries are a query name or ``(name, tuning)`` / ``(name, tuning,
    plan_kw)``; arrival times and closed-loop ``after`` edges ride on the
    coerced QuerySpecs."""
    from repro.core.session import QuerySpec, Session
    qs = [QuerySpec.coerce(s) for s in specs]
    if arrival_times is not None:
        if len(arrival_times) != len(qs):
            raise ValueError(f"{len(qs)} specs but {len(arrival_times)} "
                             "arrival times")
        qs = [dataclasses.replace(q, arrival_s=a)
              for q, a in zip(qs, arrival_times)]
    if after is not None:
        if len(after) != len(qs):
            raise ValueError(f"{len(qs)} specs but {len(after)} after "
                             "entries")
        qs = [dataclasses.replace(q, after=dep)
              for q, dep in zip(qs, after)]
    return Session.from_coordinator(coord).run(qs)


# ---------------------------------------------------------------------------
# single-threaded oracle (independent execution path)
# ---------------------------------------------------------------------------

def oracle(name: str, tables: dict[str, Table]) -> Table:
    plan = QUERIES[name]()
    produced: dict[str, Table] = {}

    def small(tname):
        return tables[tname]

    for st in plan["stages"]:
        if st["kind"] == "scan":
            t = tables[st["table"]].project(st["columns"]) \
                if st.get("columns") else tables[st["table"]]
            t = OPS.apply_ops(t, st.get("ops", []), small)
        elif st["kind"] == "join":
            left = produced[st["left"]]
            right = produced[st["right"]]
            t = OPS.op_join(left, right, st["lkey"], st["rkey"])
            t = OPS.apply_ops(t, st.get("ops", []), small)
        elif st["kind"] == "final_agg":
            t = OPS.merge_partials([produced[st["deps"][0]]],
                                   st.get("keys", []),
                                   [tuple(a) for a in st.get("aggs", [])])
            if st.get("sort"):
                t = OPS.op_sort_limit(t, [tuple(s) for s in st["sort"]],
                                      st.get("limit"))
        else:
            raise ValueError(st["kind"])
        produced[st["name"]] = t
    return produced[plan["stages"][-1]["name"]]
