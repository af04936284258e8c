"""Fault & cold-start subsystem (ISSUE 7, paper §3): injected failures,
idempotent retries, warm-pool cold starts, journaled coordinator failover,
and their planner pricing.

The §3.2 immutability property test replays worker tasks against the same
immutable store (``ObjectStore.verify_replay`` asserts byte-identity) and
checks zero double-billing: the same query on the same data always bills
the identical ``QueryCost``, at executor widths {1, 8}.
"""
from __future__ import annotations

import numpy as np
import pytest
from hypothesis import example, given, settings, strategies as st

from repro.core.coordinator import Coordinator
from repro.core.stragglers import StragglerConfig
from repro.faults import (ColdStartConfig, CoordinatorKilled, FaultConfig,
                          Journal, JournalDivergence, RetryPolicy,
                          run_with_failover)
from repro.objectstore.store import ObjectStore, StoreConfig
from repro.planner.calibrate import calibrate
from repro.planner.model import PlanConfig, QueryModel
from repro.planner.search import SCALAR_AXES, QueryEvaluator
from repro.relational.table import Table, serialize_table

N = 8                       # tasks in the micro plan
FLOOR = 1 << 20             # billed output size per task


def _micro_store(seed: int = 0):
    store = ObjectStore(StoreConfig(seed=seed, time_scale=0.0,
                                    simulate_visibility_lag=False))
    split = serialize_table(
        Table({"x": np.arange(4000, dtype=np.float64)}))
    store.put("base/micro/p0", split)
    return store, {"micro": ["base/micro/p0"]}


def _plan(n: int = N) -> dict:
    return {"name": "micro_f",
            "stages": [{"name": "scan", "kind": "scan", "table": "micro",
                        "tasks": n, "deps": [], "out_bytes_floor": FLOOR}]}


def _coord(store, splits, *, seed=0, width=8, n=N, max_parallel=None,
           faults=None, coldstart=None, retry=None, journal=None,
           policy=None):
    return Coordinator(store, splits, policy or StragglerConfig(),
                       seed=seed,
                       max_parallel=max_parallel or n, compute_scale=0.0,
                       executor_workers=width, record_events=True,
                       faults=faults, coldstart=coldstart, retry=retry,
                       journal=journal)


def _run(*, seed=0, width=8, n=N, max_parallel=None, faults=None,
         coldstart=None, retry=None, store=None, splits=None, policy=None):
    if store is None:
        store, splits = _micro_store(seed)
    coord = _coord(store, splits, seed=seed, width=width, n=n,
                   max_parallel=max_parallel, faults=faults,
                   coldstart=coldstart, retry=retry, policy=policy)
    res = coord.run_query(_plan(n))
    return coord, res


def _log(coord):
    """Canonical event log: same-virtual-time entries are appended in
    real-thread completion order, so compare as a sorted multiset."""
    return sorted(repr(e) for e in coord.event_log)


def _sig(coord, res):
    """Bit-comparable run signature, including the full event log."""
    return (res.latency_s, res.cost.lambda_gb_s, res.cost.invocations,
            res.cost.gets, res.cost.puts, res.failed, res.retries,
            res.cold_starts, res.attribution, _log(coord))


MODERATE = FaultConfig(invoke_fail_rate=0.15, worker_loss_rate=0.1,
                       get_fail_rate=0.05, put_fail_rate=0.05)
BIG_BUDGET = RetryPolicy(max_attempts=8)


# --------------------------------------------------------- strict superset
def test_zero_rates_bit_identical_to_fault_free_engine():
    """All-zero rates + disabled cold starts must take the exact fault-free
    code path: same virtual times, costs, attribution, and event log."""
    c_plain, r_plain = _run()
    c_zero, r_zero = _run(faults=FaultConfig(),
                          coldstart=ColdStartConfig(enabled=False),
                          retry=RetryPolicy())
    assert _sig(c_plain, r_plain) == _sig(c_zero, r_zero)
    assert r_zero.retries == 0 and r_zero.cold_starts == 0
    assert not r_zero.failed


def test_width_parity_under_faults():
    """Injected failures, retries and cold starts are keyed on indices, so
    the whole run is bit-identical across executor widths {1, 8}."""
    cold = ColdStartConfig(keepalive_s=300.0)
    c8, r8 = _run(width=8, faults=MODERATE, coldstart=cold,
                  retry=BIG_BUDGET)
    c1, r1 = _run(width=1, faults=MODERATE, coldstart=cold,
                  retry=BIG_BUDGET)
    assert _sig(c8, r8) == _sig(c1, r1)
    assert r8.retries > 0          # the fault path actually exercised


# ------------------------------------------------------------ fault paths
def test_certain_invoke_failure_fails_the_query():
    _, res = _run(faults=FaultConfig(invoke_fail_rate=1.0))
    assert res.failed and res.fail_reason == "invoke"
    assert res.result is None


def test_moderate_faults_retry_to_success():
    coord, res = _run(faults=MODERATE, retry=BIG_BUDGET)
    assert not res.failed
    assert res.retries > 0
    kinds = {e[1] for e in coord.event_log}
    assert "INVOKE_FAIL" in kinds and "RETRY_FIRE" in kinds
    # failures make the query strictly slower and more expensive
    _, clean = _run()
    assert res.latency_s > clean.latency_s
    assert res.cost.total > clean.cost.total


def test_worker_loss_replays_without_double_billing():
    """A lost worker re-runs as a *virtual replay* (the real execution ran
    exactly once); every attempt is billed exactly once — invocations equal
    first dispatches plus task-level retries."""
    faults = FaultConfig(worker_loss_rate=0.3)
    no_backups = StragglerConfig(backup_tasks=False)
    coord, res = _run(faults=faults, retry=BIG_BUDGET, policy=no_backups)
    assert not res.failed
    summary = coord.event_summary()
    losses = summary["task_retries"]
    assert summary["worker_losses"] > 0 and losses > 0
    # every attempt bills exactly one invoke: first dispatches + task-level
    # retries, nothing else (backups disabled for exact arithmetic)
    assert res.cost.invocations == N + losses
    # each replayed attempt re-bills its own requests (the provider
    # charges for the re-run) — never the surviving attempt's twice
    _, clean = _run(policy=no_backups)
    assert res.cost.gets == clean.cost.gets + losses * clean.cost.gets // N
    # puts per task are not uniform (result/meta objects ride on some
    # tasks), so bound the re-billing: each of the ``losses`` replays
    # bills its own task's puts again — at least 1, at most the whole
    # clean bill minus everyone else's minimum
    extra_puts = res.cost.puts - clean.cost.puts
    assert losses <= extra_puts <= losses * (clean.cost.puts - (N - 1))


def test_request_level_get_failures_retry_in_place():
    faults = FaultConfig(get_fail_rate=0.3)
    no_backups = StragglerConfig(backup_tasks=False)
    coord, res = _run(faults=faults, retry=RetryPolicy(max_attempts=8),
                      policy=no_backups)
    assert not res.failed
    summary = coord.event_summary()
    assert summary["get_fails"] > 0
    assert summary["retry_reasons"].get("get", 0) > 0
    # a request-level retry bills one extra GET per extra try
    _, clean = _run(policy=no_backups)
    assert res.cost.gets == clean.cost.gets + summary["retry_reasons"]["get"]
    # per-attempt try counts surface for calibration
    assert summary["request_tries"].get(1, 0) > 0


def test_event_summary_reports_per_attempt_counts():
    coord, _ = _run(faults=MODERATE, retry=BIG_BUDGET)
    summary = coord.event_summary()
    assert summary["retries"] == sum(summary["retry_reasons"].values())
    assert set(summary["request_tries"]) >= {0}
    assert summary["query_fails"] == 0
    prof = summary["stages"][("micro_f", "scan")]
    assert prof["retries"] + prof["invoke_fails"] > 0


# ------------------------------------------------------------- cold starts
def test_cold_start_waves_and_warm_reuse():
    """Burst arrivals: the first wave of claims is cold (virgin slots), a
    prompt second query reuses warm slots, and a long-idle one pays a fresh
    cold wave (keep-alive expiry)."""
    store, splits = _micro_store()
    cold = ColdStartConfig(keepalive_s=300.0)
    coord = _coord(store, splits, n=4, max_parallel=4, coldstart=cold)
    r_a, r_b = coord.run_queries([_plan(4), _plan(4)],
                                 arrival_times=[0.0, 30.0])
    assert r_a.cold_starts == 4            # every virgin slot is cold
    assert r_b.cold_starts == 0            # 30s idle < 300s keep-alive
    assert r_a.attribution["cold_s"] > 0
    assert "cold_s" not in r_b.attribution

    coord2 = _coord(store, splits, n=4, max_parallel=4,
                    coldstart=ColdStartConfig(keepalive_s=10.0))
    r_c, r_d = coord2.run_queries([_plan(4), _plan(4)],
                                  arrival_times=[0.0, 40.0])
    assert r_c.cold_starts == 4
    assert r_d.cold_starts == 4            # 40s idle > 10s keep-alive
    assert r_a.latency_s > 0 and r_a.latency_s != r_b.latency_s


def test_cold_starts_disabled_is_the_default():
    _, res = _run(coldstart=None)
    assert res.cold_starts == 0
    assert "cold_s" not in res.attribution


# ---------------------------------------------------------------- failover
def test_journal_failover_resumes_bit_identically():
    """Kill the coordinator mid-query; the failover replay must end with
    the same final event log and QueryCost as an uninterrupted run."""
    store, splits = _micro_store()
    ref_coord = _coord(store, splits, faults=MODERATE, retry=BIG_BUDGET)
    ref_journal = Journal(checkpoint_every=16)
    ref_coord.journal = ref_journal
    ref = ref_coord.run_query(_plan())
    total_pops = ref_journal.count
    assert total_pops > 40

    coords = []

    def mk(journal):
        c = _coord(store, splits, faults=MODERATE, retry=BIG_BUDGET,
                   journal=journal)
        coords.append(c)
        return c

    res, journal = run_with_failover(mk, _plan(),
                                     kill_after=total_pops // 2,
                                     checkpoint_every=16)
    assert journal.replaying
    assert journal.count == total_pops           # same event sequence
    assert journal.crc == ref_journal.crc
    assert res.cost == ref.cost
    assert res.latency_s == ref.latency_s
    assert res.retries == ref.retries
    assert _log(coords[-1]) == _log(ref_coord)


def test_journal_divergence_is_detected():
    """Failing over onto a different seed walks a different event sequence
    — the journal must refuse, not silently produce a different answer."""
    store, splits = _micro_store()
    journal = Journal(checkpoint_every=8)
    c1 = _coord(store, splits, seed=0, journal=journal)
    journal.arm_kill(40)
    with pytest.raises(CoordinatorKilled):
        c1.run_query(_plan())
    journal.resume()
    c2 = _coord(store, splits, seed=1, journal=journal)
    with pytest.raises(JournalDivergence):
        c2.run_query(_plan())


def test_failover_kill_after_must_be_reached():
    store, splits = _micro_store()
    with pytest.raises(ValueError):
        run_with_failover(lambda j: _coord(store, splits, journal=j),
                          _plan(), kill_after=10 ** 9)


# ------------------------------------------------- §3.2 replay properties
@settings(max_examples=6, deadline=None)
@given(seed=st.integers(min_value=0, max_value=10 ** 6),
       loss=st.sampled_from([0.0, 0.2, 0.4]))
# a PUT's DONE event from a lost attempt pops while the replay has
# issued fewer requests
@example(seed=159568, loss=0.2)
def test_replay_is_byte_identical_and_bills_once(seed, loss):
    """§3.2 immutability: re-running any task against the immutable store
    overwrites every output with identical bytes, and the same query bills
    the identical QueryCost — at widths 1 and 8."""
    faults = FaultConfig(worker_loss_rate=loss) if loss else None
    store, splits = _micro_store(seed)
    _, first = _run(store=store, splits=splits, seed=seed, width=8,
                    faults=faults, retry=BIG_BUDGET)
    store.verify_replay = True
    try:
        _, again = _run(store=store, splits=splits, seed=seed, width=1,
                        faults=faults, retry=BIG_BUDGET)
    finally:
        store.verify_replay = False
    assert again.cost == first.cost
    assert again.latency_s == first.latency_s


# -------------------------------------------------------- planner pricing
# hot enough that every fault type fires at least once across 8 tasks
PROBE_FAULTS = FaultConfig(invoke_fail_rate=0.3, worker_loss_rate=0.25,
                           get_fail_rate=0.15, put_fail_rate=0.15)


def _faulted_probe():
    """Coordinator wired for faults + cold starts; the caller runs the
    probe query (so the fits come from the run named ``micro_f``)."""
    store, splits = _micro_store()
    return _coord(store, splits, faults=PROBE_FAULTS,
                  coldstart=ColdStartConfig(keepalive_s=300.0),
                  retry=RetryPolicy(max_attempts=10))


def test_calibrate_fits_fault_rates_from_probe():
    coord = _faulted_probe()
    res = coord.run_query(_plan())
    assert not res.failed
    calib = calibrate(coord.event_summary())
    assert calib.invoke_fail_rate > 0
    assert calib.worker_loss_rate > 0
    assert calib.get_fail_rate > 0 or calib.put_fail_rate > 0
    assert calib.cold_rate > 0 and calib.cold_overhead_s > 0
    # a fault-free probe fits all-zero rates (model terms vanish)
    clean_coord, _ = _run()
    clean = calibrate(clean_coord.event_summary())
    assert clean.invoke_fail_rate == 0 and clean.worker_loss_rate == 0
    assert clean.cold_rate == 0


def test_model_prices_retry_budget_axis():
    coord = _faulted_probe()

    def builder(ntasks=None, **kw):
        return _plan()

    model, _ = QueryModel.from_probe(coord, builder)
    tiny = model.predict(PlanConfig.make(retry_budget=1))
    roomy = model.predict(PlanConfig.make(retry_budget=4))
    # budget 1 pays the whole-query expected-rerun multiplier: worse on
    # both axes than a budget that absorbs failures in place
    assert tiny.latency_s > roomy.latency_s
    assert tiny.cost.total > roomy.cost.total
    assert "retry_budget" in SCALAR_AXES


def test_evaluator_refuses_failed_configs():
    store, splits = _micro_store()
    ev = QueryEvaluator(store, splits, lambda ntasks=None, **kw: _plan(),
                        seed=0, max_parallel=N,
                        faults=FaultConfig(invoke_fail_rate=1.0))
    lat, cost = ev(PlanConfig.make(retry_budget=2))
    assert lat == float("inf") and cost == float("inf")
    res = ev.result(PlanConfig.make(retry_budget=2))
    assert res.failed
