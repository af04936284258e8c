"""Task placement: the coordinator runs each task's device program on one
of its chips, ``devices[(tasks of the plan's earlier stages + task
index) mod len(devices)]``.

The rule is checked in process over stand-in chips (the tasks still run
on the CPU); the whole path over four virtual CPU devices in a child
process, against the benchmark's reference and a one-device run."""
import json
import os
import pathlib
import subprocess
import sys
from collections import Counter

import jax
import pytest

from repro.core import coordinator as C
from repro.core.coordinator import Coordinator
from repro.core.engine import load_base_tables
from repro.core.session import QuerySpec
from repro.faults.inject import FaultConfig
from repro.faults.retry import RetryPolicy
from repro.objectstore.store import ObjectStore, StoreConfig
from repro.relational.tpch import generate

ROOT = pathlib.Path(__file__).resolve().parents[1]


class _Chip:
    """A stand-in chip: placement only reads its ``id``."""

    def __init__(self, k: int):
        self.id = k


class _SpyWorker(C.Worker):
    """Records (query, stage, task, chip id) of every executed scan or join
    task, then runs it on JAX's default device."""
    calls: list = []

    def _record(self, query, st, task_id):
        self.calls.append((query, st["name"], task_id, self.device.id))
        self.device = None

    def run_scan(self, query, st, task_id, *a, **kw):
        self._record(query, st, task_id)
        return super().run_scan(query, st, task_id, *a, **kw)

    def run_join(self, query, st, task_id, *a, **kw):
        self._record(query, st, task_id)
        return super().run_join(query, st, task_id, *a, **kw)


def _placed(monkeypatch, n_chips: int, query: str, **coord_kw):
    """(calls, plan stages as run, result) of one query on ``n_chips``
    stand-in chips over lineitem cut into 5 objects."""
    calls = []
    monkeypatch.setattr(_SpyWorker, "calls", calls)
    monkeypatch.setattr(C, "Worker", _SpyWorker)
    store = ObjectStore(StoreConfig(seed=0, time_scale=0.0,
                                    simulate_visibility_lag=False))
    tables = generate(0.002, seed=4)
    splits = load_base_tables(store, tables, 64 << 10)
    assert len(splits["lineitem"]) >= 5
    coord = Coordinator(store, splits, seed=1, compute_scale=0,
                        devices=[_Chip(k) for k in range(n_chips)],
                        **coord_kw)
    plan = QuerySpec.coerce(query).build_plan()
    res = coord.run_query(plan)
    stages = [(st["name"], coord._ntasks(plan, st))
              for st in coord._expand_plan(plan, plan["name"])["stages"]]
    return calls, stages, res


def _expected(stages, n_chips):
    want, first = {}, 0
    for name, n in stages:
        for ti in range(n):
            want[(name, ti)] = (first + ti) % n_chips
        first += n
    return want


@pytest.mark.parametrize("n_chips", [1, 3, 4])
@pytest.mark.parametrize("query", ["q12", "q5"])
def test_tasks_follow_the_rule_and_spread_evenly(monkeypatch, query,
                                                 n_chips):
    calls, stages, res = _placed(monkeypatch, n_chips, query)
    assert not res.failed
    want = _expected(stages, n_chips)
    assert calls and all(want[(s, ti)] == k for _, s, ti, k in calls)
    by_stage = {}
    for _, s, _, k in calls:
        by_stage.setdefault(s, Counter())[k] += 1
    for s, n in stages:
        if s not in by_stage:
            continue            # the final merge runs on the host
        load = [by_stage[s][k] for k in range(n_chips)]
        assert sum(load) == n and max(load) - min(load) <= 1, (s, load)
    # a query's stages continue round the chips: not all start on chip 0
    if n_chips > 1:
        firsts = {min((ti, k) for _, s2, ti, k in calls if s2 == s)[1]
                  for s in by_stage}
        assert len(firsts) > 1


def test_placement_is_deterministic(monkeypatch):
    a, _, ra = _placed(monkeypatch, 4, "q3")
    b, _, rb = _placed(monkeypatch, 4, "q3")
    assert sorted(a) == sorted(b)
    assert ra.latency_s == rb.latency_s


def test_a_retried_task_keeps_its_chip(monkeypatch):
    calls, stages, res = _placed(
        monkeypatch, 4, "q12",
        faults=FaultConfig(invoke_fail_rate=0.3),
        retry=RetryPolicy(max_attempts=10))
    assert not res.failed and res.retries > 0
    want = _expected(stages, 4)
    assert all(want[(s, ti)] == k for _, s, ti, k in calls)


def test_the_default_is_jax_local_devices():
    store = ObjectStore(StoreConfig(seed=0, time_scale=0.0,
                                    simulate_visibility_lag=False))
    assert Coordinator(store, {}).devices == jax.local_devices()


FOUR_DEVICES = r"""
import json, sys
import jax
from chipbench import dbgen, harness, reference
from chipbench.compare import as_arrays, compare
from chipbench.spans import RowCounter
from repro.core.coordinator import Coordinator
from repro.core.engine import load_base_tables
from repro.core.session import Session
from repro.objectstore.store import ObjectStore, StoreConfig
from repro.relational.table import serialize_table

SF, OBJECT_BYTES, SEED = 0.004, 512 << 10, 2**31 + 11
QUERIES = ["q14", "q6", "q3", "q1", "q5", "q12"]
raw = dbgen.generate(SF, SEED)
tables = harness.program_tables(raw)
out = {"devices": len(jax.devices())}
results = {}
for label, devices in (("four", jax.devices()), ("one", jax.devices()[:1])):
    store = ObjectStore(StoreConfig(seed=SEED, time_scale=0.0,
                                    simulate_visibility_lag=False))
    splits = load_base_tables(store, tables, OBJECT_BYTES)
    sess = Session.from_coordinator(Coordinator(
        store, splits, None, seed=SEED, compute_scale=0, devices=devices))
    with RowCounter() as counter:
        res = {q: sess.submit(q) for q in QUERIES}
    out[label] = {"counters": counter.totals,
                  "lineitem_objects": len(splits["lineitem"]),
                  "failed": sum(r.failed for r in res.values())}
    results[label] = res
worst, wrong = 0.0, 0
for q in QUERIES:
    differs, err = compare(as_arrays(results["four"][q].result),
                           reference.answer(q, raw))
    wrong += bool(differs)
    worst = max(worst, err)
out["wrong_results"], out["max_rel_err"] = wrong, worst
out["same_as_one_device"] = all(
    serialize_table(results["four"][q].result)
    == serialize_table(results["one"][q].result) for q in QUERIES)
print(json.dumps(out))
"""


def test_six_queries_over_four_devices_match_the_reference():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(ROOT / "src"), str(ROOT)]))
    proc = subprocess.run([sys.executable, "-c", FOUR_DEVICES], cwd=ROOT,
                          env=env, capture_output=True, text=True,
                          timeout=240)
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["devices"] == 4
    assert out["wrong_results"] == 0 and out["max_rel_err"] <= 1e-8
    assert out["same_as_one_device"]
    four, one = out["four"], out["one"]
    assert four["failed"] == one["failed"] == 0
    assert four["lineitem_objects"] >= 4
    chips = {k: v for k, v in four["counters"].items()
             if k.startswith("rows_padded_chip")}
    assert sorted(chips) == [f"rows_padded_chip{k}" for k in range(4)]
    assert all(v > 0 for v in chips.values())
    assert sum(chips.values()) == four["counters"]["rows_padded"]
    # one device: every padded row on chip 0, the same total
    assert one["counters"]["rows_padded_chip0"] == \
        one["counters"]["rows_padded"] == four["counters"]["rows_padded"]
