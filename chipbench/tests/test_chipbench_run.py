"""The harness's phases on the CPU at a tiny scale: results equal the
reference's, the entry point refuses the CPU, the control and the
planted faults come out as not correct."""
import json
import pathlib
import shutil
import subprocess
import sys
import time

import jax
import numpy as np
import pytest

from chipbench import control, dbgen, harness, reference
from chipbench.compare import as_arrays, compare

ROOT = pathlib.Path(__file__).resolve().parents[2]
SF = 0.004


def _parts(config, traffic, object_bytes=64 << 20):
    """The cell of ``config`` and ``traffic`` at a tiny scale, with its
    metrics from BENCHMARK.json."""
    parts = harness.resolve(f"{config}.{traffic}", ROOT)
    parts["config"] = dict(parts["config"], scale_factor=SF,
                           object_bytes=object_bytes)
    return parts


def _run(parts, seconds=0.3, seed=2**31 + 5):
    return harness.run_cell(parts, seed, seconds, False,
                            time.perf_counter(), jax.devices(),
                            log=lambda s: None)


@pytest.mark.parametrize("config,traffic,object_bytes", [
    ("tpch-sf0.1-obj64m", "power", 64 << 20),
    ("tpch-sf0.1-obj64m", "power", 256 << 10),
    ("tpch-sf0.1-obj64m", "scan", 64 << 20)])
def test_phases_match_the_reference(config, traffic, object_bytes):
    line = _run(_parts(config, traffic, object_bytes))
    checks = line["checks"]
    assert line["correct"], checks
    assert checks["wrong_results"]["value"] == 0
    assert checks["results_checked"]["value"] >= 6
    assert line["failed"] == 0 and line["attempted"] >= 1
    suffix = "" if traffic == "power" else "." + traffic
    assert set(line["metrics"]) == {
        "query_geomean_s", "query_p90_s" + suffix, "queries_per_s" + suffix,
        "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())


def test_reference_agrees_with_the_programs_generator_and_oracle():
    """The program's own oracle, over the benchmark's tables as the
    program holds them, gives the reference's answers."""
    from repro.core.engine import oracle
    ours = dbgen.generate(SF, 11)
    theirs = harness.program_tables(ours)
    for q in reference.ANSWERS:
        differs, err = compare(as_arrays(oracle(q, theirs)),
                               reference.answer(q, ours))
        assert not differs and err < 1e-12, q


def test_float32_control_fails_the_limit():
    parts = harness.resolve("tpch-sf0.1-obj64m.power", ROOT)
    config = dict(parts["config"], scale_factor=0.02)
    for seed in (1, 2, 3):
        recs = control.control_records(config, seed, reference.ANSWERS)
        verdict = harness.check(recs, config, seed)
        assert not verdict["correct"]
        assert verdict["numbers"]["max_rel_err"]["value"] > \
            config["correct"]["max_rel_err"]


def _drop_half(run):
    """Half of each task's rows left out."""
    def broken(t, ops, builds, partition=None):
        return run(t.take(np.arange(len(t) // 2)), ops, builds, partition)
    return broken


def _alter_value(run):
    """One float of each task's output altered where it is produced."""
    def broken(t, ops, builds, partition=None):
        out = run(t, ops, builds, partition)
        for table in (out if isinstance(out, list) else [out]):
            for name, col in table.cols.items():
                if isinstance(col, np.ndarray) and col.dtype.kind == "f" \
                        and len(col):
                    col = col.copy()
                    col[0] = col[0] * (1 + 1e-6) + 1e-3
                    table.cols[name] = col
                    return out
        return out
    return broken


def _unchanged(run):
    """The operators return their input unchanged."""
    def broken(t, ops, builds, partition=None):
        if partition is None:
            return t
        return [t] + [t.take(np.arange(0))] * (partition[1] - 1)
    return broken


@pytest.mark.parametrize("fault", [_drop_half, _alter_value, _unchanged])
def test_planted_faults_are_not_correct(fault, monkeypatch):
    from repro.relational import device_ops
    monkeypatch.setattr(device_ops, "run", fault(device_ops.run))
    try:
        line = _run(_parts("tpch-sf0.1-obj64m", "scan"))
    except (KeyError, ValueError, IndexError):
        return          # the run dies and prints no result: not correct
    assert not line["correct"], line["checks"]


def _entry(cwd, *extra):
    env = {"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu",
           "HOME": str(cwd)}
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "tpch-sf0.1-obj64m.power", "--seed", "1", "--seconds", "1",
         *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(proc):
    return not any(ln.startswith("{") for ln in proc.stdout.splitlines())


def test_entry_point_refuses_the_cpu(tmp_path):
    work = tmp_path / "checkout"
    shutil.copytree(ROOT / "chipbench", work / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(ROOT / "BENCHMARK.json", work)
    # without the program beside it the benchmark refuses at once
    proc = _entry(work)
    assert proc.returncode != 0 and _no_result(proc)
    assert "src/repro" in proc.stderr
    shutil.copytree(ROOT / "src" / "repro", work / "src" / "repro",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _entry(work)
    assert proc.returncode != 0 and _no_result(proc)
    assert "needs 1 TPU" in proc.stderr


def test_result_line_is_json_with_the_checks_last(monkeypatch, capsys):
    from chipbench import run as entry
    monkeypatch.setattr(harness, "require_tpu", lambda chips: jax.devices())
    parts = _parts("tpch-sf0.1-obj64m", "scan")
    monkeypatch.setattr(harness, "resolve", lambda w, root: parts)
    monkeypatch.setattr(entry.os, "environ", dict(entry.os.environ))
    min_s = jax.config.jax_persistent_cache_min_compile_time_secs
    try:
        assert entry.main(["--workload", "x", "--seed", "3", "--seconds",
                           "0.2"]) == 0
    finally:
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          min_s)
    out, err = capsys.readouterr()
    line = json.loads(out.strip().splitlines()[-1])
    assert list(line)[-1] == "checks"
    assert {"correct", "attempted", "failed", "metrics", "device"} <= \
        set(line)
    assert err.strip().splitlines()[-1].startswith("check ")
