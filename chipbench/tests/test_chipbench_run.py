"""The harness's phases on the CPU at a tiny scale: results equal the
reference's, the entry point refuses the CPU, the control and the
planted faults come out as not correct."""
import gzip
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
SPANS_TRACE = pathlib.Path(__file__).resolve().parent / "data" / \
    "spans.xplane.pb.gz"


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


ANSWER_FILE = """import numpy as np


def answer(tables, dtype):
    li = tables["lineitem"]
    return {"lines": np.asarray([len(li["l_quantity"])]),
            "sum_qty": np.asarray([li["l_quantity"].astype(dtype).sum()])}
"""


def test_a_seventh_query_is_answered_from_its_file(tmp_path, monkeypatch):
    (tmp_path / "q99.py").write_text(ANSWER_FILE)
    monkeypatch.setattr(reference, "ANSWERS_DIR", tmp_path)
    config = _parts("tpch-sf0.1-obj64m", "power")["config"]
    seed = 2**31 + 7
    want = reference.answer("q99", dbgen.generate(SF, seed))
    assert want["lines"][0] > 0 and want["sum_qty"][0] > 0
    wrong = dict(want, sum_qty=want["sum_qty"] * (1 + 1e-6))

    def record(cols):
        return {"query": "q99", "start": 0.0, "end": 0.0, "failed": False,
                "result": control._Answer(cols)}
    assert harness.check([record(want)], config, seed)["correct"]
    verdict = harness.check([record(wrong)], config, seed)
    assert not verdict["correct"]
    assert verdict["numbers"]["max_rel_err"]["value"] > 1e-8
    with pytest.raises(KeyError, match="q98"):
        reference.answerer("q98")
    assert reference.answerer("q6") is reference.ANSWERS["q6"]


def test_float32_control_fails_the_limit():
    parts = harness.resolve("tpch-sf0.1-obj64m.power", ROOT)
    config = dict(parts["config"], scale_factor=0.02)
    for seed in (1, 2, 3):
        recs = control.control_records(config, seed, reference.ANSWERS)
        verdict = harness.check(recs, config, seed)
        assert not verdict["correct"]
        assert verdict["numbers"]["max_rel_err"]["value"] > \
            config["correct"]["max_rel_err"]


def test_traced_run_hands_spans_and_counters_to_the_readers(
        tmp_path, monkeypatch):
    """A ``--trace 1`` run on the CPU, with the recorded chip trace in
    place of the CPU's: every per-layer metric of the cell is read, the
    program's counters among them from this run's own tasks."""
    from chipbench import trace
    recorded = tmp_path / "chip.xplane.pb"
    recorded.write_bytes(gzip.decompress(SPANS_TRACE.read_bytes()))
    monkeypatch.setattr(trace, "find_xplane", lambda d: str(recorded))
    v5e = harness.peaks("TPU v5 lite")
    monkeypatch.setattr(harness, "peaks", lambda kind: v5e)
    parts = _parts("tpch-sf0.1-obj64m", "scan")
    line = harness.run_cell(parts, 2**31 + 9, 0.3, True,
                            time.perf_counter(), jax.devices(),
                            log=lambda s: None)
    assert line["correct"], line["checks"]
    assert set(line["metrics"]) == {m["name"] for m in parts["per_layer"]}
    assert 50 <= line["metrics"]["op_fill"]["value"] <= 100
    device = line["device"]
    assert len(device["memory_peak_bytes_by_chip"]) == 1
    assert device["busy_s"] > 0 and device["window_s"] > 0
    assert line["breakdown"]["device_ops"]


class _Chip:
    def __init__(self, peak):
        self.peak = peak

    def memory_stats(self):
        return None if self.peak is None else {"peak_bytes_in_use": self.peak}


@pytest.mark.parametrize("peaks, fullest", [
    ([122355200], 122355200),
    ([300, 900, 100, 500], 900),
    ([None, 700, None, 200], 700),
    ([None], None),
])
def test_memory_peak_is_the_fullest_chips(peaks, fullest):
    got = harness.memory_peaks([_Chip(p) for p in peaks])
    assert got == {"memory_peak_bytes": fullest,
                   "memory_peak_bytes_by_chip": peaks}


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
