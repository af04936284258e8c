"""Every cell of BENCHMARK.json resolves to its files by name, and the
file keeps to the shape the benchmark's readers expect."""
import json
import pathlib
import re

import pytest

from chipbench import harness, reference

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def chips_allowed(workloads) -> bool:
    """Every cell takes 1 or 4 chips, and at most half of the cells,
    rounded down, take 4; one such cell is always allowed."""
    four = sum(w["chips"] == 4 for w in workloads)
    return all(w["chips"] in (1, 4) for w in workloads) and \
        four <= max(1, len(workloads) // 2)


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_cell_resolves_to_its_files(cell):
    parts = harness.resolve(cell, ROOT)
    assert parts["config"]["name"] == parts["cell"]["config"]
    assert parts["traffic"]["kind"] == "closed_stream"
    for m in parts["end_to_end"] + parts["per_layer"]:
        assert callable(harness.reader(m["name"]))
    names = {m["name"] for m in parts["end_to_end"]}
    assert "setup_s" in names and len(names) >= 2
    assert parts["per_layer"]
    assert parts["config"]["correct"]["max_rel_err"] > 0


@pytest.mark.parametrize("path", sorted(
    (ROOT / "chipbench" / "traffic").glob("*.json")), ids=lambda p: p.stem)
def test_traffic_files_are_closed_streams_of_known_queries(path):
    traffic = json.loads(path.read_text())
    assert traffic["kind"] == "closed_stream" and traffic["order"]
    for query in traffic["order"]:
        assert callable(reference.answerer(query)), query


def test_names_units_and_bounds():
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    named = BENCH["configs"] + BENCH["workloads"] + metrics
    for entry in named:
        assert NAME.match(entry["name"]), entry["name"]
    assert len({e["name"] for e in metrics}) == len(metrics)
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25, m
        assert m["source"] in ("host_clock", "device_trace")
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    layers = {}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        layers.setdefault(m["layer"], m["layer"])
    for c in BENCH["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert c["file"].startswith(BENCH["paths"][0] + "/")
    assert chips_allowed(BENCH["workloads"])


@pytest.mark.parametrize("chips, allowed", [
    ([1], True),
    ([1, 1, 1], True),
    ([4], True),
    ([1, 4, 1], True),
    ([4, 4, 1], False),
    ([4, 4, 1, 1], True),
    ([4, 4, 4, 1], False),
    ([2, 1], False),
])
def test_chips_rule(chips, allowed):
    assert chips_allowed([{"chips": n} for n in chips]) == allowed


def test_peaks_know_the_v5e_and_refuse_others():
    assert harness.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        harness.peaks("cpu")
