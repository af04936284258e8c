"""The trace reduction, on a small trace recorded on a TPU v5e chip
(``data/small.xplane.pb.gz``, made by ``record_trace.py`` and gzip: q6 and q12 at
SF 0.01 through ``Session`` with the benchmark's probes installed)."""
import gzip
import pathlib

import pytest
from jax.profiler import ProfileData

from chipbench import trace

DATA = pathlib.Path(__file__).resolve().parent / "data" / \
    "small.xplane.pb.gz"


def test_union_merges_overlaps_and_touching_intervals():
    assert trace.union([(5, 6), (0, 2), (1, 3), (3, 4), (8, 9)]) == \
        [(0, 4), (5, 6), (8, 9)]
    assert trace.union([]) == []


def test_short_op_names():
    hlo = ("%fusion.496 = u32[1048576]{0:T(1024)S(1)} fusion(u32[1048576]"
           "{0:T(1024)} %get-tuple-element.1760, s32[131072]{0:T(1024)S(1)}"
           " %fusion.494), kind=kCustom, calls=%fused_computation.5")
    assert trace.short_op(hlo) == "%fusion.496 fusion u32[1048576]"
    loop = ("%while.38 = (u32[]{:T(128)}, s32[1048576]{0:T(1024)S(1)}) "
            "while((u32[]{:T(128)}, s32[1048576]{0:T(1024)S(1)}) %t), "
            "condition=%c, body=%b")
    assert trace.short_op(loop) == "%while.38 while s32[1048576]"


def test_category_by_most_threads_open():
    ann = {"a": [(0, 10, "chipbench.format.decode_object")],
           "b": [(0, 10, "chipbench.ops.run")],
           "c": [(5, 10, "chipbench.format.deserialize_segment")]}
    assert trace._category_at(7, ann) == "decode"
    assert trace._category_at(2, ann) in ("decode",
                                          "device_ops.run host side")
    assert trace._category_at(20, ann) == trace.IDLE_NO_ANNOTATION


@pytest.fixture(scope="module")
def reduced():
    raw = gzip.decompress(DATA.read_bytes())
    return trace.reduce_profile(ProfileData.from_serialized_xspace(raw))


def test_recorded_trace_reduces(reduced):
    r = reduced
    assert r["chips"] == 1
    assert 0 < r["busy_s"] <= r["span_s"]
    assert r["program_s"]["jit__program"] > 0
    # every op runs inside a program run, so the programs' device time
    # covers the union of op intervals
    assert sum(r["program_s"].values()) >= 0.99 * r["busy_s"]
    secs = [s for _, s in r["device_ops"]]
    assert 0 < len(secs) <= 10 and secs == sorted(secs, reverse=True)
    assert secs[0] <= r["busy_s"]
    gaps = [s for _, s in r["idle_gaps"]]
    assert gaps == sorted(gaps, reverse=True)
    assert sum(gaps) <= r["span_s"] - r["busy_s"] + 1e-9
    kinds = set(trace.CATEGORY.values()) | {trace.IDLE_NO_ANNOTATION}
    assert {name for name, _ in r["idle_gaps"]} <= kinds
    # the benchmark's probes left their annotations on the host plane
    assert any(name != trace.IDLE_NO_ANNOTATION
               for name, _ in r["idle_gaps"])


def test_find_xplane(tmp_path):
    with pytest.raises(FileNotFoundError):
        trace.find_xplane(str(tmp_path))
    d = tmp_path / "plugins" / "profile" / "x"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(gzip.decompress(DATA.read_bytes()))
    path = trace.find_xplane(str(tmp_path))
    assert path.endswith("host.xplane.pb")
    assert trace.reduce(path)["chips"] == 1
