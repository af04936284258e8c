"""Compile rehearsals for the TPU: the main path's programs at real widths,
compiled for a described (not attached) v5e chip.

Nothing runs, so these say nothing of results or times; they catch what
the chip's compiler refuses (tiling, VMEM, unsupported primitives) before
a chip run. The topology is described inside a fixture, so only the test
worker that is handed this file loads the TPU compiler.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.relational import device_ops as D
from repro.relational import ops as OPS
from repro.relational.table import DictColumn
from repro.relational.tpch import QUERIES, generate

# rows of one 64 MiB lineitem split at SF 1 (~840k) pad to this bucket
SPLIT_ROWS = 840_000


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")    # no compiler logs in /tmp
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure means no TPU
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def tables():
    return generate(0.001, seed=0)


def _abstract(t, cap, sharding):
    """Padded column shapes of table t at ``cap`` rows, plus its row count."""
    cols = {n: jax.ShapeDtypeStruct(
                (cap,), (c.codes if isinstance(c, DictColumn)
                         else np.asarray(c)).dtype, sharding=sharding)
            for n, c in t.cols.items()}
    return cols, jax.ShapeDtypeStruct((), np.int32, sharding=sharding)


def _compile_task(t, ops, builds, partition, cap, build_caps, sharding):
    spec, _, _ = D._spec(ops, t, builds, partition, cap)
    with jax.enable_x64(True):
        cols, n = _abstract(t, cap, sharding)
        dev_builds = {b: _abstract(builds[b], build_caps[b], sharding)
                      for b in builds}
        n_parts = jax.ShapeDtypeStruct((), np.uint64, sharding=sharding)
        return D._program.lower(cols, n, dev_builds, n_parts,
                                spec=spec).compile()


def _stage(plan, name):
    return next(st for st in plan["stages"] if st["name"] == name)


def test_q1_scan_partial_agg(tables, one_chip):
    st = _stage(QUERIES["q1"](), "scan_agg")
    t = tables["lineitem"].project(st["columns"])
    c = _compile_task(t, st["ops"], {}, None, D.bucket(SPLIT_ROWS), {},
                      one_chip)
    assert c.memory_analysis() is not None


def test_q12_scan_partition(tables, one_chip):
    st = _stage(QUERIES["q12"](), "scan_li")
    t = tables["lineitem"].project(st["columns"])
    _compile_task(t, st["ops"], {}, ("l_orderkey", 8),
                  D.bucket(SPLIT_ROWS), {}, one_chip)


def test_q3_join_probe(tables, one_chip):
    """q3's lineitem x (orders x customer) join task with its partial
    aggregate: ~3M probe rows and ~150k build rows over 8 tasks at SF 1."""
    plan = QUERIES["q3"]()
    li = _stage(plan, "scan_li")
    left = OPS.apply_ops(tables["lineitem"].project(li["columns"]),
                         li["ops"], tables.__getitem__)
    co = _stage(plan, "join_co")
    right = OPS.op_join(tables["orders"], tables["customer"], co["lkey"],
                        co["rkey"])
    st = _stage(plan, "join_l")
    join = {"op": "join", "table": "join_co", "lkey": st["lkey"],
            "rkey": st["rkey"]}
    _compile_task(left, [join] + st["ops"], {"join_co": right}, None,
                  D.bucket(3_000_000 // 8), {"join_co": D.bucket(150_000)},
                  one_chip)


def test_flash_gqa_smollm_widths(one_chip):
    from repro.kernels.flash_gqa.flash_gqa import flash_attention_pallas
    # smollm-135m: 9 query heads (kv expanded), head dim 64 padded to 128
    x = jax.ShapeDtypeStruct((1, 2048, 9, 128), jnp.bfloat16,
                             sharding=one_chip)
    c = jax.jit(lambda q, k, v: flash_attention_pallas(
        q, k, v, causal=True, interpret=False)).lower(x, x, x).compile()
    assert "tpu_custom_call" in c.as_text()


def test_ssd_scan_mamba2_widths(one_chip):
    from repro.kernels.ssd_scan.ssd_scan import ssd_pallas
    # mamba2-2.7b: 80 heads of 64, state 128, chunk 128; batch 1 x 2048
    BH, S, P, N = 80, 2048, 64, 128

    def sds(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    c = jax.jit(lambda x, a, b, cm: ssd_pallas(
        x, a, b, cm, chunk=128, interpret=False)).lower(
        sds(BH, S, P), sds(BH, S), sds(BH, S, N), sds(BH, S, N)).compile()
    assert "tpu_custom_call" in c.as_text()


def test_smollm_decode_step(one_chip):
    from repro.configs.base import get_config
    from repro.launch.steps import make_decode_step
    from repro.models.model import build_model
    from repro.models.modules import abstract_params

    bundle = build_model(get_config("smollm-135m"))

    def on_chip(defs):
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                           sharding=one_chip),
            abstract_params(defs))
    token = jax.ShapeDtypeStruct((4, 1), jnp.int32, sharding=one_chip)
    c = jax.jit(make_decode_step(bundle)).lower(
        on_chip(bundle.param_defs), on_chip(bundle.cache_defs(4, 144)),
        {"token": token}).compile()
    assert c.memory_analysis() is not None

