"""partition_pack Pallas kernel (interpret mode) vs jnp oracle: shape/dtype
sweep + roundtrip + hypothesis property tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.partition_pack.ops import partition_pack, partition_unpack

SHAPES = [(32, 8, 4, 16), (256, 16, 24, 64), (300, 7, 64, 128),
          (1024, 64, 24, 256)]
DTYPES = [jnp.float32, jnp.bfloat16]


@pytest.mark.parametrize("T,P,C,d", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_pallas_matches_oracle(T, P, C, d, dtype):
    k1, k2 = jax.random.split(jax.random.key(T + P))
    rows = jax.random.normal(k1, (T, d), jnp.float32).astype(dtype)
    ids = jax.random.randint(k2, (T,), 0, P, jnp.int32)
    buf_p, cnt_p, slot_p = partition_pack(rows, ids, n_parts=P, capacity=C,
                                          use_pallas=True)
    buf_r, cnt_r, slot_r = partition_pack(rows, ids, n_parts=P, capacity=C,
                                          use_pallas=False)
    np.testing.assert_array_equal(np.asarray(cnt_p), np.asarray(cnt_r))
    np.testing.assert_array_equal(np.asarray(slot_p), np.asarray(slot_r))
    np.testing.assert_allclose(np.asarray(buf_p, np.float32),
                               np.asarray(buf_r, np.float32), rtol=0, atol=0)


def test_counts_are_offsets_header():
    rows = jnp.ones((64, 8))
    ids = jnp.asarray(np.repeat(np.arange(4), 16), jnp.int32)
    _, counts, _ = partition_pack(rows, ids, n_parts=4, capacity=32)
    np.testing.assert_array_equal(np.asarray(counts), [16, 16, 16, 16])


@settings(max_examples=30, deadline=None)
@given(st.integers(2, 8), st.integers(1, 6), st.integers(10, 80),
       st.integers(0, 2 ** 31 - 1))
def test_property_roundtrip(P, Cdiv, T, seed):
    """unpack(pack(x)) == x for all kept rows; dropped rows are zero."""
    C = max(T // (P * Cdiv), 1)
    k1, k2 = jax.random.split(jax.random.key(seed))
    rows = jax.random.normal(k1, (T, 8), jnp.float32)
    ids = jax.random.randint(k2, (T,), 0, P, jnp.int32)
    buf, counts, slots = partition_pack(rows, ids, n_parts=P, capacity=C)
    back = partition_unpack(buf, ids, slots, C)
    keep = np.asarray(slots) < C
    np.testing.assert_allclose(np.asarray(back)[keep],
                               np.asarray(rows)[keep], rtol=0, atol=0)
    assert np.all(np.asarray(back)[~keep] == 0)
    # counts == true histogram (pre-capacity)
    np.testing.assert_array_equal(
        np.asarray(counts), np.bincount(np.asarray(ids), minlength=P))


@settings(max_examples=20, deadline=None)
@given(st.integers(2, 6), st.integers(16, 64), st.integers(0, 2 ** 31 - 1))
def test_property_partition_major_order(P, T, seed):
    """Within each partition, rows keep arrival order (stable pack)."""
    k = jax.random.key(seed)
    ids = jax.random.randint(k, (T,), 0, P, jnp.int32)
    rows = jnp.arange(T, dtype=jnp.float32)[:, None] * jnp.ones((1, 4))
    C = T
    buf, counts, slots = partition_pack(rows, ids, n_parts=P, capacity=C)
    buf = np.asarray(buf)
    for p in range(P):
        n = int(np.asarray(counts)[p])
        vals = buf[p, :n, 0]
        assert np.all(np.diff(vals) > 0), (p, vals)  # arrival order
