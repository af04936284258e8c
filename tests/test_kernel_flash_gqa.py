"""flash_gqa Pallas kernel (interpret) vs materialized-softmax oracle:
shape/dtype/window sweep + agreement with the model-level chunked path."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_gqa.ops import flash_gqa
from repro.kernels.flash_gqa.ref import attention_ref

CASES = [
    # B, Sq, Skv, H, Hkv, D, causal, window
    (1, 128, 128, 2, 2, 64, True, 0),
    (2, 256, 256, 4, 2, 64, True, 0),
    (1, 256, 256, 3, 1, 128, True, 0),
    (2, 128, 128, 2, 2, 32, True, 0),      # D padded to 128
    (1, 384, 384, 2, 1, 64, True, 128),    # sliding window
    (1, 200, 200, 2, 2, 64, True, 0),      # Sq padded to block
]


@pytest.mark.parametrize("B,Sq,Skv,H,Hkv,D,causal,window", CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_matches_oracle(B, Sq, Skv, H, Hkv, D, causal, window, dtype):
    ks = jax.random.split(jax.random.key(Sq + H), 3)
    q = jax.random.normal(ks[0], (B, Sq, H, D), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (B, Skv, Hkv, D), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (B, Skv, Hkv, D), jnp.float32).astype(dtype)
    got = flash_gqa(q, k, v, causal=causal, window=window, use_pallas=True)
    rep = H // Hkv
    want = attention_ref(q, jnp.repeat(k, rep, 2), jnp.repeat(v, rep, 2),
                         causal=causal, window=window)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_matches_model_chunked_attention():
    """The kernel and the model's jnp chunked attention agree."""
    from repro.models.attention import chunked_attention
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (2, 256, 4, 64))
    k = jax.random.normal(ks[1], (2, 256, 4, 64))
    v = jax.random.normal(ks[2], (2, 256, 4, 64))
    a = flash_gqa(q, k, v, causal=True, use_pallas=True)
    b = chunked_attention(q, k, v, causal=True, chunk=64)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5,
                               atol=2e-5)
