"""ssd_scan: Pallas kernel (interpret) vs chunked oracle vs sequential
recurrence, across shapes/chunk sizes/dtypes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.ssd_scan.ops import ssd
from repro.kernels.ssd_scan.ref import ssd_sequential
from repro.models.mamba2 import ssd_chunked

CASES = [
    # B, S, H, P, G, N, chunk
    (1, 128, 2, 32, 1, 32, 64),
    (2, 256, 4, 64, 2, 64, 128),
    (1, 256, 2, 64, 1, 128, 128),
    (2, 64, 2, 16, 1, 16, 32),
]


def _inputs(B, S, H, P, G, N, seed=0):
    ks = jax.random.split(jax.random.key(seed), 5)
    x = jax.random.normal(ks[0], (B, S, H, P), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)) - 1.0)
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, S, G, N)) * 0.3
    Cm = jax.random.normal(ks[4], (B, S, G, N)) * 0.3
    return x, dt, A, Bm, Cm


@pytest.mark.parametrize("B,S,H,P,G,N,chunk", CASES)
def test_pallas_matches_chunked_oracle(B, S, H, P, G, N, chunk):
    x, dt, A, Bm, Cm = _inputs(B, S, H, P, G, N, seed=S + P)
    y_k, st_k = ssd(x, dt, A, Bm, Cm, chunk=chunk, use_pallas=True)
    y_r, st_r = ssd_chunked(x, dt, A, Bm, Cm, chunk)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(st_k), np.asarray(st_r),
                               rtol=2e-4, atol=2e-4)


def test_chunked_oracle_matches_sequential():
    x, dt, A, Bm, Cm = _inputs(2, 64, 2, 16, 1, 16, seed=9)
    y_c, st_c = ssd_chunked(x, dt, A, Bm, Cm, 16)
    y_s, st_s = ssd_sequential(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(np.asarray(y_c), np.asarray(y_s, np.float32),
                               rtol=1e-4, atol=1e-4)
    # state layouts: chunked [B,H,P,N], sequential [B,H,P,N]
    np.testing.assert_allclose(np.asarray(st_c), np.asarray(st_s),
                               rtol=1e-4, atol=1e-4)


def test_decode_recurrence_matches_prefix():
    """The model's decode step continues exactly from the prefill state."""
    from repro.models.mamba2 import mamba_apply, mamba_defs
    from repro.configs.smoke import smoke_config
    from repro.models.modules import init_params, Sharder
    cfg = smoke_config("mamba2-2.7b")
    p = init_params(mamba_defs(cfg), jax.random.key(0))
    sh = Sharder()
    x = jax.random.normal(jax.random.key(1), (2, 8, cfg.d_model),
                          jnp.float32) * 0.1
    full, _ = mamba_apply(cfg, p, x, sh)
    # replay tokens one at a time through the decode path
    from repro.models.mamba2 import dims
    d_in, nheads, conv_dim = dims(cfg)
    cache = {"conv": jnp.zeros((2, cfg.ssm_conv - 1, conv_dim)),
             "ssm": jnp.zeros((2, nheads, cfg.ssm_head_dim, cfg.ssm_state))}
    outs = []
    for t in range(8):
        o, cache = mamba_apply(cfg, p, x[:, t:t + 1], sh, cache=cache)
        outs.append(o)
    step = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(step), np.asarray(full),
                               rtol=2e-3, atol=2e-3)
