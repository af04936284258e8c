"""jit'd wrapper for ssd_scan: model-layout in/out, Pallas or jnp oracle.
The kernel is interpreted off the TPU (``kernels.interpret_mode``)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.ssd_scan.ssd_scan import ssd_pallas
from repro.models.mamba2 import ssd_chunked


@functools.partial(jax.jit, static_argnames=("chunk", "use_pallas"))
def ssd(x, dt, A, Bm, Cm, *, chunk: int = 128, use_pallas: bool = True):
    """x [B,S,H,P], dt [B,S,H] (post-softplus), A [H], Bm/Cm [B,S,G,N].
    Returns (y [B,S,H,P], state [B,H,P,N])."""
    if not use_pallas:
        return ssd_chunked(x, dt, A, Bm, Cm, chunk)
    B, S, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    rep = H // G
    xdt = (x.astype(jnp.float32) * dt.astype(jnp.float32)[..., None])
    dA = dt.astype(jnp.float32) * A[None, None, :]
    Bh = jnp.repeat(Bm, rep, axis=2).astype(jnp.float32)
    Ch = jnp.repeat(Cm, rep, axis=2).astype(jnp.float32)

    def fold(t):                               # [B,S,H,...] -> [B*H,S,...]
        t = jnp.moveaxis(t, 2, 1)
        return t.reshape((B * H,) + t.shape[2:])

    y, state = ssd_pallas(fold(xdt), fold(dA), fold(Bh), fold(Ch),
                          chunk=min(chunk, S), interpret=interpret_mode())
    y = jnp.moveaxis(y.reshape(B, H, S, P), 1, 2).astype(x.dtype)
    state = state.reshape(B, H, N, P).swapaxes(-1, -2)   # [B,H,P,N]
    return y, state
