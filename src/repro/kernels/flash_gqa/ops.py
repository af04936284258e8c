"""jit'd wrapper for flash_gqa: pads D to lane multiples / S to blocks,
expands GQA kv heads, dispatches Pallas vs jnp-oracle. The kernel is
interpreted off the TPU (``kernels.interpret_mode``)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.flash_gqa.flash_gqa import flash_attention_pallas
from repro.kernels.flash_gqa.ref import attention_ref


@functools.partial(jax.jit, static_argnames=("causal", "window",
                                             "use_pallas", "blk"))
def flash_gqa(q, k, v, *, causal: bool = True, window: int = 0,
              use_pallas: bool = True, blk: int = 128):
    """q [B,Sq,H,D]; k/v [B,Skv,Hkv,D] with H % Hkv == 0."""
    B, Sq, H, D = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    if H != Hkv:
        rep = H // Hkv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    if not use_pallas:
        return attention_ref(q, k, v, causal=causal, window=window)
    padD = (-D) % 128
    padQ = (-Sq) % blk
    padK = (-Skv) % blk
    if padD or padQ or padK:
        # query padding appends rows AFTER the real ones; with causal
        # masking they attend to everything real (sliced off); kv padding
        # appends masked-out keys via an explicit valid mask trick: pad keys
        # get positions > all queries under causal masking only when Sq==Skv,
        # so for the padded case we pre-mask by pushing pad keys out of the
        # causal window (they sit at kpos >= Skv where qpos < Skv).
        q = jnp.pad(q, ((0, 0), (0, padQ), (0, 0), (0, padD)))
        k = jnp.pad(k, ((0, 0), (0, padK), (0, 0), (0, padD)))
        v = jnp.pad(v, ((0, 0), (0, padK), (0, 0), (0, padD)))
        assert causal or padK == 0, "bidir padding needs kv mask support"
    # keep softmax scale of the TRUE head dim
    if padD:
        q = q * jnp.sqrt((D + padD) / D).astype(q.dtype)
    out = flash_attention_pallas(q, k, v, causal=causal, window=window,
                                 blk_q=blk, blk_k=blk,
                                 interpret=interpret_mode())
    return out[:, :Sq, :, :D]
