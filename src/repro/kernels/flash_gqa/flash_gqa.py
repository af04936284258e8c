"""Flash attention (GQA) as a Pallas TPU kernel.

Blockwise online-softmax attention: the [Sq, Skv] score matrix never leaves
VMEM. Grid = (batch*heads, q_blocks, kv_blocks); the kv axis is the
innermost (sequential) dim, so the (m, l, acc) accumulators carry across kv
steps in VMEM scratch. Causal masking skips nothing here (masked compute),
matching the baseline; block-level skipping is the block_tri variant at the
jnp level.

Block shapes are MXU-aligned: q_block x d and kv_block x d tiles with
d padded to a multiple of 128 by ops.py; q_block=kv_block=128 default puts
the working set (q, k, v, scores, acc ~ 5 * 128 * max(d,128) * 4B) well
under VMEM.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK = 128
NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, causal: bool, window: int, blk_q: int,
                  blk_k: int, n_kv: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)                       # [blk_q, d]
    k = k_ref[0].astype(jnp.float32)                       # [blk_k, d]
    v = v_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    qpos = qi * blk_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    kpos = ki * blk_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = jnp.ones_like(s, dtype=jnp.bool_)
    if causal:
        mask &= kpos <= qpos
    if window > 0:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]                                    # [blk_q]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    p = jnp.exp(s - m_new[:, None])
    alpha = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1)
    acc_scr[...] = (acc_scr[...] * alpha[:, None]
                    + jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                          preferred_element_type=jnp.float32))
    m_scr[...] = m_new

    @pl.when(ki == n_kv - 1)
    def _finish():
        o_ref[0] = (acc_scr[...]
                    / jnp.maximum(l_scr[...], 1e-30)[:, None]
                    ).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, causal: bool = True, window: int = 0,
                           blk_q: int = DEFAULT_BLOCK,
                           blk_k: int = DEFAULT_BLOCK,
                           interpret: bool):
    """q [B,Sq,H,D], k/v [B,Skv,H,D] (kv already head-expanded).

    Host side (ops.py) pads D to 128 multiples and S to block multiples.
    """
    B, Sq, H, D = q.shape
    Skv = k.shape[1]
    assert Sq % blk_q == 0 and Skv % blk_k == 0, (Sq, Skv, blk_q, blk_k)
    scale = 1.0 / math.sqrt(D)
    # fold batch and heads into one grid axis; move seq to rows
    qr = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, D)
    kr = k.transpose(0, 2, 1, 3).reshape(B * H, Skv, D)
    vr = v.transpose(0, 2, 1, 3).reshape(B * H, Skv, D)
    n_kv = Skv // blk_k

    out = pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, causal=causal,
                          window=window, blk_q=blk_q, blk_k=blk_k,
                          n_kv=n_kv),
        grid=(B * H, Sq // blk_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, blk_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, blk_k, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, blk_k, D), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, blk_q, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((blk_q,), jnp.float32),
            pltpu.VMEM((blk_q,), jnp.float32),
            pltpu.VMEM((blk_q, D), jnp.float32),
        ],
        interpret=interpret,
    )(qr, kr, vr)
    return out.reshape(B, H, Sq, D).transpose(0, 2, 1, 3)
