"""Mamba-2 SSD chunk scan as a Pallas TPU kernel.

Grid = (batch*heads, chunks); chunks are the inner (sequential) axis, so the
inter-chunk SSM state [N, P] lives in VMEM scratch and carries across grid
steps — the Pallas version of the lax.scan recurrence, with the intra-chunk
quadratic computed on the MXU (Q x Q and Q x N tiles, 128-aligned).

Host-side prep (ops.py): dA = dt * A and xdt = x * dt are folded in, B/C are
expanded from groups to heads; everything arrives as [B*H, S, *].
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(xdt_ref, dA_col_ref, dA_row_ref, b_ref, c_ref, y_ref,
                state_out_ref, state_scr, *, n_chunks: int, blk_q: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    xdt = xdt_ref[0].astype(jnp.float32)                   # [Q, P]
    dA_col = dA_col_ref[0].astype(jnp.float32)             # [Q, 1]
    dA_row = dA_row_ref[0].astype(jnp.float32)             # [1, Q]
    Bm = b_ref[0].astype(jnp.float32)                      # [Q, N]
    Cm = c_ref[0].astype(jnp.float32)                      # [Q, N]

    # inclusive prefix sums of dA as a column and as a row: masked
    # reductions over the [Q, Q] lower triangle (Mosaic has no cumsum)
    row = jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_q), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_q), 1)
    tri = row >= col
    zeros = jnp.zeros((blk_q, blk_q), jnp.float32)
    cs = jnp.sum(jnp.where(tri, dA_row + zeros, 0.0), axis=1,
                 keepdims=True)                            # [Q, 1]
    cs_row = jnp.sum(jnp.where(row <= col, dA_col + zeros, 0.0), axis=0,
                     keepdims=True)                        # [1, Q]
    total = jnp.sum(dA_row)                                # cs[Q - 1]
    # intra-chunk: L[i,j] = exp(cs_i - cs_j) for j <= i
    L = jnp.where(tri, jnp.exp(cs - cs_row), 0.0)
    scores = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32) * L
    y = jax.lax.dot_general(scores, xdt, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)

    # inter-chunk: y += exp(cs) * (C @ state)
    state = state_scr[...]                                 # [N, P]
    y += jnp.exp(cs) * jax.lax.dot_general(
        Cm, state, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    # state' = exp(cs[-1]) * state + B^T @ (exp(cs[-1] - cs) * xdt)
    decay_in = jnp.exp(total - cs) * xdt                   # [Q, P]
    state_scr[...] = (jnp.exp(total) * state
                      + jax.lax.dot_general(
                          Bm, decay_in, (((0,), (0,)), ((), ())),
                          preferred_element_type=jnp.float32))

    y_ref[0] = y.astype(y_ref.dtype)

    @pl.when(ci == n_chunks - 1)
    def _emit_state():
        state_out_ref[0] = state_scr[...].astype(state_out_ref.dtype)


def ssd_pallas(xdt, dA, Bm, Cm, *, chunk: int = 128, interpret: bool):
    """xdt [BH,S,P], dA [BH,S], Bm/Cm [BH,S,N] -> (y [BH,S,P],
    state [BH,N,P]). dA enters twice, as [BH,S,1] and [BH,1,S] blocks,
    so each block's last two dims meet the TPU's (8, 128) tiling."""
    BH, S, P = xdt.shape
    N = Bm.shape[-1]
    assert S % chunk == 0, (S, chunk)
    n_chunks = S // chunk

    y, state = pl.pallas_call(
        functools.partial(_ssd_kernel, n_chunks=n_chunks, blk_q=chunk),
        grid=(BH, n_chunks),
        in_specs=[
            pl.BlockSpec((1, chunk, P), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, 1), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, 1, chunk), lambda b, c: (b, 0, c)),
            pl.BlockSpec((1, chunk, N), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, c: (b, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, P), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, N, P), lambda b, c: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, P), xdt.dtype),
            jax.ShapeDtypeStruct((BH, N, P), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        interpret=interpret,
    )(xdt, dA[:, :, None], dA[:, None, :], Bm, Cm)
    return y, state
