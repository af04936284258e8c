"""Pallas TPU kernels for the Starling partitioned-object layout (§3.2).

TPU adaptation of the paper's format (see DESIGN.md §5): instead of a GPU
scatter, packing is split into
  A) ``count_slots_kernel`` — sequential grid over ROW TILES (VMEM-resident),
     carrying running per-partition counts across grid steps (TPU grids
     execute in order, so the running-count carry in the output ref is
     well-defined). Emits per-row slots, final counts (the offsets header),
     and the inverse row_of[p, c] map.
  B) ``gather_pack_kernel`` — grid over (partition, feature-tile): builds the
     partition-major buffer with CONTIGUOUS writes (DMA-friendly), reading
     rows via the row_of map. Consumers then range-read [p, lo:hi] slices —
     the two-reads property of the format.

Block shapes keep the working set in VMEM: a row tile is (TILE_T, d_tile)
with d_tile a multiple of 128 (lane width); counts/slots are int32 vectors.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

TILE_T = 256


def count_slots_kernel(ids_ref, slots_ref, counts_ref, row_of_ref, *,
                       n_parts: int, capacity: int, tile_t: int):
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        counts_ref[...] = jnp.zeros_like(counts_ref)
        row_of_ref[...] = jnp.full_like(row_of_ref, -1)

    ids = ids_ref[...]                                     # [tile_t]
    snapshot = counts_ref[...]                             # running counts
    # one extra bin (index n_parts) absorbs host padding rows
    oh = (ids[:, None] == jnp.arange(n_parts + 1)[None, :])
    ohi = oh.astype(jnp.int32)
    within = jnp.cumsum(ohi, axis=0) - ohi                 # exclusive prefix
    slot = jnp.sum(ohi * (snapshot[None, :] + within), axis=1)
    slots_ref[...] = slot
    counts_ref[...] = snapshot + jnp.sum(ohi, axis=0)

    # inverse map row_of[p, slot] = global row id (scalar stores; tiny data)
    base = step * tile_t

    def body(i, _):
        p = ids[i]
        s = slot[i]

        @pl.when((s < capacity) & (p < n_parts))
        def _store():
            row_of_ref[p, s] = base + i
        return 0

    jax.lax.fori_loop(0, tile_t, body, 0)


def gather_pack_kernel(row_of_ref, rows_ref, buf_ref, *, capacity: int):
    """Grid (n_parts, d_tiles): buf[p, :, dtile] <- rows[row_of[p, :], dtile].
    rows_ref is the full row array (ANY/VMEM); writes are contiguous."""
    idx = row_of_ref[0, :]                                 # [capacity]

    def body(c, _):
        r = idx[c]

        @pl.when(r >= 0)
        def _copy():
            buf_ref[0, c, :] = rows_ref[r, :]

        @pl.when(r < 0)
        def _zero():
            buf_ref[0, c, :] = jnp.zeros_like(buf_ref[0, c, :])
        return 0

    jax.lax.fori_loop(0, capacity, body, 0)


def pack_pallas(rows: jax.Array, part_ids: jax.Array, n_parts: int,
                capacity: int, *, interpret: bool):
    """Returns (buf [n_parts, capacity, d], counts, slots). Host pads T to a
    multiple of TILE_T (padded ids -> partition n_parts, dropped)."""
    T, d = rows.shape
    tile_t = min(TILE_T, max(8, T))
    padT = (-T) % tile_t
    ids = jnp.pad(part_ids.astype(jnp.int32), (0, padT),
                  constant_values=n_parts)                # out-of-range: drop
    n_steps = (T + padT) // tile_t

    slots, counts, row_of = pl.pallas_call(
        functools.partial(count_slots_kernel, n_parts=n_parts,
                          capacity=capacity, tile_t=tile_t),
        grid=(n_steps,),
        in_specs=[pl.BlockSpec((tile_t,), lambda i: (i,))],
        out_specs=[
            pl.BlockSpec((tile_t,), lambda i: (i,)),
            pl.BlockSpec((n_parts + 1,), lambda i: (0,)),
            pl.BlockSpec((n_parts, capacity), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T + padT,), jnp.int32),
            jax.ShapeDtypeStruct((n_parts + 1,), jnp.int32),
            jax.ShapeDtypeStruct((n_parts, capacity), jnp.int32),
        ],
        interpret=interpret,
    )(ids)

    d_tile = d if d % 128 else min(d, 512)
    # keep whole rows in one block if d is not lane-aligned
    n_dt = max(d // d_tile, 1) if d % d_tile == 0 else 1
    d_tile = d // n_dt
    buf = pl.pallas_call(
        functools.partial(gather_pack_kernel, capacity=capacity),
        grid=(n_parts, n_dt),
        in_specs=[
            pl.BlockSpec((1, capacity), lambda p, j: (p, 0)),
            pl.BlockSpec((T + padT, d_tile), lambda p, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, capacity, d_tile), lambda p, j: (p, 0, j)),
        out_shape=jax.ShapeDtypeStruct((n_parts, capacity, d), rows.dtype),
        interpret=interpret,
    )(row_of, jnp.pad(rows, ((0, padT), (0, 0))))
    return buf, counts[:n_parts], slots[:T]
