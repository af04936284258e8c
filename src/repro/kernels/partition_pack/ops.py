"""jit'd public wrapper for partition_pack: dispatches Pallas vs the jnp
oracle. The kernel is interpreted off the TPU (``kernels.interpret_mode``);
it does not compile for the TPU yet (ROADMAP A3)."""
from __future__ import annotations

import functools

import jax

from repro.kernels import interpret_mode
from repro.kernels.partition_pack import ref
from repro.kernels.partition_pack.partition_pack import pack_pallas


@functools.partial(jax.jit, static_argnames=("n_parts", "capacity",
                                             "use_pallas"))
def partition_pack(rows, part_ids, *, n_parts: int, capacity: int,
                   use_pallas: bool = False):
    """rows [T,d], part_ids [T] -> (buf [n_parts,capacity,d], counts, slots).

    Entries past a partition's capacity are dropped (bounded buffers); the
    counts vector is the §3.2 offsets header (offsets = cumsum(counts)).
    """
    if use_pallas:
        return pack_pallas(rows, part_ids, n_parts, capacity,
                           interpret=interpret_mode())
    buf, counts, slot, keep = ref.pack(rows, part_ids, n_parts, capacity)
    return buf, counts, slot


def partition_unpack(buf, part_ids, slots, capacity: int):
    keep = slots < capacity
    return ref.unpack(buf, part_ids, slots, keep)
