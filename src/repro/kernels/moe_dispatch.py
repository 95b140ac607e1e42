"""Capacity-bounded token->expert slot assignment — Pallas TPU kernel.

The MoE exchange (models/moe.py) packs each (token, choice) into a fixed
per-expert message buffer: ``slot = expert * C + arrival_rank``, dropping
overflow — the paper's fixed-size reusable message pool.  The arrival-rank
computation is an inherently *sequential* running histogram over the token
stream; this kernel carries the per-expert counters in VMEM scratch across a
sequential grid (one pass over token blocks, no [T, E] cumsum materialized
in HBM like the jnp reference does — that intermediate is T×E×4 bytes,
~1 GB for olmoe's train cell).  The within-block rank is the MXU
triangular product of :func:`repro.kernels.hash_partition.block_rank`
(the TPU lowering has no in-kernel ``cumsum``); the running counters ride
on top of it as an ``[experts, 1]`` column.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .hash_partition import SUBLANES, as_blocks, block_rank, strict_upper


def _dispatch_kernel(
    dest_ref,   # [8, blk] int32: 8 blocks of token destinations
    slot_ref,   # out [8, blk] int32
    count_ref,  # out [E_pad, 1] int32 (final counts, clamped to capacity)
    run_ref,    # scratch [E_pad, 1] f32 running histogram
    *,
    num_dest: int,
    capacity: int,
):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        run_ref[...] = jnp.zeros_like(run_ref)

    upper = strict_upper(dest_ref.shape[1])
    base = run_ref[...]  # [E_pad, 1] counts before this grid step
    for r in range(SUBLANES):
        dest = dest_ref[r:r + 1, :]
        onehot, within, count = block_rank(dest, num_dest, upper)
        rank = within + jnp.sum(onehot * base, axis=0, keepdims=True).astype(
            jnp.int32
        )
        kept = rank < capacity
        slot_ref[r:r + 1, :] = jnp.where(
            kept, dest * capacity + rank, num_dest * capacity
        )
        base = base + count
    run_ref[...] = base

    @pl.when(i == pl.num_programs(0) - 1)
    def _finish():
        count_ref[...] = jnp.minimum(base, capacity).astype(jnp.int32)


def moe_dispatch(
    dest: jax.Array, num_dest: int, capacity: int, block: int = 256,
    interpret: bool = True,
) -> tuple[jax.Array, jax.Array]:
    """(slot [T] int32, counts [num_dest] int32); overflow -> num_dest*capacity.

    Any ``T``: the tail is padded with the inert id ``num_dest`` (matches no
    expert, slots to the drop bin) to whole ``(8, block)`` grid steps.
    """
    T = dest.shape[0]
    d = as_blocks(dest, num_dest, block)
    e_pad = -(-num_dest // SUBLANES) * SUBLANES
    kernel = functools.partial(
        _dispatch_kernel, num_dest=num_dest, capacity=capacity
    )
    spec = pl.BlockSpec((SUBLANES, block), lambda i: (i, 0))
    slot, counts = pl.pallas_call(
        kernel,
        grid=(d.shape[0] // SUBLANES,),
        in_specs=[spec],
        out_specs=[spec, pl.BlockSpec((e_pad, 1), lambda i: (0, 0))],
        out_shape=[
            jax.ShapeDtypeStruct(d.shape, jnp.int32),
            jax.ShapeDtypeStruct((e_pad, 1), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((e_pad, 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(d)
    return slot.reshape(-1)[:T], counts[:num_dest, 0]


__all__ = ["moe_dispatch"]
