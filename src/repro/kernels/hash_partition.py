"""Exchange-operator partition hot loop — Pallas TPU kernels (paper §3.2.1).

HyPer's decoupled exchange operator hashes each tuple's join key (CRC32 on
x86) and partitions tuples into per-destination message buffers.  On TPU the
hash is a multiply-xor avalanche (pure VPU, no CRC unit — DESIGN.md §2) and
the hot loop is fused into a single block-parallel kernel.  Three entry
points, in increasing order of fusion:

* :func:`hash_partition` — (pid, per-block histogram).  The original kernel,
  kept for the MoE-style callers that only need destination ids.
* :func:`partition_pack` — given destination ids, emits per-block histograms
  AND each row's *block-local* within-destination rank.  The global rank a
  message-buffer pack needs is then ``exclusive_scan(block_hists)[block, d]
  + local_rank`` — an ``[nblocks, bins]`` scan plus a flat gather, so the
  pack never materializes the ``[rows, bins]`` one-hot/cumsum the pure-XLA
  path needs (O(rows x bins) memory and FLOPs).
* :func:`hash_partition_pack` — the full fused hot loop: hash + validity
  masking (invalid rows routed to the overflow bin) + block-local rank +
  block histogram in one pass over the keys.  This is the kernel analogue of
  the per-tuple loop the paper code-generates with LLVM; schema
  specialization happens at trace time (Pallas kernels are shape-specialized),
  mirroring the paper's generated serialization code.

The histogram tree-combine and the actual scatter stay in XLA (dynamic
scatter is not an MXU shape) — see :func:`repro.kernels.ops.partition_ranks`
for the combine and :func:`repro.core.exchange.pack_by_destination` for the
scatter.

Rows whose destination id is outside ``[0, num_bins)`` (the padding value
used by the ``ops`` wrappers) match no bin: they get rank 0 and contribute
to no histogram bucket.

Layout (what the TPU lowering accepts).  Rows are viewed as ``[T / block,
block]``: one *block* per sublane row, ``block`` a multiple of 128 lanes.
Each grid step takes 8 such rows (one ``(8, block)`` tile).  Within a row
the one-hot lies bins-on-sublanes ``[bins, block]``, and the block-local
exclusive rank is one MXU product with the strictly-upper-triangular ones
matrix — exact, since every operand is 0/1 and the f32 accumulator counts
at most ``block``.  Histograms leave lane-major, bins padded to a multiple
of 128, so each grid step writes one aligned ``(8, 128k)`` tile; the
wrappers slice the padding off.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

# The uint32 multiply-xor mix shared with exchange.fibonacci_hash — one
# definition so the kernel/XLA bit-exactness contract can't drift.
from .ref import fibonacci_hash_ref as _avalanche  # noqa: E402

LANES = 128  # histogram bins are padded to whole lane tiles
SUBLANES = 8  # blocks per grid step: one int32 (8, 128k) tile


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def strict_upper(block: int) -> jax.Array:
    """``U[i, j] = 1`` where ``i < j``: ``onehot @ U`` is the exclusive
    prefix count along the row."""
    i = lax.broadcasted_iota(jnp.int32, (block, block), 0)
    j = lax.broadcasted_iota(jnp.int32, (block, block), 1)
    return (i < j).astype(jnp.bfloat16)


def block_rank(d: jax.Array, num_bins: int, upper: jax.Array):
    """Block-local within-bin rank of one block of dests, without a cumsum.

    ``d`` is one block as a ``[1, block]`` lane row.  Returns ``(onehot,
    rank, count)``: the f32 one-hot ``[bins, block]`` (bins padded to 8
    sublanes; ids outside ``[0, num_bins)`` match no bin), the rank as a
    ``[1, block]`` int32 row and the per-bin count as an f32 ``[bins, 1]``
    column.  ``[bins, block]`` lives only in VMEM for the duration of one
    grid step — the row-global one-hot never exists.
    """
    bins = _round_up(num_bins, SUBLANES)
    block = d.shape[1]
    bin_id = lax.broadcasted_iota(jnp.int32, (bins, block), 0)
    onehot = (bin_id == d) & (bin_id < num_bins)
    excl = jnp.dot(
        onehot.astype(jnp.bfloat16), upper, preferred_element_type=jnp.float32
    )
    ohf = onehot.astype(jnp.float32)
    rank = jnp.sum(ohf * excl, axis=0, keepdims=True).astype(jnp.int32)
    return ohf, rank, jnp.sum(ohf, axis=1, keepdims=True)


def _rank_and_hist(d: jax.Array, num_bins: int, upper: jax.Array):
    """:func:`block_rank` with the histogram as a ``[1, 128k]`` lane row
    (bins past ``num_bins`` are 0)."""
    _, rank, count = block_rank(d, num_bins, upper)
    bins, lanes = count.shape[0], _round_up(num_bins, LANES)
    # bins-on-sublanes -> bins-on-lanes through the diagonal of [bins, lanes]
    diag = lax.broadcasted_iota(jnp.int32, (bins, lanes), 0) == \
        lax.broadcasted_iota(jnp.int32, (bins, lanes), 1)
    hist = jnp.sum(jnp.where(diag, count, 0.0), axis=0, keepdims=True)
    return rank, hist.astype(jnp.int32)


def _hash_pid(keys: jax.Array, num_partitions: int) -> jax.Array:
    return (_avalanche(keys) % jnp.uint32(num_partitions)).astype(jnp.int32)


def _hash_kernel(keys_ref, pid_ref, hist_ref, *, num_partitions: int):
    upper = strict_upper(keys_ref.shape[1])
    for r in range(SUBLANES):
        pid = _hash_pid(keys_ref[r:r + 1, :], num_partitions)
        pid_ref[r:r + 1, :] = pid
        hist_ref[r:r + 1, :] = _rank_and_hist(pid, num_partitions, upper)[1]


def _partition_pack_kernel(dest_ref, hist_ref, rank_ref, *, num_bins: int):
    upper = strict_upper(dest_ref.shape[1])
    for r in range(SUBLANES):
        rank, hist = _rank_and_hist(dest_ref[r:r + 1, :], num_bins, upper)
        rank_ref[r:r + 1, :] = rank
        hist_ref[r:r + 1, :] = hist


def _hash_partition_pack_kernel(
    keys_ref, valid_ref, dest_ref, hist_ref, rank_ref, *, num_partitions: int
):
    upper = strict_upper(keys_ref.shape[1])
    for r in range(SUBLANES):
        pid = _hash_pid(keys_ref[r:r + 1, :], num_partitions)
        # Invalid rows go to the overflow bin (bin index == num_partitions).
        d = jnp.where(valid_ref[r:r + 1, :] != 0, pid, num_partitions)
        dest_ref[r:r + 1, :] = d
        rank, hist = _rank_and_hist(d, num_partitions + 1, upper)
        rank_ref[r:r + 1, :] = rank
        hist_ref[r:r + 1, :] = hist


def as_blocks(x: jax.Array, fill: int, block: int) -> jax.Array:
    """``[T]`` -> int32 ``[nb, block]`` with ``nb`` a multiple of 8: the
    tail is padded with ``fill`` to whole grid steps."""
    assert block % LANES == 0, f"block={block} must be a multiple of {LANES}"
    T = x.shape[0]
    nb = _round_up(T, block * SUBLANES) // block
    x = jnp.pad(x.astype(jnp.int32), (0, nb * block - T), constant_values=fill)
    return x.reshape(nb, block)


def _block_call(kernel, inputs, fills, num_bins, outs, block, interpret):
    """Run ``kernel`` over ``[T]`` int32 ``inputs`` viewed as
    ``[T / block, block]``, 8 blocks per grid step.

    ``outs`` lists the outputs in kernel order: ``"rows"`` (``[T]``) or
    ``"hist"`` (``[T / block, num_bins]``).  Inputs are padded with
    ``fills`` to whole grid steps; the padding blocks' outputs are dropped.
    """
    T = inputs[0].shape[0]
    assert T % block == 0, (T, block)
    nb = T // block
    nbp = _round_up(nb, SUBLANES)
    lanes = _round_up(num_bins, LANES)
    xs = [as_blocks(x, f, block) for x, f in zip(inputs, fills)]
    row_spec = pl.BlockSpec((SUBLANES, block), lambda i: (i, 0))
    hist_spec = pl.BlockSpec((SUBLANES, lanes), lambda i: (i, 0))
    shapes = {
        "rows": jax.ShapeDtypeStruct((nbp, block), jnp.int32),
        "hist": jax.ShapeDtypeStruct((nbp, lanes), jnp.int32),
    }
    res = pl.pallas_call(
        kernel,
        grid=(nbp // SUBLANES,),
        in_specs=[row_spec] * len(xs),
        out_specs=[row_spec if o == "rows" else hist_spec for o in outs],
        out_shape=[shapes[o] for o in outs],
        interpret=interpret,
    )(*xs)
    return tuple(
        r[:nb].reshape(T) if o == "rows" else r[:nb, :num_bins]
        for r, o in zip(res, outs)
    )


def hash_partition(
    keys: jax.Array, num_partitions: int, block: int = 256, interpret: bool = True
) -> tuple[jax.Array, jax.Array]:
    """(partition ids [T], per-block histograms [T/block, P])."""
    kernel = functools.partial(_hash_kernel, num_partitions=num_partitions)
    return _block_call(
        kernel, [keys], [0], num_partitions, ("rows", "hist"), block, interpret
    )


def partition_pack(
    dest: jax.Array, num_bins: int, block: int = 256, interpret: bool = True
) -> tuple[jax.Array, jax.Array]:
    """(per-block histograms [T/block, num_bins], block-local ranks [T])."""
    kernel = functools.partial(_partition_pack_kernel, num_bins=num_bins)
    return _block_call(
        kernel, [dest], [num_bins], num_bins, ("hist", "rows"), block, interpret
    )


def hash_partition_pack(
    keys: jax.Array,
    valid: jax.Array,
    num_partitions: int,
    block: int = 256,
    interpret: bool = True,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Fused hash + mask + pack metadata in one pass over the keys.

    Returns ``(dest [T], per-block histograms [T/block, P+1], block-local
    ranks [T])`` where ``dest`` is the masked destination (``P`` = overflow
    bin for invalid rows) and histograms/ranks cover all ``P + 1`` bins.
    ``valid`` is int32 (nonzero == valid).
    """
    assert valid.shape == keys.shape, (valid.shape, keys.shape)
    kernel = functools.partial(
        _hash_partition_pack_kernel, num_partitions=num_partitions
    )
    return _block_call(
        kernel, [keys, valid], [0, 0], num_partitions + 1,
        ("rows", "hist", "rows"), block, interpret,
    )


__all__ = ["hash_partition", "partition_pack", "hash_partition_pack"]
